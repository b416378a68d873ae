// Native per-record SmartModule chain engine.
//
// Capability parity: the reference's wasmtime engine executes compiled
// per-record transform loops inside the broker
// (fluvio-smartengine/src/engine/wasmtime/engine.rs:135 `process`); this
// is the same execution model as native code — a compiled stack-machine
// interpreter over the DSL expression set, driven record-at-a-time with
// filter/map/filter_map/array_map/aggregate step semantics identical to
// fluvio_tpu/smartmodule/dsl.py (the single source of truth the Python
// and TPU backends also implement).
//
// Python hands a chain *spec* (lowered from the DSL by
// fluvio_tpu/smartengine/native_backend.py) and flat record buffers; we
// return flat output buffers + per-output source indices so the host can
// rebuild Record metadata. C ABI only — loaded with ctypes.

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <regex>
#include <sstream>
#include <string>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// Values on the evaluation stack
// ---------------------------------------------------------------------------

struct Val {
    enum Kind { BYTES, BYTES_REF, INT, BOOL } kind = BYTES;
    std::string b;
    const std::string* ref = nullptr;  // BYTES_REF: borrowed record bytes
    int64_t i = 0;
    bool t = false;

    static Val bytes(std::string s) { Val v; v.kind = BYTES; v.b = std::move(s); return v; }
    static Val borrowed(const std::string* s) { Val v; v.kind = BYTES_REF; v.ref = s; return v; }
    static Val integer(int64_t x) { Val v; v.kind = INT; v.i = x; return v; }
    static Val boolean(bool x) { Val v; v.kind = BOOL; v.t = x; return v; }

    bool truthy() const {
        switch (kind) {
            case BYTES: return !b.empty();
            case BYTES_REF: return !ref->empty();
            case INT: return i != 0;
            case BOOL: return t;
        }
        return false;
    }
    const std::string& as_bytes() const { return kind == BYTES_REF ? *ref : b; }
    bool is_bytes() const { return kind == BYTES || kind == BYTES_REF; }
};

// ---------------------------------------------------------------------------
// Byte-level primitives — semantics mirror smartmodule/dsl.py exactly
// ---------------------------------------------------------------------------

bool is_ws(uint8_t c) { return c == ' ' || c == '\t' || c == '\r' || c == '\n'; }

std::string strip(const std::string& s) {
    size_t a = 0, b = s.size();
    while (a < b && is_ws((uint8_t)s[a])) a++;
    while (b > a && is_ws((uint8_t)s[b - 1])) b--;
    return s.substr(a, b - a);
}

// dsl.json_get_bytes (dsl.py:60)
std::string json_get_bytes(const std::string& value, const std::string& key) {
    std::string needle = "\"" + key + "\"";
    size_t n = value.size();
    int depth = 0;
    bool in_str = false;
    size_t i = 0;
    while (i < n) {
        uint8_t c = value[i];
        if (in_str) {
            if (c == 0x5C) { i += 2; continue; }
            if (c == 0x22) in_str = false;
            i += 1;
            continue;
        }
        if (c == 0x22) {
            if (depth == 1 && value.compare(i, needle.size(), needle) == 0) {
                size_t j = i + needle.size();
                while (j < n && is_ws((uint8_t)value[j])) j++;
                if (j < n && value[j] == ':') {
                    j += 1;
                    while (j < n && is_ws((uint8_t)value[j])) j++;
                    if (j < n && value[j] == '"') {
                        size_t k = j + 1;
                        while (k < n && value[k] != '"') {
                            if (value[k] == 0x5C) k += 1;
                            k += 1;
                        }
                        return value.substr(j + 1, k - (j + 1));
                    }
                    size_t k = j;
                    int d2 = 0;
                    while (k < n) {
                        uint8_t ck = value[k];
                        if (ck == '[' || ck == '{') d2 += 1;
                        else if (ck == ']' || ck == '}') {
                            if (d2 == 0) break;
                            d2 -= 1;
                        } else if (ck == ',' && d2 == 0) break;
                        k += 1;
                    }
                    return strip(value.substr(j, k - j));
                }
            }
            in_str = true;
            i += 1;
            continue;
        }
        if (c == '{') depth += 1;
        else if (c == '}') depth -= 1;
        i += 1;
    }
    return "";
}

// dsl.parse_int_prefix (dsl.py:176)
int64_t parse_int_prefix(const std::string& value) {
    size_t i = 0, n = value.size();
    while (i < n && is_ws((uint8_t)value[i])) i++;
    bool neg = false;
    if (i < n && (value[i] == '+' || value[i] == '-')) {
        neg = value[i] == '-';
        i++;
    }
    int64_t num = 0;
    bool seen = false;
    while (i < n && value[i] >= '0' && value[i] <= '9') {
        num = num * 10 + (value[i] - '0');
        seen = true;
        i++;
    }
    if (!seen) return 0;
    return neg ? -num : num;
}

std::string ascii_upper(const std::string& s) {
    std::string out = s;
    for (auto& c : out)
        if (c >= 'a' && c <= 'z') c -= 32;
    return out;
}

std::string ascii_lower(const std::string& s) {
    std::string out = s;
    for (auto& c : out)
        if (c >= 'A' && c <= 'Z') c += 32;
    return out;
}

int64_t count_words(const std::string& s) {
    int64_t count = 0;
    bool in_word = false;
    for (uint8_t c : s) {
        bool w = !(c == ' ' || c == '\t' || c == '\r' || c == '\n' ||
                   c == '\v' || c == '\f');
        if (w && !in_word) count++;
        in_word = w;
    }
    return count;
}

// dsl.json_array_elements (dsl.py:131); returns false for non-arrays
bool json_array_elements(const std::string& value, std::vector<std::string>& out) {
    std::string s = strip(value);
    if (s.size() < 2 || s.front() != '[' || s.back() != ']') return false;
    std::string body = s.substr(1, s.size() - 2);
    size_t i = 0, n = body.size(), start = 0;
    int depth = 0;
    bool in_str = false;
    auto push = [&](const std::string& raw) {
        std::string seg = strip(raw);
        if (seg.size() >= 2 && seg.front() == '"' && seg.back() == '"')
            seg = seg.substr(1, seg.size() - 2);
        if (!seg.empty()) out.push_back(seg);
    };
    while (i < n) {
        uint8_t c = body[i];
        if (in_str) {
            if (c == 0x5C) { i += 2; continue; }
            if (c == 0x22) in_str = false;
        } else if (c == 0x22) in_str = true;
        else if (c == '[' || c == '{') depth += 1;
        else if (c == ']' || c == '}') depth -= 1;
        else if (c == ',' && depth == 0) {
            push(body.substr(start, i - start));
            start = i + 1;
        }
        i += 1;
    }
    if (start < n) push(body.substr(start, n - start));
    return true;
}

// ---------------------------------------------------------------------------
// Instruction set (postfix program lowered from the DSL expression tree)
// ---------------------------------------------------------------------------

enum class Op {
    VALUE, KEY, CONST, UPPER, LOWER, CONCAT, JSONGET, REGEX, CONTAINS,
    STARTSWITH, ENDSWITH, LEN, PARSEINT, INT2BYTES, CMP, AND, OR, NOT,
};

struct Instr {
    Op op;
    std::string lit;      // CONST/JSONGET/CONTAINS/... literal
    int n = 0;            // CONCAT/AND/OR arity
    int cmp = 0;          // 0 eq, 1 ne, 2 lt, 3 le, 4 gt, 5 ge
    int regex_idx = -1;   // compiled regex slot
};

struct Program {
    std::vector<Instr> instrs;
};

enum class StepKind { FILTER, MAP, FILTER_MAP, ARRAY_MAP, AGGREGATE };

struct Step {
    StepKind kind;
    Program predicate;  // filter / filter_map
    Program value;      // map / filter_map
    bool has_key = false;
    Program key;        // map / filter_map optional key expr
    // array_map
    bool json_array_mode = true;
    std::string sep;
    // aggregate: agg_kind is a canned kind, or (has_contrib) the
    // combine monoid applied to the per-record contribution program
    std::string agg_kind;
    bool has_contrib = false;
    Program contrib;
    int64_t window_ms = -1;
    int64_t acc = 0;
    bool window_started = false;
    int64_t window_start = 0;
};

struct Chain {
    std::vector<Step> steps;
    std::vector<std::regex> regexes;
    std::string error;
};

// ---------------------------------------------------------------------------
// Spec parsing (the compact text form native_backend.py emits)
// ---------------------------------------------------------------------------

std::string from_hex(const std::string& hex) {
    std::string out;
    out.reserve(hex.size() / 2);
    for (size_t i = 0; i + 1 < hex.size(); i += 2) {
        auto nib = [](char c) -> int {
            if (c >= '0' && c <= '9') return c - '0';
            if (c >= 'a' && c <= 'f') return c - 'a' + 10;
            if (c >= 'A' && c <= 'F') return c - 'A' + 10;
            return 0;
        };
        out.push_back((char)((nib(hex[i]) << 4) | nib(hex[i + 1])));
    }
    return out;
}

bool parse_program(std::istringstream& in, int n_lines, Chain& chain, Program& prog) {
    std::string line;
    for (int i = 0; i < n_lines; i++) {
        if (!std::getline(in, line)) return false;
        std::istringstream ls(line);
        std::string opname;
        ls >> opname;
        Instr ins;
        std::string arg;
        if (opname == "VALUE") ins.op = Op::VALUE;
        else if (opname == "KEY") ins.op = Op::KEY;
        else if (opname == "CONST") { ins.op = Op::CONST; ls >> arg; ins.lit = from_hex(arg); }
        else if (opname == "UPPER") ins.op = Op::UPPER;
        else if (opname == "LOWER") ins.op = Op::LOWER;
        else if (opname == "CONCAT") { ins.op = Op::CONCAT; ls >> ins.n; }
        else if (opname == "JSONGET") { ins.op = Op::JSONGET; ls >> arg; ins.lit = from_hex(arg); }
        else if (opname == "REGEX") {
            ins.op = Op::REGEX;
            ls >> arg;
            ins.lit = from_hex(arg);
            // literal patterns (no metacharacters) short-circuit to a
            // substring search — std::regex is far slower than find()
            if (ins.lit.find_first_of(".^$*+?()[]{}|\\") == std::string::npos) {
                ins.op = Op::CONTAINS;
                prog.instrs.push_back(std::move(ins));
                continue;
            }
            try {
                chain.regexes.emplace_back(ins.lit, std::regex::ECMAScript | std::regex::optimize);
            } catch (const std::regex_error& e) {
                chain.error = std::string("invalid regex: ") + e.what();
                return false;
            }
            ins.regex_idx = (int)chain.regexes.size() - 1;
        }
        else if (opname == "CONTAINS") { ins.op = Op::CONTAINS; ls >> arg; ins.lit = from_hex(arg); }
        else if (opname == "STARTSWITH") { ins.op = Op::STARTSWITH; ls >> arg; ins.lit = from_hex(arg); }
        else if (opname == "ENDSWITH") { ins.op = Op::ENDSWITH; ls >> arg; ins.lit = from_hex(arg); }
        else if (opname == "LEN") ins.op = Op::LEN;
        else if (opname == "PARSEINT") ins.op = Op::PARSEINT;
        else if (opname == "INT2BYTES") ins.op = Op::INT2BYTES;
        else if (opname == "CMP") {
            ins.op = Op::CMP;
            ls >> arg;
            const char* names[] = {"eq", "ne", "lt", "le", "gt", "ge"};
            for (int k = 0; k < 6; k++)
                if (arg == names[k]) ins.cmp = k;
        }
        else if (opname == "AND") { ins.op = Op::AND; ls >> ins.n; }
        else if (opname == "OR") { ins.op = Op::OR; ls >> ins.n; }
        else if (opname == "NOT") ins.op = Op::NOT;
        else {
            chain.error = "unknown instruction: " + opname;
            return false;
        }
        prog.instrs.push_back(std::move(ins));
    }
    return true;
}

// ---------------------------------------------------------------------------
// Evaluation
// ---------------------------------------------------------------------------

int64_t as_int(const Val& v) {
    switch (v.kind) {
        case Val::INT: return v.i;
        case Val::BOOL: return v.t ? 1 : 0;
        case Val::BYTES: return parse_int_prefix(v.b);
        case Val::BYTES_REF: return parse_int_prefix(*v.ref);
    }
    return 0;
}

bool val_cmp(const Val& a, const Val& b, int op) {
    int c;
    if (a.is_bytes() && b.is_bytes()) {
        int r = a.as_bytes().compare(b.as_bytes());
        c = r < 0 ? -1 : (r == 0 ? 0 : 1);
    }
    else {
        int64_t x = as_int(a), y = as_int(b);
        c = x < y ? -1 : (x == y ? 0 : 1);
    }
    switch (op) {
        case 0: return c == 0;
        case 1: return c != 0;
        case 2: return c < 0;
        case 3: return c <= 0;
        case 4: return c > 0;
        case 5: return c >= 0;
    }
    return false;
}

Val eval_program(const Chain& chain, const Program& prog,
                 const std::string& value, const std::string* key) {
    std::vector<Val> stack;
    for (const auto& ins : prog.instrs) {
        switch (ins.op) {
            case Op::VALUE: stack.push_back(Val::borrowed(&value)); break;
            case Op::KEY: stack.push_back(key ? Val::borrowed(key) : Val::bytes("")); break;
            case Op::CONST: stack.push_back(Val::borrowed(&ins.lit)); break;
            case Op::UPPER: stack.back() = Val::bytes(ascii_upper(stack.back().as_bytes())); break;
            case Op::LOWER: stack.back() = Val::bytes(ascii_lower(stack.back().as_bytes())); break;
            case Op::CONCAT: {
                std::string out;
                for (size_t i = stack.size() - ins.n; i < stack.size(); i++)
                    out += stack[i].as_bytes();
                stack.resize(stack.size() - ins.n);
                stack.push_back(Val::bytes(std::move(out)));
                break;
            }
            case Op::JSONGET:
                stack.back() = Val::bytes(json_get_bytes(stack.back().as_bytes(), ins.lit));
                break;
            case Op::REGEX: {
                const std::string& s = stack.back().as_bytes();
                bool m = std::regex_search(s.begin(), s.end(), chain.regexes[ins.regex_idx]);
                stack.back() = Val::boolean(m);
                break;
            }
            case Op::CONTAINS:
                stack.back() = Val::boolean(
                    stack.back().as_bytes().find(ins.lit) != std::string::npos);
                break;
            case Op::STARTSWITH: {
                const std::string& s = stack.back().as_bytes();
                stack.back() = Val::boolean(s.compare(0, ins.lit.size(), ins.lit) == 0);
                break;
            }
            case Op::ENDSWITH: {
                const std::string& s = stack.back().as_bytes();
                stack.back() = Val::boolean(
                    s.size() >= ins.lit.size() &&
                    s.compare(s.size() - ins.lit.size(), ins.lit.size(), ins.lit) == 0);
                break;
            }
            case Op::LEN: stack.back() = Val::integer((int64_t)stack.back().as_bytes().size()); break;
            case Op::PARSEINT: stack.back() = Val::integer(parse_int_prefix(stack.back().as_bytes())); break;
            case Op::INT2BYTES: stack.back() = Val::bytes(std::to_string(as_int(stack.back()))); break;
            case Op::CMP: {
                Val b = std::move(stack.back()); stack.pop_back();
                Val a = std::move(stack.back()); stack.pop_back();
                stack.push_back(Val::boolean(val_cmp(a, b, ins.cmp)));
                break;
            }
            case Op::AND: {
                bool r = true;
                for (size_t i = stack.size() - ins.n; i < stack.size(); i++)
                    r = r && stack[i].truthy();
                stack.resize(stack.size() - ins.n);
                stack.push_back(Val::boolean(r));
                break;
            }
            case Op::OR: {
                bool r = false;
                for (size_t i = stack.size() - ins.n; i < stack.size(); i++)
                    r = r || stack[i].truthy();
                stack.resize(stack.size() - ins.n);
                stack.push_back(Val::boolean(r));
                break;
            }
            case Op::NOT: stack.back() = Val::boolean(!stack.back().truthy()); break;
        }
    }
    return stack.empty() ? Val::bytes("") : std::move(stack.back());
}

// ---------------------------------------------------------------------------
// Records through chain steps
// ---------------------------------------------------------------------------

struct Rec {
    std::string value;
    std::string key;
    bool has_key = false;
    int64_t src = 0;       // input record index (offset/timestamp recovery)
    int64_t timestamp = -1;
    bool fresh = false;    // fan-out record: host resets offset deltas
    int64_t off_delta = 0;
    int64_t ts_delta = 0;
};

int64_t agg_init(const std::string& kind) {
    if (kind == "max_int" || kind == "max") return INT64_MIN;
    if (kind == "min_int" || kind == "min") return INT64_MAX;
    return 0;
}

int64_t agg_combine(const std::string& op, int64_t acc, int64_t x) {
    if (op == "max") return x > acc ? x : acc;
    if (op == "min") return x < acc ? x : acc;
    return acc + x;  // add
}

int64_t agg_step(const std::string& kind, int64_t acc, const Rec& r) {
    if (kind == "sum_int") return acc + parse_int_prefix(r.value);
    if (kind == "count") return acc + 1;
    if (kind == "word_count") return acc + count_words(r.value);
    if (kind == "max_int") {
        int64_t v = parse_int_prefix(r.value);
        return v > acc ? v : acc;
    }
    if (kind == "min_int") {
        int64_t v = parse_int_prefix(r.value);
        return v < acc ? v : acc;
    }
    return acc;
}

// returns error src index, or -1
int64_t run_step(Chain& chain, Step& step, std::vector<Rec>& recs,
                 std::vector<Rec>& out) {
    out.clear();
    out.reserve(recs.size());
    switch (step.kind) {
        case StepKind::FILTER:
            for (auto& r : recs) {
                Val v = eval_program(chain, step.predicate, r.value,
                                     r.has_key ? &r.key : nullptr);
                if (v.truthy()) out.push_back(std::move(r));
            }
            return -1;
        case StepKind::MAP:
        case StepKind::FILTER_MAP:
            for (auto& r : recs) {
                const std::string* kp = r.has_key ? &r.key : nullptr;
                if (step.kind == StepKind::FILTER_MAP) {
                    Val p = eval_program(chain, step.predicate, r.value, kp);
                    if (!p.truthy()) continue;
                }
                Val v = eval_program(chain, step.value, r.value, kp);
                if (step.has_key) {
                    Val k = eval_program(chain, step.key, r.value, kp);
                    r.key = k.as_bytes();
                    r.has_key = true;
                }
                r.value = v.is_bytes() ? v.as_bytes() : std::to_string(as_int(v));
                out.push_back(std::move(r));
            }
            return -1;
        case StepKind::ARRAY_MAP:
            for (auto& r : recs) {
                std::vector<std::string> elements;
                if (step.json_array_mode) {
                    if (!json_array_elements(r.value, elements)) {
                        chain.error = "input record is not a JSON array";
                        return r.src;
                    }
                } else {
                    size_t start = 0;
                    while (start <= r.value.size()) {
                        size_t pos = r.value.find(step.sep, start);
                        if (pos == std::string::npos) pos = r.value.size();
                        if (pos > start)
                            elements.push_back(r.value.substr(start, pos - start));
                        if (pos == r.value.size()) break;
                        start = pos + step.sep.size();
                    }
                }
                for (auto& el : elements) {
                    Rec nr;
                    nr.value = std::move(el);
                    nr.key = r.key;
                    nr.has_key = r.has_key;
                    nr.src = r.src;
                    nr.timestamp = r.timestamp;
                    nr.fresh = true;
                    out.push_back(std::move(nr));
                }
            }
            return -1;
        case StepKind::AGGREGATE:
            for (auto& r : recs) {
                if (step.window_ms > 0) {
                    int64_t ts = r.timestamp;
                    int64_t window = ts < 0 ? 0 : ts - (ts % step.window_ms);
                    if (!step.window_started || window != step.window_start) {
                        step.window_started = true;
                        step.window_start = window;
                        step.acc = agg_init(step.agg_kind);
                    }
                }
                if (step.has_contrib) {
                    Val v = eval_program(chain, step.contrib, r.value,
                                         r.has_key ? &r.key : nullptr);
                    step.acc = agg_combine(step.agg_kind, step.acc, as_int(v));
                } else {
                    step.acc = agg_step(step.agg_kind, step.acc, r);
                }
                r.value = std::to_string(step.acc);
                out.push_back(std::move(r));
            }
            return -1;
    }
    return -1;
}

}  // namespace

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

extern "C" {

struct NativeResult {
    int64_t count;
    int64_t error_src;  // -1 = no error; else failing input record index
    uint8_t* val_flat;
    int64_t* val_off;   // count + 1
    uint8_t* key_flat;
    int64_t* key_off;   // count + 1
    uint8_t* key_present;
    int64_t* src_idx;
    uint8_t* fresh;
    int64_t* out_off_delta;
    int64_t* out_ts_delta;
    int64_t* acc_out;   // per-aggregate-step final accumulators
    int64_t acc_count;
};

void* chain_create(const char* spec, char* err_buf, int err_len) {
    auto* chain = new Chain();
    std::istringstream in(spec);
    std::string line;
    bool ok = true;
    while (ok && std::getline(in, line)) {
        if (line.empty()) continue;
        std::istringstream ls(line);
        std::string tag, kind;
        ls >> tag;
        if (tag != "STEP") { chain->error = "expected STEP, got: " + line; ok = false; break; }
        ls >> kind;
        Step step;
        if (kind == "FILTER" || kind == "FILTER_MAP" || kind == "MAP") {
            step.kind = kind == "FILTER" ? StepKind::FILTER
                        : (kind == "MAP" ? StepKind::MAP : StepKind::FILTER_MAP);
            int n_pred = 0, n_val = 0, n_key = 0;
            ls >> n_pred >> n_val >> n_key;
            if (n_pred && !parse_program(in, n_pred, *chain, step.predicate)) { ok = false; break; }
            if (n_val && !parse_program(in, n_val, *chain, step.value)) { ok = false; break; }
            if (n_key) {
                step.has_key = true;
                if (!parse_program(in, n_key, *chain, step.key)) { ok = false; break; }
            }
        } else if (kind == "ARRAY_MAP") {
            step.kind = StepKind::ARRAY_MAP;
            std::string mode, sep_hex;
            ls >> mode >> sep_hex;
            step.json_array_mode = mode == "json_array";
            step.sep = from_hex(sep_hex);
        } else if (kind == "AGGREGATE") {
            step.kind = StepKind::AGGREGATE;
            std::string acc_hex;
            ls >> step.agg_kind >> step.window_ms >> acc_hex;
            std::string seed = from_hex(acc_hex);
            step.acc = seed.empty() ? agg_init(step.agg_kind) : parse_int_prefix(seed);
        } else if (kind == "AGGREGATE_EXPR") {
            step.kind = StepKind::AGGREGATE;
            step.has_contrib = true;
            std::string acc_hex;
            int n_contrib = 0;
            ls >> step.agg_kind >> step.window_ms >> acc_hex >> n_contrib;
            if (acc_hex == "-") acc_hex.clear();
            if (n_contrib && !parse_program(in, n_contrib, *chain, step.contrib)) { ok = false; break; }
            std::string seed = from_hex(acc_hex);
            step.acc = seed.empty() ? agg_init(step.agg_kind) : parse_int_prefix(seed);
        } else {
            chain->error = "unknown step kind: " + kind;
            ok = false;
            break;
        }
        chain->steps.push_back(std::move(step));
    }
    if (!ok || !chain->error.empty()) {
        if (err_buf && err_len > 0) {
            std::snprintf(err_buf, err_len, "%s", chain->error.c_str());
        }
        delete chain;
        return nullptr;
    }
    return chain;
}

void chain_destroy(void* p) { delete static_cast<Chain*>(p); }

void chain_set_accumulator(void* p, int step_idx, const uint8_t* acc, int64_t len) {
    auto* chain = static_cast<Chain*>(p);
    int seen = 0;
    for (auto& step : chain->steps) {
        if (step.kind != StepKind::AGGREGATE) continue;
        if (seen == step_idx) {
            std::string s((const char*)acc, (size_t)len);
            step.acc = s.empty() ? agg_init(step.agg_kind) : parse_int_prefix(s);
            step.window_started = false;
            return;
        }
        seen++;
    }
}

static NativeResult* run_and_pack(Chain* chain, std::vector<Rec>& recs) {
    std::vector<Rec> next;
    int64_t error_src = -1;
    for (auto& step : chain->steps) {
        error_src = run_step(*chain, step, recs, next);
        recs.swap(next);
        if (error_src >= 0) break;
    }

    auto* result = new NativeResult();
    result->count = (int64_t)recs.size();
    result->error_src = error_src;
    int64_t total_val = 0, total_key = 0;
    for (auto& r : recs) {
        total_val += (int64_t)r.value.size();
        total_key += (int64_t)r.key.size();
    }
    result->val_flat = (uint8_t*)std::malloc(total_val ? total_val : 1);
    result->val_off = (int64_t*)std::malloc((recs.size() + 1) * sizeof(int64_t));
    result->key_flat = (uint8_t*)std::malloc(total_key ? total_key : 1);
    result->key_off = (int64_t*)std::malloc((recs.size() + 1) * sizeof(int64_t));
    result->key_present = (uint8_t*)std::malloc(recs.size() ? recs.size() : 1);
    result->src_idx = (int64_t*)std::malloc(recs.size() ? recs.size() * sizeof(int64_t) : 8);
    result->fresh = (uint8_t*)std::malloc(recs.size() ? recs.size() : 1);
    result->out_off_delta = (int64_t*)std::malloc(recs.size() ? recs.size() * sizeof(int64_t) : 8);
    result->out_ts_delta = (int64_t*)std::malloc(recs.size() ? recs.size() * sizeof(int64_t) : 8);
    int64_t vo = 0, ko = 0;
    for (size_t i = 0; i < recs.size(); i++) {
        result->val_off[i] = vo;
        std::memcpy(result->val_flat + vo, recs[i].value.data(), recs[i].value.size());
        vo += (int64_t)recs[i].value.size();
        result->key_off[i] = ko;
        std::memcpy(result->key_flat + ko, recs[i].key.data(), recs[i].key.size());
        ko += (int64_t)recs[i].key.size();
        result->key_present[i] = recs[i].has_key ? 1 : 0;
        result->src_idx[i] = recs[i].src;
        result->fresh[i] = recs[i].fresh ? 1 : 0;
        result->out_off_delta[i] = recs[i].fresh ? 0 : recs[i].off_delta;
        result->out_ts_delta[i] = recs[i].fresh ? 0 : recs[i].ts_delta;
    }
    result->val_off[recs.size()] = vo;
    result->key_off[recs.size()] = ko;

    // final accumulator per aggregate step (host re-syncs chain state)
    std::vector<int64_t> accs;
    for (auto& step : chain->steps)
        if (step.kind == StepKind::AGGREGATE) accs.push_back(step.acc);
    result->acc_count = (int64_t)accs.size();
    result->acc_out = (int64_t*)std::malloc(accs.empty() ? 8 : accs.size() * sizeof(int64_t));
    for (size_t i = 0; i < accs.size(); i++) result->acc_out[i] = accs[i];
    return result;
}

NativeResult* chain_run(void* p, const uint8_t* flat, const int64_t* val_off,
                        const uint8_t* key_flat, const int64_t* key_off,
                        const uint8_t* key_present, const int64_t* timestamps,
                        int64_t n) {
    auto* chain = static_cast<Chain*>(p);
    std::vector<Rec> recs(n);
    for (int64_t i = 0; i < n; i++) {
        recs[i].value.assign((const char*)flat + val_off[i],
                             (size_t)(val_off[i + 1] - val_off[i]));
        if (key_present && key_present[i]) {
            recs[i].has_key = true;
            recs[i].key.assign((const char*)key_flat + key_off[i],
                               (size_t)(key_off[i + 1] - key_off[i]));
        }
        recs[i].src = i;
        recs[i].timestamp = timestamps ? timestamps[i] : -1;
    }
    return run_and_pack(chain, recs);
}

// zigzag varint (fluvio-protocol varint.rs semantics)
static bool read_varint(const uint8_t* buf, int64_t len, int64_t& pos, int64_t& out) {
    uint64_t result = 0;
    int shift = 0;
    while (pos < len) {
        uint8_t b = buf[pos++];
        result |= (uint64_t)(b & 0x7F) << shift;
        if (!(b & 0x80)) {
            out = (int64_t)(result >> 1) ^ -(int64_t)(result & 1);
            return true;
        }
        shift += 7;
        if (shift > 63) return false;
    }
    return false;
}

// Decode an encoded SmartModuleInput record slab in native code — the
// wasmtime-guest execution model (decode + transform compiled, host only
// rebuilds the final outputs).
NativeResult* chain_run_encoded(void* p, const uint8_t* raw, int64_t raw_len,
                                int64_t base_timestamp) {
    auto* chain = static_cast<Chain*>(p);
    std::vector<Rec> recs;
    int64_t pos = 0, i = 0;
    while (pos < raw_len) {
        int64_t inner = 0;
        if (!read_varint(raw, raw_len, pos, inner)) break;
        int64_t end = pos + inner;
        if (end > raw_len) break;
        Rec r;
        pos += 1;  // attributes
        read_varint(raw, end, pos, r.ts_delta);
        read_varint(raw, end, pos, r.off_delta);
        uint8_t has_key = pos < end ? raw[pos++] : 0;
        if (has_key) {
            int64_t klen = 0;
            read_varint(raw, end, pos, klen);
            r.has_key = true;
            r.key.assign((const char*)raw + pos, (size_t)klen);
            pos += klen;
        }
        int64_t vlen = 0;
        read_varint(raw, end, pos, vlen);
        r.value.assign((const char*)raw + pos, (size_t)vlen);
        pos += vlen;
        pos = end;  // skip headers
        r.src = i++;
        r.timestamp = base_timestamp >= 0 ? base_timestamp + r.ts_delta : -1;
        recs.push_back(std::move(r));
    }
    return run_and_pack(chain, recs);
}

// ---------------------------------------------------------------------------
// Columnar record codecs — the broker's TPU staging path. The SPU feeds
// stored record slabs straight into RecordBuffer columns (and back) with no
// per-record Python objects; mirrors the layout fluvio-storage hands to the
// engine (FileBatch, fluvio-spu/src/smartengine/file_batch.rs:10).
// ---------------------------------------------------------------------------

static int64_t varint_encoded_size(int64_t v) {
    uint64_t u = ((uint64_t)v << 1) ^ (uint64_t)(v >> 63);
    int64_t size = 1;
    while (u >= 0x80) { u >>= 7; size++; }
    return size;
}

static void write_varint(uint8_t*& p, int64_t v) {
    uint64_t u = ((uint64_t)v << 1) ^ (uint64_t)(v >> 63);
    while (u >= 0x80) { *p++ = (uint8_t)(u | 0x80); u >>= 7; }
    *p++ = (uint8_t)u;
}

struct RecordColumns {
    int64_t count;
    int64_t parsed;     // bytes consumed; != input len => malformed slab
    uint8_t* val_flat;
    int64_t* val_off;   // count + 1
    uint8_t* key_flat;
    int64_t* key_off;   // count + 1
    uint8_t* key_present;
    int64_t* off_delta;
    int64_t* ts_delta;
};

// Thin wrapper over the v2 parser at align=1 (exact offsets, compact
// flat) — ONE parse loop serves both decoders, so wire-format or
// bounds-check fixes cannot desynchronize them.
RecordColumns* decode_record_columns(const uint8_t* raw, int64_t raw_len);

void record_columns_free(RecordColumns* c) {
    if (!c) return;
    std::free(c->val_flat);
    std::free(c->val_off);
    std::free(c->key_flat);
    std::free(c->key_off);
    std::free(c->key_present);
    std::free(c->off_delta);
    std::free(c->ts_delta);
    delete c;
}

// v2: val_flat written at `align`-aligned offsets so it IS the engine's
// ragged upload form (no host-side re-pad / re-flatten pass). val_off
// holds the aligned starts (count + 1, last = total aligned bytes) and
// val_len the exact per-record lengths. Keys/deltas identical to v1.
struct RecordColumnsV2 {
    RecordColumns base;
    int64_t* val_len;  // count (exact lengths; val_off is aligned)
};

RecordColumnsV2* decode_record_columns_v2(const uint8_t* raw, int64_t raw_len,
                                          int64_t align) {
    // the rounding below is mask-based: align must be a power of two
    if (align <= 0 || (align & (align - 1)) != 0) align = 1;
    struct View { int64_t voff, vlen, koff, klen, od, td; bool has_key; };
    std::vector<View> views;
    int64_t pos = 0, total_va = 0, total_k = 0, good = 0;
    while (pos < raw_len) {
        int64_t rec_start = pos;
        int64_t inner = 0;
        if (!read_varint(raw, raw_len, pos, inner)) { pos = rec_start; break; }
        int64_t end = pos + inner;
        if (end > raw_len || inner < 0) { pos = rec_start; break; }
        View v{};
        if (pos >= end) { pos = rec_start; break; }
        pos += 1;  // attributes
        if (!read_varint(raw, end, pos, v.td) ||
            !read_varint(raw, end, pos, v.od)) { pos = rec_start; break; }
        if (pos >= end) { pos = rec_start; break; }
        uint8_t has_key = raw[pos++];
        if (has_key) {
            int64_t klen = 0;
            if (!read_varint(raw, end, pos, klen)) { pos = rec_start; break; }
            if (klen < 0 || pos + klen > end) { pos = rec_start; break; }
            v.has_key = true;
            v.koff = pos;
            v.klen = klen;
            pos += klen;
            total_k += klen;
        }
        int64_t vlen = 0;
        if (!read_varint(raw, end, pos, vlen)) { pos = rec_start; break; }
        if (vlen < 0 || pos + vlen > end) { pos = rec_start; break; }
        v.voff = pos;
        v.vlen = vlen;
        pos = end;  // skip record headers
        good = pos;
        total_va += (vlen + align - 1) & ~(align - 1);
        views.push_back(v);
    }
    auto* c2 = new RecordColumnsV2();
    RecordColumns* c = &c2->base;
    int64_t n = (int64_t)views.size();
    c->count = n;
    c->parsed = good;
    // calloc: the alignment gap bytes must be zero (they ride the H2D
    // link inside the flat and the device masks by exact length)
    c->val_flat = (uint8_t*)std::calloc(total_va ? total_va : 1, 1);
    c->val_off = (int64_t*)std::malloc((n + 1) * sizeof(int64_t));
    c->key_flat = (uint8_t*)std::malloc(total_k ? total_k : 1);
    c->key_off = (int64_t*)std::malloc((n + 1) * sizeof(int64_t));
    c->key_present = (uint8_t*)std::malloc(n ? n : 1);
    c->off_delta = (int64_t*)std::malloc(n ? n * sizeof(int64_t) : 8);
    c->ts_delta = (int64_t*)std::malloc(n ? n * sizeof(int64_t) : 8);
    c2->val_len = (int64_t*)std::malloc(n ? n * sizeof(int64_t) : 8);
    int64_t vo = 0, ko = 0;
    for (int64_t i = 0; i < n; i++) {
        const View& v = views[(size_t)i];
        c->val_off[i] = vo;
        c2->val_len[i] = v.vlen;
        std::memcpy(c->val_flat + vo, raw + v.voff, (size_t)v.vlen);
        vo += (v.vlen + align - 1) & ~(align - 1);
        c->key_off[i] = ko;
        if (v.has_key) {
            std::memcpy(c->key_flat + ko, raw + v.koff, (size_t)v.klen);
            ko += v.klen;
        }
        c->key_present[i] = v.has_key ? 1 : 0;
        c->off_delta[i] = v.od;
        c->ts_delta[i] = v.td;
    }
    c->val_off[n] = vo;
    c->key_off[n] = ko;
    return c2;
}

void record_columns_v2_free(RecordColumnsV2* c2) {
    if (!c2) return;
    std::free(c2->base.val_flat);
    std::free(c2->base.val_off);
    std::free(c2->base.key_flat);
    std::free(c2->base.key_off);
    std::free(c2->base.key_present);
    std::free(c2->base.off_delta);
    std::free(c2->base.ts_delta);
    std::free(c2->val_len);
    delete c2;
}

RecordColumns* decode_record_columns(const uint8_t* raw, int64_t raw_len) {
    RecordColumnsV2* c2 = decode_record_columns_v2(raw, raw_len, 1);
    auto* c = new RecordColumns(c2->base);  // steal the column pointers
    std::free(c2->val_len);
    delete c2;
    return c;
}

struct EncodedRecords {
    uint8_t* data;
    int64_t len;
};

}  // extern "C"

namespace {

// Readers of a record's value bytes (`len`, then `write` of those `n`
// bytes; `ok` bounds-checks what the caller handed over) and of its key
// (`len` < 0 = null key).
// ONE record writer below serves them all, so a wire-format fix cannot
// land in one form and miss another.

// exact-packed columns: RecordBuffer.to_columns (the general form)
struct ColumnValues {
    const uint8_t* flat;
    const int64_t* off;
    bool ok(int64_t) const { return true; }
    int64_t len(int64_t i) const { return off[i + 1] - off[i]; }
    void write(uint8_t*& p, int64_t i, int64_t n) const {
        std::memcpy(p, flat + off[i], (size_t)n);
        p += n;
    }
};

// the 4-aligned flat a fetch already holds (flat-backed RecordBuffer)
struct FlatValues {
    const uint8_t* flat;
    int64_t flat_len;
    const int32_t* starts;
    const int32_t* lengths;
    bool ok(int64_t i) const {
        return starts[i] >= 0 && lengths[i] >= 0
            && (int64_t)starts[i] + lengths[i] <= flat_len;
    }
    int64_t len(int64_t i) const { return lengths[i]; }
    void write(uint8_t*& p, int64_t i, int64_t n) const {
        std::memcpy(p, flat + starts[i], (size_t)n);
        p += n;
    }
};

// the decimal of an int64 (byte-equal to kernels.int_to_ascii): its
// length, and its digits written into the `n` bytes at `p`
inline uint64_t decimal_magnitude(int64_t v) {
    return v < 0 ? ~(uint64_t)v + 1 : (uint64_t)v;  // exact at INT64_MIN
}

inline int64_t decimal_len(int64_t v) {
    int64_t n = v < 0 ? 2 : 1;
    for (uint64_t m = decimal_magnitude(v);; m /= 10000, n += 4) {
        if (m < 10) return n;
        if (m < 100) return n + 1;
        if (m < 1000) return n + 2;
        if (m < 10000) return n + 3;
    }
}

inline void decimal_write(uint8_t*& p, int64_t v, int64_t n) {
    static const char pairs[] =
        "00010203040506070809101112131415161718192021222324"
        "25262728293031323334353637383940414243444546474849"
        "50515253545556575859606162636465666768697071727374"
        "75767778798081828384858687888990919293949596979899";
    uint64_t m = decimal_magnitude(v);
    uint8_t* q = p + n;
    for (; m >= 100; m /= 100) {  // two digits a division
        q -= 2;
        std::memcpy(q, pairs + 2 * (m % 100), 2);
    }
    if (m >= 10) {
        q -= 2;
        std::memcpy(q, pairs + 2 * m, 2);
    } else {
        *--q = (uint8_t)('0' + m);
    }
    if (v < 0) *p = '-';
    p += n;
}

// an int64 column rendered as decimals into the record being written
// (int-backed RecordBuffer)
struct IntValues {
    const int64_t* ints;
    bool ok(int64_t) const { return true; }
    int64_t len(int64_t i) const { return decimal_len(ints[i]); }
    void write(uint8_t*& p, int64_t i, int64_t n) const {
        decimal_write(p, ints[i], n);
    }
};

// one value a row rendered from int64 columns (a keyed table's answer
// rows; buffer.py:RowFormat): literal pieces around slots, a slot the
// decimal of a column, of a column shifted right, of the floor quotient
// of two columns (0 where the divisor is 0), or a text of a table
// indexed by a column's low bits (the rendered days)
struct RowValues {
    const int64_t* ints;   // [columns, stride], a column contiguous
    int64_t stride;
    int64_t columns;
    const uint8_t* lit;    // the pieces, one more than slots
    const int64_t* lit_off;
    const int64_t* slots;  // (kind, a, b) a slot
    int64_t n_slots;
    const uint8_t* tab;
    const int64_t* tab_off;
    int64_t tab_n;
    int64_t tab_base;
    enum { INT = 0, SHIFTED = 1, DIV = 2, TABLE = 3 };

    int64_t at(int64_t col, int64_t i) const { return ints[col * stride + i]; }
    int64_t table_index(const int64_t* s, int64_t i) const {
        return (at(s[1], i) & (((int64_t)1 << s[2]) - 1)) - tab_base;
    }
    int64_t number(const int64_t* s, int64_t i) const {
        int64_t a = at(s[1], i);
        if (s[0] == SHIFTED) return a >> s[2];
        if (s[0] != DIV) return a;
        int64_t b = at(s[2], i);
        if (b == 0) return 0;
        if (b == -1) return (int64_t)(0 - (uint64_t)a);  // no INT64_MIN / -1
        int64_t q = a / b;  // floor, as Python's //
        return (a % b != 0 && ((a < 0) != (b < 0))) ? q - 1 : q;
    }
    bool ok(int64_t i) const {
        for (int64_t k = 0; k < n_slots; k++) {
            const int64_t* s = slots + 3 * k;
            if (s[1] < 0 || s[1] >= columns) return false;
            if (s[0] == DIV && (s[2] < 0 || s[2] >= columns)) return false;
            if (s[0] == TABLE) {
                if (s[2] < 0 || s[2] > 62) return false;
                int64_t t = table_index(s, i);
                if (t < 0 || t >= tab_n) return false;
            } else if (s[0] == SHIFTED && (s[2] < 0 || s[2] > 63)) {
                return false;
            }
        }
        return true;
    }
    int64_t len(int64_t i) const {
        int64_t n = lit_off[n_slots + 1] - lit_off[0];
        for (int64_t k = 0; k < n_slots; k++) {
            const int64_t* s = slots + 3 * k;
            if (s[0] == TABLE) {
                int64_t t = table_index(s, i);
                n += tab_off[t + 1] - tab_off[t];
            } else {
                n += decimal_len(number(s, i));
            }
        }
        return n;
    }
    void write(uint8_t*& p, int64_t i, int64_t) const {
        for (int64_t k = 0;; k++) {
            int64_t n = lit_off[k + 1] - lit_off[k];
            std::memcpy(p, lit + lit_off[k], (size_t)n);
            p += n;
            if (k == n_slots) return;
            const int64_t* s = slots + 3 * k;
            if (s[0] == TABLE) {
                int64_t t = table_index(s, i);
                n = tab_off[t + 1] - tab_off[t];
                std::memcpy(p, tab + tab_off[t], (size_t)n);
                p += n;
            } else {
                int64_t v = number(s, i);
                decimal_write(p, v, decimal_len(v));
            }
        }
    }
};

struct ColumnKeys {
    const uint8_t* flat;
    const int64_t* off;
    const uint8_t* present;
    bool ok(int64_t) const { return true; }
    int64_t len(int64_t i) const { return present[i] ? off[i + 1] - off[i] : -1; }
    const uint8_t* at(int64_t i) const { return flat + off[i]; }
};

// a (rows, width) key matrix with per-row lengths, -1 = null
struct MatrixKeys {
    const uint8_t* keys;
    int64_t width;
    const int32_t* lengths;
    bool ok(int64_t i) const { return lengths[i] <= width; }
    int64_t len(int64_t i) const { return lengths[i]; }
    const uint8_t* at(int64_t i) const { return keys + i * width; }
};

// Append rows [first, end) to the slab as wire records (parity:
// protocol.record.Record.encode). With `max_bytes` > 0 the rows stop
// after the first one at which the SLAB (what earlier calls appended
// included) reaches `max_bytes`: a row is kept while the bytes before
// it are under the budget, so the first row of an empty slab always
// is. Returns the rows kept, -1 out of memory, -2 a row out of bounds.
template <class Values, class Keys, class Off>
int64_t append_records(EncodedRecords* e, const Values& vals, const Keys& keys,
                       const Off* off_delta, const int64_t* ts_delta,
                       int64_t first, int64_t end, int64_t max_bytes) {
    // per kept row: the record's inner size, and its value's length (a
    // rendered value's length is not free to ask for twice)
    std::vector<int64_t> inner_sizes, value_lens;
    inner_sizes.reserve((size_t)(end > first ? end - first : 0));
    value_lens.reserve(inner_sizes.capacity());
    int64_t total = 0;
    for (int64_t i = first; i < end; i++) {
        if (max_bytes > 0 && e->len + total >= max_bytes) break;
        if (!vals.ok(i) || !keys.ok(i)) return -2;
        int64_t vlen = vals.len(i), klen = keys.len(i);
        int64_t inner = 1;  // attributes
        inner += varint_encoded_size(ts_delta[i]);
        inner += varint_encoded_size((int64_t)off_delta[i]);
        inner += 1;  // key tag
        if (klen >= 0) inner += varint_encoded_size(klen) + klen;
        inner += varint_encoded_size(vlen) + vlen;
        inner += varint_encoded_size(0);  // header count
        inner_sizes.push_back(inner);
        value_lens.push_back(vlen);
        total += varint_encoded_size(inner) + inner;
    }
    int64_t kept = (int64_t)inner_sizes.size();
    if (!total) return kept;
    uint8_t* grown = (uint8_t*)std::realloc(e->data, (size_t)(e->len + total));
    if (!grown) return -1;
    e->data = grown;
    uint8_t* p = grown + e->len;
    for (int64_t i = first; i < first + kept; i++) {
        write_varint(p, inner_sizes[(size_t)(i - first)]);
        *p++ = 0;  // attributes
        write_varint(p, ts_delta[i]);
        write_varint(p, (int64_t)off_delta[i]);
        int64_t klen = keys.len(i);
        if (klen >= 0) {
            *p++ = 1;
            write_varint(p, klen);
            std::memcpy(p, keys.at(i), (size_t)klen);
            p += klen;
        } else {
            *p++ = 0;
        }
        int64_t vlen = value_lens[(size_t)(i - first)];
        write_varint(p, vlen);
        vals.write(p, i, vlen);
        write_varint(p, 0);  // no record headers
    }
    e->len += total;
    return kept;
}

}  // namespace

extern "C" {

EncodedRecords* encoded_records_new() {
    auto* e = new EncodedRecords();
    e->data = nullptr;
    e->len = 0;
    return e;
}

int64_t encode_append_columns(
    EncodedRecords* e, const uint8_t* val_flat, const int64_t* val_off,
    const uint8_t* key_flat, const int64_t* key_off,
    const uint8_t* key_present,
    const int64_t* off_delta, const int64_t* ts_delta,
    int64_t first, int64_t end, int64_t max_bytes) {
    return append_records(e, ColumnValues{val_flat, val_off},
                          ColumnKeys{key_flat, key_off, key_present},
                          off_delta, ts_delta, first, end, max_bytes);
}

int64_t encode_append_flat(
    EncodedRecords* e, const uint8_t* flat, int64_t flat_len,
    const int32_t* starts, const int32_t* lengths,
    const uint8_t* keys, int64_t key_width, const int32_t* key_lengths,
    const int32_t* off_delta, const int64_t* ts_delta,
    int64_t first, int64_t end, int64_t max_bytes) {
    return append_records(e, FlatValues{flat, flat_len, starts, lengths},
                          MatrixKeys{keys, key_width, key_lengths},
                          off_delta, ts_delta, first, end, max_bytes);
}

int64_t encode_append_ints(
    EncodedRecords* e, const int64_t* ints,
    const uint8_t* keys, int64_t key_width, const int32_t* key_lengths,
    const int32_t* off_delta, const int64_t* ts_delta,
    int64_t first, int64_t end, int64_t max_bytes) {
    return append_records(e, IntValues{ints},
                          MatrixKeys{keys, key_width, key_lengths},
                          off_delta, ts_delta, first, end, max_bytes);
}

int64_t encode_append_rows(
    EncodedRecords* e, const int64_t* ints, int64_t stride, int64_t columns,
    const uint8_t* lit, const int64_t* lit_off,
    const int64_t* slots, int64_t n_slots,
    const uint8_t* tab, const int64_t* tab_off, int64_t tab_n,
    int64_t tab_base,
    const uint8_t* keys, int64_t key_width, const int32_t* key_lengths,
    const int32_t* off_delta, const int64_t* ts_delta,
    int64_t first, int64_t end, int64_t max_bytes) {
    return append_records(
        e,
        RowValues{ints, stride, columns, lit, lit_off, slots, n_slots,
                  tab, tab_off, tab_n, tab_base},
        MatrixKeys{keys, key_width, key_lengths},
        off_delta, ts_delta, first, end, max_bytes);
}

void encoded_records_free(EncodedRecords* e) {
    if (!e) return;
    std::free(e->data);
    delete e;
}

void result_free(NativeResult* r) {
    if (!r) return;
    std::free(r->val_flat);
    std::free(r->val_off);
    std::free(r->key_flat);
    std::free(r->key_off);
    std::free(r->key_present);
    std::free(r->src_idx);
    std::free(r->fresh);
    std::free(r->out_off_delta);
    std::free(r->out_ts_delta);
    std::free(r->acc_out);
    delete r;
}

}  // extern "C"
