// glz: gather-LZ — link compression whose DECOMPRESSION is expressible
// as a fixed number of vectorized gather rounds (scatter + cumsum +
// gather), i.e. runs inside an XLA/TPU program with no sequential
// byte-by-byte decode.
//
// Why it exists: the host->device link is the first wall a byte-bound
// SmartModule chain meets. Classic LZ4/snappy decompression is
// inherently serial (matches copy from just-written output, including
// overlapping RLE copies), so compressed bytes would have to be
// inflated on the HOST — the wrong side of the link. glz restricts the
// format so the device can resolve everything in parallel:
//
//   * the stream is a list of SEQUENCES (LZ4-shaped): each copies
//     `lit_len` bytes from the literal stream, then `match_len` bytes
//     from out[src : src+match_len).
//   * matches NEVER overlap their own output: src + match_len <= dst.
//   * every output byte has a DEPTH: literal bytes are 0; a match
//     byte is 1 + max depth over its source range. The compressor
//     bounds depth at max_depth, so decompression is exactly
//     max_depth gather rounds: round k resolves every depth-k byte
//     because its sources resolved in earlier rounds.
//
// Long literal runs / matches are chains of sequences (lit-only /
// match-only); there are no escape codes, every sequence is
// self-describing: (lit_len u8, match_len u8, src i32) = 6 B across
// three struct-of-array link buffers.
//
// Parity note: the reference ships record batches compressed on the
// wire (fluvio-compression/src/lib.rs) but inflates them on the CPU
// before the engine touches bytes. Here the engine's staging keeps the
// bytes compressed ACROSS the host->device link, which the reference's
// wasmtime-on-CPU architecture has no equivalent of.

#include <cstdint>
#include <cstring>
#include <cstdlib>

namespace {

constexpr int HASH_BITS = 17;
constexpr uint32_t HASH_SIZE = 1u << HASH_BITS;

inline uint64_t load64(const uint8_t* p) {
    uint64_t v;
    std::memcpy(&v, p, 8);
    return v;
}

inline uint32_t hash64(uint64_t v) {
    return (uint32_t)((v * 0x9E3779B185EBCA87ull) >> (64 - HASH_BITS));
}

}  // namespace

extern "C" {

struct GlzResult {
    int64_t n_seqs;
    int64_t n_lits;
    int32_t depth;    // max match depth in the stream (gather rounds)
    int32_t status;   // 0 ok; 1 bailed (incompressible — ship raw)
};

// Greedy single-pass compressor. An 8-byte rolling hash with two
// candidate slots per bucket: the most recent occurrence and the most
// recent DEPTH-0 (literal-region) occurrence — preferring shallow
// sources keeps match chains short so the device needs few gather
// rounds. Match extension is DEPTH-BOUNDED: it walks source bytes only
// while their depth stays under max_depth, so a too-deep source
// naturally truncates the match instead of rejecting it (and the
// range-max depth scan merges into the extension pass — no separate
// rejection scans).
GlzResult glz_compress(const uint8_t* in, int64_t n,
                       uint8_t* lit_lens, uint8_t* match_lens,
                       int32_t* srcs, int64_t seq_cap,
                       uint8_t* lits, int64_t lit_cap,
                       int32_t max_depth, int32_t min_match) {
    GlzResult res = {0, 0, 0, 0};
    if (n <= 0) return res;
    if (min_match < 8) min_match = 8;
    if (max_depth < 1) max_depth = 1;
    if (max_depth > 254) max_depth = 254;

    // one cache line per probe: the three candidate generations live
    // in a single 32-byte-padded slot instead of three parallel tables
    // (three random misses per probed byte collapse to one)
    struct Slot { int64_t anchor, shallow, recent, _pad; };
    Slot* table = (Slot*)std::malloc(sizeof(Slot) * HASH_SIZE);
    uint8_t* depth = (uint8_t*)std::calloc((size_t)n, 1);
    if (!table || !depth) {
        std::free(table); std::free(depth);
        res.status = 1;
        return res;
    }
    std::memset(table, 0xFF, sizeof(Slot) * HASH_SIZE);  // all -1

    int64_t n_seq = 0, n_lit = 0;
    int64_t lit_anchor = 0;
    int max_seen_depth = 0;
    bool overflow = false;

    auto push_seq = [&](int64_t ll, int64_t ml, int64_t src) {
        if (n_seq >= seq_cap || n_lit + ll > lit_cap) {
            overflow = true;
            return;
        }
        lit_lens[n_seq] = (uint8_t)ll;
        match_lens[n_seq] = (uint8_t)ml;
        srcs[n_seq] = (int32_t)src;
        n_seq++;
    };

    // emit the pending literal run [lit_anchor, upto) plus a match of
    // match_len bytes from match_src; either part may be zero
    auto emit = [&](int64_t upto, int64_t match_len, int64_t match_src) {
        int64_t run = upto - lit_anchor;
        const uint8_t* lp = in + lit_anchor;
        while (run > 255) {
            push_seq(255, 0, 0);
            if (overflow) return;
            std::memcpy(lits + n_lit, lp, 255);
            n_lit += 255; lp += 255; run -= 255;
        }
        int64_t ml = match_len > 255 ? 255 : match_len;
        push_seq(run, ml, match_src);
        if (overflow) return;
        if (run) { std::memcpy(lits + n_lit, lp, (size_t)run); n_lit += run; }
        match_len -= ml; match_src += ml;
        while (match_len > 0) {
            ml = match_len > 255 ? 255 : match_len;
            push_seq(0, ml, match_src);
            if (overflow) return;
            match_len -= ml; match_src += ml;
        }
        lit_anchor = upto;
    };

    // probe the three candidate generations at `pos`: the FIRST
    // occurrence ever (a stable early-corpus dictionary; also the only
    // slot far enough back to encode short-period runs, since matches
    // may not overlap their own output), the most recent depth-0
    // occurrence, and the most recent occurrence
    auto probe = [&](int64_t pos, int64_t& best_len, int64_t& best_src,
                     int& best_d) {
        uint64_t seq8 = load64(in + pos);
        uint32_t h = hash64(seq8);
        Slot& s = table[h];
        int64_t cands[3] = {s.anchor, s.shallow, s.recent};
        best_len = 0; best_src = -1; best_d = 0;
        for (int ci = 0; ci < 3; ci++) {
            int64_t c = cands[ci];
            if (c < 0 || c == best_src) continue;
            if (load64(in + c) != seq8) continue;
            // non-overlap invariant: source must end at or before dst
            int64_t cap = pos - c;
            if (cap > n - pos) cap = n - pos;
            if (cap < min_match) continue;
            // two-phase extension: word-wise equality first (the 8-byte
            // prefix is already known equal), then one linear scan of
            // the source's depth bytes, truncating at the first byte
            // that would push the match past max_depth
            int64_t len = 8;
            while (len + 8 <= cap) {
                uint64_t x = load64(in + c + len) ^ load64(in + pos + len);
                if (x) { len += __builtin_ctzll(x) >> 3; goto scanned; }
                len += 8;
            }
            while (len < cap && in[c + len] == in[pos + len]) len++;
        scanned:
            // cheap rejects BEFORE paying the depth scan
            if (len < min_match || len <= best_len) continue;
            int d;
            d = 0;
            for (int64_t k = 0; k < len; k++) {
                if (depth[c + k] >= max_depth) { len = k; break; }
                if (depth[c + k] > d) d = depth[c + k];
            }
            if (len < min_match || len <= best_len) continue;
            best_len = len;
            best_src = c;
            best_d = d + 1;
        }
        return h;
    };

    int64_t i = 0;
    int64_t next_bail = 1 << 20;
    // lazy carry: a deferred-to match probed at i+1 last iteration is
    // reused as this iteration's match instead of re-probing (the only
    // table insert since — the skipped position itself — can never win:
    // its cap is 1 < min_match)
    int64_t pend_len = 0, pend_src = -1;
    int pend_d = 0;
    bool pend_valid = false;
    while (i + 8 <= n && !overflow) {
        int64_t best_len, best_src;
        int best_d;
        uint32_t h;
        if (pend_valid) {
            h = hash64(load64(in + i));  // tables still learn this pos
            best_len = pend_len; best_src = pend_src; best_d = pend_d;
            pend_valid = false;
        } else {
            h = probe(i, best_len, best_src, best_d);
        }
        Slot& slot = table[h];
        if (slot.anchor < 0) slot.anchor = i;
        slot.recent = i;
        if (best_len && i + 9 <= n) {
            // one-step-lazy (LZ4-HC flavor): when the match starting at
            // the NEXT byte is strictly longer, keeping this byte
            // literal buys a longer sequence overall
            int64_t lazy_len, lazy_src;
            int lazy_d;
            probe(i + 1, lazy_len, lazy_src, lazy_d);
            if (lazy_len > best_len + 1) {
                slot.shallow = i;
                pend_len = lazy_len; pend_src = lazy_src; pend_d = lazy_d;
                pend_valid = true;
                i += 1;
                continue;
            }
        }
        if (best_len) {
            emit(i, best_len, best_src);
            std::memset(depth + i, best_d, (size_t)best_len);
            if (best_d > max_seen_depth) max_seen_depth = best_d;
            // sparse table inserts inside the match keep long repeats
            // findable without hashing every byte (LZ4's skip trick)
            int64_t step = best_len >= 64 ? best_len / 8 : 16;
            for (int64_t p = i + step; p + 8 <= i + best_len; p += step)
                table[hash64(load64(in + p))].recent = p;
            i += best_len;
            lit_anchor = i;
        } else {
            // this byte stays literal: depth 0 — remember it as a
            // shallow source for future matches
            slot.shallow = i;
            i += 1;
        }
        if (i >= next_bail) {
            next_bail += 1 << 20;
            // encoded-so-far must be beating the raw bytes consumed
            if (n_seq * 6 + n_lit > i - i / 8) overflow = true;
        }
    }
    if (!overflow && lit_anchor < n) emit(n, 0, 0);
    std::free(table); std::free(depth);
    if (overflow || n_seq * 6 + n_lit >= n - n / 8) {
        GlzResult r = {0, 0, 0, 1};
        return r;
    }
    res.n_seqs = n_seq;
    res.n_lits = n_lit;
    res.depth = max_seen_depth;
    return res;
}

// Reference decompressor (host-side): the sequential mirror of the
// device's gather rounds. Used by tests to round-trip fuzz corpora and
// as a debugging oracle; the production decode path is the traced JAX
// program in smartengine/tpu/glz.py.
int32_t glz_decompress(const uint8_t* lit_lens, const uint8_t* match_lens,
                       const int32_t* srcs, int64_t n_seqs,
                       const uint8_t* lits, int64_t n_lits,
                       uint8_t* out, int64_t out_len) {
    int64_t dst = 0, lp = 0;
    for (int64_t t = 0; t < n_seqs; t++) {
        int64_t ll = lit_lens[t], ml = match_lens[t];
        // zero-total sequences are INVALID glz: the device decode's
        // scatter+cumsum token labeling cannot represent them (staging
        // pads with zero-total entries only past the real count, where
        // they scatter out of range). The oracle must reject what the
        // device would misdecode.
        if (ll + ml == 0) return 5;
        if (dst + ll + ml > out_len) return 1;
        if (ll) {
            if (lp + ll > n_lits) return 2;
            std::memcpy(out + dst, lits + lp, (size_t)ll);
            lp += ll; dst += ll;
        }
        if (ml) {
            int64_t s = srcs[t];
            if (s < 0 || s + ml > dst) return 3;  // overlap = invalid glz
            std::memcpy(out + dst, out + s, (size_t)ml);
            dst += ml;
        }
    }
    return (dst == out_len && lp == n_lits) ? 0 : 4;
}

}  // extern "C"
