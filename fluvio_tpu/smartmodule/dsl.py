"""Declarative transform DSL — the TPU-lowerable SmartModule format.

The reference ships user logic as WASM; arbitrary code cannot run on a TPU,
so this framework defines a declarative program format for the transform
hot path. A DSL program is pure data (JSON-serializable — it is an artifact
format that crosses the wire like WASM payloads do), with two executors:

- the Python engine backend interprets it per record (reference semantics),
- the TPU engine backend lowers it to fused JAX kernels (regex -> DFA byte
  scans, JSON field access -> structural byte kernels, aggregate ->
  lax.scan) over the batched record buffer.

Both executors implement *exactly* the byte-level semantics defined here
(see `json_get_bytes`, `parse_int_prefix`), so outputs are bit-identical
across backends. Modules authored with arbitrary Python hooks and no DSL
program run only on the Python backend.

Expression types (over one record):

    Value()                  record value bytes
    Key()                    record key bytes (b"" when absent)
    Const(b)                 literal bytes
    Param(name, default)     chain-config parameter (resolved at build time)
    Upper(e) / Lower(e)      ASCII case fold
    Concat([e...])           byte concatenation
    JsonGet(e, key)          top-level JSON field extraction (see below)
    RegexMatch(e, pattern)   unanchored regex search -> bool
    Contains/StartsWith/EndsWith(e, lit) -> bool
    Len(e)                   length -> int
    ParseInt(e)              leading-integer parse -> int
    IntToBytes(i)            ASCII decimal render
    Cmp(op, a, b)            int comparison -> bool
    And/Or/Not               boolean combinators

Programs (one per transform kind):

    FilterProgram(predicate)
    MapProgram(value, key=None)          key=None preserves the input key
    FilterMapProgram(predicate, value, key=None)
    ArrayMapProgram(mode="json_array" | "split", sep=b"\\n")
    AggregateProgram(kind="sum_int"|"count"|"word_count"|"max_int"|"min_int",
                     window_ms=None)     window_ms -> windowed materialized
                                         view (accumulator resets per
                                         timestamp window; record key set to
                                         the window start)
    WindowProgram(key, event_time, contribution, combine, window_ms,
                  slide_ms, lateness_ms, emit="top"|"all")
                                         keyed sliding EVENT-TIME windows (an
                                         aggregate-kind program): one output
                                         row per closed (window, key), or per
                                         window only the key(s) whose aggregate
                                         is the window's maximum
    GroupProgram(key, event_time, bucket_ms, columns=[GroupColumn...])
                                         a keyed RUNNING aggregate (an
                                         aggregate-kind program; an unbounded
                                         GROUP BY): every record yields the
                                         row of its (key, time bucket) group
                                         as it stands after that record
"""

from __future__ import annotations

import re as _re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# Shared byte-level primitive semantics (single source of truth for both
# executors)
# ---------------------------------------------------------------------------


def json_get_bytes(value: bytes, key: str) -> bytes:
    """Extract a top-level JSON field's bytes by structural scan.

    Deterministic byte-level semantics (shared with the TPU kernel):
    find ``"key"`` at brace depth 1, skip ``:`` and whitespace, then

    - string value: the raw bytes between the quotes (escapes NOT
      processed; values containing escaped quotes are unsupported),
    - other values: bytes up to the next top-level ``,`` or ``}``,
      whitespace-trimmed.

    Missing key, non-object input, or malformed structure yield ``b""``.
    """
    needle = b'"' + key.encode("utf-8") + b'"'
    n = len(value)
    depth = 0
    in_str = False
    i = 0
    while i < n:
        c = value[i]
        if in_str:
            if c == 0x5C:  # backslash
                i += 2
                continue
            if c == 0x22:  # quote
                in_str = False
            i += 1
            continue
        if c == 0x22:
            # quote opens a string; check for the needle at depth 1
            if depth == 1 and value[i : i + len(needle)] == needle:
                j = i + len(needle)
                while j < n and value[j] in b" \t\r\n":
                    j += 1
                if j < n and value[j] == 0x3A:  # ':'
                    j += 1
                    while j < n and value[j] in b" \t\r\n":
                        j += 1
                    if j < n and value[j] == 0x22:  # string value
                        k = j + 1
                        while k < n and value[k] != 0x22:
                            if value[k] == 0x5C:
                                k += 1
                            k += 1
                        return value[j + 1 : k]
                    # scalar / nested value: until top-level , or }
                    k = j
                    d2 = 0
                    while k < n:
                        ck = value[k]
                        if ck in b"[{":
                            d2 += 1
                        elif ck in b"]}":
                            if d2 == 0:
                                break
                            d2 -= 1
                        elif ck == 0x2C and d2 == 0:  # ','
                            break
                        k += 1
                    return value[j:k].strip()
            in_str = True
            i += 1
            continue
        if c == 0x7B:  # '{'
            depth += 1
        elif c == 0x7D:  # '}'
            depth -= 1
        i += 1
    return b""


def json_array_elements(value: bytes) -> Optional[List[bytes]]:
    """Split a top-level JSON array into element byte-slices.

    Strings keep their quotes stripped; other elements are raw trimmed
    bytes. Returns None if the input is not a JSON array (transform error).
    """
    s = value.strip()
    if not s.startswith(b"[") or not s.endswith(b"]"):
        return None
    body = s[1:-1]
    elements: List[bytes] = []
    i = 0
    n = len(body)
    start = 0
    depth = 0
    in_str = False
    def push(seg: bytes) -> None:
        seg = seg.strip()
        if seg.startswith(b'"') and seg.endswith(b'"') and len(seg) >= 2:
            seg = seg[1:-1]
        if seg:
            elements.append(seg)
    while i < n:
        c = body[i]
        if in_str:
            if c == 0x5C:
                i += 2
                continue
            if c == 0x22:
                in_str = False
        elif c == 0x22:
            in_str = True
        elif c in b"[{":
            depth += 1
        elif c in b"]}":
            depth -= 1
        elif c == 0x2C and depth == 0:
            push(body[start:i])
            start = i + 1
        i += 1
    if start < n:
        push(body[start:n])
    return elements


def parse_int_prefix(value: bytes) -> int:
    """Parse a leading ASCII integer (optional ``-``); 0 if none."""
    i = 0
    n = len(value)
    while i < n and value[i] in b" \t\r\n":
        i += 1
    neg = False
    if i < n and value[i] in b"+-":
        neg = value[i] == 0x2D
        i += 1
    num = 0
    seen = False
    while i < n and 0x30 <= value[i] <= 0x39:
        num = num * 10 + (value[i] - 0x30)
        seen = True
        i += 1
    if not seen:
        return 0
    return -num if neg else num


def ascii_upper(value: bytes) -> bytes:
    return bytes((c - 32) if 0x61 <= c <= 0x7A else c for c in value)


def ascii_lower(value: bytes) -> bytes:
    return bytes((c + 32) if 0x41 <= c <= 0x5A else c for c in value)


def count_words(value: bytes) -> int:
    return len(value.split())


# ---------------------------------------------------------------------------
# Expression nodes
# ---------------------------------------------------------------------------

_NODE_REGISTRY: Dict[str, type] = {}


def _node(cls):
    _NODE_REGISTRY[cls.__name__] = cls
    return cls


@dataclass
class Expr:
    def to_json(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"op": type(self).__name__}
        for k, v in self.__dict__.items():
            if isinstance(v, Expr):
                d[k] = v.to_json()
            elif isinstance(v, bytes):
                d[k] = {"__bytes__": v.decode("latin-1")}
            elif isinstance(v, list):
                d[k] = [x.to_json() if isinstance(x, Expr) else x for x in v]
            else:
                d[k] = v
        return d

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "Expr":
        cls = _NODE_REGISTRY[d["op"]]
        kwargs = {}
        for k, v in d.items():
            if k == "op":
                continue
            if isinstance(v, dict) and "__bytes__" in v:
                kwargs[k] = v["__bytes__"].encode("latin-1")
            elif isinstance(v, dict) and "op" in v:
                kwargs[k] = Expr.from_json(v)
            elif isinstance(v, list):
                kwargs[k] = [
                    Expr.from_json(x) if isinstance(x, dict) and "op" in x else x
                    for x in v
                ]
            else:
                kwargs[k] = v
        return cls(**kwargs)


@_node
@dataclass
class Value(Expr):
    pass


@_node
@dataclass
class Key(Expr):
    pass


@_node
@dataclass
class Const(Expr):
    data: bytes = b""


@_node
@dataclass
class Param(Expr):
    """Chain-config parameter, resolved at build time to Const bytes."""

    name: str = ""
    default: Optional[str] = None


@_node
@dataclass
class Upper(Expr):
    arg: Expr = field(default_factory=Value)


@_node
@dataclass
class Lower(Expr):
    arg: Expr = field(default_factory=Value)


@_node
@dataclass
class Concat(Expr):
    args: List[Expr] = field(default_factory=list)


@_node
@dataclass
class JsonGet(Expr):
    arg: Expr = field(default_factory=Value)
    key: str = ""


@_node
@dataclass
class RegexMatch(Expr):
    arg: Expr = field(default_factory=Value)
    pattern: str = ""


@_node
@dataclass
class Contains(Expr):
    arg: Expr = field(default_factory=Value)
    literal: bytes = b""


@_node
@dataclass
class StartsWith(Expr):
    arg: Expr = field(default_factory=Value)
    literal: bytes = b""


@_node
@dataclass
class EndsWith(Expr):
    arg: Expr = field(default_factory=Value)
    literal: bytes = b""


@_node
@dataclass
class Len(Expr):
    arg: Expr = field(default_factory=Value)


@_node
@dataclass
class ParseInt(Expr):
    arg: Expr = field(default_factory=Value)


@_node
@dataclass
class IntToBytes(Expr):
    arg: Expr = None


@_node
@dataclass
class Cmp(Expr):
    cmp: str = "eq"  # eq ne lt le gt ge
    left: Expr = None
    right: Expr = None


@_node
@dataclass
class And(Expr):
    args: List[Expr] = field(default_factory=list)


@_node
@dataclass
class Or(Expr):
    args: List[Expr] = field(default_factory=list)


@_node
@dataclass
class Not(Expr):
    arg: Expr = None


# ---------------------------------------------------------------------------
# Programs
# ---------------------------------------------------------------------------


@_node
@dataclass
class FilterProgram(Expr):
    predicate: Expr = None


@_node
@dataclass
class MapProgram(Expr):
    value: Expr = None
    key: Optional[Expr] = None  # None -> preserve input key


@_node
@dataclass
class FilterMapProgram(Expr):
    predicate: Expr = None
    value: Expr = None
    key: Optional[Expr] = None


@_node
@dataclass
class ArrayMapProgram(Expr):
    mode: str = "json_array"  # or "split"
    sep: bytes = b"\n"


AGGREGATE_KINDS = ("sum_int", "count", "word_count", "max_int", "min_int")
AGGREGATE_COMBINES = ("add", "max", "min")  # associative monoids
AGGREGATE_COMBINE_NEUTRAL = {"add": 0, "max": -(2**63), "min": 2**63 - 1}


@_node
@dataclass
class AggregateProgram(Expr):
    """Stateful reduction (ref transforms/aggregate.rs:22-101).

    Two authoring forms:

    - canned ``kind`` (the 5 classic reductions), or
    - a user ``contribution`` int expression over the record combined
      into the accumulator by an associative ``combine`` monoid —
      e.g. max-by-json-field: ``contribution=ParseInt(JsonGet(Value(),
      "price")), combine="max"``. Associativity is what lets every
      backend (interpreter, native, TPU segmented scan) share exact
      semantics; the canned kinds are just prebuilt instances.
    """

    kind: str = "sum_int"
    window_ms: Optional[int] = None  # windowed materialized view when set
    contribution: Optional[Expr] = None  # int expr over the record
    combine: Optional[str] = None  # one of AGGREGATE_COMBINES


WINDOW_EMITS = ("top", "all")
# a window key is an int in [0, WINDOW_KEY_LIMIT): (key, window index)
# packs into one sortable int64 on the device (`windows/spec.py:
# KEY_STRIDE`, the same number); a key outside is dropped and counted
WINDOW_KEY_LIMIT = 1 << 31
INT64_MIN = -(2**63)


@_node
@dataclass
class WindowProgram(Expr):
    """Keyed sliding event-time windows, an AGGREGATE-kind program
    (NEXmark Q5 "hot items" is the model case).

    Every record yields a ``key`` (int in [0, 2**31)), an ``event_time``
    (epoch ms, int) and an int ``contribution``; it counts in every
    window ``[k*slide_ms, k*slide_ms + window_ms)`` that holds its event
    time, folded per (window, key) by the ``combine`` monoid (a record
    whose key is outside the range is dropped and counted invalid). The
    stream's watermark is ``max(event_time seen) - lateness_ms``; a
    window is emitted, once and complete, when the watermark reaches
    its end, and a contribution to a window already emitted is dropped
    and counted late. ``emit="all"`` emits every (window, key)
    aggregate, ``"top"`` only the key(s) whose aggregate equals the
    window's maximum. Output rows, ordered by (window end, key), are
    fresh records whose value is
    ``{"window_end":<ms>,"<key_field>":<key>,"<value_field>":<agg>}``.
    Windows still open when the stream ends are not emitted. The state
    (open windows, watermark) belongs to the consumer stream."""

    key: Expr = None
    event_time: Expr = None
    contribution: Expr = None
    combine: str = "add"
    window_ms: int = 0
    slide_ms: int = 0  # 0 -> tumbling (slide == window)
    lateness_ms: int = 0
    emit: str = "top"
    key_field: str = "key"
    value_field: str = "value"


def window_row_bytes(program: WindowProgram, window_end: int, key: int,
                     value: int) -> bytes:
    """The value of one `WindowProgram` output record: the ONE
    rendering both executors use."""
    return (
        b'{"window_end":%d,"%s":%d,"%s":%d}'
        % (window_end, program.key_field.encode(), key,
           program.value_field.encode(), value)
    )


GROUP_COLUMN_COMBINES = AGGREGATE_COMBINES + ("div",)
# a group's time bucket renders as the UTC date of its start, which the
# calendar (`datetime`) holds up to the year 9999
GROUP_TIME_LIMIT_MS = 253_402_300_800_000


@_node
@dataclass
class GroupColumn(Expr):
    """One named column of a `GroupProgram`'s row. ``combine`` add / max
    / min folds the record's int ``contribution`` into the group's
    accumulator, for records where ``where`` (a bool expression; None =
    every record) holds; a column that no record of the group has
    contributed to reads the monoid's neutral. ``combine="div"`` is a
    DERIVED column: ``num // den`` of two accumulator columns of the
    same program, named (0 where ``den`` is 0)."""

    name: str = ""
    combine: str = "add"
    contribution: Optional[Expr] = None
    where: Optional[Expr] = None
    num: str = ""
    den: str = ""


@_node
@dataclass
class GroupProgram(Expr):
    """A keyed running aggregate, an AGGREGATE-kind program: an
    unbounded ``GROUP BY key, event_time // bucket_ms`` answered as a
    changelog (NEXmark Q17 "auction statistics" is the model case).

    Every record yields a ``key`` (int in [0, 2**31)) and an
    ``event_time`` (epoch ms in [0, `GROUP_TIME_LIMIT_MS`)); its group
    is (key, event_time // bucket_ms). The record is folded into each
    accumulator column of its group, and ONE output record takes its
    place, at its own offset, whose value is the group's row after the
    fold (`group_row_bytes`). A record whose key cannot be formed (key
    or time out of range, or the bytes of a ``ParseInt`` key / time
    empty: a field missing) yields no output and is counted invalid.
    No watermark and nothing late: disorder changes which row a record
    sees, never whether it is answered. The table (one entry a group,
    never closed) belongs to the consumer stream and starts empty."""

    key: Expr = None
    event_time: Expr = None
    bucket_ms: int = 86_400_000
    columns: List[GroupColumn] = field(default_factory=list)
    key_field: str = "key"
    bucket_field: str = "day"


def group_accumulators(program: GroupProgram) -> List[GroupColumn]:
    """The program's accumulator columns, in row order (the table's
    lanes; a derived column has none)."""
    return [c for c in program.columns if c.combine != "div"]


def group_key(program: GroupProgram, value: bytes, key: Optional[bytes]):
    """(key, time bucket) of one record, or None where it has none: the
    ONE statement of which records a `GroupProgram` answers."""
    parts = []
    for expr in (program.key, program.event_time):
        if isinstance(expr, ParseInt) and not eval_expr(expr.arg, value, key):
            return None
        parts.append(int(eval_expr(expr, value, key)))
    k, t = parts
    if not (0 <= k < WINDOW_KEY_LIMIT and 0 <= t < GROUP_TIME_LIMIT_MS):
        return None
    return k, t // program.bucket_ms


def group_bucket_text(program: GroupProgram, bucket: int) -> bytes:
    """A time bucket as its row says it: the UTC date of its start."""
    import datetime

    day = datetime.datetime(1970, 1, 1) + datetime.timedelta(
        milliseconds=bucket * program.bucket_ms
    )
    return day.strftime("%Y-%m-%d").encode("ascii")


def group_row_bytes(program: GroupProgram, key: int, bucket: int,
                    accumulators) -> bytes:
    """The value of one `GroupProgram` output record: the ONE rendering
    both executors use. ``accumulators`` are the group's, in
    `group_accumulators` order."""
    acc = dict(zip((c.name for c in group_accumulators(program)), accumulators))
    parts = [b'{"%s":%d,"%s":"%s"' % (
        program.key_field.encode(), key, program.bucket_field.encode(),
        group_bucket_text(program, bucket),
    )]
    for c in program.columns:
        if c.combine == "div":
            v = acc[c.num] // acc[c.den] if acc[c.den] else 0
        else:
            v = acc[c.name]
        parts.append(b',"%s":%d' % (c.name.encode(), v))
    return b"".join(parts) + b"}"


# ---------------------------------------------------------------------------
# Build-time resolution & interpretation (reference semantics)
# ---------------------------------------------------------------------------


def _subst_str(s: str, params: Dict[str, str]) -> str:
    """``@param:name`` or ``@param:name=default`` string substitution."""
    if not isinstance(s, str) or not s.startswith("@param:"):
        return s
    spec = s[len("@param:") :]
    name, _, default = spec.partition("=")
    if name in params:
        return str(params[name])
    if _:
        return default
    raise KeyError(f"missing required SmartModule param {name!r}")


def resolve_params(expr: Expr, params: Dict[str, str]) -> Expr:
    """Substitute Param nodes and ``@param:`` strings (chain build time)."""
    if isinstance(expr, Param):
        if expr.name in params:
            return Const(str(params[expr.name]).encode("utf-8"))
        if expr.default is not None:
            return Const(expr.default.encode("utf-8"))
        raise KeyError(f"missing required SmartModule param {expr.name!r}")
    kwargs = {}
    for k, v in expr.__dict__.items():
        if isinstance(v, Expr):
            kwargs[k] = resolve_params(v, params)
        elif isinstance(v, list) and v and isinstance(v[0], Expr):
            kwargs[k] = [resolve_params(x, params) for x in v]
        elif isinstance(v, str):
            kwargs[k] = _subst_str(v, params)
        else:
            kwargs[k] = v
    resolved = type(expr)(**kwargs)
    # typed post-fixups for non-string fields configured via @param
    if isinstance(resolved, AggregateProgram) and isinstance(resolved.window_ms, str):
        resolved.window_ms = int(resolved.window_ms)
    if isinstance(resolved, WindowProgram):
        for name in ("window_ms", "slide_ms", "lateness_ms"):
            setattr(resolved, name, int(getattr(resolved, name)))
    if isinstance(resolved, GroupProgram):
        resolved.bucket_ms = int(resolved.bucket_ms)
    return resolved


class _Interp:
    """Per-record interpreter over resolved expressions."""

    def __init__(self) -> None:
        self._regex_cache: Dict[str, Any] = {}

    def _regex(self, pattern: str):
        r = self._regex_cache.get(pattern)
        if r is None:
            r = _re.compile(pattern.encode("utf-8"))
            self._regex_cache[pattern] = r
        return r

    def eval(self, expr: Expr, value: bytes, key: Optional[bytes]):
        e = self.eval
        if isinstance(expr, Value):
            return value
        if isinstance(expr, Key):
            return key if key is not None else b""
        if isinstance(expr, Const):
            return expr.data
        if isinstance(expr, Upper):
            return ascii_upper(e(expr.arg, value, key))
        if isinstance(expr, Lower):
            return ascii_lower(e(expr.arg, value, key))
        if isinstance(expr, Concat):
            return b"".join(e(a, value, key) for a in expr.args)
        if isinstance(expr, JsonGet):
            return json_get_bytes(e(expr.arg, value, key), expr.key)
        if isinstance(expr, RegexMatch):
            return self._regex(expr.pattern).search(e(expr.arg, value, key)) is not None
        if isinstance(expr, Contains):
            return expr.literal in e(expr.arg, value, key)
        if isinstance(expr, StartsWith):
            return e(expr.arg, value, key).startswith(expr.literal)
        if isinstance(expr, EndsWith):
            return e(expr.arg, value, key).endswith(expr.literal)
        if isinstance(expr, Len):
            return len(e(expr.arg, value, key))
        if isinstance(expr, ParseInt):
            return parse_int_prefix(e(expr.arg, value, key))
        if isinstance(expr, IntToBytes):
            return str(int(e(expr.arg, value, key))).encode("ascii")
        if isinstance(expr, Cmp):
            a = e(expr.left, value, key)
            b = e(expr.right, value, key)
            return {
                "eq": a == b,
                "ne": a != b,
                "lt": a < b,
                "le": a <= b,
                "gt": a > b,
                "ge": a >= b,
            }[expr.cmp]
        if isinstance(expr, And):
            return all(e(a, value, key) for a in expr.args)
        if isinstance(expr, Or):
            return any(e(a, value, key) for a in expr.args)
        if isinstance(expr, Not):
            return not e(expr.arg, value, key)
        raise TypeError(f"cannot interpret {type(expr).__name__}")


INTERP = _Interp()


def eval_expr(expr: Expr, value: bytes, key: Optional[bytes]):
    return INTERP.eval(expr, value, key)
