"""Striped wide-record device layout.

Records wider than the narrow layout's ``MAX_WIDTH`` used to spill every
batch to the interpreter (the record-too-wide ``TpuSpill``). Streaming
accelerators instead decompose variable-width records into fixed-width
tiles with segment bookkeeping (cf. Diba's segment streams and Sextans'
streaming tiling); this module is that decomposition for the
HBM-resident ``RecordBuffer``:

- a record of ``len`` bytes becomes K consecutive device rows ("stripes")
  of ``STRIPE_WIDTH`` bytes sharing a segment id, with a per-row
  ``(segment_id, stripe_idx, stripe_len)`` sidecar DERIVED ON DEVICE from
  the record lengths — the flat H2D copy stays the single contiguous
  ragged transfer the narrow path ships;
- consecutive stripes overlap by ``STRIPE_OVERLAP`` bytes, so any byte
  window up to the overlap length is wholly contained in some stripe:
  filter literals evaluate per stripe and reduce per segment
  (``jax.ops.segment_max`` over stripe verdicts) with no boundary miss;
- map transforms are restricted to the position-wise postop family
  (upper/lower), which commute with striping — outputs ship as the
  segment survivor bitmask and the host re-materializes from the slab it
  already holds (the narrow view-mode diet, unchanged);
- aggregate contributions evaluate on a segment-level state (full
  lengths, stripe-0 byte prefix) and the existing segmented-scan
  aggregate stages run unchanged over the segment axis, so carries
  accumulate per segment;
- array_map ``split`` explodes compute separator positions per stripe
  (each owned by exactly one stripe) and resolve cross-stripe element
  extents with a suffix-min over the segment's stripe rows.

Exactness bounds (build-time checked where possible, documented where
data-dependent):

- filter literals within ``STRIPE_OVERLAP`` (start-anchored: the stripe
  width) evaluate by windowed compare + segment reduce; non-literal
  regexes (and overlap-exceeding literals, whose ~1-state-per-byte
  DFAs need the gate raised) chain DFA state ACROSS stripes via
  transition composition (`striped_dfa_verdict` — exact at
  stripe joints, gated on ``FLUVIO_DFA_ASSOC_MAX_STATES``); a
  single-level ``JsonGet`` map carries the structural machine state
  across stripes (`striped_json_span`) and ships view descriptors;
  ``JsonGet``-sourced predicates run fused too — the same cross-stripe
  span machine resolves the field's absolute span, then short literals
  window-compare inside it (`striped_literal_in_span`) and non-literal
  regexes / overlap-exceeding literals chain an in-span DFA
  (`striped_dfa_in_span`, the round-2 de-spill) — while nested
  ``JsonGet`` sources, ``word_count``, and ``json_array`` explodes
  remain outside the subset — chains containing them keep the
  interpreter spill for wide batches;
- ``ParseInt`` contributions parse the record's leading int from the
  first stripe: a record whose int prefix (whitespace + sign + digits)
  extends past ``STRIPE_WIDTH`` bytes parses only the in-stripe prefix.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fluvio_tpu.ops.regex_dfa import UnsupportedRegex, compile_regex_cached, literal_of
from fluvio_tpu.analysis.envreg import env_int
from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.smartengine.tpu import kernels
from fluvio_tpu.smartengine.tpu.lower import Unlowerable, apply_postops, lower_expr
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.spans import stage_scope

STRIPE_WIDTH = 8192    # bytes per device row (pow2; must be 4-aligned)
STRIPE_OVERLAP = 128   # shared bytes between consecutive stripes


def stripe_params() -> Tuple[int, int]:
    """(stripe width, overlap) with env overrides for tests/benches.

    The step (width - overlap) must stay 4-aligned so stripe starts land
    on i32 word boundaries and the ragged word gather stays word-exact.
    """
    s = int(env_int("FLUVIO_STRIPE_WIDTH"))
    v = int(env_int("FLUVIO_STRIPE_OVERLAP"))
    if s % 4 or v % 4 or v >= s:
        raise ValueError(f"bad stripe params width={s} overlap={v}")
    return s, v


def stripe_counts(lengths: np.ndarray, s: int, v: int) -> np.ndarray:
    """Host mirror of the device stripe-count formula (must agree)."""
    step = s - v
    lengths = np.asarray(lengths, dtype=np.int64)
    return np.maximum(1, (np.maximum(lengths - v, 0) + step - 1) // step)


def plan_rows(lengths: np.ndarray, count: int, s: int, v: int) -> int:
    """Exact live stripe-row total for a batch (host side; the executor
    buckets it into the static compile shape)."""
    if count == 0:
        return 1
    return int(stripe_counts(lengths[:count], s, v).sum())


def plan_device(lengths, live, r: int, s: int, v: int) -> dict:
    """Derive the stripe sidecar on device from the record lengths.

    ``lengths`` is int32[n] (record rows, zero past the live count),
    ``live`` bool[n], ``r`` the static stripe-row count. Returns per
    stripe-row arrays: ``seg`` (record row), ``stripe_idx``,
    ``abs_start`` (byte offset of the stripe within its record),
    ``stripe_len``, ``row_live``, ``is_last``, plus the per-record
    ``first_row`` index and stripe count ``k``.
    """
    step = s - v
    n = lengths.shape[0]
    k = jnp.where(
        live,
        jnp.maximum(1, (jnp.maximum(lengths - v, 0) + step - 1) // step),
        0,
    ).astype(jnp.int32)
    cum = jnp.cumsum(k)
    r_live = cum[-1]
    rr = jnp.arange(r, dtype=jnp.int32)
    seg = jnp.searchsorted(cum, rr, side="right").astype(jnp.int32)
    seg_c = jnp.clip(seg, 0, n - 1)
    first_row = cum - k
    stripe_idx = rr - jnp.take(first_row, seg_c)
    row_live = rr < r_live
    seg_len = jnp.take(lengths, seg_c)
    abs_start = stripe_idx * step
    stripe_len = jnp.where(row_live, jnp.clip(seg_len - abs_start, 0, s), 0)
    is_last = row_live & (stripe_idx == jnp.take(k, seg_c) - 1)
    return {
        "seg": seg_c,
        "stripe_idx": stripe_idx,
        "abs_start": abs_start,
        "stripe_len": stripe_len,
        "row_live": row_live,
        "is_last": is_last,
        "first_row": first_row,
        "k": k,
        "step": step,
        "s": s,
        "v": v,
    }


def striped_repad_words(flat, lengths, plan, s: int):
    """Build the striped byte matrix [r, s] from the 4-aligned ragged
    flat upload (the narrow ``ragged_repad_words``'s rebuild: a stripe
    row is ``s // 4`` consecutive words of the flat, fetched as aligned
    blocks and shifted into place by `kernels.rows_from_word_starts`;
    stripe starts are word-aligned because the stripe step is
    4-aligned). Overlap bytes are read twice from the same flat: HBM
    cost only, never link bytes."""
    lengths = lengths.astype(jnp.int32)
    lengths4 = (lengths + 3) & ~3
    # i32 accumulator is safe: buffer.check_flat_addressing refused any
    # batch whose 4-aligned flat exceeds i32 before it staged
    word_starts = (jnp.cumsum(lengths4) - lengths4) >> 2  # noqa: FLV303
    ws = jnp.take(word_starts, plan["seg"]) + (plan["abs_start"] >> 2)
    words = kernels.rows_from_word_starts(flat, ws, s // 4)
    return kernels.unpack_row_bytes(words, plan["stripe_len"])


def owned_lengths(plan):
    """Bytes of each stripe row OWNED by that row: the overlap tail
    belongs to the next stripe; the last stripe owns through record end.
    Every record byte is owned by exactly one row, in (segment,
    stripe_idx) order — the invariant the split fan-out, the DFA chain,
    and the JsonGet carry all build on (ownership must not fork)."""
    return jnp.where(
        plan["is_last"],
        plan["stripe_len"],
        jnp.minimum(plan["step"], plan["stripe_len"]),
    )


def striped_dfa_verdict(sv, plan, dfa, n: int):
    """Regex match per segment via cross-stripe DFA state chaining.

    Each stripe row reduces its OWNED bytes to one transition function
    over DFA states (kernels.dfa_compose_columns — the associative-scan
    engine); a segmented `associative_scan` over the row axis composes
    them across each segment's rows, so the automaton state chains
    through stripe joints exactly — no overlap containment needed, which
    is what lifts the literal-only restriction on striped regex filters.
    The EOS symbol applies once per segment after the composition (PAD
    never runs: un-owned columns compose as identity, and `dfa_match`'s
    trailing PADs only preserve acceptance, which EOS-then-accept-check
    reproduces because accept states are absorbing).
    """
    r, s = sv.shape
    byte_class = jnp.asarray(dfa.byte_class.astype(np.int32))
    cls = jnp.take(byte_class, sv.astype(jnp.int32))
    jidx = jnp.arange(s, dtype=jnp.int32)[None, :]
    cls = jnp.where(jidx < owned_lengths(plan)[:, None], cls, -1)
    return _seg_dfa_accept(cls, plan, dfa, n)


def _seg_dfa_accept(cls, plan, dfa, n: int):
    """Segment verdicts from per-position class symbols int32[r, s]
    (-1 = identity): per-row composition (`kernels.dfa_compose_columns`),
    segmented composition across each segment's rows, one EOS per
    segment, accept check — the shared tail of the record-level and
    in-span striped DFA chains (the two must never diverge on the
    carry/EOS semantics)."""
    r = cls.shape[0]
    table_t = jnp.asarray(dfa.table.T.astype(np.int32))
    rowf = kernels.dfa_compose_columns(cls, table_t, dfa.n_states)  # [r, S]

    reset = plan["stripe_idx"] == 0

    def comb(a, b):
        ra, fa = a
        rb, fb = b
        return ra | rb, jnp.where(rb[..., None], fb, kernels.dfa_compose(fa, fb))

    _, f_incl = jax.lax.associative_scan(comb, (reset, rowf))
    last_row = jnp.clip(plan["first_row"] + plan["k"] - 1, 0, r - 1)
    seg_f = jnp.take(f_incl, last_row, axis=0)  # [n, S]
    state = seg_f[:, dfa.start]
    table_flat = jnp.asarray(dfa.table.reshape(-1).astype(np.int32))
    state = jnp.take(table_flat, state * dfa.n_classes + dfa.eos_class)
    return jnp.take(jnp.asarray(dfa.accept), state) & (plan["k"] > 0)


def striped_dfa_in_span(sv, plan, dfa, vst, vln, n: int):
    """Regex match per segment INSIDE a JsonGet-extracted field span.

    The same cross-stripe composition as `striped_dfa_verdict`, with the
    class stream additionally masked to the slab-absolute span
    ``[vst, vst+vln)``: bytes outside the span (or un-owned) compose as
    identity, so each row's transition function covers exactly its owned
    slice of the FIELD bytes and the segmented scan chains them across
    stripe joints — bit-equal to running the DFA over the extracted
    bytes. A missing or empty field composes pure identity and the EOS
    step evaluates the empty string, matching the narrow extract's
    ``json_get_bytes(...) or b""`` semantics. This is the chain that
    moves the non-literal-regex-over-JsonGet family (and, via the
    escaped-literal fallback, overlap-exceeding JsonGet literals) off
    the interpreter."""
    r, s = sv.shape
    byte_class = jnp.asarray(dfa.byte_class.astype(np.int32))
    cls = jnp.take(byte_class, sv.astype(jnp.int32))
    jidx = jnp.arange(s, dtype=jnp.int32)[None, :]
    lo = jnp.take(vst.astype(jnp.int32), plan["seg"])[:, None]
    hi = lo + jnp.take(vln.astype(jnp.int32), plan["seg"])[:, None]
    abs_pos = plan["abs_start"][:, None] + jidx
    owned = jidx < owned_lengths(plan)[:, None]
    in_span = (abs_pos >= lo) & (abs_pos < hi)
    cls = jnp.where(owned & in_span, cls, -1)
    return _seg_dfa_accept(cls, plan, dfa, n)


def striped_json_span(sv, plan, lengths, key: str, kmax: int, n: int):
    """Per-SEGMENT JsonGet field span over striped record bytes.

    The same structural machine as `kernels.json_get_span`
    (`kernels.json_step`), with the state carried ACROSS STRIPES: the
    outer scan walks stripe positions 0..kmax-1 and at position k feeds
    every segment's k-th stripe row through the machine simultaneously
    (n lanes), so a segment's carry flows from its stripe k into its
    stripe k+1 — spans that straddle stripe joints resolve exactly.
    Only OWNED columns are active (overlap bytes process once), and
    positions are absolute within the record, so the returned
    (start, length) are slab-valid view descriptors. ``kmax`` is the
    static per-record stripe-count bound (from the batch width bucket).
    """
    needle_arr, klen = kernels.json_needle(key)
    r, s = sv.shape
    step = plan["step"]
    ol = owned_lengths(plan)
    lengths = lengths.astype(jnp.int32)

    def outer(carry, k):
        rows = jnp.clip(plan["first_row"] + k, 0, r - 1)
        sm = jnp.take(sv, rows, axis=0)  # [n, s]
        ol_k = jnp.take(ol, rows)
        seg_active = k < plan["k"]
        base = k * step

        def inner(c, xs):
            col, j = xs
            active = seg_active & (j < ol_k)
            return (
                kernels.json_step(
                    c, col.astype(jnp.int32), base + j, active, needle_arr, klen
                ),
                None,
            )

        carry, _ = lax.scan(
            inner, carry, (sm.T, jnp.arange(s, dtype=jnp.int32))
        )
        return carry, None

    final, _ = lax.scan(
        outer,
        kernels.json_span_carry0(n),
        jnp.arange(max(kmax, 1), dtype=jnp.int32),
    )
    return kernels.json_span_finalize(final, lengths, lengths)


def striped_literal_in_span(sv, plan, lit: bytes, vst, vln, kind: str, n: int):
    """Literal predicate evaluated INSIDE a per-segment field span.

    ``(vst, vln)`` are slab-absolute (start, length) descriptors (from
    `striped_json_span`); the literal matches only where its window lies
    wholly within ``[vst, vst+vln)``. Per stripe row the windowed
    compare runs at OWNED byte positions: a window of ≤ overlap bytes
    starting at an owned byte is wholly contained in its row (non-last
    rows hold ``step + overlap = s`` bytes; last rows run to record
    end), so the per-row verdict OR per segment is exact — the same
    containment argument as record-level literals, shifted into the
    extracted field's absolute span. ``kind``: contains | startswith |
    endswith | equals (position-pinned against the span bounds).
    """
    r, s = sv.shape
    k = len(lit)
    if k == 0:
        # parity with the narrow kernels: an empty literal matches every
        # field for contains/startswith/endswith — but "equals" (an
        # anchored-empty regex like ^$) still requires the FIELD to be
        # empty, exactly like literal_startswith(b"") & (len == 0)
        if kind == "equals":
            return vln.astype(jnp.int32) == 0
        return jnp.ones((n,), dtype=bool)
    if k > s:
        return jnp.zeros((n,), dtype=bool)
    lo = jnp.take(vst.astype(jnp.int32), plan["seg"])  # [r]
    hi = lo + jnp.take(vln.astype(jnp.int32), plan["seg"])
    span = s - k + 1
    acc = jnp.ones((r, span), dtype=bool)
    for i, b in enumerate(lit):
        acc = acc & (sv[:, i : i + span] == b)
    jidx = jnp.arange(span, dtype=jnp.int32)[None, :]
    abs_pos = plan["abs_start"][:, None] + jidx
    owned = jidx < owned_lengths(plan)[:, None]
    fits = jidx + k <= plan["stripe_len"][:, None]
    m = acc & owned & fits
    in_span = (abs_pos >= lo[:, None]) & (abs_pos + k <= hi[:, None])
    if kind in ("startswith", "equals"):
        in_span = in_span & (abs_pos == lo[:, None])
    elif kind == "endswith":
        in_span = in_span & (abs_pos + k == hi[:, None])
    hit = seg_any(jnp.any(m & in_span, axis=1), plan, n)
    if kind == "equals":
        hit = hit & (vln.astype(jnp.int32) == k)
    return hit


def seg_any(verdict, plan, n: int):
    """Per-segment OR of per-stripe verdicts (the segment reduce the
    striped filter engine is built on)."""
    x = (verdict & plan["row_live"]).astype(jnp.int32)
    return (
        jax.ops.segment_max(
            x, plan["seg"], num_segments=n, indices_are_sorted=True
        )
        > 0
    )


def seg_state_of(plan, striped_values, lengths, arrays: dict, s: int) -> dict:
    """Segment-level state view: full record lengths + the stripe-0 byte
    prefix, alongside the un-striped meta columns. Narrow lowerings over
    this state are exact for length/key/const expressions and
    prefix-exact (within the first stripe) for byte parses."""
    n = lengths.shape[0]
    r = striped_values.shape[0]
    s0 = jnp.clip(plan["first_row"], 0, r - 1)
    seg_values = jnp.take(striped_values, s0, axis=0)
    live = plan["k"] > 0
    seg_values = jnp.where(live[:, None], seg_values, 0)
    return {
        "values": seg_values,
        "lengths": lengths.astype(jnp.int32),
        "keys": arrays["keys"],
        "key_lengths": arrays["key_lengths"],
        "offset_deltas": arrays["offset_deltas"],
        "timestamp_deltas": arrays["timestamp_deltas"],
    }


# ---------------------------------------------------------------------------
# Build-time lowering
# ---------------------------------------------------------------------------

_SEG_EXACT_NODES = (
    dsl.Cmp, dsl.Len, dsl.ParseInt, dsl.Value, dsl.Key, dsl.Const,
    dsl.Upper, dsl.Lower, dsl.And, dsl.Or, dsl.Not, dsl.Contains,
    dsl.StartsWith, dsl.EndsWith,
)


def _check_seg_exact(expr) -> None:
    """Whitelist for expressions evaluated on the segment-level state:
    length/key/const reads are exact; ``ParseInt`` over record bytes is
    prefix-exact within the first stripe (module docstring). Anything
    touching full record bytes structurally (JsonGet, Concat, regex)
    is rejected."""
    if not isinstance(expr, _SEG_EXACT_NODES):
        raise Unlowerable(f"{type(expr).__name__} not stripeable")
    for f in ("arg", "left", "right"):
        sub = getattr(expr, f, None)
        if isinstance(sub, dsl.Expr):
            _check_seg_exact(sub)
    for sub in getattr(expr, "args", []) or []:
        _check_seg_exact(sub)
    if isinstance(expr, (dsl.Contains, dsl.StartsWith, dsl.EndsWith)):
        # byte searches are only seg-exact over key/const sources; a
        # Value-sourced search must go through the striped kernels
        if _value_postops(expr.arg) is not None:
            raise Unlowerable("value search must lower striped")


def _value_postops(arg) -> Optional[Tuple[str, ...]]:
    """``arg`` as a postop chain over the record value: ``Upper(Lower(
    Value()))`` -> ("lower", "upper"). None when the byte source is not
    the record value (key/const — exact on the segment state); raises
    for sources that are neither (JsonGet etc.)."""
    if isinstance(arg, dsl.Value):
        return ()
    if isinstance(arg, (dsl.Upper, dsl.Lower)):
        inner = _value_postops(arg.arg)
        if inner is None:
            return None
        return inner + ("upper" if isinstance(arg, dsl.Upper) else "lower",)
    if isinstance(arg, (dsl.Key, dsl.Const)):
        return None
    raise Unlowerable(f"{type(arg).__name__} not stripeable as a byte source")


def _jsonget_source(arg) -> Optional[Tuple[str, Tuple[str, ...], Tuple[str, ...]]]:
    """``arg`` as a (postop-folded) single-level JsonGet over the record
    value: ``(key, pre, outer)`` — ``pre`` the folds inside the JsonGet
    arg (what the structural machine must see: case folds change key
    bytes), ``outer`` the folds applied to the extracted field bytes.
    None when the source is not a JsonGet; raises Unlowerable for a
    nested/structural JsonGet arg (one structural level, like the span
    map)."""
    outer: List[str] = []
    expr = arg
    while isinstance(expr, (dsl.Upper, dsl.Lower)):
        outer.append("upper" if isinstance(expr, dsl.Upper) else "lower")
        expr = expr.arg
    if not isinstance(expr, dsl.JsonGet):
        return None
    pre = _value_postops(expr.arg)
    if pre is None:
        raise Unlowerable("striped JsonGet must read the record value")
    outer.reverse()
    return expr.key, pre, tuple(outer)


def _cached_json_span(ctx, key: str, pre):
    """The cross-stripe span machine is the dominant cost of a JsonGet
    stage (an O(kmax·s·n) scan); a chain with several predicates (or a
    predicate plus the span map) over the same (key, postops) source
    must run it ONCE per batch. Memoized in the run ctx, keyed on the
    CURRENT stripe bytes' identity so a postop stage between two
    readers (which rebinds ctx["sv"]) correctly invalidates."""
    cache = ctx.setdefault("_span_cache", {})
    ck = (key, tuple(pre))
    hit = cache.get(ck)
    # the entry pins the SOURCE array it was computed from and is only
    # valid while ctx["sv"] *is* that object — an id()-keyed cache
    # could validate a stale entry after the old array is freed and a
    # new one reuses its id
    if hit is None or hit[0] is not ctx["sv"]:
        sv_pre = apply_postops(ctx["sv"], pre)
        span = striped_json_span(
            sv_pre, ctx["plan"], ctx["seg_state"]["lengths"], key,
            ctx["kmax"], ctx["n"],
        )
        hit = cache[ck] = (ctx["sv"], sv_pre, span)
    return hit[1], hit[2]


def _lower_striped_json_literal(
    kind: str, lit: bytes, key: str, pre, outer, s: int, v: int
):
    """One literal predicate over a JsonGet-extracted field — the spill
    family the ROADMAP names "JsonGet-sourced predicates", fused.

    The cross-stripe span machine (`striped_json_span`) resolves the
    field's slab-absolute (start, length); the literal then windows
    inside that span per stripe. Every kind needs containment within
    the overlap (the field can start anywhere in the record, so no
    stripe anchoring helps the anchored forms)."""
    if len(lit) > v:
        raise Unlowerable(
            f"JsonGet-sourced literal of {len(lit)} bytes exceeds the "
            f"stripe overlap ({v})"
        )

    def fn(ctx):
        sv_pre, (vst, vln) = _cached_json_span(ctx, key, pre)
        # outer folds transform the extracted bytes; they are
        # length-preserving, so the span positions stay valid and the
        # match runs on the fully folded stripe bytes
        sv_m = apply_postops(sv_pre, outer)
        return striped_literal_in_span(
            sv_m, ctx["plan"], lit, vst, vln, kind, ctx["n"]
        )

    return fn


def predicate_reads_json(expr) -> bool:
    """Does this (already-lowerable) predicate run the JsonGet span
    machine? Drives the chain's ``has_json_pred`` flag (kmax sizing)."""
    if isinstance(expr, (dsl.And, dsl.Or)):
        return any(predicate_reads_json(a) for a in expr.args)
    if isinstance(expr, dsl.Not):
        return predicate_reads_json(expr.arg)
    if isinstance(expr, (dsl.Contains, dsl.StartsWith, dsl.EndsWith,
                         dsl.RegexMatch)):
        try:
            return _jsonget_source(expr.arg) is not None
        except Unlowerable:
            return False
    return False


def _lower_striped_literal(kind: str, lit: bytes, postops, s: int, v: int):
    """One literal predicate over striped record bytes.

    ``kind``: contains | startswith | endswith | equals. Containment
    windows up to the overlap length are whole in some stripe, so the
    per-stripe verdict OR is exact; anchored forms additionally pin the
    stripe (first/last) and, for equals, the full segment length.
    """
    limit = s if kind in ("startswith", "equals") else v
    if len(lit) > limit:
        raise Unlowerable(
            f"literal of {len(lit)} bytes exceeds the stripe "
            f"{'width' if limit == s else 'overlap'} ({limit})"
        )

    def fn(ctx):
        sv = apply_postops(ctx["sv"], postops)
        slen = ctx["plan"]["stripe_len"]
        plan, n = ctx["plan"], ctx["n"]
        if kind == "contains":
            row = kernels.literal_search(sv, slen, lit)
            return seg_any(row, plan, n)
        if kind == "startswith":
            row = kernels.literal_startswith(sv, slen, lit)
            return seg_any(row & (plan["stripe_idx"] == 0), plan, n)
        if kind == "endswith":
            row = kernels.literal_endswith(sv, slen, lit)
            return seg_any(row & plan["is_last"], plan, n)
        # equals: start-anchored match plus exact segment length
        row = kernels.literal_startswith(sv, slen, lit)
        hit = seg_any(row & (plan["stripe_idx"] == 0), plan, n)
        return hit & (ctx["seg_state"]["lengths"] == len(lit))

    return fn


def lower_striped_predicate(expr, s: int, v: int) -> Callable:
    """Lower a filter predicate to fn(ctx) -> bool[n] (segment level).

    ``ctx`` carries ``sv`` (striped values, with any upstream postops
    already applied), ``plan``, ``seg_state``, ``n``.
    """
    if isinstance(expr, dsl.And):
        fns = [lower_striped_predicate(a, s, v) for a in expr.args]
        return lambda c: _fold(fns, c, lambda x, y: x & y)
    if isinstance(expr, dsl.Or):
        fns = [lower_striped_predicate(a, s, v) for a in expr.args]
        return lambda c: _fold(fns, c, lambda x, y: x | y)
    if isinstance(expr, dsl.Not):
        inner = lower_striped_predicate(expr.arg, s, v)
        return lambda c: ~inner(c)
    if isinstance(expr, dsl.Cmp):
        _check_seg_exact(expr)
        fn = lower_expr(expr)
        return lambda c: fn(c["seg_state"])
    if isinstance(expr, (dsl.Contains, dsl.StartsWith, dsl.EndsWith)):
        kind = {
            dsl.Contains: "contains",
            dsl.StartsWith: "startswith",
            dsl.EndsWith: "endswith",
        }[type(expr)]
        json_src = _jsonget_source(expr.arg)
        if json_src is not None:
            key, pre, outer = json_src
            try:
                return _lower_striped_json_literal(
                    kind, expr.literal, key, pre, outer, s, v
                )
            except Unlowerable:
                # literal longer than the overlap: no containment inside
                # the span — chain it as an in-span DFA instead (same
                # fallback as record-level overlap-exceeding literals)
                pass
            return _lower_striped_dfa_in_span(
                _literal_regex(expr.literal, kind), key, pre, outer
            )
        postops = _value_postops(expr.arg)
        if postops is None:  # key/const source: exact on the segment state
            _check_seg_exact(expr)
            fn = lower_expr(expr)
            return lambda c: fn(c["seg_state"])
        try:
            return _lower_striped_literal(kind, expr.literal, postops, s, v)
        except Unlowerable:
            # literal longer than the overlap: chain it across stripes
            # as a DFA instead of spilling (same fallback as the
            # literal-regex form below)
            pass
        return _lower_striped_dfa(_literal_regex(expr.literal, kind), postops)
    if isinstance(expr, dsl.RegexMatch):
        json_src = _jsonget_source(expr.arg)
        if json_src is not None:
            # JsonGet-sourced regex: the literal family fuses via the
            # windowed compare inside the span; everything else (real
            # regexes, overlap-exceeding literals) chains an in-span
            # DFA — the spill family the round-2 engine retired
            key, pre, outer = json_src
            info = literal_of(expr.pattern)
            if info is not None:
                lit, a_start, a_end = info
                if a_start and a_end:
                    kind = "equals"
                elif a_start:
                    kind = "startswith"
                elif a_end:
                    kind = "endswith"
                else:
                    kind = "contains"
                try:
                    return _lower_striped_json_literal(
                        kind, lit, key, pre, outer, s, v
                    )
                except Unlowerable:
                    pass  # overlap-exceeding: in-span DFA below
            return _lower_striped_dfa_in_span(expr.pattern, key, pre, outer)
        postops = _value_postops(expr.arg)
        if postops is None:
            raise Unlowerable("striped regex must read the record value")
        info = literal_of(expr.pattern)
        if info is not None:
            lit, a_start, a_end = info
            if a_start and a_end:
                kind = "equals"
            elif a_start:
                kind = "startswith"
            elif a_end:
                kind = "endswith"
            else:
                kind = "contains"
            try:
                return _lower_striped_literal(kind, lit, postops, s, v)
            except Unlowerable:
                # literal longer than the overlap: chain it as a DFA
                # instead of spilling (containment no longer needed)
                pass
        return _lower_striped_dfa(expr.pattern, postops)
    raise Unlowerable(f"{type(expr).__name__} not stripeable as a predicate")


def _literal_regex(lit: bytes, kind: str) -> str:
    """A literal predicate as an equivalent regex pattern (every byte
    \\xhh-escaped, so metacharacters and non-ASCII bytes are inert) —
    the bridge that lets overlap-exceeding JsonGet literals ride the
    in-span DFA chain."""
    body = "".join(f"\\x{b:02x}" for b in lit)
    pre = "^" if kind in ("startswith", "equals") else ""
    post = "$" if kind in ("endswith", "equals") else ""
    return pre + body + post


def _striped_dfa_gate(pattern: str):
    """Compile + state-count gate shared by the record-level DFA chain
    and the in-span DFA. Past the gate the striped build spills, with
    the cause on the decline counter: ``dfa-classes-overflow`` when the
    packed class ceiling reduced the limit, ``dfa-stripe-states``
    otherwise — distinct from the narrow lowering's "dfa-assoc-states"
    (one gate trip would otherwise double-count across the two builds,
    and the consequences differ: sequential scan vs spill)."""
    try:
        dfa = compile_regex_cached(pattern)
    except UnsupportedRegex as e:
        raise Unlowerable(str(e)) from e
    limit, reason = kernels.dfa_effective_max_states(dfa)
    if dfa.n_states > limit:
        TELEMETRY.add_decline(reason or "dfa-stripe-states")
        raise Unlowerable(
            f"DFA of {dfa.n_states} states exceeds the associative gate "
            "(FLUVIO_DFA_ASSOC_MAX_STATES)"
        )
    return dfa


def _lower_striped_dfa(pattern: str, postops):
    """Non-literal regex (or an overlap-exceeding literal) as a
    cross-stripe DFA chain — the composition trick that lifts the
    literal-only restriction on striped regex filters. Same state-count
    gate as the narrow associative path; past it the chain spills to the
    interpreter (with the decline reason on the telemetry counter)."""
    dfa = _striped_dfa_gate(pattern)

    def fn(ctx):
        sv = apply_postops(ctx["sv"], postops)
        return striped_dfa_verdict(sv, ctx["plan"], dfa, ctx["n"])

    return fn


def _lower_striped_dfa_in_span(pattern: str, key: str, pre, outer):
    """Regex over a JsonGet-extracted field as an in-span DFA chain
    (`striped_dfa_in_span`): the cross-stripe span machine resolves the
    field's slab-absolute bounds, the DFA composes over exactly those
    bytes. Same gate + spill semantics as `_lower_striped_dfa`."""
    dfa = _striped_dfa_gate(pattern)

    def fn(ctx):
        sv_pre, (vst, vln) = _cached_json_span(ctx, key, pre)
        # outer folds are length-preserving: span positions stay valid
        sv_m = apply_postops(sv_pre, outer)
        return striped_dfa_in_span(sv_m, ctx["plan"], dfa, vst, vln, ctx["n"])

    return fn


def _fold(fns, ctx, op):
    out = fns[0](ctx)
    for f in fns[1:]:
        out = op(out, f(ctx))
    return out


def _striped_view(value):
    """Classify a striped map value.

    ``("postops", ops)`` for a pure postop chain over the record value;
    ``("span", key, pre, total)`` for a single-level JsonGet view —
    ``pre`` are the folds the structural machine must see (those inside
    the JsonGet arg), ``total`` the full host-side view postops, which
    must equal the narrow build's `lower_span` postops for the same
    program (the executor cross-checks). Anything else (key/const
    sources, Concat, nested JsonGet) raises Unlowerable.
    """
    outer: List[str] = []
    expr = value
    while isinstance(expr, (dsl.Upper, dsl.Lower)):
        outer.append("upper" if isinstance(expr, dsl.Upper) else "lower")
        expr = expr.arg
    outer.reverse()  # application order is innermost-first
    if isinstance(expr, dsl.JsonGet):
        # _value_postops raises for a nested JsonGet arg (one structural
        # level) and returns None for key/const sources
        pre = _value_postops(expr.arg)
        if pre is None:
            raise Unlowerable("striped JsonGet must read the record value")
        return ("span", expr.key, pre, pre + tuple(outer))
    post = _value_postops(value)
    if post is None:
        raise Unlowerable("striped map must transform the record value")
    return ("postops", post)


def _make_span_fn(key: str, pre: Tuple[str, ...]):
    """JsonGet span op over the striped ctx: the machine consumes the
    (postop-folded) stripe bytes and emits slab-absolute descriptors
    (shared with any JsonGet predicate on the same source via the ctx
    span cache)."""

    def fn(ctx):
        _, span = _cached_json_span(ctx, key, pre)
        return span

    return fn


def _check_contribution(prog) -> None:
    if prog.contribution is not None:
        _check_seg_exact(prog.contribution)
    elif prog.kind == "word_count":
        # per-stripe word counts double-count tokens spanning overlap
        raise Unlowerable("word_count is not stripeable")


# ---------------------------------------------------------------------------
# Striped fan-out (array_map split mode, single-byte separator)
# ---------------------------------------------------------------------------

# packs (segment, byte position) into one int64 for the segment-fenced
# suffix-min; plain int so importing this module never initializes a
# jax backend (same rule as kernels._AGG_OPS neutrals)
_ENC_BASE = 1 << 22  # > MAX_RECORD_WIDTH


def striped_split_bounds(sv, plan, sep: int, n: int):
    """Element emission grid for ``value.split(sep)`` over striped rows.

    Each byte position is OWNED by exactly one stripe (the overlap tail
    belongs to the next stripe), so separator positions dedup by
    construction, and the row-major flag order is record order per
    segment. Element extents that cross stripe rows resolve with a
    suffix-min of each row's first separator position over the segment's
    rows. Returns (flag[r,s], abs_start[r,s], elen[r,s]).
    """
    r, s = sv.shape
    step = plan["step"]
    jidx = jnp.arange(s, dtype=jnp.int32)[None, :]
    owned = jidx < owned_lengths(plan)[:, None]
    m = (sv == sep) & owned

    # record-order predecessor of column 0: the previous stripe's last
    # owned byte (non-last rows own exactly `step` bytes), or record start
    prev_last = jnp.concatenate([jnp.zeros((1,), bool), m[:-1, step - 1]])
    col0_boundary = (plan["stripe_idx"] == 0) | prev_last
    prev_boundary = jnp.concatenate([col0_boundary[:, None], m[:, :-1]], axis=1)
    starts = owned & ~m & prev_boundary
    abs_pos = plan["abs_start"][:, None] + jidx

    # next separator at >= j: within-row next where one exists, else the
    # suffix-min of later rows' first separator — segment-fenced by
    # packing the segment id into the high bits of the encoded position
    row_next = kernels._next_index_ge(m, s)  # [r, s]; == s when none
    has_sep = jnp.any(m, axis=1)
    first_abs = jnp.where(
        has_sep,
        plan["abs_start"] + row_next[:, 0],
        jnp.int32(_ENC_BASE - 1),  # "no separator in this row" sentinel
    )
    enc = plan["seg"].astype(jnp.int64) * _ENC_BASE + first_abs.astype(jnp.int64)
    enc = jnp.where(plan["row_live"], enc, jnp.int64(2**62))
    suffix = jax.lax.cummin(enc[::-1])[::-1]
    after = jnp.concatenate([suffix[1:], jnp.full((1,), 2**62, jnp.int64)])
    cross_next = jnp.where(
        (after // _ENC_BASE == plan["seg"].astype(jnp.int64))
        & (after % _ENC_BASE < _ENC_BASE - 1),
        (after % _ENC_BASE).astype(jnp.int32),
        jnp.int32(-1),
    )
    # full record length per stripe row (the last stripe carries it; the
    # segment reduce broadcasts it to the earlier stripes)
    seg_last_len = jax.ops.segment_max(
        jnp.where(plan["is_last"], plan["abs_start"] + plan["stripe_len"], 0),
        plan["seg"],
        num_segments=n,
        indices_are_sorted=True,
    )
    row_rec_len = jnp.take(seg_last_len, plan["seg"])
    fallback = jnp.where(cross_next >= 0, cross_next, row_rec_len)
    next_abs = jnp.where(
        row_next < s,
        plan["abs_start"][:, None] + row_next,
        fallback[:, None],
    )
    elen = jnp.where(starts, next_abs - abs_pos, 0)
    return starts, jnp.where(starts, abs_pos, 0), elen


# ---------------------------------------------------------------------------
# Chain build + run
# ---------------------------------------------------------------------------


@dataclass
class StripedChain:
    """Stripe-capable lowering of a whole SmartModule chain.

    ``ops`` entries: ("filter", fn) | ("postops", tuple) |
    ("span", fn) | ("agg", aggregate_stage) | ("fanout", sep_byte).
    Postops accumulate into ``postops`` — the executor's host-side view
    materialization applies them (they must equal the narrow build's
    ``_view_postops``). A span op (JsonGet map) makes output values
    sub-record views: the executor ships its (start, length)
    descriptors instead of the whole-record mask.
    """

    ops: List = field(default_factory=list)
    postops: Tuple[str, ...] = ()
    fanout: bool = False
    has_agg: bool = False
    has_span: bool = False
    # a filter predicate runs the cross-stripe JsonGet span machine:
    # the executor must size kmax (the per-record stripe-count bound)
    # even though the chain ships no span-view outputs
    has_json_pred: bool = False

    @property
    def needs_kmax(self) -> bool:
        return self.has_span or self.has_json_pred

    def run(self, ctx, valid, carries, base_ts, agg_ctx):
        """Execute the striped chain; returns (valid[n], seg_state,
        carries, fan, vspan) — ``fan`` is the (flag, start, elen)
        emission grid for fan-out chains, ``vspan`` the per-segment
        (start, length) view descriptors for span chains (else None)."""
        fan = None
        vspan = None
        for i, (kind, arg) in enumerate(self.ops):
            # device scope per striped op (the op list is the striped
            # lowering's own: a filter_map is a filter op and a span op)
            with jax.named_scope(stage_scope(i, kind)):
                if kind == "filter":
                    valid = valid & arg(ctx)
                elif kind == "postops":
                    ctx["sv"] = apply_postops(ctx["sv"], arg)
                    ctx["seg_state"]["values"] = apply_postops(
                        ctx["seg_state"]["values"], arg
                    )
                elif kind == "span":
                    vspan = arg(ctx)
                elif kind == "agg":
                    st = dict(ctx["seg_state"])
                    st["valid"] = valid
                    agg_ctx["stage_index"] = i
                    st, carries = arg.apply(st, carries, base_ts, agg_ctx)
                    ctx["seg_state"] = st
                else:  # fanout (terminal)
                    fan = striped_split_bounds(
                        ctx["sv"], ctx["plan"], arg, ctx["n"]
                    )
        return valid, ctx["seg_state"], carries, fan, vspan


def try_build_striped(programs, stages, s: int, v: int) -> Optional[StripedChain]:
    """Striped lowering of the chain's resolved programs; None when any
    stage is outside the stripeable subset (wide batches then keep the
    interpreter spill). ``stages`` are the executor's narrow stages — the
    aggregate stages are REUSED so segment-level aggregation shares the
    narrow path's carry slots and scan kernels exactly."""
    from fluvio_tpu.smartengine.tpu import executor as _ex

    chain = StripedChain()
    try:
        for i, prog in enumerate(programs):
            terminal = chain.fanout or (
                chain.has_agg and not isinstance(prog, dsl.AggregateProgram)
            )
            if terminal:
                # aggregates only as a chain suffix; fan-out only last
                raise Unlowerable("stage after a striped terminal stage")
            if isinstance(prog, dsl.FilterProgram):
                if chain.has_span:
                    # downstream filters would read the extracted view,
                    # not the stripe bytes the striped predicates scan
                    raise Unlowerable("filter after a striped span map")
                chain.ops.append(
                    ("filter", lower_striped_predicate(prog.predicate, s, v))
                )
                chain.has_json_pred |= predicate_reads_json(prog.predicate)
            elif isinstance(prog, (dsl.MapProgram, dsl.FilterMapProgram)):
                if isinstance(prog, dsl.FilterMapProgram):
                    if chain.has_span:
                        raise Unlowerable("filter after a striped span map")
                    chain.ops.append(
                        ("filter", lower_striped_predicate(prog.predicate, s, v))
                    )
                    chain.has_json_pred |= predicate_reads_json(
                        prog.predicate
                    )
                if prog.key is not None:
                    raise Unlowerable("striped map cannot rewrite keys")
                view = _striped_view(prog.value)
                if view[0] == "postops":
                    post = view[1]
                    if post:
                        if not chain.has_span:
                            # after a span map the stripe bytes are dead;
                            # the fold applies host-side via `postops`
                            chain.ops.append(("postops", post))
                        chain.postops += post
                else:
                    _, key, pre, total = view
                    if chain.has_span:
                        raise Unlowerable("one striped span map per chain")
                    chain.ops.append(("span", _make_span_fn(key, pre)))
                    chain.has_span = True
                    chain.postops += total
            elif isinstance(prog, dsl.AggregateProgram):
                if chain.has_span:
                    # contributions evaluate on the segment state's
                    # stripe-0 prefix, not the extracted view
                    raise Unlowerable("aggregate after a striped span map")
                _check_contribution(prog)
                stage = stages[i]
                assert isinstance(stage, _ex._AggregateStage)
                chain.ops.append(("agg", stage))
                chain.has_agg = True
            elif isinstance(prog, dsl.ArrayMapProgram):
                if prog.mode != "split" or len(prog.sep) != 1:
                    raise Unlowerable(
                        "striped array_map supports single-byte split only"
                    )
                if chain.has_agg or chain.has_span:
                    raise Unlowerable("striped fan-out after aggregate/span")
                chain.ops.append(("fanout", prog.sep[0]))
                chain.fanout = True
            else:
                raise Unlowerable(f"{type(prog).__name__} not stripeable")
    except Unlowerable:
        return None
    return chain
