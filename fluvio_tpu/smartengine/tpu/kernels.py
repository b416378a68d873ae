"""JAX kernels over the RecordBuffer columns.

Every kernel is a pure function over padded arrays, vectorized across the
record axis (N lanes) with any per-byte iteration expressed as `lax.scan`
fixed-trip loops — no data-dependent Python control flow, so whole chains
fuse under one jit. Byte-level semantics are pinned by
`fluvio_tpu.smartmodule.dsl` (json_get_bytes / parse_int_prefix / ...);
tests assert bit-equality against those references.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from fluvio_tpu.ops.regex_dfa import CompiledDfa, classes_enabled
from fluvio_tpu.analysis.envreg import env_int

INT64_MIN = -(2**63)
INT64_MAX = 2**63 - 1


# ---------------------------------------------------------------------------
# Regex DFA scan
# ---------------------------------------------------------------------------


def dfa_match(values: jnp.ndarray, lengths: jnp.ndarray, dfa: CompiledDfa) -> jnp.ndarray:
    """Run a compiled DFA over each record; True where the regex matches.

    O(L) scan steps of N-lane gathers from a VMEM-resident flat table.
    Padding uses the PAD class (dead unless absorbed), end-of-record feeds
    one EOS symbol so ``$`` anchors work.
    """
    n, width = values.shape
    n_classes = dfa.n_classes
    table_flat = jnp.asarray(dfa.table.reshape(-1).astype(np.int32))
    byte_class = jnp.asarray(dfa.byte_class.astype(np.int32))
    accept = jnp.asarray(dfa.accept)
    lengths = lengths.astype(jnp.int32)

    def step(state, xs):
        col, t = xs
        cls = jnp.take(byte_class, col.astype(jnp.int32))
        cls = jnp.where(
            t < lengths,
            cls,
            jnp.where(t == lengths, dfa.eos_class, dfa.pad_class),
        )
        state = jnp.take(table_flat, state * n_classes + cls)
        return state, None

    state0 = jnp.full((n,), dfa.start, dtype=jnp.int32)
    final, _ = lax.scan(step, state0, (values.T, jnp.arange(width, dtype=jnp.int32)))
    # one trailing symbol for records exactly `width` long (EOS) / shorter (PAD)
    cls = jnp.where(lengths == width, dfa.eos_class, dfa.pad_class)
    final = jnp.take(table_flat, final * n_classes + cls)
    return jnp.take(accept, final)


# ---------------------------------------------------------------------------
# Associative-scan DFA engine (parallel-prefix automaton evaluation)
# ---------------------------------------------------------------------------
#
# Each byte column maps to a TRANSITION VECTOR over DFA states
# (tv[s] = next state from s on this column's symbol); vectors compose
# under an associative operator ((b . a)[s] = b[a[s]]), so a whole
# record's automaton run is a composition reduction — O(log L) depth via
# `lax.associative_scan` instead of the O(L) sequential `lax.scan` above,
# fully parallel across the record-lane axis. The trade is S x the work
# and S x the live material, hence the state-count gate
# (FLUVIO_DFA_ASSOC_MAX_STATES) and the column blocking below. The same
# composition is what a stripe-boundary carry needs: stripes.py composes
# per-stripe-row vectors across a segment's rows to chain DFA state
# across stripes.

DFA_ASSOC_MAX_STATES = 64  # default FLUVIO_DFA_ASSOC_MAX_STATES (packed tables)
DFA_ASSOC_MAX_STATES_UNPACKED = 16  # legacy gate when class packing is off
DFA_MAX_CLASSES = 32  # packed class ceiling the raised state default is sized for
_DFA_ASSOC_BLOCK = 256  # max columns composed per parallel tree
_DFA_ASSOC_BLOCK_ELEMS = 1 << 25  # live transition-vector element budget


def dfa_assoc_max_states() -> int:
    """State-count gate for the associative path: past it, the S x work
    multiplier loses to the sequential scan (and the transition material
    stops fitting VMEM-friendly tiles).

    The raised default (64) is sized for byte-class-packed tables, whose
    live material is classes x S rather than 258 x S. With packing
    disabled (FLUVIO_DFA_CLASSES=0) and no explicit operator override,
    the gate falls back to the legacy 16 — that pairing is the zero-cost
    tripwire's "today's paths" baseline."""
    if (
        os.environ.get("FLUVIO_DFA_ASSOC_MAX_STATES") is None
        and not classes_enabled()
    ):
        return DFA_ASSOC_MAX_STATES_UNPACKED
    return int(env_int("FLUVIO_DFA_ASSOC_MAX_STATES"))


def dfa_effective_max_states(dfa: CompiledDfa) -> Tuple[int, Optional[str]]:
    """Per-DFA associative gate: ``(limit, decline_reason | None)``.

    What the raised default actually budgets is the S x C live-element
    product, not S alone — so a PACKED table whose class count blew past
    DFA_MAX_CLASSES only keeps the legacy unpacked limit. When that
    reduction is what rejects the DFA, the decline reason is
    ``dfa-classes-overflow`` (distinct from the plain gate reasons so
    the two causes never blur in telemetry). An explicit
    FLUVIO_DFA_ASSOC_MAX_STATES override always wins: the operator
    pinned the limit, the heuristic steps aside. Mirrored by
    analysis/spec.py — keep prediction and runtime in lockstep."""
    limit = dfa_assoc_max_states()
    if (
        getattr(dfa, "packed", True)
        and dfa.n_classes > DFA_MAX_CLASSES
        and limit > DFA_ASSOC_MAX_STATES_UNPACKED
        and os.environ.get("FLUVIO_DFA_ASSOC_MAX_STATES") is None
    ):
        limit = DFA_ASSOC_MAX_STATES_UNPACKED
        if dfa.n_states > limit:
            return limit, "dfa-classes-overflow"
    return limit, None


def dfa_compose(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Compose transition vectors along the trailing state axis:
    ``(b . a)[s] = b[a[s]]`` — ``a`` applied first. Associative, which is
    the whole trick."""
    return jnp.take_along_axis(b, a, axis=-1)


def dfa_classes(values: jnp.ndarray, lengths: jnp.ndarray, dfa: CompiledDfa) -> jnp.ndarray:
    """Per-position byte-class symbols int32[n, width+1], including the
    end-of-record tail column (EOS at t == len, PAD beyond) — the same
    symbol stream `dfa_match` scans sequentially."""
    n, width = values.shape
    lengths = lengths.astype(jnp.int32)
    byte_class = jnp.asarray(dfa.byte_class.astype(np.int32))
    t = jnp.arange(width, dtype=jnp.int32)[None, :]
    cls = jnp.take(byte_class, values.astype(jnp.int32))
    cls = jnp.where(
        t < lengths[:, None],
        cls,
        jnp.where(t == lengths[:, None], dfa.eos_class, dfa.pad_class),
    )
    tail = jnp.where(lengths == width, dfa.eos_class, dfa.pad_class)
    return jnp.concatenate([cls, tail[:, None]], axis=1)


def _dfa_column_blocks(cls: jnp.ndarray, s_states: int):
    """Shared column-blocking scaffold for the composition scans below.

    Splits the column axis into blocks sized so live transition material
    stays under the element budget (rows x block x S), padding the tail
    with the -1 identity class. Returns ``(blocks [nb, rows, block],
    tv_of)`` where ``tv_of(cls_blk, table_t)`` builds the block's
    transition vectors ([rows, block, S]; identity where cls < 0). One
    home for the budget math and the identity encoding — the two scans
    must never diverge on them.
    """
    rows, t_len = cls.shape
    per_col = max(rows * s_states, 1)
    block = max(8, min(_DFA_ASSOC_BLOCK, _DFA_ASSOC_BLOCK_ELEMS // per_col))
    nb = -(-t_len // block)
    pad = nb * block - t_len
    if pad:
        cls = jnp.pad(cls, ((0, 0), (0, pad)), constant_values=-1)
    blocks = cls.reshape(rows, nb, block).transpose(1, 0, 2)

    def tv_of(cls_blk, table_t):
        return jnp.where(
            cls_blk[:, :, None] >= 0,
            jnp.take(
                table_t, jnp.clip(cls_blk, 0, table_t.shape[0] - 1), axis=0
            ),
            jnp.arange(s_states, dtype=jnp.int32)[None, None, :],
        )

    return blocks, tv_of


def dfa_compose_columns(
    cls: jnp.ndarray, table_t: jnp.ndarray, n_states: int
) -> jnp.ndarray:
    """Total transition function of each row's column sequence.

    ``cls`` int32[rows, T] (symbol class per column; -1 = identity, used
    for padding and un-owned stripe bytes), ``table_t`` int32[C, S] (the
    transposed transition table). Returns int32[rows, S].

    Columns split into blocks: within a block the per-column vectors
    compose in a log-depth `lax.associative_scan`, and one sequential
    `lax.scan` folds block results into the running composition. That
    bounds live transition material at rows x block x S elements
    (block shrinks as rows x S grows) instead of rows x T x S, while
    keeping the sequential depth at T/block instead of T.
    """
    rows = cls.shape[0]
    blocks, tv_of = _dfa_column_blocks(cls, n_states)
    ident = jnp.broadcast_to(
        jnp.arange(n_states, dtype=jnp.int32), (rows, n_states)
    )

    def one_block(carry, cls_blk):
        comp = lax.associative_scan(dfa_compose, tv_of(cls_blk, table_t), axis=1)[:, -1]
        return dfa_compose(carry, comp), None

    out, _ = lax.scan(one_block, ident, blocks)
    return out


def dfa_match_assoc(
    values: jnp.ndarray, lengths: jnp.ndarray, dfa: CompiledDfa
) -> jnp.ndarray:
    """`dfa_match` semantics via transition composition (bit-equal).

    Gate on `dfa_assoc_max_states` before choosing this path — see the
    section comment for the work/depth trade."""
    cls = dfa_classes(values, lengths, dfa)
    table_t = jnp.asarray(dfa.table.T.astype(np.int32))
    f = dfa_compose_columns(cls, table_t, dfa.n_states)
    return jnp.take(jnp.asarray(dfa.accept), f[:, dfa.start])


def dfa_prefix_states(
    cls: jnp.ndarray, table_t: jnp.ndarray, n_states: int, start: int
) -> jnp.ndarray:
    """EXCLUSIVE automaton state before each column: out[j] = the state
    after consuming columns [0, j) from ``start``.

    Same blocked composition as `dfa_compose_columns` (shared scaffold
    `_dfa_column_blocks`), but the block carry is the actual state (one
    int per row) and every within-block prefix evaluates at it —
    int32[rows, T] of per-position states for tiny automata used as
    structural masks (e.g. the 3-state JSON string/escape machine
    below)."""
    rows, t_len = cls.shape
    blocks, tv_of = _dfa_column_blocks(cls, n_states)

    def one_block(carry, cls_blk):
        pf = lax.associative_scan(dfa_compose, tv_of(cls_blk, table_t), axis=1)
        incl = jnp.take_along_axis(pf, carry[:, None, None], axis=2)[..., 0]
        excl = jnp.concatenate([carry[:, None], incl[:, :-1]], axis=1)
        return incl[:, -1], excl

    carry0 = jnp.full((rows,), start, dtype=jnp.int32)
    _, ys = lax.scan(one_block, carry0, blocks)
    return ys.transpose(1, 0, 2).reshape(rows, -1)[:, :t_len]


# the JSON string/escape automaton (exclusive-state form): 0 = outside
# any string, 1 = inside a string, 2 = inside with an escape pending.
# Mirrors the sequential machine's (in_str, esc) updates exactly —
# escapes exist only INSIDE strings, which is what the backslash-run
# parity heuristic it replaces got wrong on malformed input.
_STR_OUT, _STR_IN, _STR_ESC = 0, 1, 2
_STRING_TABLE_T = np.array(
    [
        [0, 1, 1],  # other:     OUT->OUT, IN->IN,  ESC->IN
        [1, 0, 1],  # quote:     OUT->IN,  IN->OUT, ESC->IN
        [0, 2, 1],  # backslash: OUT->OUT, IN->ESC, ESC->IN
    ],
    dtype=np.int32,
)


def string_state_excl(c: jnp.ndarray, inrec: jnp.ndarray) -> jnp.ndarray:
    """Per-position exclusive string-automaton state (int32[n, width])."""
    is_q = (c == 0x22) & inrec
    is_b = (c == 0x5C) & inrec
    # pinned: the unpinned pair would make cls (and the whole prefix
    # automaton's state arrays) weak i64 under the package-wide x64
    cls = jnp.where(is_q, jnp.int32(1), jnp.where(is_b, jnp.int32(2), jnp.int32(0)))
    cls = jnp.where(inrec, cls, -1)
    return dfa_prefix_states(cls, jnp.asarray(_STRING_TABLE_T), 3, _STR_OUT)


# ---------------------------------------------------------------------------
# JSON top-level field extraction (structural scan)
# ---------------------------------------------------------------------------

_P_SCAN, _P_COLON, _P_WS, _P_STR, _P_RAW, _P_DONE = range(6)


def extract_span(
    values: jnp.ndarray, start: jnp.ndarray, out_lengths: jnp.ndarray
) -> jnp.ndarray:
    """Materialize per-record substrings ``values[i, start:start+len]``.

    The gather half of every extraction kernel; span-producing kernels
    (`json_get_span` family) stay gather-free so the executor can ship
    descriptors instead of bytes and let XLA dead-code-eliminate this.
    """
    width = values.shape[1]
    idx = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    gathered = jnp.take_along_axis(values, jnp.clip(idx, 0, width - 1), axis=1)
    mask = jnp.arange(width, dtype=jnp.int32)[None, :] < out_lengths[:, None]
    return jnp.where(mask, gathered, 0).astype(jnp.uint8)


def pack_mask(valid: jnp.ndarray) -> jnp.ndarray:
    """bool[N] -> little-endian bitmask u8[N/8] (N padded to a byte).

    The survivor set crosses the host link as one bit per input row; the
    host rebuilds survivor indices with ``np.unpackbits(bitorder="little")``.
    """
    n = valid.shape[0]
    pad = (-n) % 8
    v = jnp.pad(valid.astype(jnp.uint8), (0, pad)) if pad else valid.astype(jnp.uint8)
    weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], dtype=jnp.uint8)
    return jnp.sum(v.reshape(-1, 8) * weights[None, :], axis=1, dtype=jnp.int32).astype(jnp.uint8)


def json_get(
    values: jnp.ndarray, lengths: jnp.ndarray, key: str
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-record top-level JSON field extraction.

    Returns ``(out_values u8[N, L], out_lengths i32[N])`` — missing/
    malformed yields length 0. Span computation + shared gather.
    """
    start, out_lengths = json_get_span(values, lengths, key)
    return extract_span(values, start, out_lengths), out_lengths


def json_needle(key: str) -> Tuple[jnp.ndarray, int]:
    """The quoted key byte needle the structural machine matches."""
    needle = b'"' + key.encode("utf-8") + b'"'
    return (
        jnp.asarray(np.frombuffer(needle, dtype=np.uint8).astype(np.int32)),
        len(needle),
    )


def json_span_carry0(n: int):
    """Initial machine state, one lane per record (see `json_step`)."""
    zeros_i = jnp.zeros((n,), dtype=jnp.int32)
    zeros_b = jnp.zeros((n,), dtype=bool)
    return (
        jnp.full((n,), _P_SCAN, dtype=jnp.int32),  # phase
        zeros_i,  # kmatch
        zeros_b,  # in_str
        zeros_b,  # esc
        zeros_i,  # depth
        zeros_i,  # d2
        zeros_b,  # vesc
        zeros_i,  # start
        zeros_i,  # end
        jnp.full((n,), -1, dtype=jnp.int32),  # lastnw
    )


def json_span_finalize(final, lengths: jnp.ndarray, start_cap):
    """End-of-record fixups (unterminated values run to the end) +
    (start, length) extraction from the machine's final state."""
    phase, _, _, _, _, _, _, start, end, lastnw = final
    end = jnp.where(phase == _P_STR, lengths, end)
    end = jnp.where(phase == _P_RAW, lastnw + 1, end)
    found = (phase == _P_DONE) | (phase == _P_STR) | (phase == _P_RAW)
    out_lengths = jnp.where(found, jnp.maximum(end - start, 0), 0).astype(jnp.int32)
    return jnp.clip(start, 0, start_cap), out_lengths


def json_get_span(
    values: jnp.ndarray, lengths: jnp.ndarray, key: str
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Field span (start, length) within each record's value bytes.

    Bit-identical to `dsl.json_get_bytes`: a byte state machine tracking
    (in-string, escape, brace depth, progressive needle match, value phase)
    as N-lane vectors, scanned over the L byte columns. The per-column
    update lives in `json_step` so the striped layout can run the same
    machine with a cross-stripe state carry (stripes.striped_json_span).
    """
    needle_arr, klen = json_needle(key)
    n, width = values.shape
    lengths = lengths.astype(jnp.int32)

    def step(carry, xs):
        col, t = xs
        return (
            json_step(carry, col.astype(jnp.int32), t, t < lengths, needle_arr, klen),
            None,
        )

    final, _ = lax.scan(
        step, json_span_carry0(n), (values.T, jnp.arange(width, dtype=jnp.int32))
    )
    return json_span_finalize(final, lengths, width)


def json_step(carry, c: jnp.ndarray, t, active: jnp.ndarray, needle_arr, klen: int):
    """One byte column through the structural machine.

    ``c`` int32 byte values, ``t`` the column's position WITHIN THE
    RECORD (a scalar or per-lane vector — the striped runner feeds
    absolute positions), ``active`` which lanes this column belongs to.
    Returns the updated carry tuple (shape of `json_span_carry0`).
    """
    (phase, kmatch, in_str, esc, depth, d2, vesc, start, end, lastnw) = carry
    is_ws = (c == 32) | (c == 9) | (c == 13) | (c == 10)
    is_quote = c == 0x22
    is_bslash = c == 0x5C

    # ---- phase COLON: ws -> stay; ':' -> WS phase; else abort+reprocess
    colon_here = (phase == _P_COLON) & (c == 0x3A)
    colon_stay = (phase == _P_COLON) & is_ws
    colon_abort = (phase == _P_COLON) & ~is_ws & (c != 0x3A)

    # ---- general scan applies in SCAN phase or on COLON abort
    g = (phase == _P_SCAN) | colon_abort

    # inside string
    gs = g & in_str
    s_esc_consume = gs & esc
    s_set_esc = gs & ~esc & is_bslash
    s_close = gs & ~esc & is_quote
    s_key_done = s_close & (kmatch == klen - 1)
    # progressive needle match on ordinary string bytes
    s_ordinary = gs & ~esc & ~is_bslash & ~is_quote
    expected = jnp.take(needle_arr, jnp.clip(kmatch, 0, klen - 1))
    k_next = jnp.where(
        (kmatch > 0) & (kmatch < klen - 1) & (c == expected), kmatch + 1, 0
    )

    # outside string
    go = g & ~in_str
    o_open = go & is_quote
    o_depth_up = go & (c == 0x7B)
    o_depth_dn = go & (c == 0x7D)

    new_in_str = jnp.where(
        active & s_close, False, jnp.where(active & o_open, True, in_str)
    )
    new_esc = jnp.where(active & gs, s_set_esc, esc)
    # both-literal where branches pin int32: under the package-wide x64
    # an unpinned pair is a weak i64 select (silent 64-bit emulation on
    # the VPU; the preflight jaxpr lint flags it as weak-64bit-promotion)
    new_depth = (
        depth
        + jnp.where(active & o_depth_up, jnp.int32(1), jnp.int32(0))
        - jnp.where(active & o_depth_dn, jnp.int32(1), jnp.int32(0))
    )
    new_kmatch = kmatch
    new_kmatch = jnp.where(active & s_ordinary, k_next, new_kmatch)
    new_kmatch = jnp.where(
        active & (s_set_esc | s_esc_consume | s_close), 0, new_kmatch
    )
    new_kmatch = jnp.where(
        active & o_open,
        jnp.where(depth == 1, jnp.int32(1), jnp.int32(0)),
        new_kmatch,
    )

    # ---- phase WS (after colon): skip ws, classify value start
    w = (phase == _P_WS) & active
    w_go = w & ~is_ws
    w_str = w_go & is_quote
    is_closer = (c == 0x5D) | (c == 0x7D) | (c == 0x2C)  # ] } ,
    w_empty = w_go & ~is_quote & is_closer
    w_raw = w_go & ~is_quote & ~is_closer
    w_raw_open = w_raw & ((c == 0x5B) | (c == 0x7B))

    # ---- phase STR (string value)
    s3 = (phase == _P_STR) & active
    s3_esc_consume = s3 & vesc
    s3_set_esc = s3 & ~vesc & is_bslash
    s3_close = s3 & ~vesc & is_quote

    # ---- phase RAW (scalar / nested value)
    s4 = (phase == _P_RAW) & active
    r_open = s4 & ((c == 0x5B) | (c == 0x7B))
    r_close = s4 & ((c == 0x5D) | (c == 0x7D))
    r_comma = s4 & (c == 0x2C)
    r_end = (r_close & (d2 == 0)) | (r_comma & (d2 == 0))
    r_dec = r_close & (d2 > 0)

    # ---- transitions
    new_phase = phase
    new_phase = jnp.where(active & s_key_done, _P_COLON, new_phase)
    new_phase = jnp.where(active & colon_here, _P_WS, new_phase)
    new_phase = jnp.where(active & colon_abort, _P_SCAN, new_phase)
    new_phase = jnp.where(w_str, _P_STR, new_phase)
    new_phase = jnp.where(w_empty, _P_DONE, new_phase)
    new_phase = jnp.where(w_raw, _P_RAW, new_phase)
    new_phase = jnp.where(s3_close, _P_DONE, new_phase)
    new_phase = jnp.where(r_end, _P_DONE, new_phase)

    new_vesc = jnp.where(s3, ~vesc & is_bslash, vesc)
    new_d2 = (
        d2
        + jnp.where(w_raw_open, jnp.int32(1), jnp.int32(0))
        + jnp.where(r_open, jnp.int32(1), jnp.int32(0))
        - jnp.where(r_dec, jnp.int32(1), jnp.int32(0))
    )
    new_start = jnp.where(w_str, t + 1, jnp.where(w_raw | w_empty, t, start))
    new_end = jnp.where(s3_close, t, jnp.where(r_end, lastnw + 1, jnp.where(w_empty, t, end)))
    new_lastnw = jnp.where((w_raw & ~is_ws) | (s4 & ~r_end & ~is_ws), t, lastnw)

    return (
        new_phase,
        new_kmatch,
        new_in_str,
        new_esc,
        new_depth,
        new_d2,
        new_vesc,
        new_start,
        new_end,
        new_lastnw,
    )


# ---------------------------------------------------------------------------
# Case folding, int parse/render, word count
# ---------------------------------------------------------------------------


def ascii_upper(values: jnp.ndarray) -> jnp.ndarray:
    lower = (values >= 0x61) & (values <= 0x7A)
    return jnp.where(lower, values - 32, values).astype(jnp.uint8)


def ascii_lower(values: jnp.ndarray) -> jnp.ndarray:
    upper = (values >= 0x41) & (values <= 0x5A)
    return jnp.where(upper, values + 32, values).astype(jnp.uint8)


def parse_int(values: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Leading ASCII integer per record (parity: dsl.parse_int_prefix)."""
    n, width = values.shape
    # scan the full width: leading whitespace is unbounded in the reference
    # semantics, so a fixed window would silently misparse padded values
    steps = width
    lengths = lengths.astype(jnp.int32)

    def step(carry, xs):
        phase, neg, num, seen, done = carry
        col, t = xs
        c = col.astype(jnp.int32)
        active = (t < lengths) & ~done
        is_ws = (c == 32) | (c == 9) | (c == 13) | (c == 10)
        is_digit = (c >= 0x30) & (c <= 0x39)
        is_sign = (c == 0x2B) | (c == 0x2D)

        p0 = active & (phase == 0)
        p1 = active & (phase == 1)

        start_digit = p0 & is_digit
        start_sign = p0 & is_sign
        cont_digit = p1 & is_digit

        new_num = jnp.where(
            start_digit,
            (c - 0x30).astype(jnp.int64),
            jnp.where(cont_digit, num * 10 + (c - 0x30).astype(jnp.int64), num),
        )
        new_seen = seen | start_digit | cont_digit
        new_neg = jnp.where(start_sign, c == 0x2D, neg)
        new_phase = jnp.where(start_digit | start_sign, 1, phase)
        new_done = done | (p0 & ~is_ws & ~is_digit & ~is_sign) | (p1 & ~is_digit)
        return (new_phase, new_neg, new_num, new_seen, new_done), None

    zeros_b = jnp.zeros((n,), dtype=bool)
    carry0 = (
        jnp.zeros((n,), dtype=jnp.int32),
        zeros_b,
        jnp.zeros((n,), dtype=jnp.int64),
        zeros_b,
        zeros_b,
    )
    cols = values[:, :steps].T
    (phase, neg, num, seen, done), _ = lax.scan(
        step, carry0, (cols, jnp.arange(steps, dtype=jnp.int32))
    )
    return jnp.where(seen, jnp.where(neg, -num, num), 0)


_POW10 = np.ones(20, dtype=np.uint64)
for _i in range(1, 20):
    _POW10[_i] = _POW10[_i - 1] * np.uint64(10)

INT_ASCII_WIDTH = 20  # sign + 19 digits covers all of int64


def int_to_ascii(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Render int64 -> ASCII decimal. Returns (u8[N, 20], lengths[N])."""
    n = x.shape[0]
    neg = x < 0
    xu = x.astype(jnp.uint64)
    mag = jnp.where(neg, (~xu) + jnp.uint64(1), xu)  # |x| exact incl. INT64_MIN
    pow10 = jnp.asarray(_POW10)
    ndigits = 1 + jnp.sum(
        mag[:, None] >= pow10[None, 1:20], axis=1
    ).astype(jnp.int32)
    length = ndigits + neg.astype(jnp.int32)

    j = jnp.arange(INT_ASCII_WIDTH, dtype=jnp.int32)[None, :]
    digit_idx = j - neg[:, None].astype(jnp.int32)
    pos = ndigits[:, None] - 1 - digit_idx
    pos_c = jnp.clip(pos, 0, 19)
    digit = (mag[:, None] // jnp.take(pow10, pos_c)) % jnp.uint64(10)
    ch = (digit.astype(jnp.int32) + 0x30).astype(jnp.uint8)
    out = jnp.where((j == 0) & neg[:, None], jnp.uint8(0x2D), ch)
    in_range = (digit_idx >= 0) & (digit_idx < ndigits[:, None])
    sign_pos = (j == 0) & neg[:, None]
    out = jnp.where(in_range | sign_pos, out, 0).astype(jnp.uint8)
    return out, length


def count_words(values: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Whitespace-separated token count per record (parity: bytes.split())."""
    n, width = values.shape
    c = values.astype(jnp.int32)
    is_ws = (c == 32) | (c == 9) | (c == 13) | (c == 10) | (c == 11) | (c == 12)
    in_rec = jnp.arange(width, dtype=jnp.int32)[None, :] < lengths[:, None].astype(jnp.int32)
    nonws = (~is_ws) & in_rec
    prev_ws = jnp.concatenate(
        [jnp.ones((n, 1), dtype=bool), ~nonws[:, :-1]], axis=1
    )
    starts = nonws & prev_ws
    return jnp.sum(starts, axis=1).astype(jnp.int64)


# ---------------------------------------------------------------------------
# array_map element bounds (fan-out engine)
# ---------------------------------------------------------------------------
#
# Bounds kernels emit per-position grids: flag[N, W] marks an element
# EMISSION position (ascending position = element order within the record)
# carrying payload (start, len) — the element's span within the record's
# value bytes. A per-record "final segment" triple covers the one element a
# scan can only finalize at end-of-record. The fan-out stage scatters these
# into capacity rows; outputs stay views of the input slab, so the whole
# explode ships as (src, start, len) descriptors.

_WS_BYTES = (9, 10, 11, 12, 13, 32)  # bytes.strip() whitespace set


def _is_ws(c: jnp.ndarray) -> jnp.ndarray:
    out = c == _WS_BYTES[0]
    for w in _WS_BYTES[1:]:
        out = out | (c == w)
    return out


def split_bounds(values: jnp.ndarray, lengths: jnp.ndarray, sep: bytes):
    """Element bounds for ``value.split(sep)`` with empties dropped
    (parity: python_backend ArrayMap split mode — bytes.split semantics:
    non-overlapping left-to-right separator matches).

    Returns (flag[N,W], start[N,W], elen[N,W], fflag[N], fstart[N],
    felen[N], err[N]); err is always False for split mode.
    """
    n, width = values.shape
    lengths = lengths.astype(jnp.int32)
    c = values.astype(jnp.int32)
    jidx = jnp.arange(width, dtype=jnp.int32)[None, :]
    inrec = jidx < lengths[:, None]
    no_final = jnp.zeros((n,), dtype=bool)
    zeros_n = jnp.zeros((n,), dtype=jnp.int32)
    if len(sep) == 1:
        m = (c == sep[0]) & inrec
        prev_boundary = jnp.concatenate(
            [jnp.ones((n, 1), dtype=bool), m[:, :-1]], axis=1
        ) | (jidx == 0)
        starts = inrec & ~m & prev_boundary
        cond = m | ~inrec
        nxt = _next_index_ge(cond, width)
        elen = nxt - jidx
        return (
            starts,
            jnp.broadcast_to(jidx, (n, width)),
            jnp.where(starts, elen, 0),
            no_final,
            zeros_n,
            zeros_n,
            no_final,
        )

    # multi-byte separator: greedy left-to-right matches need a scan
    L = len(sep)
    match = jnp.ones((n, width), dtype=bool)
    for i, b in enumerate(sep):
        shifted = (
            c[:, i:] if i == 0 else jnp.pad(c[:, i:], ((0, 0), (0, i)), constant_values=-1)
        )
        match = match & (shifted == b)
    match = match & (jidx + L <= lengths[:, None])

    def step(carry, xs):
        skip, seg_start = carry
        m_col, t = xs
        is_sep = m_col & (t >= skip)
        ln = t - seg_start
        emit = is_sep & (ln > 0)
        y = (emit, jnp.where(emit, seg_start, 0), jnp.where(emit, ln, 0))
        skip = jnp.where(is_sep, t + L, skip)
        seg_start = jnp.where(is_sep, t + L, seg_start)
        return (skip, seg_start), y

    carry0 = (jnp.zeros((n,), dtype=jnp.int32), jnp.zeros((n,), dtype=jnp.int32))
    (skip, seg_start), ys = lax.scan(
        step, carry0, (match.T, jnp.arange(width, dtype=jnp.int32))
    )
    flag, start_g, len_g = (y.T for y in ys)
    flen = lengths - seg_start
    fflag = flen > 0
    return flag, start_g, len_g, fflag, seg_start, jnp.where(fflag, flen, 0), no_final


def json_array_bounds(values: jnp.ndarray, lengths: jnp.ndarray):
    """Element bounds for a top-level JSON array explode.

    Bit-identical to `dsl.json_array_elements`: outer-whitespace strip,
    ``[``/``]`` bracket check (err when absent), depth-0 comma split
    respecting strings/escapes, per-segment whitespace trim, quote strip
    on fully-quoted segments, empty segments dropped. Returns the same
    7-tuple as `split_bounds` (final-segment slots unused; elements all
    finalize at a comma or the closing bracket, both in-grid positions).
    """
    n, width = values.shape
    lengths = lengths.astype(jnp.int32)
    c = values.astype(jnp.int32)
    jidx = jnp.arange(width, dtype=jnp.int32)[None, :]
    inrec = jidx < lengths[:, None]
    ws = _is_ws(c)
    nonws = ~ws & inrec
    big = jnp.int32(width)
    fa = jnp.min(jnp.where(nonws, jidx, big), axis=1)
    fb = jnp.max(jnp.where(nonws, jidx, -1), axis=1)
    fa_c = jnp.clip(fa, 0, width - 1)
    fb_c = jnp.clip(fb, 0, width - 1)
    open_b = jnp.take_along_axis(c, fa_c[:, None], axis=1)[:, 0]
    close_b = jnp.take_along_axis(c, fb_c[:, None], axis=1)[:, 0]
    err = (fa >= big) | (open_b != 0x5B) | (close_b != 0x5D) | (fb <= fa)

    def step(carry, xs):
        in_str, esc, depth, seg_fnw, seg_lnw, first_b, last_b = carry
        col, ws_col, t = xs
        body = (t > fa) & (t < fb) & ~err
        closer = (t == fb) & ~err

        # string/escape state (reference: backslash inside a string skips
        # the next byte entirely)
        consume = body & in_str & esc
        set_esc = body & in_str & ~esc & (col == 0x5C)
        s_close = body & in_str & ~esc & ~set_esc & (col == 0x22)
        o_open = body & ~in_str & (col == 0x22)
        o_up = body & ~in_str & ((col == 0x5B) | (col == 0x7B))
        o_dn = body & ~in_str & ((col == 0x5D) | (col == 0x7D))
        comma = body & ~in_str & (col == 0x2C) & (depth == 0)
        boundary = comma | closer

        # segment trim trackers skip the delimiter itself
        upd = body & ~ws_col & ~comma
        fresh = seg_fnw < 0
        n_fnw = jnp.where(upd & fresh, t, seg_fnw)
        n_first = jnp.where(upd & fresh, col, first_b)
        n_lnw = jnp.where(upd, t, seg_lnw)
        n_last = jnp.where(upd, col, last_b)

        has = n_fnw >= 0
        quoted = has & (n_first == 0x22) & (n_last == 0x22) & (n_lnw > n_fnw)
        st = jnp.where(quoted, n_fnw + 1, n_fnw)
        en = jnp.where(quoted, n_lnw - 1, n_lnw)
        ln = en - st + 1
        emit = boundary & has & (ln > 0)
        y = (emit, jnp.where(emit, st, 0), jnp.where(emit, ln, 0))

        n_in_str = jnp.where(s_close, False, jnp.where(o_open, True, in_str))
        n_esc = jnp.where(body & in_str, set_esc, esc)
        n_depth = depth + o_up.astype(jnp.int32) - o_dn.astype(jnp.int32)
        reset = boundary
        carry = (
            n_in_str,
            n_esc,
            n_depth,
            jnp.where(reset, -1, n_fnw),
            jnp.where(reset, -1, n_lnw),
            jnp.where(reset, 0, n_first),
            jnp.where(reset, 0, n_last),
        )
        return carry, y

    zeros_b = jnp.zeros((n,), dtype=bool)
    zeros_i = jnp.zeros((n,), dtype=jnp.int32)
    carry0 = (
        zeros_b,
        zeros_b,
        zeros_i,
        jnp.full((n,), -1, dtype=jnp.int32),
        jnp.full((n,), -1, dtype=jnp.int32),
        zeros_i,
        zeros_i,
    )
    _, ys = lax.scan(
        step, carry0, (c.T, ws.T, jnp.arange(width, dtype=jnp.int32))
    )
    flag, start_g, len_g = (y.T for y in ys)
    return flag, start_g, len_g, zeros_b, zeros_i, zeros_i, err


def fanout_scatter(
    flag, start_g, len_g, fflag, fstart, flen, contributing, cap: int
):
    """Compact element descriptors into ``cap`` output rows.

    Placement: exclusive prefix sum of per-record element counts gives
    each record's base row; elements order by emission position; the
    final-segment slot lands after a record's grid elements. Returns
    (total, local_row[cap], rel_start[cap], elen[cap]) — total is exact
    (pre-cap), so the caller can detect overflow and retry with a larger
    bucketed capacity.

    Formulated as gather, not scatter: the target indices are strictly
    increasing in flattened (row-major, final-slot-after-grid) order, so
    the inverse permutation is ``searchsorted(cumsum(flags), 1..cap)`` —
    a log-depth prefix sum plus a vectorized binary search. TPU scatters
    lower to sort-based loops; three n*width-element scatters were the
    dominant device cost of the explode chain.
    """
    n, width = flag.shape
    flag = flag & contributing[:, None]
    fflag = fflag & contributing
    # flattened emission order: each record's grid columns then its
    # final-segment slot — one (n, width+1) flag/start/len set
    allflag = jnp.concatenate([flag, fflag[:, None]], axis=1).reshape(-1)
    allstart = jnp.concatenate([start_g, fstart[:, None]], axis=1).reshape(-1)
    alllen = jnp.concatenate([len_g, flen[:, None]], axis=1).reshape(-1)
    cum = jnp.cumsum(allflag.astype(jnp.int32))
    total = cum[-1]
    pos = jnp.searchsorted(
        cum, jnp.arange(1, cap + 1, dtype=jnp.int32), side="left"
    )
    pos = jnp.clip(pos, 0, allflag.shape[0] - 1)
    live = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(total, jnp.int32(cap))
    out_row = jnp.where(live, pos // jnp.int32(width + 1), 0)
    out_start = jnp.where(live, jnp.take(allstart, pos), 0)
    out_len = jnp.where(live, jnp.take(alllen, pos), 0)
    return total, out_row, out_start, out_len


# ---------------------------------------------------------------------------
# Segmented prefix scans (aggregate engine)
# ---------------------------------------------------------------------------

# neutrals stay plain ints — creating jax arrays at import time would
# force backend initialization as an import side effect
_AGG_OPS = {
    "add": (0, lambda a, b: a + b),
    "max": (INT64_MIN, jnp.maximum),
    "min": (INT64_MAX, jnp.minimum),
}


def segmented_scan(
    x: jnp.ndarray, reset: jnp.ndarray, op_name: str
) -> jnp.ndarray:
    """Inclusive segmented scan: resets start a new running value.

    The add monoid rides primitive cumulative ops instead of a
    tuple-carry ``associative_scan``: ``out[i] = cumsum[i] -
    cumsum[last_reset(i) - 1]`` with the last reset position found by a
    ``cummax`` over flagged indices. Bit-exact (int64 addition is
    associative under any reassociation) and a far smaller XLA program —
    the tuple scan unrolls ~log2(n) tuple-where steps, which dominated
    the aggregate configs' 85-119 s on-chip compiles.
    """
    if op_name == "add":
        n = x.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        c = jnp.cumsum(x)
        last_reset = lax.cummax(jnp.where(reset, idx, -1))
        base = jnp.where(
            last_reset >= 1,
            jnp.take(c, jnp.clip(last_reset - 1, 0, n - 1)),
            jnp.zeros((), c.dtype),
        )
        return c - base
    _, op = _AGG_OPS[op_name]

    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, op(va, vb))

    _, out = lax.associative_scan(combine, (reset, x))
    return out


def last_true_value(
    flags: jnp.ndarray, values: jnp.ndarray, fallback: jnp.ndarray
) -> jnp.ndarray:
    """Value at the last True flag, else fallback (scalar)."""
    n = flags.shape[0]
    idxs = jnp.where(flags, jnp.arange(n, dtype=jnp.int32), -1)
    li = jnp.max(idxs)
    return jnp.where(li >= 0, values[jnp.clip(li, 0, n - 1)], fallback)


def propagate_last_valid(
    values: jnp.ndarray, valid: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inclusive forward-fill of the last valid value; (filled, has_any).

    One ``cummax`` over flagged indices + one gather replaces the
    tuple-carry ``associative_scan`` (same compile-size rationale as
    ``segmented_scan``'s add path). Rows before any valid one gather
    index 0 — exactly the value the tuple scan propagated there — and
    ``has`` gates every consumer."""
    n = values.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    li = lax.cummax(jnp.where(valid, idx, -1))
    filled = jnp.take(values, jnp.clip(li, 0, n - 1))
    return filled, li >= 0


def assoc_scan_with_prefix(combine, elems, prefix, axis_name=None):
    """(exclusive, inclusive) associative scans seeded by ``prefix``.

    ``elems``/``prefix`` are tuples of arrays/scalars. With ``axis_name``
    the scan spans the sharded row axis: each shard scans locally, shard
    totals are all-gathered, and every shard folds (prefix + the totals
    of the shards before it) into its local results — the standard
    inter-block prefix fixup, exact for the integer monoids the engine
    uses. This is how aggregate state crosses shards under `shard_map`
    while pallas kernels stay active inside each shard (GSPMD tracing
    cannot partition `pallas_call`; explicit collectives can).
    """
    local_incl = lax.associative_scan(combine, elems)
    if axis_name is not None:
        totals = tuple(a[-1] for a in local_incl)
        gathered = tuple(lax.all_gather(t, axis_name) for t in totals)
        gathered = tuple(
            jnp.concatenate([jnp.asarray(p)[None], g])
            for p, g in zip(prefix, gathered)
        )
        g_incl = lax.associative_scan(combine, gathered)
        i = lax.axis_index(axis_name)
        shard_prefix = tuple(g[i] for g in g_incl)
    else:
        shard_prefix = tuple(jnp.asarray(p) for p in prefix)
    bcast = tuple(p[None] for p in shard_prefix)
    incl = combine(
        tuple(jnp.broadcast_to(b, a.shape) for b, a in zip(bcast, local_incl)),
        local_incl,
    )
    shifted = tuple(a[:-1] for a in local_incl)
    if shifted[0].shape[0]:
        tail = combine(
            tuple(
                jnp.broadcast_to(b, a.shape) for b, a in zip(bcast, shifted)
            ),
            shifted,
        )
        excl = tuple(
            jnp.concatenate([p[None], t]) for p, t in zip(shard_prefix, tail)
        )
    else:
        excl = tuple(p[None] for p in shard_prefix)
    return excl, incl


def global_last_true(flags, values, fallback, g0, axis_name=None):
    """Value at the globally-last True flag, else fallback.

    ``g0`` is this shard's first global row index; with ``axis_name`` the
    winner is picked across shards by all-gathered (index, value) pairs.
    """
    n = flags.shape[0]
    li = jnp.max(jnp.where(flags, jnp.arange(n, dtype=jnp.int32), -1))
    val = values[jnp.clip(li, 0, n - 1)]
    gli = jnp.where(li >= 0, g0 + li, jnp.int32(-1))
    if axis_name is None:
        return jnp.where(gli >= 0, val, fallback)
    glis = lax.all_gather(gli, axis_name)
    vals = lax.all_gather(val, axis_name)
    best = jnp.argmax(glis)
    return jnp.where(jnp.max(glis) >= 0, vals[best], fallback)


def global_any(flag, axis_name=None):
    local = jnp.any(flag)
    if axis_name is None:
        return local
    return jnp.any(lax.all_gather(local, axis_name))


# ---------------------------------------------------------------------------
# Ragged flat -> padded rows
# ---------------------------------------------------------------------------

# words of one aligned block of the flat: one full lane row of the chip,
# so the block fetch is a ROW gather of the [blocks, 128] view
ROW_BLOCK_WORDS = 128


def _shift_rows_left(win: jnp.ndarray, shift: jnp.ndarray, keep: int, bits: int):
    """``win[r, shift[r] : shift[r] + keep]`` for ``shift < 2**bits``: a
    barrel shifter along the lane axis, one static slice pair a bit of
    the shift, highest bit first so the window narrows as it goes (after
    bit ``b`` the shift left over is under ``2**b``). Element-wise work,
    no index per word. ``win`` is at least ``keep + 2**bits - 1`` wide."""
    for b in reversed(range(bits)):
        step = 1 << b
        width = keep + step - 1
        moved = ((shift >> b) & 1).astype(bool)[:, None]
        win = jnp.where(moved, win[:, step:step + width], win[:, :width])
    return win


def rows_from_word_starts(flat: jnp.ndarray, word_starts: jnp.ndarray, wwidth: int):
    """int32[rows, wwidth] with ``out[r, j] = flat[word_starts[r] + j]``
    wherever that word lies inside the flat (a start is clipped into the
    flat; a word past its end reads some word of the flat's last blocks:
    callers mask by length). Row ``r`` is ``wwidth`` CONSECUTIVE words,
    so the rebuild addresses blocks, not words: the flat is viewed as
    aligned 128-word blocks, a row gather fetches the
    ``ceil(wwidth / 128) + 1`` blocks a row can touch (that many indices
    a row, each a whole lane row, where a per-word gather hands XLA
    ``rows x wwidth``), and `_shift_rows_left` moves each row left by
    ``start % 128`` words. Plain XLA: shards under GSPMD and adds no
    Pallas call site to a chain program's cache key."""
    lb = ROW_BLOCK_WORDS.bit_length() - 1
    n_words = flat.shape[0]
    pad = -n_words % ROW_BLOCK_WORDS
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, ROW_BLOCK_WORDS)
    starts = jnp.clip(word_starts.astype(jnp.int32), 0, n_words - 1)
    per_row = (wwidth + ROW_BLOCK_WORDS - 2) // ROW_BLOCK_WORDS + 1
    idx = jnp.minimum(
        (starts >> lb)[:, None] + jnp.arange(per_row, dtype=jnp.int32),
        blocks.shape[0] - 1,
    )
    # `idx` is in bounds already; the default fill mode lowers through a
    # function that drops the caller's scope
    win = jnp.take(blocks, idx, axis=0, mode="clip")
    win = win.reshape(starts.shape[0], per_row * ROW_BLOCK_WORDS)
    return _shift_rows_left(win, starts & (ROW_BLOCK_WORDS - 1), wwidth, lb)


def unpack_row_bytes(words: jnp.ndarray, row_lengths: jnp.ndarray):
    """uint8[rows, 4 * wwidth] from rebuilt int32 words: every word's LE
    bytes, zero at and past each row's length (the flat's 0-3 pad bytes
    and whatever follows the last record never leak)."""
    n, wwidth = words.shape
    # byte k of word w = (w >> 8k) & 0xFF
    shifts = jnp.arange(4, dtype=jnp.int32)[None, None, :] * 8
    unpacked = ((words[:, :, None] >> shifts) & 0xFF).reshape(n, wwidth * 4)
    jidx = jnp.arange(wwidth * 4, dtype=jnp.int32)[None, :]
    return jnp.where(jidx < row_lengths[:, None], unpacked, 0).astype(jnp.uint8)


def compact_rows(mask: jnp.ndarray, *arrays: jnp.ndarray):
    """Scatter surviving rows to the front; returns (count, packed arrays).

    Rows past the survivor count keep zeros. Used for on-device output
    compaction before D2H.
    """
    n = mask.shape[0]
    dest = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dest = jnp.where(mask, dest, n)  # out-of-bounds -> dropped
    out = []
    for arr in arrays:
        zeros = jnp.zeros_like(arr)
        out.append(zeros.at[dest].set(arr, mode="drop"))
    return jnp.sum(mask.astype(jnp.int32)), tuple(out)


# ---------------------------------------------------------------------------
# Parallel (scan-free) fast paths
# ---------------------------------------------------------------------------


def literal_search(values: jnp.ndarray, lengths: jnp.ndarray, literal: bytes) -> jnp.ndarray:
    """Substring search via windowed equality — no sequential scan.

    K shifted compares over the byte matrix; the whole thing is a handful
    of fused VPU ops. Used when a regex reduces to a literal (the common
    chain pattern) instead of the DFA scan.
    """
    n, width = values.shape
    k = len(literal)
    if k == 0:
        return jnp.ones((n,), dtype=bool)
    if k > width:
        return jnp.zeros((n,), dtype=bool)
    span = width - k + 1
    acc = jnp.ones((n, span), dtype=bool)
    for i, b in enumerate(literal):
        acc = acc & (values[:, i : i + span] == b)
    pos_ok = (
        jnp.arange(span, dtype=jnp.int32)[None, :] + k
        <= lengths[:, None].astype(jnp.int32)
    )
    return jnp.any(acc & pos_ok, axis=1)


def literal_startswith(values: jnp.ndarray, lengths: jnp.ndarray, literal: bytes) -> jnp.ndarray:
    n, width = values.shape
    k = len(literal)
    if k == 0:
        return jnp.ones((n,), dtype=bool)
    if k > width:
        return jnp.zeros((n,), dtype=bool)
    lit = jnp.asarray(np.frombuffer(literal, dtype=np.uint8))
    ok = jnp.all(values[:, :k] == lit[None, :], axis=1)
    return ok & (lengths >= k)


def literal_endswith(values: jnp.ndarray, lengths: jnp.ndarray, literal: bytes) -> jnp.ndarray:
    n, width = values.shape
    k = len(literal)
    if k == 0:
        return jnp.ones((n,), dtype=bool)
    if k > width:
        return jnp.zeros((n,), dtype=bool)
    start = lengths.astype(jnp.int32) - k
    idx = start[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    tail = jnp.take_along_axis(values, jnp.clip(idx, 0, width - 1), axis=1)
    lit = jnp.asarray(np.frombuffer(literal, dtype=np.uint8))
    return jnp.all(tail == lit[None, :], axis=1) & (lengths >= k)


def _excl_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.cumsum(x, axis=1) - x


def _next_index_ge(cond: jnp.ndarray, width: int) -> jnp.ndarray:
    """next_idx[:, j] = smallest j' >= j with cond[:, j'], else width.

    Native reverse running-minimum along the byte axis.
    """
    jidx = jnp.arange(width, dtype=jnp.int32)[None, :]
    cand = jnp.where(cond, jidx, width)
    return lax.cummin(cand, axis=1, reverse=True)


def _prev_index_le(cond: jnp.ndarray, width: int) -> jnp.ndarray:
    """prev_idx[:, j] = largest j' <= j with cond[:, j'], else -1."""
    jidx = jnp.arange(width, dtype=jnp.int32)[None, :]
    cand = jnp.where(cond, jidx, -1)
    return lax.cummax(cand, axis=1)


def _bwd_fill_flag(cond: jnp.ndarray, flag: jnp.ndarray, width: int) -> jnp.ndarray:
    """For each j: the ``flag`` at the NEXT position j' >= j where ``cond``.

    Gather-free: encode (position, flag) as an integer and take a native
    reverse cumulative max; positions closer to j dominate. False where no
    such j' exists.
    """
    jidx = jnp.arange(width, dtype=jnp.int32)[None, :]
    enc = jnp.where(cond, (width - jidx) * 2 + flag.astype(jnp.int32), -1)
    filled = lax.cummax(enc, axis=1, reverse=True)
    return (filled >= 0) & ((filled & 1) == 1)


def json_get_parallel(
    values: jnp.ndarray, lengths: jnp.ndarray, key: str
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Structural-index extraction: span computation + shared gather."""
    start, out_lengths = json_get_parallel_span(values, lengths, key)
    return extract_span(values, start, out_lengths), out_lengths


def json_get_parallel_span(
    values: jnp.ndarray, lengths: jnp.ndarray, key: str
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Structural-index JSON field span — scan-free.

    simdjson-style: build per-byte structural masks with parallel
    prefixes (the 3-state string/escape automaton via transition
    composition, brace depth), find the first colon-confirmed ``"key"``
    occurrence at depth 1 by windowed compare, then resolve the value
    span with next/prev index fills.

    Matches `dsl.json_get_bytes` bit-for-bit — `string_state_excl`
    replaced the backslash-run parity heuristic whose escaped-quote
    handling outside strings was this kernel's one documented deviation
    (fuzzed against the scan kernel on structural-garbage corpora in
    tests/test_tpu_kernels.py).
    """
    needle = b'"' + key.encode("utf-8") + b'"'
    klen = len(needle)
    n, width = values.shape
    lengths = lengths.astype(jnp.int32)
    c = values.astype(jnp.int32)
    jidx = jnp.arange(width, dtype=jnp.int32)[None, :]
    inrec = jidx < lengths[:, None]

    is_q = (c == 0x22) & inrec
    is_ws = ((c == 32) | (c == 9) | (c == 13) | (c == 10)) & inrec

    # exact in-string/escape tracking: the 3-state string automaton
    # evaluated by transition composition — a quote is real unless an
    # escape is pending, and escapes exist only inside strings
    str_state = string_state_excl(c, inrec)
    q_real = is_q & (str_state != _STR_ESC)
    outside = str_state == _STR_OUT  # true at opening quotes and between strings

    brace_open = (c == 0x7B) & outside & inrec
    brace_close = (c == 0x7D) & outside & inrec
    depth_excl = _excl_cumsum(brace_open.astype(jnp.int32) - brace_close.astype(jnp.int32))

    # windowed needle compare at candidate opening quotes
    span = width - klen + 1
    if span <= 0:
        z = jnp.zeros((n,), dtype=jnp.int32)
        return z, z
    wc = jnp.ones((n, span), dtype=bool)
    for i, b in enumerate(needle):
        wc = wc & (c[:, i : i + span] == b)
    fits = jidx[:, :span] + klen <= lengths[:, None]
    cand = (
        wc
        & fits
        & q_real[:, :span]
        & outside[:, :span]
        & (depth_excl[:, :span] == 1)
    )

    nonws_in = ~is_ws & inrec
    next_nonws = _next_index_ge(nonws_in, width)
    # colon confirmation per candidate, gather-free: colon_reach[j] is true
    # when the next non-ws byte at >= j is ':'; shift left by klen aligns
    # it with candidate starts
    colon_reach = _bwd_fill_flag(nonws_in, (c == 0x3A), width)
    pad_f = jnp.zeros((n, klen), dtype=bool)
    colon_after = jnp.concatenate([colon_reach[:, klen:], pad_f], axis=1)[:, :span]
    ok = cand & colon_after
    big = jnp.int32(width + 1)
    p = jnp.min(jnp.where(ok, jidx[:, :span], big), axis=1)
    found = p <= width

    p_c = jnp.clip(p, 0, width - 1)
    # colon position for the winning candidate, then value start
    jcol_win = jnp.take_along_axis(
        next_nonws, jnp.clip(p_c + klen, 0, width - 1)[:, None], axis=1
    )[:, 0]
    j2 = jnp.take_along_axis(
        next_nonws, jnp.clip(jcol_win + 1, 0, width - 1)[:, None], axis=1
    )[:, 0]
    j2_in = j2 < lengths
    vchar = jnp.take_along_axis(c, jnp.clip(j2, 0, width - 1)[:, None], axis=1)[:, 0]
    is_strval = j2_in & (vchar == 0x22)

    # string value: [j2+1, next real quote)
    next_q = _next_index_ge(q_real, width)
    sstart = jnp.clip(j2 + 1, 0, width)
    q_end = jnp.take_along_axis(
        next_q, jnp.clip(sstart, 0, width - 1)[:, None], axis=1
    )[:, 0]
    s_end = jnp.minimum(jnp.where(q_end < width, q_end, lengths), lengths)

    # raw value: first , ] } at relative bracket depth 0 from j2
    br = ((c == 0x5B) | (c == 0x7B)).astype(jnp.int32) - (
        (c == 0x5D) | (c == 0x7D)
    ).astype(jnp.int32)
    br = jnp.where(inrec, br, 0)
    br_excl = _excl_cumsum(br)
    base = jnp.take_along_axis(br_excl, jnp.clip(j2, 0, width - 1)[:, None], axis=1)
    rel = br_excl - base
    is_term = ((c == 0x2C) | (c == 0x5D) | (c == 0x7D)) & (rel == 0) & inrec
    term_from = jnp.where(jidx >= j2[:, None], is_term, False)
    r_end_raw = jnp.min(jnp.where(term_from, jidx, big), axis=1)
    r_end_raw = jnp.minimum(r_end_raw, lengths)
    # strip trailing ws: last non-ws in [j2, r_end_raw)
    prev_nonws = _prev_index_le(~is_ws & inrec, width)
    r_last = jnp.take_along_axis(
        prev_nonws, jnp.clip(r_end_raw - 1, 0, width - 1)[:, None], axis=1
    )[:, 0]
    r_end = jnp.maximum(r_last + 1, j2)

    start = jnp.where(is_strval, sstart, j2)
    end = jnp.where(is_strval, s_end, r_end)
    out_lengths = jnp.where(found & j2_in, jnp.maximum(end - start, 0), 0)
    # found but value beyond record end (e.g. colon then EOF) -> empty
    out_lengths = jnp.where(found & ~j2_in, 0, out_lengths).astype(jnp.int32)
    return jnp.clip(start, 0, width), out_lengths
