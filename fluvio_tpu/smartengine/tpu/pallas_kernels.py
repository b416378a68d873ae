"""Pallas TPU kernels: fused single-pass byte-state machines.

The XLA lowering of ``json_get`` costs ~12 separate gather/scan
primitives per call, each a full pass over the byte matrix through
HBM; collapsing the whole field extraction into ONE pallas kernel keeps
the automaton's state in VMEM for a single pass.

Layout: the byte matrix is processed TRANSPOSED — (width, rows) — so the
sequential scan walks sublanes (cheap dynamic index) while records ride
the 128-wide lanes. The state machine is the *sequential* reference
automaton of ``dsl.json_get_bytes`` (exact semantics, including the
malformed-input corners where the parallel structural kernel deviates).

Every kernel here compiles for the v5e at bench shapes — pinned by the
described-chip compiles in tests/test_chip_compile.py. A kernel the
chip's compiler refuses is deleted, not demoted from at run time.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fluvio_tpu.analysis.envreg import env_raw
from fluvio_tpu.telemetry import instrument_jit


LANES = 512  # records per block (lane axis, multiple of 128)
MAX_PALLAS_WIDTH = 1024  # VMEM: width x LANES x int32 blocks must fit

# scan phases
_SCAN, _SKIP_KEY, _SEEK_COLON, _SEEK_VAL, _STR_VAL, _RAW_VAL, _DONE = range(7)


# ---------------------------------------------------------------------------
# Kernel selection: when the lowerer should emit pallas calls
# ---------------------------------------------------------------------------

_disable_depth = 0


@contextlib.contextmanager
def disable_pallas():
    """Trace-time escape hatch: GSPMD cannot partition `pallas_call`, so
    the sharded chain path traces with pallas off (XLA kernels shard
    transparently)."""
    global _disable_depth
    _disable_depth += 1
    try:
        yield
    finally:
        _disable_depth -= 1


def interpret_mode() -> bool:
    """Interpret pallas on non-TPU backends (tests on the CPU mesh)."""
    return jax.default_backend() in ("cpu", "gpu")


def pallas_active(width: int = 0) -> bool:
    """Should the lowerer emit a pallas kernel here?

    ``FLUVIO_TPU_PALLAS``: ``0`` disables, ``interpret`` forces the
    (slow) interpreter on CPU for equivalence testing, ``auto`` (default)
    enables on real TPU backends only.
    """
    if _disable_depth:
        return False
    if width > MAX_PALLAS_WIDTH:
        return False
    mode = env_raw("FLUVIO_TPU_PALLAS")
    if mode == "0":
        return False
    if mode in ("interpret", "1"):
        return True
    return not interpret_mode()


def _json_scan_kernel(needle: bytes, width: int, vt_ref, len_ref,
                      start_ref, vlen_ref, wc_ref):
    """One row-block: full json_get state machine + in-kernel extraction.

    vt_ref: (width, LANES) int32 transposed bytes; len_ref: (1, LANES).
    Outputs: out_ref (width, LANES) extracted bytes (zero-padded),
    start_ref/vlen_ref (1, LANES). wc_ref: VMEM scratch holding the
    precomputed windowed needle-compare, read back with a dynamic row
    index inside the scan (refs support pl.ds; values don't).
    """
    klen = len(needle)
    lengths = len_ref[0:1, :]  # (1, n) — keep every state vector 2-D
    n = lengths.shape[1]
    zero = jnp.zeros((1, n), dtype=jnp.int32)

    # windowed needle compare (static shifts): wc[j] = needle matches at j
    vt = vt_ref[:, :]  # (width, n)
    wc = jnp.ones((width, n), dtype=jnp.bool_)
    for i, b in enumerate(needle):
        if i == 0:
            shifted = vt
        else:
            shifted = jnp.concatenate(
                [vt[i:, :], jnp.zeros((i, n), dtype=jnp.int32)], axis=0
            )
        wc = wc & (shifted == b)
    jcol = jax.lax.broadcasted_iota(jnp.int32, (width, n), 0)
    wc = wc & (jcol + klen <= lengths)
    # NOTE: x64 is enabled package-wide, so `jnp.where(wc, 1, 0)` would
    # produce int64 — and Mosaic's convert lowering infinitely recurses on
    # any i64->i32 convert. Every kernel value must stay explicitly int32.
    wc_ref[:, :] = wc.astype(jnp.int32)

    def step(j, state):
        (phase, in_str, esc, depth, d2, skip, start, end, last_nonws) = state
        c = vt_ref[pl.ds(j, 1), :]  # (1, n)
        wc_j = wc_ref[pl.ds(j, 1), :] != 0
        inrec = j < lengths
        is_ws = (c == 32) | (c == 9) | (c == 13) | (c == 10)

        # ---- key-match branch arming (only in _SCAN phase) -------------
        in_str_b = in_str != 0
        esc_b = esc != 0
        scanning = (phase == _SCAN) & inrec
        instr_now = scanning & in_str_b
        new_esc = (instr_now & ~esc_b & (c == 92)).astype(jnp.int32)
        exit_str = instr_now & ~esc_b & (c == 34)
        in_str1 = jnp.where(
            instr_now, jnp.where(exit_str, jnp.int32(0), in_str), in_str
        )
        esc1 = jnp.where(instr_now, new_esc, esc)

        outside = scanning & ~in_str_b
        quote_here = outside & (c == 34)
        matched = quote_here & (depth == 1) & wc_j
        open_str = quote_here & ~matched
        in_str2 = jnp.where(open_str, jnp.int32(1), in_str1)
        depth1 = jnp.where(
            outside & (c == 123), depth + 1,
            jnp.where(outside & (c == 125), depth - 1, depth),
        )

        phase1 = jnp.where(matched, jnp.int32(_SKIP_KEY), phase)
        skip1 = jnp.where(matched, jnp.int32(klen - 1), skip)

        # ---- skip over the needle bytes --------------------------------
        skipping = (phase == _SKIP_KEY) & inrec
        skip2 = jnp.where(skipping, skip - 1, skip1)
        phase2 = jnp.where(skipping & (skip <= 1), jnp.int32(_SEEK_COLON), phase1)

        # ---- whitespace to the colon -----------------------------------
        seek_c = (phase == _SEEK_COLON) & inrec
        phase3 = jnp.where(
            seek_c & ~is_ws,
            # not a colon: resume scanning (int32 literals: see x64 note)
            jnp.where(c == 58, jnp.int32(_SEEK_VAL), jnp.int32(_SCAN)),
            phase2,
        )

        # ---- whitespace to the value -----------------------------------
        seek_v = (phase == _SEEK_VAL) & inrec
        val_here = seek_v & ~is_ws
        str_val = val_here & (c == 34)
        phase4 = jnp.where(
            val_here,
            jnp.where(str_val, jnp.int32(_STR_VAL), jnp.int32(_RAW_VAL)),
            phase3,
        )
        start1 = jnp.where(str_val, j + 1, jnp.where(val_here, j, start))
        esc2 = jnp.where(str_val, jnp.int32(0), esc1)
        d2a = jnp.where(val_here & ~str_val, jnp.int32(0), d2)
        raw_now = val_here & ~str_val

        # ---- string value: to the closing quote ------------------------
        instrval = (phase == _STR_VAL) & inrec
        esc_sv = jnp.where(instrval & ~esc_b & (c == 92), jnp.int32(1),
                           jnp.where(instrval, jnp.int32(0), esc2))
        close = instrval & ~esc_b & (c == 34)
        phase5 = jnp.where(close, jnp.int32(_DONE), phase4)
        end1 = jnp.where(close, j, end)

        # ---- raw value: to top-level , ] } -----------------------------
        inraw = ((phase == _RAW_VAL) & inrec) | raw_now
        opens = inraw & ((c == 91) | (c == 123))
        closes = inraw & ((c == 93) | (c == 125))
        term = inraw & (
            (((c == 93) | (c == 125)) & (d2a == 0))
            | ((c == 44) & (d2a == 0))
        )
        d2b = jnp.where(opens, d2a + 1, jnp.where(closes & ~term, d2a - 1, d2a))
        phase6 = jnp.where(term, jnp.int32(_DONE), phase5)
        end2 = jnp.where(term, j, end1)
        last_nonws1 = jnp.where(inraw & ~is_ws & ~term, j, last_nonws)

        # ---- end of record: unterminated values resolve ----------------
        at_end = (j + 1 >= lengths) & inrec
        raw_eof = at_end & (phase6 == _RAW_VAL)
        str_eof = at_end & (phase6 == _STR_VAL)
        phase7 = jnp.where(raw_eof | str_eof, jnp.int32(_DONE), phase6)
        end3 = jnp.where(raw_eof | str_eof, lengths, end2)

        return (
            phase7,
            in_str2,
            esc_sv,  # chains the in-string and string-value escape updates
            depth1,
            d2b,
            skip2,
            start1,
            end3,
            last_nonws1,
        )

    init = (
        jnp.full((1, n), _SCAN, dtype=jnp.int32),
        zero,  # in_str (0/1 int32: Mosaic bool vectors are fragile)
        zero,  # esc
        zero,
        zero,
        zero,
        zero,
        zero,
        jnp.full((1, n), -1, dtype=jnp.int32),
    )
    # int32 loop bounds: under x64 a Python-int fori index is i64 and every
    # use site would emit Mosaic-unlowerable i64<->i32 converts
    (phase, _in_str, _esc, _depth, _d2, _skip, start, end, last_nonws) = (
        jax.lax.fori_loop(jnp.int32(0), jnp.int32(width), step, init)
    )

    found = phase == _DONE
    raw_trim = found & (last_nonws >= 0)
    end = jnp.where(
        raw_trim & (last_nonws + 1 < end), last_nonws + 1, end
    )
    vlen = jnp.where(found, jnp.maximum(end - start, 0), jnp.int32(0))
    start = jnp.where(found, start, jnp.int32(0))
    start_ref[0:1, :] = start
    vlen_ref[0:1, :] = vlen


def _extract_kernel(width: int, vt_ref, start_ref, vlen_ref, out_ref):
    """Shift each record's rows up by its `start` and mask to `vlen`.

    Separate pallas call: fusing this into the scan kernel trips an
    infinite recursion in the Mosaic convert-lowering on this jax
    version; two kernels still collapse ~12 XLA primitives into 2.
    """
    vt = vt_ref[:, :]
    n = vt.shape[1]
    start = start_ref[0:1, :]
    vlen = vlen_ref[0:1, :]
    shifted = vt
    for bit in range(int(np.log2(max(width, 2))) + 1):
        amount = 1 << bit
        if amount >= width:
            break
        take = jnp.concatenate(
            [shifted[amount:, :], jnp.zeros((amount, n), dtype=jnp.int32)],
            axis=0,
        )
        cond = ((start >> bit) & 1) == 1  # (1, n)
        shifted = jnp.where(cond, take, shifted)
    rows = jax.lax.broadcasted_iota(jnp.int32, (width, n), 0)
    out_ref[:, :] = jnp.where(rows < vlen, shifted, jnp.int32(0))


def json_get_span_pallas(
    values: jnp.ndarray,
    lengths: jnp.ndarray,
    key: str,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """JSON field span (start, length) via the pallas byte automaton.

    Semantics: exactly ``dsl.json_get_bytes``. Gather-free — the span
    feeds either `extract_pallas` (materialized bytes) or the executor's
    descriptor D2H path (late materialization on the host).
    """
    needle = b'"' + key.encode("utf-8") + b'"'
    n, width = values.shape
    blocks = max(1, (n + LANES - 1) // LANES)
    padded_n = blocks * LANES
    vt = jnp.transpose(values.astype(jnp.int32))  # (width, n)
    if padded_n != n:
        vt = jnp.pad(vt, ((0, 0), (0, padded_n - n)))
        lengths = jnp.pad(lengths, (0, padded_n - n))
    len2d = lengths.astype(jnp.int32)[None, :]

    scan = functools.partial(_json_scan_kernel, needle, width)
    # kernels trace with x64 off: under the package-wide x64 every weak
    # Python-int literal becomes i64 and Mosaic's convert lowering recurses
    # infinitely on the resulting i64->i32 casts
    with jax.enable_x64(False):
        start, vlen = pl.pallas_call(
            scan,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec((width, LANES), lambda b: (0, b)),
                pl.BlockSpec((1, LANES), lambda b: (0, b)),
            ],
            out_specs=[
                pl.BlockSpec((1, LANES), lambda b: (0, b)),
                pl.BlockSpec((1, LANES), lambda b: (0, b)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, padded_n), jnp.int32),
                jax.ShapeDtypeStruct((1, padded_n), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM((width, LANES), jnp.int32)],
            interpret=interpret,
        )(vt, len2d)
    return start[0, :n], vlen[0, :n]


def extract_pallas(
    values: jnp.ndarray,
    start: jnp.ndarray,
    vlen: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    """Materialize per-record substrings with the pallas shift kernel."""
    n, width = values.shape
    blocks = max(1, (n + LANES - 1) // LANES)
    padded_n = blocks * LANES
    vt = jnp.transpose(values.astype(jnp.int32))
    start = start.astype(jnp.int32)
    vlen = vlen.astype(jnp.int32)
    if padded_n != n:
        vt = jnp.pad(vt, ((0, 0), (0, padded_n - n)))
        start = jnp.pad(start, (0, padded_n - n))
        vlen = jnp.pad(vlen, (0, padded_n - n))
    with jax.enable_x64(False):
        extract = functools.partial(_extract_kernel, width)
        outT = pl.pallas_call(
            extract,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec((width, LANES), lambda b: (0, b)),
                pl.BlockSpec((1, LANES), lambda b: (0, b)),
                pl.BlockSpec((1, LANES), lambda b: (0, b)),
            ],
            out_specs=pl.BlockSpec((width, LANES), lambda b: (0, b)),
            out_shape=jax.ShapeDtypeStruct((width, padded_n), jnp.int32),
            interpret=interpret,
        )(vt, start[None, :], vlen[None, :])
    return jnp.transpose(outT[:, :n]).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("key", "interpret"))
def json_get_pallas(
    values: jnp.ndarray,
    lengths: jnp.ndarray,
    key: str,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused JSON field extraction: (out_values, out_lengths).

    Semantics: exactly ``dsl.json_get_bytes`` (sequential automaton).
    Span + extract trace inline (un-jitted helpers) so XLA CSEs the
    shared transpose/pad of the values matrix between the two kernels.
    """
    start, vlen = json_get_span_pallas(values, lengths, key, interpret=interpret)
    out_values = extract_pallas(values, start, vlen, interpret=interpret)
    return out_values, vlen


def _describe_json_get(*a, **k) -> str:
    key = k.get("key", a[2] if len(a) > 2 else "?")
    shape = getattr(a[0], "shape", ("?",)) if a else ("?",)
    return f"json_get key={key} shape={tuple(shape)}"


# compile observability: this is the one module-level jit entry point in
# the pallas layer — trace-cache misses record "pallas" compile events
# (telemetry/compiles.py; free when FLUVIO_TELEMETRY=0)
json_get_pallas = instrument_jit(
    json_get_pallas, "pallas", describe=_describe_json_get
)


# ---------------------------------------------------------------------------
# DFA regex scan
# ---------------------------------------------------------------------------

MAX_DFA_SELECTS = 512  # select-chain length bound (compile time + VPU cost)


def _dfa_mode(table_flat) -> int:
    """Most common transition target — the select-chain default."""
    vals, counts = np.unique(np.asarray(table_flat), return_counts=True)
    return int(vals[np.argmax(counts)])


def dfa_supported(dfa) -> bool:
    flat = dfa.table.reshape(-1)
    bc = dfa.byte_class
    cvals, ccounts = np.unique(bc, return_counts=True)
    n_byte_selects = int(np.sum(bc != cvals[np.argmax(ccounts)]))
    n_edge_selects = int(np.sum(flat != _dfa_mode(flat)))
    return n_byte_selects + n_edge_selects <= MAX_DFA_SELECTS


def _dfa_scan_kernel(
    table_flat: tuple,
    byte_to_class: tuple,
    default_class: int,
    n_classes: int,
    eos_class: int,
    pad_class: int,
    accept_states: tuple,
    start_state: int,
    width: int,
    vt_ref,
    len_ref,
    out_ref,
):
    """One row-block: DFA scan over raw (transposed) byte columns.

    Gather-free end to end: both the byte->class map and the transition
    ``table[state, cls]`` are chains of compare-selects — Mosaic has no
    vector gather, but constant selects on the lane vectors cost ~one
    VPU op each (an XLA-side 64M-element class gather costs ~600ms on
    this chip; the in-kernel chain is ~free). Both chains only cover
    entries that differ from their modal value: for literal-heavy DFAs
    most bytes map to the catch-all class and most transitions hit the
    dead state.
    """
    lengths = len_ref[0:1, :]
    n = lengths.shape[1]
    default = _dfa_mode(table_flat)

    def classify(c):
        cls = jnp.full_like(c, default_class)
        for b, k in byte_to_class:
            cls = jnp.where(c == b, jnp.int32(k), cls)
        return cls

    def transition(state, cls):
        idx = state * n_classes + cls
        nxt = jnp.full_like(state, default)
        for k, v in enumerate(table_flat):
            if v != default:
                nxt = jnp.where(idx == k, jnp.int32(v), nxt)
        return nxt

    eos_i32, pad_i32 = jnp.int32(eos_class), jnp.int32(pad_class)

    def step(j, state):
        c = vt_ref[pl.ds(j, 1), :]
        cls = classify(c)
        cls = jnp.where(
            j < lengths,
            cls,
            jnp.where(j == lengths, eos_i32, pad_i32),
        )
        return transition(state, cls)

    state = jnp.full((1, n), start_state, dtype=jnp.int32)
    state = jax.lax.fori_loop(jnp.int32(0), jnp.int32(width), step, state)
    # trailing symbol: records exactly `width` long still need their EOS
    cls = jnp.where(lengths == width, eos_i32, pad_i32)
    state = transition(state, cls)

    acc = jnp.zeros((1, n), dtype=jnp.int32)
    for s in accept_states:
        acc = jnp.where(state == s, jnp.int32(1), acc)
    out_ref[0:1, :] = acc


def dfa_match_pallas(
    values: jnp.ndarray,
    lengths: jnp.ndarray,
    dfa,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas DFA match: True where the regex matches (semantics:
    `kernels.dfa_match` / the numpy reference in `ops.regex_dfa`).

    Two device primitives total — a transpose and one pallas scan —
    replacing the XLA `lax.scan` whose per-step dual gathers dominate
    the regex stage's 0.58s/1M-record cost.
    """
    if not dfa_supported(dfa):
        raise ValueError("DFA too large for the select-chain kernel")
    n, width = values.shape
    blocks = max(1, (n + LANES - 1) // LANES)
    padded_n = blocks * LANES
    vt = jnp.transpose(values.astype(jnp.int32))  # (width, n)
    lengths = lengths.astype(jnp.int32)
    if padded_n != n:
        vt = jnp.pad(vt, ((0, 0), (0, padded_n - n)))
        # padded lanes get length -1: every column reads PAD, state stays dead
        lengths = jnp.pad(lengths, (0, padded_n - n), constant_values=-1)
    len2d = lengths[None, :]

    bc = dfa.byte_class.astype(np.int32)
    cvals, ccounts = np.unique(bc, return_counts=True)
    default_class = int(cvals[np.argmax(ccounts)])
    byte_to_class = tuple(
        (int(b), int(bc[b])) for b in range(256) if int(bc[b]) != default_class
    )
    kernel = functools.partial(
        _dfa_scan_kernel,
        tuple(int(x) for x in dfa.table.reshape(-1)),
        byte_to_class,
        default_class,
        dfa.n_classes,
        dfa.eos_class,
        dfa.pad_class,
        tuple(int(s) for s in np.nonzero(dfa.accept)[0]),
        dfa.start,
        width,
    )
    with jax.enable_x64(False):  # see the x64/Mosaic note in json_get_pallas
        out = pl.pallas_call(
            kernel,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec((width, LANES), lambda b: (0, b)),
                pl.BlockSpec((1, LANES), lambda b: (0, b)),
            ],
            out_specs=pl.BlockSpec((1, LANES), lambda b: (0, b)),
            out_shape=jax.ShapeDtypeStruct((1, padded_n), jnp.int32),
            interpret=interpret,
        )(vt, len2d)
    return out[0, :n] != 0
