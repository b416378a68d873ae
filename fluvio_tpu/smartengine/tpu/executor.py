"""Fused TPU chain executor.

Lowers a whole SmartModule chain (every module carrying a DSL program) into
ONE jitted function over the RecordBuffer arrays:

- filters/filter_maps update a lazy validity mask — no mid-chain
  compaction, no host round trips between modules,
- maps rewrite the value/key columns,
- aggregates run segmented prefix scans (`lax.associative_scan`) with the
  accumulator/window carry passed through the jit boundary, so state stays
  on device across `process()` calls,
- output rows compact on device before D2H.

This replaces the reference's per-module wasmtime round trip
(encode -> guest call -> decode, engine.rs:135-185 + instance.rs:164-191)
with a single XLA program per shape bucket.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fluvio_tpu.telemetry import TELEMETRY, instrument_jit
from fluvio_tpu.telemetry.spans import (
    annotate,
    scoped_program,
    stage_scope,
    timed,
)
from fluvio_tpu.resilience import faults
from fluvio_tpu.resilience.policy import RetryPolicy, is_program_fault

from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.smartmodule.sdk import SmartModuleDef
from fluvio_tpu.smartmodule.types import (
    SmartModuleInput,
    SmartModuleKind,
    SmartModuleOutput,
)
from fluvio_tpu.smartengine.config import SmartModuleConfig
from fluvio_tpu.smartengine.metrics import SmartModuleChainMetrics
from fluvio_tpu.smartengine.tpu import glz, kernels, stripes, window_stage
from fluvio_tpu.smartengine.tpu.buffer import (
    MAX_RECORD_WIDTH,
    RecordBuffer,
    apply_postops_host,
    view_fold,
)
from fluvio_tpu.smartengine.tpu.lower import (
    Unlowerable,
    apply_postops,
    infer_type,
    lower_expr,
    lower_span,
    materialize_span,
)

from fluvio_tpu.analysis.envreg import env_int, env_raw
from fluvio_tpu.analysis.lockwatch import make_lock

_AGG_OP = {
    "sum_int": "add",
    "count": "add",
    "word_count": "add",
    "max_int": "max",
    "min_int": "min",
}
_AGG_NEUTRAL = {
    "add": 0,
    "max": kernels.INT64_MIN,
    "min": kernels.INT64_MAX,
}


class TpuSpill(Exception):
    """Raised when a batch must be re-run on the interpreting backend for
    exact semantics (device-detected transform error, or fan-out capacity
    exhaustion after retry). Aggregate device carries are restored before
    raising so the rerun cannot double-count. ``reason`` is a short
    stable key for the telemetry spill counter."""

    def __init__(self, message: str, reason: str = "transform-error"):
        super().__init__(message)
        self.reason = reason


class _FanoutOverflow(Exception):
    def __init__(self, total: int, cap: int = 0, over: int = 0):
        super().__init__(f"fanout of {total} in {cap} rows; a record over the bound: {over}")
        self.total, self.cap, self.over = total, cap, over


@dataclass
class _FilterStage:
    predicate: Callable

    kind = "filter"  # device-scope label (`stage<i>.<kind>`), no user data

    # structural invariants the executor checks at build time (ADVICE r2):
    # stages that break them force the off/ts columns onto the D2H path
    preserves_rows = True      # output row i corresponds to input row i
    rewrites_offsets = False   # touches offset/timestamp delta columns

    def apply(self, state: Dict, carries, base_ts, ctx):
        state = dict(state)
        state["valid"] = state["valid"] & self.predicate(state)
        return state, carries


@dataclass
class _MapStage:
    value_fn: Optional[Callable]
    key_fn: Optional[Callable]
    predicate: Optional[Callable] = None  # filter_map when set
    span_fn: Optional[Callable] = None    # value is a view of current values
    span_postops: Tuple[str, ...] = ()    # static byte-wise folds on the view

    kind = "map"
    preserves_rows = True
    rewrites_offsets = False

    def apply(self, state: Dict, carries, base_ts, ctx):
        new_state = dict(state)
        if self.predicate is not None:
            new_state["valid"] = state["valid"] & self.predicate(state)
        if self.span_fn is not None:
            # view-preserving rewrite: track provenance into the original
            # record bytes; byte materialization below is DCE'd by XLA
            # whenever no later stage (and no output) reads it
            st, ln = self.span_fn(state)
            ln = ln.astype(jnp.int32)
            new_state["view_start"] = state["view_start"] + st
            new_state["values"] = apply_postops(
                materialize_span(state["values"], st, ln), self.span_postops
            )
            new_state["lengths"] = ln
        else:
            v, l = self.value_fn(state)
            new_state["values"], new_state["lengths"] = v, l.astype(jnp.int32)
        if self.key_fn is not None:
            kv, kl = self.key_fn(state)
            new_state["keys"], new_state["key_lengths"] = kv, kl.astype(jnp.int32)
        return new_state, carries


@dataclass
class _ArrayMapStage:
    """Fan-out explode (reference transform kind array_map,
    transforms/mod.rs:24-52). Every output element is a contiguous
    substring of its source record, so the stage places (rel_start, len)
    descriptors, each with its record's view start and source row, into
    ``ctx["fanout_cap"]`` rows, at most ``ctx["fan_bound"]`` a record
    (`kernels.fanout_place`); byte materialization for downstream stages
    is DCE'd when nothing reads it. Output offset/timestamp deltas are
    "fresh" (zero relative to the source record's batch), synthesized
    host-side from the src column."""

    mode: str  # "json_array" | "split"
    sep: bytes

    kind = "array_map"
    preserves_rows = False
    rewrites_offsets = True

    def apply(self, state: Dict, carries, base_ts, ctx):
        cap, bound = ctx["fanout_cap"], ctx["fan_bound"]
        if cap is None:
            raise Unlowerable("array_map needs a fanout capacity (unsharded path)")
        values, lengths, valid = state["values"], state["lengths"], state["valid"]
        n = values.shape[0]
        if self.mode == "json_array":
            flag, sg, lg, ff, fs, fl, err = kernels.json_array_bounds(values, lengths)
        else:
            flag, sg, lg, ff, fs, fl, err = kernels.split_bounds(
                values, lengths, self.sep
            )
        err_v = err & valid
        # first-error masking: the failing record and everything after it
        # contribute nothing (partial-output parity, engine.rs:159-161);
        # the host spills the batch to the interpreter for the exact error
        ridx = jnp.arange(n, dtype=jnp.int32)
        first_err = jnp.min(jnp.where(err_v, ridx, jnp.int32(n)))
        contributing = valid & (ridx < first_err)
        # every slot carries its record's view start, source and local row
        total, largest, rel_start, elen, view, src, lr = kernels.fanout_place(
            flag, sg, lg, ff, fs, fl, contributing, cap, bound,
            (state["view_start"], state["src_row"], ridx),
        )
        new_state: Dict = {}
        new_state["valid"] = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(
            total, jnp.int32(cap)
        )
        new_state["view_start"] = view + rel_start
        new_state["src_row"] = src
        new_state["lengths"] = elen
        new_state["values"] = materialize_span(
            jnp.take(values, lr, axis=0), rel_start, elen
        )
        new_state["keys"] = jnp.take(state["keys"], lr, axis=0)
        new_state["key_lengths"] = jnp.take(state["key_lengths"], lr)
        new_state["offset_deltas"] = jnp.zeros((cap,), state["offset_deltas"].dtype)
        new_state["timestamp_deltas"] = jnp.zeros((cap,), state["timestamp_deltas"].dtype)
        new_state["fan_total"] = total
        new_state["fan_err"] = jnp.any(err_v)
        # a record over the bound lost its elements past it: the header
        # carries the largest count, and the host re-runs the slice
        new_state["fan_over"] = jnp.where(largest > bound, largest, 0)
        ax = ctx.get("axis_name")
        if ax is not None:
            # the stage replaced each shard's n_local input rows with its
            # own cap explode rows; downstream cross-shard ranking (the
            # aggregate's global_last_true) must rank by the EXPLODE
            # block origin or shard blocks overlap and a longer earlier
            # shard outranks the true last row
            ctx["g0"] = lax.axis_index(ax) * cap
        return new_state, carries


def _canned_contribution(kind: str) -> Callable:
    """The 5 classic reductions as contribution functions — prebuilt
    instances of the general (contribution, combine-monoid) form."""
    if kind in ("sum_int", "max_int", "min_int"):
        return lambda s: kernels.parse_int(s["values"], s["lengths"])
    if kind == "count":
        return lambda s: jnp.ones(s["values"].shape[0], dtype=jnp.int64)
    if kind == "word_count":
        return lambda s: kernels.count_words(s["values"], s["lengths"])
    raise Unlowerable(f"aggregate kind {kind}")


@dataclass
class _AggregateStage:
    op: str  # combine monoid: "add" | "max" | "min"
    window_ms: Optional[int]
    index: int  # carry slot
    contribution_fn: Callable  # state -> i64[N] per-record contribution

    kind = "aggregate"
    preserves_rows = True
    rewrites_offsets = False

    def apply(self, state: Dict, carries, base_ts, ctx):
        acc_in, win_in, has_in = carries[self.index]
        valid = state["valid"]
        op = self.op
        neutral = jnp.int64(_AGG_NEUTRAL[op])

        x = self.contribution_fn(state).astype(jnp.int64)
        xm = jnp.where(valid, x, neutral)
        if self.window_ms:
            ts = base_ts + state["timestamp_deltas"]
            ts = jnp.where(base_ts < 0, jnp.int64(0), ts)
            ts = jnp.where(ts < 0, jnp.int64(0), ts)
            w = ts - ts % jnp.int64(self.window_ms)
        else:
            w = jnp.zeros(x.shape[0], dtype=jnp.int64)

        # the contribution above (e.g. a JSON span + ParseInt) stays
        # under the stage's own scope; the carry chain and the scan get
        # one of their own inside it, so a profile tells the two apart
        with jax.named_scope(
            stage_scope(ctx.get("stage_index", 0), "aggregate_scan")
        ):
            ax = ctx.get("axis_name")
            if ax is not None:
                return self._apply_sharded(
                    state, carries, ctx, valid, xm, w, acc_in, win_in, has_in,
                    neutral, ax,
                )

            # prepend the carry as a virtual row
            x_all = jnp.concatenate([jnp.where(has_in, acc_in, neutral)[None], xm])
            w_all = jnp.concatenate([win_in[None], w])
            valid_all = jnp.concatenate([has_in[None], valid])

            prevw_incl, prevhas_incl = kernels.propagate_last_valid(w_all, valid_all)
            prevw = jnp.concatenate([jnp.int64(0)[None], prevw_incl[:-1]])
            prevhas = jnp.concatenate([jnp.asarray(False)[None], prevhas_incl[:-1]])
            reset_all = valid_all & (~prevhas | (w_all != prevw))

            scan = kernels.segmented_scan(x_all, reset_all, op)
            out_vals = scan[1:]

            new_acc = kernels.last_true_value(valid_all, scan, acc_in)
            new_win = kernels.last_true_value(valid_all, w_all, win_in)
            new_has = has_in | jnp.any(valid)

            new_state = dict(state)
            v, l = kernels.int_to_ascii(out_vals)
            new_state["values"], new_state["lengths"] = v, l.astype(jnp.int32)
            if self.window_ms:
                kv, kl = kernels.int_to_ascii(w)
                new_state["keys"], new_state["key_lengths"] = kv, kl.astype(jnp.int32)
            # raw integers for the int-output D2H mode (8 bytes/row instead of
            # a padded ASCII matrix); the ascii materialization above is
            # DCE'd when the executor ships these instead
            new_state["agg_out_int"] = out_vals
            new_state["agg_win_int"] = w
            new_carries = list(carries)
            new_carries[self.index] = (new_acc, new_win, new_has)
            return new_state, tuple(new_carries)

    def _apply_sharded(
        self, state, carries, ctx, valid, xm, w, acc_in, win_in, has_in,
        neutral, ax,
    ):
        """The same math under `shard_map`: the virtual carry row becomes
        the PREFIX element of explicit cross-shard associative scans
        (kernels.assoc_scan_with_prefix), which is bit-equal for the
        integer monoids — and keeps pallas kernels active inside each
        shard, which GSPMD tracing cannot.
        """
        g0 = ctx["g0"]
        op_fn = kernels._AGG_OPS[self.op][1]

        def prop_combine(a, b):
            ha, wa = a
            hb, wb = b
            return ha | hb, jnp.where(hb, wb, wa)

        # prev window per row = fold over (carry + all earlier global rows)
        (prevhas, prevw), _ = kernels.assoc_scan_with_prefix(
            prop_combine, (valid, w), (has_in, win_in), ax
        )
        reset = valid & (~prevhas | (w != prevw))

        def seg_combine(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb, vb, op_fn(va, vb))

        prefix = (has_in, jnp.where(has_in, acc_in, neutral))
        _, (_, out_vals) = kernels.assoc_scan_with_prefix(
            seg_combine, (reset, xm), prefix, ax
        )

        new_acc = kernels.global_last_true(valid, out_vals, acc_in, g0, ax)
        new_win = kernels.global_last_true(valid, w, win_in, g0, ax)
        new_has = has_in | kernels.global_any(valid, ax)

        new_state = dict(state)
        v, l = kernels.int_to_ascii(out_vals)
        new_state["values"], new_state["lengths"] = v, l.astype(jnp.int32)
        if self.window_ms:
            kv, kl = kernels.int_to_ascii(w)
            new_state["keys"], new_state["key_lengths"] = kv, kl.astype(jnp.int32)
        new_state["agg_out_int"] = out_vals
        new_state["agg_win_int"] = w
        new_carries = list(carries)
        new_carries[self.index] = (new_acc, new_win, new_has)
        return new_state, tuple(new_carries)


def ragged_repad_words(flat, lengths, width: int):
    """Device-side re-pad of a 4-aligned ragged upload (traced).

    The host link only carried sum(lengths) bytes; the padded value
    matrix is rebuilt here. Row ``r`` is the ``width // 4`` consecutive
    i32 words of the flat that start at the record's word offset, so the
    rebuild fetches whole aligned blocks a row and shifts them into
    place (`kernels.rows_from_word_starts`) instead of gathering one
    index a word, which is what the TPU's gather throughput is sensitive
    to. Shared by the single-device ragged dispatch and the per-shard
    rebuild in `parallel/sharded.py` (one implementation: a re-pad fix
    cannot land in one path and miss the other). Returns
    (values uint8[n, width], lengths int32[n])."""
    lengths = lengths.astype(jnp.int32)
    lengths4 = (lengths + 3) & ~3
    # i32 accumulator is safe: buffer.check_flat_addressing refused any
    # batch whose 4-aligned flat exceeds i32 before it staged
    word_starts = (jnp.cumsum(lengths4) - lengths4) >> 2  # noqa: FLV303
    words = kernels.rows_from_word_starts(flat, word_starts, width // 4)
    return kernels.unpack_row_bytes(words, lengths), lengths


def derived_meta_columns(
    n: int,
    kwidth: int,
    has_keys: bool,
    keys,
    key_lengths,
    has_offsets: bool,
    offset_deltas,
    ts_mode: str,
    timestamp_deltas,
    idx_base=0,
):
    """Device-side synthesis of the columns `stage_link_columns` kept off
    the link (traced; shared by the single-device ragged dispatch and the
    per-shard rebuild — the sentinels and widenings must not fork).
    ``idx_base`` is 0 single-device and the shard's global row origin
    under shard_map. Returns (keys, key_lengths, offset_deltas,
    timestamp_deltas)."""
    if not has_keys:
        keys = jnp.zeros((n, kwidth), dtype=jnp.uint8)
        key_lengths = jnp.full((n,), -1, dtype=jnp.int32)
    else:
        key_lengths = key_lengths.astype(jnp.int32)
    if not has_offsets:
        offset_deltas = idx_base + jnp.arange(n, dtype=jnp.int32)
    if ts_mode == "zero":
        timestamp_deltas = jnp.zeros((n,), dtype=jnp.int64)
    else:
        timestamp_deltas = timestamp_deltas.astype(jnp.int64)
    return keys, key_lengths, offset_deltas, timestamp_deltas


def stage_link_columns(buf):
    """Host-side link policy: which columns cross the H2D link, at which
    dtypes (shared by the single-device dispatch and the sharded
    staging — the narrowing thresholds are policy and must not fork).

    Returns (lengths_up, has_keys, has_offsets, ts_mode, ts_up):
    derivable columns report as absent (arange offsets, zero
    timestamps), timestamps ride the narrowest of u16/i32/i64 that
    holds every delta, lengths ride
    the narrowest of u8/u16 the record width allows. Arrays are
    unpadded — each caller pads/buckets for its own layout."""
    has_keys = buf.has_keys()
    off = buf.offset_deltas[: buf.count]
    has_offsets = not np.array_equal(
        off, np.arange(buf.count, dtype=off.dtype)
    )
    live_ts = buf.timestamp_deltas[: buf.count]
    if buf.count == 0 or not live_ts.any():
        ts_mode, ts_up = "zero", None
    elif live_ts.min() >= 0 and live_ts.max() < 2**16:
        # the common stream shape: small non-negative deltas from the
        # batch base — half the i32 tier's link bytes. Each narrowing
        # below is branch-guarded by the range test that selects it.
        ts_mode, ts_up = "u16", buf.timestamp_deltas.astype(np.uint16)  # noqa: FLV302
    elif np.abs(live_ts).max() < 2**31:
        ts_mode, ts_up = "i32", buf.timestamp_deltas.astype(np.int32)  # noqa: FLV302
    else:
        ts_mode, ts_up = "i64", buf.timestamp_deltas
    # lengths <= width, so the width test guards each narrowing
    if buf.width < (1 << 8):
        lengths_up = buf.lengths.astype(np.uint8)  # noqa: FLV302
    elif buf.width < (1 << 16):
        lengths_up = buf.lengths.astype(np.uint16)  # noqa: FLV302
    else:
        lengths_up = buf.lengths
    return lengths_up, has_keys, has_offsets, ts_mode, ts_up


def effective_result_compact() -> bool:
    """``FLUVIO_RESULT_COMPACT`` (on/off/auto): device-side result
    compaction — byte-mode outputs ship as ONE packed payload +
    lengths instead of a padded matrix, and view/byte materialization
    builds FLAT-BACKED output buffers (the padded output matrix never
    exists; the broker split-back consumes the flat directly). "auto"
    is ON everywhere: it reduces D2H bytes and host materialization
    cost on every backend."""
    mode = env_raw("FLUVIO_RESULT_COMPACT")
    return mode != "off"


def effective_result_compress() -> bool:
    """``FLUVIO_RESULT_COMPRESS`` (on/off/auto): the device-side glz
    ENCODE ladder for result streams (descriptor blocks, packed
    payloads). "auto" enables off-CPU only (on CPU there is no link to
    save), and only composes with compaction (the encoder runs over the
    packed streams compaction builds)."""
    mode = env_raw("FLUVIO_RESULT_COMPRESS")
    if mode == "off":
        return False
    if not effective_result_compact():
        return False
    return mode == "on" or jax.default_backend() != "cpu"


def effective_donation() -> bool:
    """``FLUVIO_DONATE`` (on/off/auto): donate the staged flat into the
    chain jits — the staged input is dead after the device re-pad, so
    XLA may alias it for outputs instead of the fetch paying a copy.
    "auto" is off on CPU (donation is unimplemented
    there and warns). Every dispatch stages FRESH device
    arrays (`jnp.asarray` per call), so heal/retry re-dispatches can
    never read a donated buffer — pinned in tests/test_glz_encode.py."""
    mode = env_raw("FLUVIO_DONATE")
    if mode == "off":
        return False
    return mode == "on" or jax.default_backend() != "cpu"


def effective_fetch_overlap() -> bool:
    """``FLUVIO_FETCH_OVERLAP`` (on/off/auto): overlap batch N's host
    materialization with batch N+1's device phase in the pipelined
    stream loops. Auto is ON: the deferred half is pure numpy over
    already-downloaded arrays (all executor-state mutation — failure
    ladders, carry bookkeeping — resolves before the thunk exists), so
    the only cost is one shared worker thread."""
    mode = env_raw("FLUVIO_FETCH_OVERLAP")
    return mode != "off"


# -- transfer-guard strictness (FLUVIO_TRANSFER_GUARD) ------------------------
#
# The static arm (analysis FLV003/FLV214) bans implicit D2H syncs in
# dispatch-side hot code syntactically; this is the dynamic arm. Armed
# ("disallow" | "log"), every dispatch-side region runs under
# ``jax.transfer_guard_device_to_host(mode)`` so an implicit
# device->host materialization (np.asarray on a jit result, int() on a
# device scalar) raises/logs at the exact offending line instead of
# silently stalling the async dispatch overlap. The fetch side is the
# ONE intentional D2H seam: when the env arm is set, it runs under an
# explicit "allow" scope. Unarmed (default): both helpers return a
# shared nullcontext — one env read + one context enter per BATCH
# dispatched, nothing per record — so a guard armed process-globally
# via jax.config alone is NOT allowlisted at the fetch seam; arm via
# FLUVIO_TRANSFER_GUARD to get the seam selection.

_TRANSFER_GUARD_ENV = "FLUVIO_TRANSFER_GUARD"
_TRANSFER_GUARD_MODES = ("disallow", "log")
_TRANSFER_GUARD_OFF = ("", "0", "off", "none", "allow")
_NULL_CTX = contextlib.nullcontext()


def _transfer_guard_mode() -> str:
    raw = (env_raw(_TRANSFER_GUARD_ENV) or "").strip().lower()
    if raw in _TRANSFER_GUARD_OFF:
        return ""
    if raw not in _TRANSFER_GUARD_MODES:
        raise ValueError(
            f"{_TRANSFER_GUARD_ENV}={raw!r}: expected one of "
            f"{list(_TRANSFER_GUARD_MODES)} (or 0/off to disable)"
        )
    return raw


def transfer_guard_dispatch():
    """Guard context for dispatch-side hot regions: forbids (or logs)
    implicit D2H while staging/dispatching; free when unarmed."""
    mode = _transfer_guard_mode()
    if mode:
        return jax.transfer_guard_device_to_host(mode)
    return _NULL_CTX


def transfer_guard_fetch():
    """Guard context for the intentional fetch/d2h seam: explicitly
    allowed even when the guard is armed process-wide."""
    if _transfer_guard_mode():
        return jax.transfer_guard_device_to_host("allow")
    return _NULL_CTX


_FETCH_POOL = None
_FETCH_POOL_LOCK = make_lock("executor.fetch_pool")


def _fetch_mat_pool():
    """Process-wide single-worker pool for the stream loops' deferred
    host materialization (`effective_fetch_overlap`): batch N's pure
    numpy split-back runs here while the main thread dispatches N+1 and
    blocks on N+1's downloads. One worker keeps completion in dispatch
    order; shared across executors so a broker that builds a chain per
    consumer session holds ONE idle thread, lazily created."""
    global _FETCH_POOL
    # double-checked lazy init: the unlocked fast-path read is a
    # GIL-atomic reference load (a stale None just falls through to the
    # locked re-check), so the per-dispatch cost is one attribute read
    if _FETCH_POOL is None:  # noqa: FLV202 — double-checked lazy init
        from concurrent.futures import ThreadPoolExecutor

        with _FETCH_POOL_LOCK:
            if _FETCH_POOL is None:
                _FETCH_POOL = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="fetch-mat"
                )
    return _FETCH_POOL  # noqa: FLV202 — published once, never rebound


class StreamState:
    """What ONE consumer stream owns of a chain: everything that a
    dispatch reads or advances and that must not be seen by another
    stream of the same compiled chain.

    - ``carries`` / ``device_carries``: the aggregate state, host
      mirror and device-resident form (the carry stays on the device
      between the slices of the stream; the host mirror syncs on
      demand),
    - ``instances``: the interpreter's instances of this stream, which
      mirror the carries for backend parity,
    - the heal lineage (``heal_epoch``, ``heal_carries``,
      ``heal_dispatch_seq``, ``dispatch_seq``): a heal invalidates the
      device carry lineage of every aggregate dispatch of this stream
      already in flight; the epoch marks them stale and the dispatch
      sequence tells a stale finish whether the healed carry tip is
      still current (safe to re-dispatch from) or already consumed by
      later dispatches,
    - ``window_bank``: a window chain's bank, which advances at a
      slice's FETCH and not at its dispatch (`window_stage.py`),
    - the partition identity (``span_chain``, ``partition_tag``) the
      partition layer installs around a slice: a chain@partition span
      label (SLO and admission key on it) and a per-partition:group
      label on down-link/decline telemetry; None by default.

    A state starts from the chain spec's seed
    (`TpuChainExecutor.initial_carries`)."""

    __slots__ = (
        "carries", "device_carries", "instances", "heal_epoch",
        "heal_carries", "heal_dispatch_seq", "dispatch_seq",
        "span_chain", "partition_tag", "window_bank",
    )

    def __init__(self, carries: List[Tuple[int, int, bool]]) -> None:
        self.carries = carries
        self.device_carries = None
        self.instances: List = []
        self.heal_epoch = 0
        self.heal_carries = None
        self.heal_dispatch_seq = -1
        self.dispatch_seq = 0
        self.span_chain: Optional[str] = None
        self.partition_tag: Optional[str] = None
        self.window_bank = None


def _stream_field(name: str) -> property:
    """An executor attribute that lives in its `StreamState`."""
    return property(
        lambda self: getattr(self.state, name),
        lambda self, value: setattr(self.state, name, value),
    )


class TpuChainExecutor(window_stage.WindowChainMixin):
    """A compiled chain, and the state of the one stream that owns it.

    The compiled half (stages, the instrumented jits and their
    shape-bucket caches, the striped lowering, learned fan-out
    capacity, link latches, byte counters) is everything below that is
    not a `StreamState` field. The stream half is ``self.state``; the
    names the dispatch/finish code reads it by (``carries``,
    ``_device_carries``, ``_heal_epoch``, ...) are properties onto it.
    A chain built for one owner (an engine chain, the bench, a
    partition runtime's bank) uses the executor's own state;
    `open_stream` gives a further stream the same compiled half with a
    state of its own."""

    carries = _stream_field("carries")
    _device_carries = _stream_field("device_carries")
    _instances = _stream_field("instances")
    _heal_epoch = _stream_field("heal_epoch")
    _heal_carries = _stream_field("heal_carries")
    _heal_dispatch_seq = _stream_field("heal_dispatch_seq")
    _dispatch_seq = _stream_field("dispatch_seq")
    span_chain = _stream_field("span_chain")
    partition_tag = _stream_field("partition_tag")

    def __init__(self, stages: List, agg_configs: List[Tuple[str, Optional[int], bytes]]):
        self.stages = stages
        # agg_configs rows are (combine_op, window_ms, initial_data)
        self.agg_configs = agg_configs
        self.state = StreamState(self.initial_carries())
        # short chain signature for compile-event attribution: which
        # chain shape a trace-cache miss compiled for
        self._chain_sig = (
            "+".join(
                type(s).__name__.lstrip("_").replace("Stage", "").lower()
                for s in stages
            )
            or "empty"
        )
        # buffer donation (effective_donation): the staged flat (arg 0)
        # is dead after the device re-pad, so the jits may alias it for
        # outputs — fetch stops paying that copy. Every dispatch stages
        # a fresh device array, so retries never touch a donated buffer.
        donate = (0,) if effective_donation() else ()
        # jit entry points wrapped for compile observability: every
        # trace-cache miss records {kind, chain signature + shape
        # bucket, wall seconds, persistent-cache outcome} (free when
        # FLUVIO_TELEMETRY=0 — see telemetry/compiles.py)
        self._jit_ragged = instrument_jit(
            jax.jit(
                scoped_program(self._chain_fn_ragged),
                static_argnames=(
                    "width", "kwidth", "has_keys", "has_offsets", "ts_mode",
                    "fanout_cap", "fan_bound", "enc", "pack",
                ),
                donate_argnums=donate,
            ),
            "ragged",
            describe=self._describe_ragged,
        )
        # striped wide-record layout (stripes.py): records wider than the
        # narrow layout stage as fixed-width stripe rows sharing a
        # segment id; the striped lowering is built lazily on the first
        # wide batch (resolved DSL programs ride along from try_build)
        self._programs: List = []
        self._striped = None
        self._striped_tried = False
        self._stripe_s, self._stripe_v = stripes.stripe_params()
        self._stripe_threshold = int(env_int("FLUVIO_STRIPE_THRESHOLD"))
        self._jit_striped = instrument_jit(
            jax.jit(
                scoped_program(self._chain_fn_striped),
                static_argnames=(
                    "srows", "kmax", "kwidth", "has_keys", "has_offsets",
                    "ts_mode", "fanout_cap", "fan_bound", "enc", "pack",
                ),
                donate_argnums=donate,
            ),
            "striped",
            describe=self._describe_striped,
        )
        # do any stages write key columns? (drives D2H key download)
        self._writes_keys = any(
            (isinstance(s, _MapStage) and s.key_fn is not None)
            or (isinstance(s, _AggregateStage) and s.window_ms)
            for s in stages
        )
        # late materialization: when every value-writing stage is a view
        # of the record's own bytes, the device ships descriptors (mask +
        # start/length per survivor) and the host rebuilds output bytes
        # from the slab it already holds — the D2H link carries ~5x less
        self._fanout = any(isinstance(s, _ArrayMapStage) for s in stages)
        self._cap_ratio: float = 0.0  # learned fan-out elements per source row
        self._fan_bound = kernels.FANOUT_MIN_BOUND  # learned: elements a record
        self.fanout_grows = 0  # times the bound grew, each a slice run again
        self._sharded = None  # multi-device delegate (enable_sharded)
        # prefetch guess: the viewable fetch's last two row buckets (armed when they agree)
        self._spec_rows: Optional[int] = None
        self._spec_prev: Optional[int] = None
        # CUMULATIVE link-byte totals since executor creation (read deltas
        # around a batch: they stay correct when dispatch k+1 interleaves
        # with fetch k); the same arrays cross the link on CPU and chip
        self.h2d_bytes_total = 0
        self.d2h_bytes_total = 0
        # gauge bookkeeping: staged link bytes per in-flight handle, so
        # the HBM/live-handle gauges go down by exactly what went up
        # (keyed by id(); entries live only dispatch->finish/discard)
        self._handle_gauge: Dict[int, int] = {}
        # recovery policy (resilience/policy.py): transient device/link
        # failures retry against the handle's carry snapshot; budgets
        # come from the FLUVIO_RETRY_* env knobs at construction
        self._retry_policy = RetryPolicy()
        self._viewable = not agg_configs and all(
            isinstance(s, (_FilterStage, _ArrayMapStage))
            or (
                isinstance(s, _MapStage)
                and s.span_fn is not None
                and s.key_fn is None
            )
            for s in stages
        )
        # pure-filter chains: every survivor's value IS its input record,
        # so the (start, length) descriptors are derivable host-side from
        # the mask + the lengths the host already holds — only the
        # bitmask crosses the D2H link (1 bit per input row)
        self._identity_view = not agg_configs and all(
            isinstance(s, _FilterStage) for s in stages
        )
        # cumulative host-side postops for view-mode materialization;
        # valid because every postop is position-wise (commutes with the
        # later stages' slicing)
        self._view_postops = tuple(
            op
            for s in stages
            if isinstance(s, _MapStage) and s.span_fn is not None
            for op in s.span_postops
        )
        # int-output mode: when the chain ENDS in an aggregate, outputs
        # are decimal renderings of int64s — ship the raw integers
        # (8 B/row) over the slow D2H link and let the host format,
        # instead of a padded ASCII matrix (16-32 B/row); the device-side
        # int_to_ascii materialization gets DCE'd. Chains where a map
        # stage rewrote keys on device are excluded: this path only
        # rebuilds keys from the input (or from window ints)
        self._int_output = (
            bool(stages)
            and isinstance(stages[-1], _AggregateStage)
            and not self._fanout
            and not any(
                isinstance(s, _MapStage) and s.key_fn is not None
                for s in stages
            )
        )
        # structural invariant (ADVICE r2): the host rebuilds off/ts
        # columns from survivor indices only while every stage passes
        # them through untouched; a stage that renumbers or fans out rows
        # forces the device columns onto the D2H path instead
        self._rebuild_offsets_from_src = all(
            s.preserves_rows and not s.rewrites_offsets for s in stages
        )
        # device-side result compaction + the down-link ENCODE ladder
        # (the PR-8 decode ladder, mirrored): byte-mode outputs pack to
        # one flat payload, view/fan-out descriptor blocks interleave
        # into one stream, and either stream optionally glz-ENCODES on
        # device before D2H ("xla" hash formulation -> raw ship;
        # `_enc_demote` latches it off from both the dispatch and the
        # fetch seams on a runtime failure). Resolved ONCE here — zero
        # per-dispatch cost when off (overhead-gate pinned).
        self._result_compact = effective_result_compact()
        self._enc_variant = "off"
        self._enc_chunk = 0
        if effective_result_compress():
            self._enc_variant = "xla"
            self._enc_chunk = glz.chunk_bytes()
        # which down-stream the encoder can apply to: descriptor blocks
        # (view/fan-out survivors) or the byte-mode packed payload;
        # identity/mask-only and int-output chains have nothing worth
        # encoding (1 bit/row and delta-narrowed ints)
        self._enc_eligible = (
            self._viewable and not self._identity_view
        ) or (not self._viewable and not self._int_output)

    # -- build --------------------------------------------------------------

    @classmethod
    def try_build(
        cls, entries: List[Tuple[SmartModuleDef, SmartModuleConfig]]
    ) -> Optional["TpuChainExecutor"]:
        stages: List = []
        agg_configs: List[Tuple[str, Optional[int], bytes]] = []
        programs: List = []
        if not entries:
            return None
        try:
            for module, config in entries:
                if stages and stages[-1].kind in window_stage.LAST_KINDS:
                    raise Unlowerable("a banked stage's rows are the output")
                kind = module.transform_kind()
                prog = module.dsl_program(kind)
                if prog is None:
                    return None
                prog = dsl.resolve_params(prog, config.params)
                programs.append(prog)
                if isinstance(prog, dsl.FilterProgram):
                    if infer_type(prog.predicate) != "bool":
                        raise Unlowerable("filter predicate must be bool")
                    stages.append(_FilterStage(lower_expr(prog.predicate)))
                elif isinstance(prog, dsl.MapProgram):
                    sp = lower_span(prog.value)
                    span_fn, span_post = sp if sp is not None else (None, ())
                    stages.append(
                        _MapStage(
                            value_fn=None if span_fn else lower_expr(prog.value),
                            key_fn=lower_expr(prog.key) if prog.key is not None else None,
                            span_fn=span_fn,
                            span_postops=span_post,
                        )
                    )
                elif isinstance(prog, dsl.FilterMapProgram):
                    sp = lower_span(prog.value)
                    span_fn, span_post = sp if sp is not None else (None, ())
                    stages.append(
                        _MapStage(
                            value_fn=None if span_fn else lower_expr(prog.value),
                            key_fn=lower_expr(prog.key) if prog.key is not None else None,
                            predicate=lower_expr(prog.predicate),
                            span_fn=span_fn,
                            span_postops=span_post,
                        )
                    )
                elif isinstance(prog, dsl.AggregateProgram):
                    if prog.window_ms and any(
                        isinstance(s, _ArrayMapStage) for s in stages
                    ):
                        # fan-out rows carry fresh (zero) timestamps, so a
                        # windowed aggregate downstream has no window key
                        raise Unlowerable("windowed aggregate after array_map")
                    if prog.contribution is not None:
                        # general form: user contribution expr + monoid
                        if prog.combine not in dsl.AGGREGATE_COMBINES:
                            raise Unlowerable(
                                f"aggregate combine {prog.combine}"
                            )
                        if infer_type(prog.contribution) != "int":
                            raise Unlowerable(
                                "aggregate contribution must be int-typed"
                            )
                        op = prog.combine
                        contribution_fn = lower_expr(prog.contribution)
                    else:
                        if prog.kind not in _AGG_OP:
                            raise Unlowerable(f"aggregate kind {prog.kind}")
                        op = _AGG_OP[prog.kind]
                        contribution_fn = _canned_contribution(prog.kind)
                    idx = len(agg_configs)
                    agg_configs.append(
                        (op, prog.window_ms or None, config.initial_data)
                    )
                    stages.append(
                        _AggregateStage(
                            op, prog.window_ms or None, idx, contribution_fn
                        )
                    )
                elif isinstance(prog, dsl.ArrayMapProgram):
                    if prog.mode not in ("json_array", "split"):
                        raise Unlowerable(f"array_map mode {prog.mode}")
                    if any(isinstance(s, _ArrayMapStage) for s in stages):
                        raise Unlowerable("one array_map per fused chain")
                    stages.append(_ArrayMapStage(mode=prog.mode, sep=prog.sep))
                else:  # a `dsl.WindowProgram` / `GroupProgram`, or Unlowerable
                    stages.append(window_stage.lower_banked(prog, stages))
        except (Unlowerable, KeyError):
            return None
        ex = cls(stages, agg_configs)
        ex._programs = programs
        return ex

    def attach(self, instances: List) -> None:
        """Python-side instances mirror aggregate state for backend parity."""
        self._instances = instances

    def open_stream(self) -> "TpuChainExecutor":
        """A further consumer stream over this compiled chain: the same
        programs, caches and latches by reference, and a `StreamState`
        of its own that starts from the chain spec's seed. Nothing of
        one stream's state is reachable from another's."""
        return _StreamExecutor(self)

    # -- device-side result compaction / down-link encode (traced) ----------

    @staticmethod
    def _desc_fields(width: int):
        """Static LE byte widths of one interleaved descriptor record:
        (start, len) at the SAME narrow tiers `_narrow_static` ships the
        raw columns at (u8 below 256, u16 below 64 Ki, i32 above) — the
        encoded stream must never start fatter than the raw fallback it
        competes with. Interleaving (rather than concatenating the
        columns) keeps each survivor's record contiguous, so corpus
        periodicity shows up as group periodicity for the encoder's
        matcher. Fan-out source rows are NOT in the stream: an (almost)
        incrementing counter defeats group matching, so the src column
        rides the existing delta-probe download next to the tokens."""
        return TpuChainExecutor._itm(width), TpuChainExecutor._itm(width + 1)

    @staticmethod
    def _desc_stream(st, ln, width: int):
        """Interleave compacted (start, len) descriptor columns into one
        LE byte stream (traced; the host `_desc_split` is the inverse —
        the two must not fork). Padded to an 8-byte boundary for the
        encoder's group alignment."""
        f_st, f_ln = TpuChainExecutor._desc_fields(width)
        with jax.named_scope("pack"):
            cols = []
            for col, f in (
                (st.astype(jnp.int32), f_st), (ln.astype(jnp.int32), f_ln)
            ):
                for b in range(f):
                    cols.append((col >> (8 * b)) & 0xFF)
            desc = jnp.stack(cols, axis=1).astype(jnp.uint8).reshape(-1)
            pad = (-desc.shape[0]) % 8
            if pad:
                desc = jnp.concatenate([desc, jnp.zeros((pad,), jnp.uint8)])
        return desc

    @staticmethod
    def _desc_split(desc: np.ndarray, count: int, width: int):
        """Host inverse of `_desc_stream` over the decoded down bytes:
        (start, len) columns for ``count`` survivors."""
        f_st, f_ln = TpuChainExecutor._desc_fields(width)
        stride = f_st + f_ln
        rec = (
            np.ascontiguousarray(desc[: count * stride])
            .reshape(count, stride)
            .astype(np.int64)
        )
        st = rec[:, 0:f_st] @ (1 << (8 * np.arange(f_st, dtype=np.int64)))
        ln = rec[:, f_st:stride] @ (1 << (8 * np.arange(f_ln, dtype=np.int64)))
        return st, ln.astype(np.int32)

    def _down_encode(self, packed: Dict, stream, enc: str) -> None:
        """Run the device encoder over a down-link byte stream and stash
        the token arrays + decision scalars in ``packed``. ``stream``'s
        static length must be a multiple of 8 (descriptor caps and
        payload caps are). The fetch decides per batch whether the
        tokens beat the raw slice — losing costs nothing extra on the
        wire (the raw columns are in ``packed`` either way)."""
        with jax.named_scope("link_encode"):
            ll, ml, srcs, lits, n_seq, n_lit, depth = glz.encode_result(
                stream, self._enc_chunk or glz.GLZ_CHUNK
            )
            packed["down_ll"] = ll
            packed["down_ml"] = ml
            packed["down_src"] = srcs
            packed["down_lits"] = lits
            packed["down_meta"] = jnp.stack(
                [n_seq, n_lit, depth]
            ).astype(jnp.int32)

    @staticmethod
    def _packed_payload(values_c, lengths_c):
        """Byte-mode result compaction: compacted value rows -> ONE flat
        4-aligned payload + per-row aligned starts (the exact
        `RecordBuffer.ragged_values` wire form, so the fetch adopts the
        download as a flat-backed output buffer with zero reshaping).
        Returns (payload u8[rows*width], payload_len scalar)."""
        rows, width = values_c.shape
        with jax.named_scope("pack"):
            l4 = (lengths_c.astype(jnp.int32) + 3) & ~3
            # i32 accumulator is safe: lengths <= the bucketed width, and
            # the staging guard (_check_matrix_addressing) bounds
            # rows * width — hence sum(l4) — under i32
            starts = jnp.cumsum(l4) - l4  # noqa: FLV303
            cap = rows * width
            col = jnp.arange(width, dtype=jnp.int32)[None, :]
            dst = jnp.where(col < l4[:, None], starts[:, None] + col, cap)
            payload = (
                jnp.zeros((cap,), jnp.uint8)
                .at[dst.reshape(-1)]
                .set(values_c.reshape(-1), mode="drop")
            )
            # same staging bound as the cumsum above: total fits i32
            return payload, jnp.sum(l4)  # noqa: FLV303

    # -- execution ----------------------------------------------------------

    def _chain_fn(self, arrays: Dict, count, base_ts, carries, fanout_cap=None,
                  enc: str = "off", pack: bool = False,
                  fan_bound: int = kernels.FANOUT_MIN_BOUND):
        """Fused chain body. Returns (header, packed dict, carries).

        Result bytes cross the host link on every batch, so outputs ship as
        the smallest representation; ``packed``'s keys are static per chain:

        - row-preserving chains ship the survivor set as a
          1-bit-per-input-row bitmask (the host rebuilds survivor
          indices and the untouched offset/timestamp columns from it);
          fan-out chains ship an explicit compacted ``src_row`` column.
        - view-mode chains ship (start, length) descriptors instead of
          value bytes — the host rebuilds outputs from the input slab it
          already holds.

        Header layout: [count, max_value_len, max_key_len, fanout_error,
        fanout_total], and a fan-out chain's fanout_over (a record's count
        over ``fan_bound``, else 0); a nonzero error spills the batch, a
        total above capacity or a nonzero over re-runs it larger.
        """
        n = arrays["values"].shape[0]
        state = dict(arrays)
        state["valid"] = jnp.arange(n, dtype=jnp.int32) < count
        state["view_start"] = jnp.zeros((n,), dtype=jnp.int32)
        state["src_row"] = jnp.arange(n, dtype=jnp.int32)
        ctx = {"fanout_cap": fanout_cap, "fan_bound": fan_bound}
        for i, stage in enumerate(self.stages):
            ctx["stage_index"] = i  # for a stage's own inner scopes
            with jax.named_scope(stage_scope(i, stage.kind)):
                state, carries = stage.apply(state, carries, base_ts, ctx)
        with jax.named_scope("compact"):
            return self._chain_outputs(arrays, state, carries, enc, pack)

    def _chain_outputs(self, arrays: Dict, state: Dict, carries,
                       enc: str, pack: bool):
        """The chain body's tail under the ``compact`` device scope
        (``pack`` / ``link_encode`` open inside it; the innermost names an
        operation): compaction, mask, header, the result's down-link form."""
        if self._window is not None:
            return window_stage.chain_outputs(state, carries)
        valid = state["valid"]
        out_count = jnp.sum(valid.astype(jnp.int32))
        fan_err = state.get("fan_err", jnp.asarray(False))
        fan_total = state.get("fan_total", jnp.int32(0))
        fan_over = [state["fan_over"]] if "fan_over" in state else []
        def _header(max_v, max_k):
            return jnp.stack(
                [
                    out_count.astype(jnp.int64),
                    max_v.astype(jnp.int64),
                    max_k.astype(jnp.int64),
                    fan_err.astype(jnp.int64),
                    fan_total.astype(jnp.int64),
                ] + [v.astype(jnp.int64) for v in fan_over]
            )

        packed: Dict = {}
        if self._viewable:
            if self._identity_view:
                # filter-only: the host derives every descriptor from
                # the mask + its own lengths — packing (and returning)
                # span columns would force XLA to keep compaction
                # gathers the fetch never reads
                packed["mask"] = kernels.pack_mask(valid)
                mx = jnp.max(jnp.where(valid, state["lengths"], 0))
                return _header(mx, jnp.int32(0)), packed, carries
            cols = [state["view_start"], state["lengths"]]
            if self._fanout:
                cols.append(state["src_row"])
            _, compacted = kernels.compact_rows(valid, *cols)
            packed["span_start"] = compacted[0]
            packed["span_len"] = compacted[1]
            if self._fanout:
                packed["src_row"] = compacted[2]
            else:
                packed["mask"] = kernels.pack_mask(valid)
            if enc != "off":
                # down-link encode of the interleaved descriptor block;
                # the raw columns stay in packed for the fetch's
                # per-batch raw-vs-tokens choice
                self._down_encode(
                    packed,
                    self._desc_stream(
                        compacted[0], compacted[1],
                        arrays["values"].shape[1],
                    ),
                    enc,
                )
            return _header(jnp.max(compacted[1]), jnp.int32(0)), packed, carries
        if self._int_output:
            windowed = bool(self.stages[-1].window_ms)
            cols = [state["agg_out_int"]]
            if windowed:
                cols.append(state["agg_win_int"])
            _, compacted = kernels.compact_rows(valid, *cols)
            packed["agg_int"] = compacted[0]
            if windowed:
                packed["agg_win"] = compacted[1]
            packed["mask"] = kernels.pack_mask(valid)
            return _header(jnp.int32(0), jnp.int32(0)), packed, carries
        compact_cols = [
            state["values"],
            state["lengths"],
            state["keys"],
            state["key_lengths"],
        ]
        if self._fanout:
            compact_cols.append(state["src_row"])
        elif not self._rebuild_offsets_from_src:
            compact_cols += [state["offset_deltas"], state["timestamp_deltas"]]
        _, compacted = kernels.compact_rows(valid, *compact_cols)
        packed["lengths"] = compacted[1]
        packed["keys"] = compacted[2]
        packed["key_lengths"] = compacted[3]
        if pack:
            # byte-mode result compaction: the padded output matrix
            # never crosses the link (or, flat-backed, even exists on
            # the host) — one packed 4-aligned payload does, sliced to
            # the batch's real byte count at fetch time
            payload, payload_len = self._packed_payload(
                compacted[0], compacted[1]
            )
            packed["payload"] = payload
            packed["payload_meta"] = payload_len.astype(jnp.int32)[None]
            if enc != "off":
                self._down_encode(packed, payload, enc)
        else:
            packed["values"] = compacted[0]
        if self._fanout:
            packed["src_row"] = compacted[4]
        elif not self._rebuild_offsets_from_src:
            packed["offset_deltas"] = compacted[4]
            packed["timestamp_deltas"] = compacted[5]
        else:
            packed["mask"] = kernels.pack_mask(valid)
        header = _header(jnp.max(packed["lengths"]), jnp.max(packed["key_lengths"]))
        return header, packed, carries

    def _chain_fn_ragged(
        self,
        flat,
        lengths,
        keys,
        key_lengths,
        offset_deltas,
        timestamp_deltas,
        count,
        base_ts,
        carries,
        *,
        width: int,
        kwidth: int,
        has_keys: bool,
        has_offsets: bool,
        ts_mode: str,
        fanout_cap: Optional[int] = None,
        fan_bound: int = kernels.FANOUT_MIN_BOUND,
        enc: str = "off",
        pack: bool = False,
    ):
        """Reconstruct the padded matrix on device from the flat upload.

        The host link only carried sum(lengths) bytes (plus bucketing)
        instead of rows x width. The flat staging is 4-byte aligned per
        record, so a row is consecutive i32 words of the flat and the
        re-pad fetches aligned blocks and shifts them into place
        (`ragged_repad_words`): no gather index per byte or per word,
        which is what the TPU's gather throughput is sensitive to.
        Derivable columns never cross the link: row starts come from a
        device cumsum of the aligned lengths, arange offset deltas
        (``has_offsets=False``) and zero timestamp deltas (``ts_mode=
        'zero'``) are synthesized, narrowed ones (u16/i32) widen here.
        """
        with jax.named_scope("repad"):
            values, lengths = ragged_repad_words(flat, lengths, width)
            n = lengths.shape[0]
            keys, key_lengths, offset_deltas, timestamp_deltas = (
                derived_meta_columns(
                    n, kwidth, has_keys, keys, key_lengths,
                    has_offsets, offset_deltas, ts_mode, timestamp_deltas,
                )
            )
        arrays = {
            "values": values,
            "lengths": lengths,
            "keys": keys,
            "key_lengths": key_lengths,
            "offset_deltas": offset_deltas,
            "timestamp_deltas": timestamp_deltas,
        }
        return self._chain_fn(
            arrays, count, base_ts, carries, fanout_cap, enc=enc, pack=pack, fan_bound=fan_bound
        )

    # -- striped wide-record path -------------------------------------------

    def _needs_stripes(self, buf: RecordBuffer) -> bool:
        """Layout decision only: does this batch's width exceed the
        narrow (one row per record) layout? Whether the CHAIN can run
        striped is `_striped_chain`'s call."""
        return buf.width > self._stripe_threshold

    def _striped_chain(self):
        """Lazily-built striped lowering of the chain (None when any
        stage is outside the stripeable subset — wide batches then keep
        the interpreter spill)."""
        if not self._striped_tried:
            self._striped_tried = True
            sc = None
            if self._programs and (self._viewable or self._int_output):
                sc = stripes.try_build_striped(
                    self._programs, self.stages, self._stripe_s, self._stripe_v
                )
            if (
                sc is not None
                and not self._int_output
                and tuple(sc.postops) != tuple(self._view_postops)
            ):  # pragma: no cover — both derive from the same programs
                sc = None
            self._striped = sc
        return self._striped

    def max_stageable_width(self) -> int:
        """Widest record value this chain stages on device (the broker's
        record-too-wide decline keys off this instead of a constant).
        Must be conservative: a slice this guard admits may never raise
        TpuSpill at dispatch time (in-flight chunks would be abandoned),
        so the sharded fan-out exclusion counts against it."""
        if self._sharded is not None and self._fanout:
            return self._stripe_threshold
        if self._striped_chain() is not None:
            return MAX_RECORD_WIDTH
        return self._stripe_threshold

    def _stripe_rows(self, buf: RecordBuffer) -> int:
        """Static stripe-row count for a batch (bucketed pow2/8 so
        compile variants stay bounded, like every other shape axis)."""
        exact = stripes.plan_rows(
            buf.lengths, buf.count, self._stripe_s, self._stripe_v
        )
        return self._bucket_bytes(max(exact, 8), floor=8)

    def _stripe_kmax(self, buf: RecordBuffer) -> int:
        """Static per-record stripe-count bound for the cross-stripe
        JsonGet carry (stripes.striped_json_span's outer trip count).
        0 for span-free striped chains, so they keep their
        width-independent compile key."""
        sc = self._striped_chain()
        if sc is None or not sc.needs_kmax:
            return 0
        return int(
            stripes.stripe_counts(
                np.asarray([buf.width]), self._stripe_s, self._stripe_v
            )[0]
        )

    def _striped_has_span(self) -> bool:
        """Does the striped lowering ship view descriptors (JsonGet map)
        instead of the whole-record mask? Routing only — callers already
        know the batch took the striped path."""
        return self._striped is not None and self._striped.has_span

    def _chain_fn_striped(
        self,
        flat,
        lengths,
        keys,
        key_lengths,
        offset_deltas,
        timestamp_deltas,
        count,
        base_ts,
        carries,
        *,
        srows: int,
        kmax: int = 0,
        kwidth: int,
        has_keys: bool,
        has_offsets: bool,
        ts_mode: str,
        fanout_cap: Optional[int] = None,
        fan_bound: int = kernels.FANOUT_MIN_BOUND,
        enc: str = "off",
        pack: bool = False,
    ):
        """Striped chain body: same ragged flat upload as the narrow
        path, re-padded into ``srows`` stripe rows of ``_stripe_s`` bytes
        with the segment sidecar derived on device from the lengths.
        Filters reduce per segment, aggregates run on the segment axis
        (the narrow scan stages, reused), and outputs ship as the segment
        survivor bitmask / aggregate ints / span view descriptors /
        fan-out descriptors — the narrow fetch paths consume all four.
        ``kmax`` is the static per-record stripe-count bound the JsonGet
        cross-stripe carry scans over (0 without a span stage); no
        ``fan_bound`` cuts a stripe row (`kernels.fanout_scatter`).
        """
        with jax.named_scope("repad"):
            lengths = lengths.astype(jnp.int32)
            n = lengths.shape[0]
            s, v = self._stripe_s, self._stripe_v
            live = jnp.arange(n, dtype=jnp.int32) < count
            plan = stripes.plan_device(lengths, live, srows, s, v)
            sv = stripes.striped_repad_words(flat, lengths, plan, s)
            keys, key_lengths, offset_deltas, timestamp_deltas = (
                derived_meta_columns(
                    n, kwidth, has_keys, keys, key_lengths,
                    has_offsets, offset_deltas, ts_mode, timestamp_deltas,
                )
            )
            arrays = {
                "keys": keys,
                "key_lengths": key_lengths,
                "offset_deltas": offset_deltas,
                "timestamp_deltas": timestamp_deltas,
            }
            seg_state = stripes.seg_state_of(plan, sv, lengths, arrays, s)
        ctx = {
            "sv": sv, "plan": plan, "seg_state": seg_state, "n": n,
            "kmax": kmax,
        }
        valid, seg_state, carries, fan, vspan = self._striped.run(
            ctx, live, carries, base_ts, {"fanout_cap": fanout_cap}
        )
        with jax.named_scope("compact"):
            return self._striped_outputs(
                valid, seg_state, carries, fan, vspan, plan, lengths,
                srows, fanout_cap, enc,
            )

    def _striped_outputs(self, valid, seg_state, carries, fan, vspan, plan,
                         lengths, srows: int, fanout_cap, enc: str):
        """`_chain_fn_striped`'s tail under the ``compact`` device scope
        (see `_chain_outputs`)."""
        packed: Dict = {}
        if fan is not None:
            flag, st_g, len_g = fan
            contributing = jnp.take(valid, plan["seg"]) & plan["row_live"]
            zeros_b = jnp.zeros((srows,), bool)
            zeros_i = jnp.zeros((srows,), jnp.int32)
            total, local_row, rel_start, elen = kernels.fanout_scatter(
                flag, st_g, len_g, zeros_b, zeros_i, zeros_i,
                contributing, fanout_cap,
            )
            src_seg = jnp.take(plan["seg"], jnp.clip(local_row, 0, srows - 1))
            out_count = jnp.minimum(total, jnp.int32(fanout_cap))
            packed["span_start"] = rel_start
            packed["span_len"] = elen
            packed["src_row"] = src_seg
            header = jnp.stack(
                [
                    out_count.astype(jnp.int64),
                    jnp.max(elen).astype(jnp.int64),
                    jnp.int64(0),
                    jnp.int64(0),  # split mode cannot error
                    total.astype(jnp.int64),
                    jnp.int64(0),  # nor is a stripe row cut by a bound
                ]
            )
            return header, packed, carries
        out_count = jnp.sum(valid.astype(jnp.int32))

        def _header(max_v):
            return jnp.stack(
                [
                    out_count.astype(jnp.int64),
                    max_v.astype(jnp.int64),
                    jnp.int64(0),
                    jnp.int64(0),
                    jnp.int64(0),
                ]
            )

        if self._int_output:
            windowed = bool(self.stages[-1].window_ms)
            cols = [seg_state["agg_out_int"]]
            if windowed:
                cols.append(seg_state["agg_win_int"])
            _, compacted = kernels.compact_rows(valid, *cols)
            packed["agg_int"] = compacted[0]
            if windowed:
                packed["agg_win"] = compacted[1]
            packed["mask"] = kernels.pack_mask(valid)
            return _header(jnp.int32(0)), packed, carries
        if vspan is not None:
            # span-view chain (JsonGet map): survivors are sub-record
            # views — ship compacted (start, length) descriptors + the
            # mask, the same packing the narrow viewable path uses
            st, ln = vspan
            _, compacted = kernels.compact_rows(
                valid, st.astype(jnp.int32), ln.astype(jnp.int32)
            )
            packed["span_start"] = compacted[0]
            packed["span_len"] = compacted[1]
            packed["mask"] = kernels.pack_mask(valid)
            if enc != "off":
                # striped spans index into records wider than the u16
                # tier by definition of the path: always the u32 fields
                # (MAX_RECORD_WIDTH forces the stride host-side too)
                self._down_encode(
                    packed,
                    self._desc_stream(
                        compacted[0], compacted[1], MAX_RECORD_WIDTH
                    ),
                    enc,
                )
            return _header(jnp.max(compacted[1])), packed, carries
        # viewable (filters + postop maps): survivors are whole records,
        # so the 1-bit segment mask is the entire D2H payload
        packed["mask"] = kernels.pack_mask(valid)
        mx = jnp.max(jnp.where(valid, lengths, 0))
        return _header(mx), packed, carries

    def _describe_ragged(self, *a, **k) -> str:
        """Compile-event signature for the narrow jit: chain + the
        static shape-bucket kwargs (never touches array values)."""
        return (
            f"{self._chain_sig} w={k.get('width')} "
            f"cap={k.get('fanout_cap')}{self._bank_sig(a)}"
            f"{self._down_sig(k)}"
        )

    @staticmethod
    def _down_sig(k) -> str:
        """Down-link static-axis tag: the encode rung and byte-mode
        packing flag are distinct XLA programs per shape bucket."""
        tag = ""
        if k.get("enc", "off") != "off":
            tag += f" enc={k['enc']}"
        if k.get("pack"):
            tag += " pack"
        return tag

    def _describe_striped(self, *a, **k) -> str:
        return (
            f"{self._chain_sig} srows={k.get('srows')} "
            f"kmax={k.get('kmax', 0)}"
            f"{self._down_sig(k)}"
        )

    # -- device-memory / in-flight gauges ------------------------------------

    def _gauge_track(self, handle, nbytes: int) -> None:
        """A dispatch went up: its staged link bytes are HBM-resident
        until the fetch (or discard) releases them. Booked in the
        device-memory ledger under a typed owner — ``shard_staging``
        on the sharded path, else ``staged_batch`` — and the old
        ``hbm_staged_bytes`` gauge republishes from the ledger as an
        alias, so finish/discard/dead-letter imbalance cannot drift
        the gauge from the balance the ledger proves."""
        if not TELEMETRY.enabled:
            return
        owner = "shard_staging" if self._sharded is not None else "staged_batch"
        self._handle_gauge[id(handle)] = nbytes
        TELEMETRY.mem_acquire(owner, ("batch", id(handle)), nbytes)
        TELEMETRY.gauge_add("live_batch_handles", 1)

    def _gauge_release(self, handle) -> None:
        """Idempotent: finish and discard may both see a handle on the
        recovery ladders — only the first release moves the ledger."""
        nbytes = self._handle_gauge.pop(id(handle), None)
        if nbytes is None:
            return
        TELEMETRY.mem_release(("batch", id(handle)))
        TELEMETRY.gauge_add("live_batch_handles", -1)

    def _dispatch(
        self,
        buf: RecordBuffer,
        fanout_cap: Optional[int] = None,
        span=None,
    ):
        """Async-dispatch one batch under the transfer-guard scope (see
        `transfer_guard_dispatch`): armed, an implicit D2H sync anywhere
        in the staging/dispatch path raises at the offending line."""
        with transfer_guard_dispatch():
            return self._dispatch_inner(buf, fanout_cap=fanout_cap, span=span)

    def _dispatch_inner(
        self,
        buf: RecordBuffer,
        fanout_cap: Optional[int] = None,
        span=None,
    ):
        """Async-dispatch one batch.

        Values go up ragged (flat bytes + starts) and are re-padded on
        device; key columns are synthesized on device when the batch has
        no keys. Remaining columns go as separate arrays — the host link
        runs per-array transfer streams concurrently. ``span`` (a
        telemetry BatchSpan, or None) books stage / h2d / dispatch.
        """
        if self._window is not None:
            carries = self._stream_bank().arrays()
        elif self._device_carries is not None:
            carries = self._device_carries
        else:
            carries = tuple((jnp.int64(acc), jnp.int64(win), jnp.asarray(has))
                            for acc, win, has in self.carries)
        striped = self._needs_stripes(buf)
        if striped and self._striped_chain() is None:
            # the one structural fallback left: a wide batch whose chain
            # is outside the stripeable subset spills to the interpreter
            TELEMETRY.add_stripe_fallback()
            raise TpuSpill(
                f"record width {buf.width} exceeds the narrow layout and "
                "the chain is not stripeable",
                reason="record-too-wide-unstripeable",
            )
        if striped and span is not None:
            # telemetry records the path the batch ACTUALLY executed:
            # striped batches land in their own latency/record family
            span.path = "striped"
        enc_now, pack_now = self._down_axes(striped)
        with timed(span, "stage"):
            faults.maybe_fire("stage")
            flat, bucket = self._flat_and_bucket(buf)
        with timed(span, "h2d"):
            faults.maybe_fire("h2d")
            flat_up, flat_h2d = self._stage_flat(flat, bucket)
        lengths_up, has_keys, has_offsets, ts_mode, ts_np = (
            stage_link_columns(buf)
        )
        ts_up = jnp.asarray(ts_np) if ts_np is not None else None

        def _call(enc, pack):
            if enc != "off":
                # the device-ENCODE seam: the sync half of the encode
                # ladder; async runtime failures surface at fetch and
                # heal there
                faults.maybe_fire("glz_encode")
            faults.maybe_fire("dispatch")
            args = (
                flat_up,
                jnp.asarray(lengths_up),
                jnp.asarray(buf.keys) if has_keys else None,
                jnp.asarray(buf.key_lengths) if has_keys else None,
                jnp.asarray(buf.offset_deltas) if has_offsets else None,
                ts_up,
                jnp.int32(buf.count),
                jnp.int64(buf.base_timestamp),
                carries,
            )
            kwargs = dict(
                kwidth=buf.keys.shape[1],
                has_keys=has_keys,
                has_offsets=has_offsets,
                ts_mode=ts_mode,
                fanout_cap=fanout_cap,
                fan_bound=self._fan_bound,
                enc=enc,
                pack=pack,
            )
            if striped:
                return self._jit_striped(
                    *args,
                    srows=self._stripe_rows(buf),
                    kmax=self._stripe_kmax(buf),
                    **kwargs,
                )
            return self._jit_ragged(*args, width=buf.width, **kwargs)

        with timed(span, "dispatch"):
            while True:
                try:
                    header, packed, new_carries = _call(enc_now, pack_now)
                    break
                except (KeyboardInterrupt, SystemExit):
                    # operator interrupts must unwind, never convert into a
                    # heal/spill (they are BaseException, but be explicit: no
                    # broadened rewrite of this handler may ever swallow them)
                    raise
                except Exception as e:
                    if is_program_fault(e):
                        # what only lowering/compiling raises is a fault of
                        # the PROGRAM, not device weather: no quieter rung
                        # answers it — the compiler's own error stops the run
                        raise
                    if enc_now == "off":
                        raise
                    # sync half of the ENCODE heal (runtime failures
                    # only): the encoder is output-side, so the batch
                    # re-dispatches with encode latched off
                    enc_now = self._enc_demote(e, where="dispatch")
                    # the failed attempt's flat already crossed the link —
                    # keep it on the counter — and may have been DONATED
                    # into the failed call: a healed re-dispatch stages a
                    # fresh device array, never a consumed buffer
                    self.h2d_bytes_total += flat_h2d
                    flat_up, flat_h2d = self._stage_flat(flat, bucket)
        self._enc_last = enc_now if enc_now != "off" else None
        # keep aggregate state device-resident; host mirrors sync on demand
        self._device_carries = new_carries
        self._dispatch_seq += 1
        self.h2d_bytes_total += (
            flat_h2d
            + lengths_up.nbytes
            + (buf.keys.nbytes + buf.key_lengths.nbytes if has_keys else 0)
            + (buf.offset_deltas.nbytes if has_offsets else 0)
            + (ts_up.nbytes if ts_up is not None else 0)
        )
        return header, packed

    @staticmethod
    def _flat_and_bucket(buf: RecordBuffer):
        """The flat's link form: 4-aligned ragged bytes + the pow2/8
        bucket it pads to — bounded compile count (four per size
        doubling) without pow2's up-to-2x H2D blowup. Returned UNPADDED:
        `_stage_flat` pads, under the ``h2d`` phase."""
        flat, _starts = buf.ragged_values()
        bucket = TpuChainExecutor._bucket_bytes(max(len(flat), 4))
        return flat, bucket

    @staticmethod
    def _padded(flat: np.ndarray, bucket: int) -> np.ndarray:
        if len(flat) < bucket:
            return np.pad(flat, (0, bucket - len(flat)))
        return flat

    def _down_axes(self, striped: bool) -> Tuple[str, bool]:
        """The down-link STATIC jit axes for a batch on the given
        layout: (encode rung, byte-mode packing flag). The ONE home for
        this arming rule — the dispatch seam, the jaxpr-lint/AOT-warmup
        work list, and the sharded dispatch (which additionally
        restricts to narrow viewable chains) all resolve through it, so
        warmup can never compile a program serving won't request. The
        encode ladder applies to descriptor/payload streams only
        (striped: span chains ship descriptors, mask-only chains have
        nothing to encode); byte-mode packing never applies striped
        (there is no striped byte mode). A window chain's few answer
        rows take neither."""
        if self._window is not None:
            return "off", False
        enc = self._enc_variant if self._enc_eligible else "off"
        if striped and not self._striped_has_span():
            enc = "off"
        pack = (
            self._result_compact
            and not striped
            and not self._viewable
            and not self._int_output
        )
        return enc, pack

    def _enc_demote(self, e, where: str = "dispatch") -> str:
        """A RUNTIME failure of an encode-armed batch, shared by the
        sync dispatch seam, the async fetch seam, and both sharded
        seams: encode latches off for this executor (the raw packed
        columns are still in every dispatch's ``packed``, so nothing is
        lost mid-flight). Counts the heal;
        returns the new variant ("off")."""
        TELEMETRY.add_heal()
        logging.getLogger(__name__).warning(
            "glz result-encode failed at %s; result compression disabled: %s",
            where, e,
        )
        self._enc_variant = "off"
        return "off"

    @staticmethod
    def _stage_flat(flat: np.ndarray, bucket: int):
        """The flat's one link form: padded to its bucket and viewed as
        i32 words (see `_chain_fn_ragged`; derivable columns stay off
        the link, synthesized on device). Returns (device array, bytes
        that crossed the link)."""
        words = TpuChainExecutor._padded(flat, bucket).view(np.int32)
        return jnp.asarray(words), words.nbytes

    def _download_carries(self):
        """The device carries as host values; None where the host
        mirror is the authority (nothing dispatched yet)."""
        if self._device_carries is None:
            return None
        with transfer_guard_fetch():
            return jax.device_get(self._device_carries)

    def _ensure_host_state(self, host=None) -> None:
        """Bring the host mirror (and the interpreter's instances) up to
        the device carries, or to ``host``: the carries a caller
        downloaded earlier, before a later dispatch replaced the device
        ones by futures of its own."""
        if host is None:
            host = self._download_carries()
        if host is None:
            return
        self.carries = [(int(a), int(w), bool(h)) for a, w, h in host]
        self._sync_instances()

    @staticmethod
    def _pad_slice(n: int, floor: int = 8) -> int:
        v = floor
        while v < n:
            v <<= 1
        return v

    @staticmethod
    def _bucket_bytes(n: int, floor: int = 1024) -> int:
        """pow2/8-granular bucket: padding under an eighth of the enclosing
        power of two (under a quarter of ``n``), four buckets per size
        doubling (each distinct bucket is a fresh XLA compile — persisted
        across processes by the compilation cache, but still paid once)."""
        v = floor
        while v < n:
            v <<= 1
        step = max(floor, v >> 3)
        return ((n + step - 1) // step) * step

    @staticmethod
    def _narrow_static(col, bound: int):
        """Cast a device column whose values are < ``bound`` to the
        narrowest unsigned dtype (static decision — no sync)."""
        if bound <= (1 << 8):
            return col.astype(jnp.uint8)
        if bound <= (1 << 16):
            return col.astype(jnp.uint16)
        return col

    @staticmethod
    def _delta_probe(col, count):
        """Device-side delta transform of an int column for narrow D2H.

        Returns (delta column, max|delta| scalar, base scalar) — all
        device-resident futures. delta[0] is forced to 0 so the caller
        reconstructs ``col[i] = base + cumsum(delta)[i]`` host-side; the
        scalars are tiny syncs the caller rides along with the header
        fetch to pick the narrowest lossless dtype per batch. Values past
        ``count`` are zeroed (the compaction tail would otherwise inject
        a bogus negative delta at position ``count``)."""
        n = col.shape[0]
        prev = jnp.concatenate([col[:1], col[:-1]])
        d = col - prev
        in_rng = jnp.arange(n, dtype=jnp.int32) < count
        d = jnp.where(in_rng, d, 0)
        d = d.at[0].set(0)
        return d, jnp.max(jnp.abs(d)), col[0]

    @staticmethod
    def _delta_decode(raw: np.ndarray, base: int, count: int) -> np.ndarray:
        vals = np.cumsum(raw[:count].astype(np.int64))
        return vals + base

    def _fan_probe(self, header, packed):
        """Delta-probe the fan-out src_row column (one implementation for
        the dispatch-time prefetch AND the fetch fallback — the guard
        policy must not fork). The uint8 cast downstream is only lossless
        for non-negative deltas; src_row is non-decreasing after
        compaction by construction, but verify per batch (signed min)
        rather than assume — a negative delta < 256 in magnitude would
        otherwise wrap silently and corrupt survivor row indices."""
        d, mx, b = self._delta_probe(packed["src_row"], header[0])
        return d, mx, jnp.min(d), b

    def _int_probe(self, header, packed):
        """Delta-probe the int-output accumulator (and window) columns;
        shared by the dispatch-time prefetch and the fetch fallback."""
        a_d, a_mx, a_b = self._delta_probe(packed["agg_int"], header[0])
        probes = [header, a_mx, a_b]
        w_d = None
        if bool(self.stages[-1].window_ms):
            w_d, w_mx, w_b = self._delta_probe(packed["agg_win"], header[0])
            probes += [w_mx, w_b]
        return a_d, w_d, probes

    def _view_slices(self, packed, width: int, rows: int):
        """Narrow + slice the viewable (start, length) descriptor columns
        (one implementation for the dispatch-time speculative copy AND
        the fetch-time slice — the narrowing bounds must not fork).
        Span starts/lengths are bounded by the input record width."""
        st_col = self._narrow_static(packed["span_start"], width)
        ln_col = self._narrow_static(packed["span_len"], width + 1)
        return (
            lax.slice(st_col, (0,), (rows,)),
            lax.slice(ln_col, (0,), (rows,)),
        )

    def _charge_unfetched_spec(self, handle) -> None:
        """Account the dispatch-time D2H copies of a dispatch whose fetch
        never ran (discarded speculation, interpreter spill): the bytes
        crossed the link either way, and the counters feed the bench's
        link attribution."""
        if len(handle) < 4 or handle[3] is None:
            return
        packed, spec = handle[2], handle[3]
        if spec.get("charged"):
            # idempotent: the recovery ladders (retry loop, abandon,
            # discard) may each try to charge the same handle once
            return
        spec["charged"] = True
        n = 64  # header + probe scalars
        view = spec.get("view")
        if view is not None:
            n += view[1].nbytes + view[2].nbytes
        mask = packed.get("mask")
        if mask is not None:
            n += mask.nbytes
        self.d2h_bytes_total += n

    def _download(self, slices, span=None):
        """Start every D2H copy, block once, account the bytes — the ONE
        point where result arrays leave the device (the sharded fetch
        routes through it too, so the counters cannot silently miss a
        path). Accumulates: a batch whose fetch runs twice (fan-out
        capacity retry) reports its total traffic."""
        with timed(span, "d2h"):
            faults.maybe_fire("fetch")
            for s in slices:
                s.copy_to_host_async()
            host = jax.device_get(slices)
        self.d2h_bytes_total += 64 + sum(np.asarray(a).nbytes for a in host)
        return host

    @staticmethod
    def _itm(bound: int) -> int:
        """Byte width `_narrow_static` ships a column of this bound at."""
        if bound <= (1 << 8):
            return 1
        if bound <= (1 << 16):
            return 2
        return 4

    def _down_try_fetch(
        self, packed, down_meta, raw_cost: int, span, extra_slices=(),
    ):
        """Fetch half of the result-encode ladder: download the token
        slices and inflate host-side — or decline. Returns
        (stream bytes, extra host arrays) on success, (None, None) when
        the tokens lose the per-batch ratio race (counted on the
        decline surface) or the host decode fails (one ladder rung
        down via `_enc_demote`; the raw columns are still in ``packed``
        so the caller falls back without a re-dispatch)."""
        n_seq, n_lit, depth = down_meta
        cap_s = packed["down_ll"].shape[0]
        cap_l = packed["down_lits"].shape[0]
        bs = min(self._bucket_bytes(max(n_seq, 8), floor=256), cap_s)
        bl = min(self._bucket_bytes(max(n_lit, 8), floor=256), cap_l)
        if bs * 6 + bl >= raw_cost:
            TELEMETRY.add_decline(glz.DECLINE_ENC_RATIO)
            self.tag_decline(glz.DECLINE_ENC_RATIO)
            return None, None
        slices = [
            lax.slice(packed["down_ll"], (0,), (bs,)),
            lax.slice(packed["down_ml"], (0,), (bs,)),
            lax.slice(packed["down_src"], (0,), (bs,)),
            lax.slice(packed["down_lits"], (0,), (bl,)),
            *extra_slices,
        ]
        host = self._download(slices, span)
        try:
            stream = glz.decode_result_host(
                host[0], host[1], host[2], host[3], n_seq, n_lit, cap_l,
                depth,
            )
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            # corrupt tokens: the download already counted its bytes;
            # demote one rung and let the caller ship the raw columns
            self._enc_demote(e, where="fetch")
            return None, None
        return stream, host[4:]

    def initial_carries(self) -> List[Tuple[int, int, bool]]:
        """The chain SPEC's starting aggregate state — what a brand-new
        executor (or a brand-new partition of this chain) begins from,
        independent of anything this instance has processed."""
        out: List[Tuple[int, int, bool]] = []
        for op, window_ms, initial in self.agg_configs:
            neutral = _AGG_NEUTRAL[op]
            if window_ms:
                out.append((neutral, 0, False))
            else:
                acc = dsl.parse_int_prefix(initial) if initial else neutral
                out.append((acc, 0, True))
        return out

    def set_partition_identity(self, key: Optional[str], group=None):
        """Install (or clear, key=None) the chain@partition identity —
        the ONE format every partition-keyed telemetry family joins on
        (span chains / SLO verdicts, down-* link variants, decline
        tags). Returns the previous (span_chain, partition_tag) pair
        for restore."""
        prev = (self.span_chain, self.partition_tag)
        if key is None:
            self.span_chain = None
            self.partition_tag = None
        else:
            self.span_chain = f"{self._chain_sig}@{key}"
            self.partition_tag = f"{key}:g{group}"
        return prev

    def restore_partition_identity(self, prev) -> None:
        self.span_chain, self.partition_tag = prev

    def tag_decline(self, reason: str) -> None:
        """Per-partition decline attribution: when the partition layer
        tagged this executor, count the decline AGAIN under its
        ``reason@topic/partition:group`` key. Zero work untagged — one
        attr read."""
        if self.partition_tag is not None:
            TELEMETRY.add_decline(f"{reason}@{self.partition_tag}")

    def _count_down_variant(self, variant: Optional[str]) -> None:
        """Per-batch down-link attribution (the `link_variants` family,
        and the preflight's differential truth):
        ``down-glz-xla`` when encoded tokens shipped,
        ``down-packed`` for mask/descriptor/delta-int/packed-payload
        downloads, ``down-raw`` only for the unpacked byte-mode matrix."""
        if variant:
            name = f"down-glz-{variant}"
        elif self._result_compact or self._viewable or self._int_output:
            name = "down-packed"
        else:
            name = "down-raw"
        TELEMETRY.add_link_variant(name)
        if self.partition_tag is not None:
            # partitioned streams: per-partition down-link attribution
            # (each partition's result stream compresses independently)
            TELEMETRY.add_link_variant(f"{name}@{self.partition_tag}")

    def _fetch(
        self, buf: RecordBuffer, header, packed, spec: Optional[Dict] = None,
        defer: bool = False,
    ):
        """The intentional D2H seam: `_fetch_inner` under the explicit
        transfer-guard allow scope (see `transfer_guard_fetch`)."""
        with transfer_guard_fetch():
            try:
                return self._fetch_inner(buf, header, packed, spec, defer)
            except window_stage.WindowOverflow as o:
                # here, under every caller (first fetch, a retry's
                # refetch), so a replayed slice grows alike
                return self._refetch_grown(buf, o, spec, defer)

    def _fetch_inner(
        self, buf: RecordBuffer, header, packed, spec: Optional[Dict] = None,
        defer: bool = False,
    ):
        """Minimal-D2H materialization.

        Always downloads the survivor bitmask (1 bit per input row) and
        rebuilds survivor indices + untouched offset/timestamp columns
        host-side. View-mode chains additionally download only the
        compacted (start, length) descriptors and rebuild output bytes
        from the input slab the host already holds; byte-mode chains
        download the compacted value (and key) columns sliced to
        count x used-width. All copies start async so the link runs them
        as concurrent streams; ``spec`` carries the copies
        `_start_result_copies` already put in flight at dispatch time
        (None on the fan-out retry path, which re-dispatched).
        """
        spec = spec or {}
        span = spec.get("span")
        # device-side failures surface at the first blocking sync on this
        # batch's results — the seam an armed "device" fault models
        faults.maybe_fire("device")
        if self._window is not None:
            return self._fetch_window(buf, header, packed, span, defer)
        # fan-out source rows are non-decreasing after compaction, so they
        # ship as uint8 deltas + a scalar base whenever the max delta fits
        # (the probe scalars ride the header sync the fetch pays anyway) —
        # 4x fewer bytes on the slow D2H direction for explode chains
        src_delta = None
        int_probe = None
        # down-link decision scalars ride the same blocking sync as the
        # header: encode token counts + the packed payload's byte count
        tail = []
        if "down_meta" in packed:
            tail.append(spec.get("down_meta", packed["down_meta"]))
        if "payload_meta" in packed:
            tail.append(spec.get("payload_meta", packed["payload_meta"]))
        # the ONE place the calling thread blocks on this batch's device
        # work: the header sync (its probe scalars ride the same sync)
        with timed(span, "wait"):
            if self._fanout:
                d, mx, mn, b = (
                    spec["fan_probe"]
                    if "fan_probe" in spec
                    else self._fan_probe(header, packed)
                )
                got = jax.device_get([header, mx, mn, b] + tail)
                hdr, mx, mn, b = got[:4]
                tail = got[4:]
                if int(mx) < (1 << 8) and int(mn) >= 0:
                    src_delta = (d.astype(jnp.uint8), int(b))
            elif self._int_output:
                # the delta-probe scalars ride the header sync — one blocking
                # round-trip, not two
                a_d, w_d, probes = (
                    spec["int_probe"]
                    if "int_probe" in spec
                    else self._int_probe(header, packed)
                )
                got = jax.device_get(probes)
                hdr = got[0]
                int_probe = (a_d, w_d, [int(x) for x in got[1:]])
            else:
                got = jax.device_get([header] + tail)
                hdr = got[0]
                tail = got[1:]
        down_meta = None
        payload_len = None
        if "down_meta" in packed:
            down_meta = [int(x) for x in tail[0]]
            tail = tail[1:]
        if "payload_meta" in packed:
            payload_len = int(tail[0][0])
        if span is not None:
            # the header sync is the first blocking wait on this batch's
            # results: everything up to here since dispatch-end is the
            # batch's time out on the device (queue + compute)
            span.mark_device_ready()
        count, max_v, max_k = int(hdr[0]), int(hdr[1]), int(hdr[2])
        if int(hdr[3]):
            raise TpuSpill("array_map transform error: interpreter decides")
        if self._fanout:
            cap = packed["span_len" if self._viewable else "lengths"].shape[0]
            total, over = int(hdr[4]), int(hdr[5])
            if total > cap or over:
                raise _FanoutOverflow(total, cap, over)
        width = buf.width

        def _mat(rows, st, ln, src):
            """View materialization, optionally deferred: the pure-numpy
            split-back the overlapped stream loop runs on the fetch
            worker — every download, probe, and failure ladder has
            already resolved by the time the thunk exists."""
            thunk = functools.partial(
                self._materialize_view, buf, count, rows, width, st, ln,
                src, max_v,
            )
            return thunk if defer else thunk()

        def _src_col():
            if src_delta is not None:
                return src_delta[0]
            return packed["src_row"]

        def _src_decode(raw: np.ndarray) -> np.ndarray:
            if src_delta is not None:
                return self._delta_decode(raw, src_delta[1], count)
            return np.asarray(raw[:count]).astype(np.int64)

        if self._viewable and (
            self._identity_view
            or (
                self._needs_stripes(buf)
                and not self._fanout
                and not self._striped_has_span()
            )
        ):
            # filter-only (and striped filter/postop chains, whose
            # survivors are whole records): the mask alone crosses the
            # link; spans are (0, input_length) for every survivor by
            # construction and postops apply host-side. Striped SPAN
            # chains (JsonGet map) fall through to the descriptor
            # download below instead.
            rows = self._bucket_bytes(max(count, 1), 8)
            host = self._download([packed["mask"]], span)
            src = self._mask_to_src(host[0], buf)[:count]
            st = np.zeros(count, dtype=np.int64)
            ln = buf.lengths[src].astype(np.int32)
            self._count_down_variant(None)
            return _mat(rows, st, ln, src)
        if self._viewable:
            n_desc = packed["span_start"].shape[0]
            rows = min(self._bucket_bytes(max(count, 1), 8), n_desc)
            if not self._fanout:
                self._spec_prev, self._spec_rows = self._spec_rows, rows
            if down_meta is not None:
                # encoded descriptor block: the token download replaces
                # the (start, len) column slices whenever it wins the
                # per-batch ratio race; survivor recovery (the mask, or
                # the fan-out src column through its usual delta-probe
                # tiers) rides the same download
                desc_width = (
                    MAX_RECORD_WIDTH if self._needs_stripes(buf) else width
                )
                raw_cost = rows * sum(self._desc_fields(desc_width))
                stream, extra = self._down_try_fetch(
                    packed, down_meta, raw_cost, span,
                    (lax.slice(_src_col(), (0,), (rows,)),)
                    if self._fanout
                    else (packed["mask"],),
                )
                if stream is not None:
                    view_spec = spec.get("view")
                    if view_spec is not None:
                        # dispatch-time speculative descriptor copies
                        # crossed for nothing: charge them
                        self.d2h_bytes_total += (
                            view_spec[1].nbytes + view_spec[2].nbytes
                        )
                    st, ln = self._desc_split(stream, count, desc_width)
                    if self._fanout:
                        src = _src_decode(extra[0])
                    else:
                        src = self._mask_to_src(extra[0], buf)[:count]
                    self._count_down_variant("xla")
                    return _mat(rows, st, ln, src)
            view_spec = spec.get("view")
            if view_spec is not None and view_spec[0] == rows:
                # the dispatch-time speculative copies guessed this
                # bucket: their transfers are already in flight (or done)
                slices = [view_spec[1], view_spec[2], packed["mask"]]
            else:
                if view_spec is not None:
                    # wrong guess: the speculative descriptors crossed the
                    # link for nothing — charge them so the D2H counters
                    # reflect real traffic
                    self.d2h_bytes_total += (
                        view_spec[1].nbytes + view_spec[2].nbytes
                    )
                slices = list(self._view_slices(packed, width, rows))
                if self._fanout:
                    slices.append(lax.slice(_src_col(), (0,), (rows,)))
                else:
                    slices.append(packed["mask"])
            host = self._download(slices, span)
            st_h, ln_h = host[0], host[1]
            if self._fanout:
                src = _src_decode(host[2])
            else:
                src = self._mask_to_src(host[2], buf)[:count]
            st = st_h[:count].astype(np.int64)
            ln = ln_h[:count].astype(np.int32)
            self._count_down_variant(None)
            return _mat(rows, st, ln, src)

        if self._int_output:
            self._count_down_variant(None)
            return self._fetch_ints(
                buf, count, packed, int_probe, span, defer=defer
            )

        return self._fetch_bytes(
            buf, count, packed, max_v, max_k, _src_col, _src_decode, span,
            down_meta=down_meta, payload_len=payload_len, defer=defer,
        )

    @staticmethod
    def _mask_to_src(mask_bytes: np.ndarray, buf: RecordBuffer) -> np.ndarray:
        """Survivor indices from the packed 1-bit mask (little-endian
        bit order, truncated to the buffer's live rows) — the ONE
        decode for every mask-shipping fetch path."""
        return np.flatnonzero(
            np.unpackbits(mask_bytes, bitorder="little")[: buf.rows]
        )

    def _materialize_view(
        self, buf: RecordBuffer, count: int, rows: int, width: int,
        st: np.ndarray, ln: np.ndarray, src: np.ndarray, max_v: int,
    ) -> RecordBuffer:
        """Rebuild view-mode output bytes from the input slab the host
        already holds (shared by the descriptor-download path and the
        filter-only identity path, which derives st/ln host-side).

        With result compaction armed the output is VIEW-BACKED: spans
        of the input's flat, copied once, by the encoder, instead of a
        rows x width padded matrix — the fat-record fetch wall was this
        very matrix (and the masked re-extraction `to_columns` paid on
        top of it)."""
        vw = min(self._pad_slice(max(max_v, 1)), width)
        if self._result_compact:
            return self._materialize_view_flat(
                buf, count, rows, vw, st, ln, src
            )
        out_values = np.zeros((rows, vw), dtype=np.uint8)
        if count:
            keep = np.arange(vw, dtype=np.int32)[None, :] < ln[:, None]
            if buf.values is None:
                # flat-backed buffer: slice views straight out of the
                # aligned flat (never builds the padded matrix)
                flat, starts = buf.ragged_values()
                if len(flat):
                    base = starts.astype(np.int64)[src] + st
                    cols = (
                        base[:, None]
                        + np.arange(vw, dtype=np.int64)[None, :]
                    )
                    gathered = flat[np.clip(cols, 0, len(flat) - 1)]
                else:  # all-empty values: every view is empty
                    gathered = np.zeros((count, vw), dtype=np.uint8)
            else:
                cols = st[:, None] + np.arange(vw, dtype=np.int64)[None, :]
                gathered = buf.values[
                    src[:, None], np.clip(cols, 0, width - 1)
                ]
            gathered = np.where(keep, gathered, 0)
            out_values[:count] = apply_postops_host(
                gathered, self._view_postops
            )
        out_lengths = np.zeros((rows,), dtype=np.int32)
        out_lengths[:count] = ln
        out_keys, out_klens = self._view_keys(buf, count, rows, src)
        return self._assemble(buf, count, rows, out_values, out_lengths,
                              out_keys, out_klens, src)

    def _view_keys(self, buf: RecordBuffer, count: int, rows: int, src):
        """Survivor key columns for view-mode outputs (shared by the
        dense and flat materializers)."""
        if buf.has_keys():
            out_keys = np.zeros((rows, buf.keys.shape[1]), dtype=np.uint8)
            out_klens = np.full((rows,), -1, dtype=np.int32)
            out_keys[:count] = buf.keys[src]
            out_klens[:count] = buf.key_lengths[src]
        else:
            out_keys = np.zeros((rows, 1), dtype=np.uint8)
            out_klens = np.full((rows,), -1, dtype=np.int32)
        return out_keys, out_klens

    def _materialize_view_flat(
        self, buf: RecordBuffer, count: int, rows: int, vw: int,
        st: np.ndarray, ln: np.ndarray, src: np.ndarray,
    ) -> RecordBuffer:
        """View-backed materialization: no byte is copied. Each survivor
        is a span of the input's 4-aligned flat, one int64 column of
        first bytes gathered from the input's starts; the output buffer
        keeps that flat, the column and the chain's postops as one case
        fold (`RecordBuffer._view`). The served encode copies the spans
        straight into the wire records; any other consumer renders the
        flat of its own (`buffer.render_view_flat`)."""
        in_flat, in_starts = buf.ragged_values()
        base = np.add(in_starts[src], st, dtype=np.int64)
        out_lengths = np.zeros((rows,), dtype=np.int32)
        out_lengths[:count] = ln
        out_keys, out_klens = self._view_keys(buf, count, rows, src)
        return self._assemble(
            buf, count, rows, None, out_lengths, out_keys, out_klens, src,
            view=(in_flat, base, view_fold(self._view_postops)), vw=vw,
        )

    def _fetch_bytes(
        self, buf: RecordBuffer, count: int, packed, max_v, max_k,
        _src_col, _src_decode, span=None, down_meta=None,
        payload_len=None, defer: bool = False,
    ):
        """Byte-mode materialization: compacted value/key columns cross
        the link sliced to count x used-width (tail of `_fetch`; the
        src-column helpers close over its probe state). ``defer``: as
        in the view mode's `_mat`, everything after the download is
        numpy over host arrays and is returned as a thunk.

        With result compaction armed (``packed["payload"]``) the value
        matrix never crosses at all: ONE packed 4-aligned payload does —
        sliced to the batch's real byte count, or inflated from the
        device-encoded tokens when they win the ratio race — and the
        output buffer adopts it FLAT-BACKED (the padded output matrix
        never exists on the host either; `to_columns`/`to_records`
        consume the flat directly)."""
        use_payload = "payload" in packed
        n_rows = packed["lengths"].shape[0]
        rows = min(self._bucket_bytes(max(count, 1), 8), n_rows)
        val_w = (
            packed["payload"].shape[0] // n_rows
            if use_payload
            else packed["values"].shape[1]
        )
        vw = min(self._pad_slice(max(max_v, 1)), val_w)
        kw = (
            min(self._pad_slice(max(max_k, 1)), packed["keys"].shape[1])
            if max_k > 0
            else 0
        )
        # byte mode: output widths can exceed the input width (e.g.
        # Concat), so the narrow-length cast keys off the OUTPUT matrix
        out_len_col = self._narrow_static(packed["lengths"], val_w + 1)
        want_keys = buf.has_keys() or self._writes_keys
        # survivor recovery: fan-out chains ship an explicit src column;
        # row-preserving chains ship the 1-bit mask when the host rebuilds
        # off/ts from it, or the device off/ts columns when a stage
        # rewrote them
        want_mask = self._rebuild_offsets_from_src and not self._fanout
        want_dev_offsets = (
            not self._rebuild_offsets_from_src and not self._fanout
        )
        slices = []
        payload_np = None
        used_tokens = None
        if use_payload:
            pb = min(
                self._bucket_bytes(max(payload_len, 1), floor=256),
                packed["payload"].shape[0],
            )
            if down_meta is not None:
                stream, _ = self._down_try_fetch(
                    packed, down_meta, pb, span
                )
                if stream is not None:
                    payload_np = stream
                    used_tokens = "xla"
            if payload_np is None:
                slices.append(lax.slice(packed["payload"], (0,), (pb,)))
        else:
            slices.append(lax.slice(packed["values"], (0, 0), (rows, vw)))
        slices.append(lax.slice(out_len_col, (0,), (rows,)))
        if self._fanout:
            slices.append(lax.slice(_src_col(), (0,), (rows,)))
        elif want_mask:
            slices.append(packed["mask"])
        if want_keys:
            slices.append(lax.slice(packed["key_lengths"], (0,), (rows,)))
            if kw:
                slices.append(lax.slice(packed["keys"], (0, 0), (rows, kw)))
        if want_dev_offsets:
            slices.append(lax.slice(packed["offset_deltas"], (0,), (rows,)))
            slices.append(lax.slice(packed["timestamp_deltas"], (0,), (rows,)))
        host = self._download(slices, span)
        self._count_down_variant(used_tokens)

        def _split_back() -> RecordBuffer:
            payload = payload_np
            pos = 0
            out_values = None
            if use_payload:
                if payload is None:
                    payload = np.asarray(host[pos])
                    pos += 1
            else:
                out_values = host[pos]
                pos += 1
            out_lengths = np.asarray(host[pos]).astype(np.int32)
            pos += 1
            src = None
            if self._fanout:
                src = _src_decode(host[pos])
                pos += 1
            elif want_mask:
                src = self._mask_to_src(host[pos], buf)
                pos += 1
            if want_keys:
                out_klens = host[pos]
                out_keys = host[pos + 1] if kw else np.zeros((rows, 1), dtype=np.uint8)
                pos += 1 + (1 if kw else 0)
            else:
                out_klens = np.full((rows,), -1, dtype=np.int32)
                out_keys = np.zeros((rows, 1), dtype=np.uint8)
            flat = starts = None
            if use_payload:
                # adopt the payload flat-backed: per-row aligned starts are
                # one cumsum over the downloaded lengths (bit-identical to
                # the device's packing by construction)
                out_lengths = out_lengths.copy()
                out_lengths[count:] = 0
                l4 = (out_lengths.astype(np.int64) + 3) & ~3
                starts_all = np.cumsum(l4) - l4
                starts = starts_all.astype(np.int32)
                flat = np.ascontiguousarray(payload[: int(l4.sum())])
            if want_dev_offsets:
                out_off = np.asarray(host[pos]).astype(np.int32)
                out_ts = np.asarray(host[pos + 1]).astype(np.int64)
                out_off[count:] = 0
                out_ts[count:] = 0
                return RecordBuffer(
                    values=out_values, lengths=out_lengths, keys=out_keys,
                    key_lengths=out_klens, offset_deltas=out_off,
                    timestamp_deltas=out_ts, count=count,
                    base_offset=buf.base_offset, base_timestamp=buf.base_timestamp,
                    _flat=flat, _starts=starts,
                    _width=vw if use_payload else 0,
                    _rows=rows if use_payload else 0,
                )
            return self._assemble(buf, count, rows, out_values, out_lengths,
                                  out_keys, out_klens, src,
                                  flat=flat, starts=starts, vw=vw)

        return _split_back if defer else _split_back()

    @staticmethod
    def _ints_to_ascii_host(ints: np.ndarray):
        """int64 -> decimal ASCII matrix + lengths, vectorized via numpy's
        fixed-width bytes cast (bit-equal to kernels.int_to_ascii)."""
        n = len(ints)
        if n == 0:
            return np.zeros((0, 1), np.uint8), np.zeros((0,), np.int32)
        fixed = ints.astype("S20")  # NUL-padded decimal renderings
        mat = np.frombuffer(fixed.tobytes(), dtype=np.uint8).reshape(n, 20)
        lens = (mat != 0).sum(axis=1).astype(np.int32)  # digits have no NULs
        return mat, lens

    def _int_value_columns(self, ints, rows: int, count: int):
        """An int column's decimals as the padded value matrix + lengths:
        what an int-backed `RecordBuffer` renders on demand, and the
        value half of `_int_output_columns`."""
        mat, lens = self._ints_to_ascii_host(ints)
        vw = min(self._pad_slice(max(int(lens.max()) if count else 1, 1)), 32)
        out_values = np.zeros((rows, vw), dtype=np.uint8)
        out_lengths = np.zeros((rows,), dtype=np.int32)
        if count:
            w = min(vw, mat.shape[1])
            out_values[:count, :w] = mat[:, :w]
            out_lengths[:count] = lens
        return out_values, out_lengths

    def _int_output_columns(self, buf, ints, wins, src, rows: int, count: int):
        """Shared host assembly for int-output chains (the sharded
        executor's, and the single-device one's windowed outputs): render
        decimals, window keys (``wins``; None when unwindowed), or pass
        input keys through — one implementation so both engine modes
        stay bit-identical by construction."""
        out_values, out_lengths = self._int_value_columns(ints, rows, count)
        if wins is not None:
            kmat, klens = self._ints_to_ascii_host(wins)
            kw = min(self._pad_slice(max(int(klens.max()) if count else 1, 1)), 32)
            out_keys = np.zeros((rows, kw), dtype=np.uint8)
            out_klens = np.full((rows,), -1, dtype=np.int32)
            if count:
                w = min(kw, kmat.shape[1])
                out_keys[:count, :w] = kmat[:, :w]
                out_klens[:count] = klens
        elif buf.has_keys():
            out_keys = np.zeros((rows, buf.keys.shape[1]), dtype=np.uint8)
            out_klens = np.full((rows,), -1, dtype=np.int32)
            if count:
                out_keys[:count] = buf.keys[src[:count]]
                out_klens[:count] = buf.key_lengths[src[:count]]
        else:
            out_keys = np.zeros((rows, 1), dtype=np.uint8)
            out_klens = np.full((rows,), -1, dtype=np.int32)
        return out_values, out_lengths, out_keys, out_klens

    def _fetch_ints(
        self, buf: RecordBuffer, count: int, packed, probe, span=None,
        defer: bool = False,
    ):
        """Int-output D2H: survivor mask + accumulator column(s). The
        output buffer is INT-BACKED: it carries the reconstructed int64
        column, and the decimals are rendered where they are wanted (by
        the native encoder straight into the served records, or by
        `dense_values()` through `_int_value_columns`). Window-key
        outputs are rendered here (no served path takes them yet).

        Running-aggregate outputs are the one mode whose D2H would be a
        full 8 B/row int64 column, and consecutive accumulator values
        differ by one record's contribution — so the columns ship as
        int16/int32 deltas plus a scalar base whenever the batch's max
        |delta| fits (decided per batch by a tiny scalar sync), and the
        host reconstructs with one cumsum. Window ids are non-decreasing
        and delta-compress the same way. ``defer``: the delta decode and
        the assembly are numpy over the downloaded arrays and are
        returned as a thunk."""
        windowed = bool(self.stages[-1].window_ms)
        n_c = packed["agg_int"].shape[0]
        rows = min(self._bucket_bytes(max(count, 1), 8), n_c)
        a_d, w_d, scal = probe

        def _pick(col, d, mx):
            if mx < (1 << 15):
                return d.astype(jnp.int16), True
            if mx < (1 << 31):
                return d.astype(jnp.int32), True
            return col, False

        a_col, a_is_delta = _pick(packed["agg_int"], a_d, scal[0])
        # which form the accumulator column crossed the down-link in,
        # next to the `down-*` variants (`_count_down_variant`)
        TELEMETRY.add_link_variant(
            f"agg-delta-{a_col.dtype.name}" if a_is_delta else "agg-full"
        )
        slices = [packed["mask"], lax.slice(a_col, (0,), (rows,))]
        if windowed:
            w_col, w_is_delta = _pick(packed["agg_win"], w_d, scal[2])
            slices.append(lax.slice(w_col, (0,), (rows,)))
        host = self._download(slices, span)

        def _split_back() -> RecordBuffer:
            src = self._mask_to_src(host[0], buf)
            ints = (
                self._delta_decode(host[1], scal[1], count)
                if a_is_delta
                else np.asarray(host[1][:count]).astype(np.int64)
            )
            if not windowed:
                out_keys, out_klens = self._view_keys(
                    buf, count, rows, src[:count]
                )
                return self._assemble(buf, count, rows, None, None,
                                      out_keys, out_klens, src, ints=ints)
            wins = (
                self._delta_decode(host[2], scal[3], count)
                if w_is_delta
                else np.asarray(host[2][:count]).astype(np.int64)
            )
            out_values, out_lengths, out_keys, out_klens = self._int_output_columns(
                buf, ints, wins, src, rows, count
            )
            return self._assemble(buf, count, rows, out_values, out_lengths,
                                  out_keys, out_klens, src)

        return _split_back if defer else _split_back()

    def _assemble(self, buf, count, rows, out_values, out_lengths, out_keys,
                  out_klens, src, flat=None, starts=None,
                  vw: int = 0, ints=None, render=None,
                  row_format=None, view=None) -> RecordBuffer:
        """Rebuild offset/timestamp columns from survivor source rows.

        Row-preserving chains pass the source deltas through; fan-out
        outputs are "fresh" — zero relative to their source record's
        batch, i.e. the batch-rebase columns the broker attaches (zeros
        at the engine surface, matching the interpreter's fresh
        Records). With ``flat``/``starts`` set (result compaction) the
        output buffer is FLAT-BACKED: ``out_values`` is None and the
        padded matrix is never built; with ``view`` set it is
        VIEW-BACKED (`RecordBuffer._view`). With ``ints`` set (an int-output
        fetch) it is INT-BACKED: ``out_values`` and ``out_lengths`` are
        None until a consumer asks for the rendered form (``render``,
        by default one column's decimals; ``row_format``: a keyed
        table's rows, `RecordBuffer._row_format`)."""
        src_c = np.clip(
            src[:count] if len(src) >= count else np.zeros(count, np.int64),
            0,
            buf.offset_deltas.shape[0] - 1,
        )
        out_off = np.zeros((rows,), dtype=np.int32)
        out_ts = np.zeros((rows,), dtype=np.int64)
        if self._fanout:
            if buf.fresh_offset_deltas is not None:
                out_off[:count] = buf.fresh_offset_deltas[src_c]
            if buf.fresh_timestamp_deltas is not None:
                out_ts[:count] = buf.fresh_timestamp_deltas[src_c]
        else:
            out_off[:count] = buf.offset_deltas[src_c]
            out_ts[:count] = buf.timestamp_deltas[src_c]
        return RecordBuffer(
            values=out_values,
            lengths=out_lengths,
            keys=out_keys,
            key_lengths=out_klens,
            offset_deltas=out_off,
            timestamp_deltas=out_ts,
            count=count,
            base_offset=buf.base_offset,
            base_timestamp=buf.base_timestamp,
            _flat=flat,
            _starts=starts,
            _width=vw if flat is not None or view is not None else 0,
            _rows=rows if out_values is None else 0,
            _ints=ints,
            _render=(render or self._int_value_columns)
            if ints is not None else None,
            _row_format=row_format,
            _view=view,
        )

    def _fanout_cap(self, buf: RecordBuffer) -> Optional[int]:
        """Capacity for this batch: learned elements-per-source-row ratio
        scaled by the batch's rows (an outlier batch raises the ratio,
        not an absolute row count, so small batches stay small). A
        window chain's is its learned emit capacity."""
        if self._window is not None:
            return self._window_emit_cap(buf)
        if not self._fanout:
            return None
        rows = buf.rows
        ratio = max(self._cap_ratio, 4.0)
        return self._bucket_bytes(max(int(ratio * rows), 1024), 1024)

    def _learn_cap(self, buf: RecordBuffer, total: int) -> None:
        rows = max(buf.rows, 1)
        # 25% headroom over the observed density
        self._cap_ratio = max(self._cap_ratio, 1.25 * total / rows)

    def _learn_bound(self, largest: int) -> None:
        """The most elements one record of a batch held, where that
        passed the chain's bound (0 where none did): the bound grows to
        the next power of two, so a few compiled bounds serve any
        stream, and the grow books a `fanout-grow` event."""
        if largest > self._fan_bound:
            grown = 1 << (largest - 1).bit_length()
            TELEMETRY.add_fanout_grow(f"rows:{self._fan_bound}->{grown}")
            self._fan_bound = grown
            self.fanout_grows += 1

    def enable_sharded(self, n_devices: int, devices=None) -> None:
        """Switch this chain to the multi-device engine mode: the same
        stage pipeline under `shard_map` over an ``n_devices`` record
        mesh, pallas kernels active per shard. Raises ValueError when
        the chain or the device set cannot shard (caller decides whether
        that is fatal)."""
        from fluvio_tpu.parallel.sharded import ShardedChainExecutor

        if self._window is not None:
            raise ValueError(
                "a window chain cannot be sharded: a stream's window bank "
                "lives on one device"
            )
        self._sharded = ShardedChainExecutor(self, n_devices, devices)

    # -- recovery (resilience/policy.py) -------------------------------------

    def _dispatch_with_retry(self, call):
        """Bounded transient retry of the dispatch half.

        Carry-safe by construction: `_dispatch` (and the sharded
        delegate) only commits new device carries after the jitted call
        returns, so a staging/transfer/trace failure leaves the carry
        chain exactly where it was — every attempt starts from the same
        state. Deterministic faults and exhausted budgets re-raise for
        the engine's spill/quarantine ladder."""
        attempt = 0
        while True:
            try:
                return call()
            except (TpuSpill, KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                if not self._retry_policy.should_retry(e, attempt):
                    raise
                point = getattr(e, "point", None) or "dispatch"
                TELEMETRY.add_retry(point)
                logging.getLogger(__name__).warning(
                    "transient dispatch failure (retry %d at %s): %s",
                    attempt + 1, point, e,
                )
                self._retry_policy.sleep(attempt)
                attempt += 1

    def _redispatch_refetch(self, buf: RecordBuffer, handle, span):
        """Roll device state back to the handle's pre-dispatch carry
        snapshot and re-run the batch end to end (the re-dispatch of
        every fetch-side recovery: the encode heal, the transient retry).

        The heal-epoch bump marks every OTHER in-flight aggregate
        dispatch stale — their carry lineage chained through the failed
        dispatch, so their finishes must re-dispatch from the repaired
        tip (or spill) instead of fetching diverged results; that same
        bookkeeping is what makes a replayed batch unable to
        double-count a carry."""
        self._device_carries = handle[0]
        if self.agg_configs:
            self._heal_epoch += 1
        header, packed = self._dispatch(
            buf, fanout_cap=self._fanout_cap(buf), span=span
        )
        if span is not None:
            span.mark_dispatched()
        if self.agg_configs:
            self._heal_carries = self._device_carries
            self._heal_dispatch_seq = self._dispatch_seq
        return self._fetch(buf, header, packed, {"span": span} if span else None)

    def _finish_retry(self, buf: RecordBuffer, handle, span, exc):
        """Bounded transient retry of the device/fetch half; carries are
        restored before every attempt AND before any re-raise, so the
        interpreter rerun downstream can never double-count."""
        # the original dispatch's speculative D2H copies crossed the
        # link but will never be fetched — charge them (idempotently) so
        # the byte counters reflect real traffic whatever the outcome
        self._charge_unfetched_spec(handle)
        attempt = 0
        while self._retry_policy.should_retry(exc, attempt):
            point = getattr(exc, "point", None) or "fetch"
            TELEMETRY.add_retry(point)
            logging.getLogger(__name__).warning(
                "transient device/fetch failure (retry %d at %s): %s",
                attempt + 1, point, exc,
            )
            self._retry_policy.sleep(attempt)
            try:
                return self._redispatch_refetch(buf, handle, span)
            except (KeyboardInterrupt, SystemExit):
                raise
            except TpuSpill:
                # transform error on the replay: restore the snapshot and
                # hand the batch to the interpreter rerun
                self._abandon_handle(buf, handle)
                raise
            except _FanoutOverflow as o:
                # compound case (transient fault + capacity overflow in
                # one batch): the overflow retry machinery is tuned for
                # the main path — spill instead of compounding retries
                self._abandon_handle(buf, handle)
                raise TpuSpill(
                    f"fanout overflow during retry: {o.total}",
                    reason="fanout-overflow",
                )
            except Exception as e2:
                exc = e2
                attempt += 1
        # deterministic fault or budget exhausted: surface the error with
        # device state rolled back for the engine's spill/quarantine ladder
        self._abandon_handle(buf, handle)
        raise exc

    def _abandon_handle(self, buf: RecordBuffer, handle) -> None:
        """Restore the handle's pre-dispatch carry snapshot and mark any
        in-flight aggregate lineage stale (shared by every finish-side
        giving-up path)."""
        self._charge_unfetched_spec(handle)
        self._device_carries = handle[0]
        if self.agg_configs:
            self._heal_epoch += 1
            self._heal_dispatch_seq = -1

    def _sharded_dispatch(self, buf: RecordBuffer, reuse_span=None):
        """Sharded dispatch delegation. The dispatch-side transfer-guard
        scope lives inside `ShardedChainExecutor.dispatch_buffer` so
        every entry point — including the retry re-dispatch in
        `_finish_sharded_inner`, which runs inside the fetch ALLOW
        scope — re-enters it without per-call-site wrapping."""
        return self._sharded.dispatch_buffer(buf, reuse_span=reuse_span)

    def _finish_sharded(self, buf: RecordBuffer, handle):
        """finish_buffer's sharded delegation with the same bounded
        transient retry. A retry is only lineage-safe when no LATER
        dispatch chained off this handle's carries (`_pending_carries is
        handle[1]`); otherwise the error re-raises and the interpreter
        rerun re-syncs authoritative state. Runs under the fetch-side
        transfer-guard allow scope: the sharded download is the same
        intentional D2H seam as `_fetch`."""
        with transfer_guard_fetch():
            return self._finish_sharded_inner(buf, handle)

    def _finish_sharded_inner(self, buf: RecordBuffer, handle):
        attempt = 0
        while True:
            try:
                return self._sharded.finish_buffer(buf, handle)
            except (TpuSpill, KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                if is_program_fault(e):
                    raise
                lineage_ok = (
                    not self.agg_configs
                    or self._sharded._pending_carries is handle[1]
                )
                if self.agg_configs and lineage_ok:
                    self._sharded._pending_carries = handle[0]
                if not (lineage_ok and self._retry_policy.should_retry(e, attempt)):
                    if handle[6] is not None and lineage_ok:
                        # async half of the sharded ENCODE ladder: a
                        # deterministic runtime failure of an
                        # encode-armed batch at the stacked-header sync
                        # latches encode off and re-dispatches. Transient
                        # faults never reach this branch (the bounded
                        # retry below re-ships the same form), and the
                        # raw re-dispatch's handle[6] (its encode form) is
                        # None, so a repeat failure re-raises: the loop
                        # is bounded.
                        self._enc_demote(e, where="sharded fetch")
                        handle = self._sharded_dispatch(
                            buf, reuse_span=handle[5]
                        )
                        continue
                    raise
                point = getattr(e, "point", None) or "fetch"
                TELEMETRY.add_retry(point)
                logging.getLogger(__name__).warning(
                    "transient sharded fetch failure (retry %d at %s): %s",
                    attempt + 1, point, e,
                )
                self._retry_policy.sleep(attempt)
                attempt += 1
                handle = self._sharded_dispatch(buf, reuse_span=handle[5])

    def dispatch_buffer(self, buf: RecordBuffer, flow_id: int = 0):
        """Phase 1: stage + dispatch without blocking on results.

        JAX dispatch is async, so the H2D transfer and device compute
        proceed in the background; the returned handle feeds
        `finish_buffer`. The broker's stream loop dispatches slice k+1
        here once slice k is fetched, so the device works while slice k
        is split back, encoded and sent. ``flow_id`` names the slice flow that caused this
        dispatch on its span (0 = none; a buffer the admission
        pipeline tagged with its flow names it itself).
        """
        if not flow_id:
            flow = getattr(buf, "_flow", None)
            if flow is not None:
                flow_id = flow.batch_id
        if self._sharded is not None:
            # one span threads through every retry attempt (the fan-out
            # retry convention: phase time accumulates onto the batch's
            # single span — the batch really paid staging twice — and a
            # failed attempt's span is never orphaned)
            sh_span = TELEMETRY.begin_batch(
                chain=self.span_chain or self._chain_sig, flow_id=flow_id
            )
            h0 = self.h2d_bytes_total
            handle = self._dispatch_with_retry(
                lambda: self._sharded_dispatch(buf, reuse_span=sh_span)
            )
            self._gauge_track(handle, self.h2d_bytes_total - h0)
            return handle
        # chain identity on the span: the per-chain windowed latency
        # family the SLO engine's e2e_p99 verdicts key on — partitioned
        # dispatches carry the chain@partition identity instead
        span = TELEMETRY.begin_batch(
            chain=self.span_chain or self._chain_sig, flow_id=flow_id
        )
        prev_carries = self._device_carries
        h0 = self.h2d_bytes_total
        header, packed = self._dispatch_with_retry(
            lambda: self._dispatch(
                buf, fanout_cap=self._fanout_cap(buf), span=span
            )
        )
        # the probe math + async D2H registration: charged to d2h — it
        # is the download's initiation half
        with timed(span, "d2h"):
            spec = self._start_result_copies(buf, header, packed)
        if span is not None:
            span.mark_dispatched()
            spec["span"] = span
        # finish-side self-heal markers: whether THIS dispatch armed the
        # result encoder (async runtime failures surface at fetch), and
        # the heal epoch its carry lineage belongs to
        spec["enc_used"] = getattr(self, "_enc_last", None) is not None
        spec["epoch"] = self._heal_epoch
        if self._window is not None:
            # where `rollback_finished` puts the bank back to
            spec["bank0"] = self._window_bank.checkpoint()
        handle = (prev_carries, header, packed, spec)
        self._gauge_track(handle, self.h2d_bytes_total - h0)
        return handle

    def dispatch_buffers(
        self, bufs: List[RecordBuffer], flow_id: int = 0
    ) -> List[tuple]:
        """Dispatch several buffers in order. Returns
        [(buf, handle), ...] for `finish_buffer`; the SPU slice bridge
        (spu/smart_chain.py) builds on this. ``flow_id``: the slice
        flow every one of these chunks belongs to."""
        out = []
        try:
            for buf in bufs:
                out.append((buf, self.dispatch_buffer(buf, flow_id)))
        except BaseException:
            # a mid-list dispatch failure (post-retries) must not leak
            # the earlier chunks' in-flight handles: discard them so
            # carries and byte accounting stay coherent for the rerun
            for _, h in reversed(out):
                self.discard_dispatch(h)
            raise
        return out

    def _start_result_copies(self, buf: RecordBuffer, header, packed) -> Dict:
        """Begin the D2H copies the fetch will block on, at dispatch time.

        The link's round-trip latency is paid per *blocking* sync, not
        per byte: a copy whose request is already registered streams back
        the moment device compute finishes, so the pipelined loop's
        finish-side ``device_get`` finds the value resolved instead of
        paying a fresh round trip. Three tiers:

        - the header (and the delta-probe scalars that ride its sync)
          always start here;
        - the survivor bitmask is static-shaped, so it always starts;
        - the viewable (start, length) descriptor slices depend on the
          survivor-count bucket, so they start speculatively with the
          bucket the last two batches agreed on — a steady stream hits
          every batch, a shifting one falls back to the fetch-time slice
          (the wasted speculative bytes are charged to the D2H counter).
        """
        spec: Dict = {}
        header.copy_to_host_async()
        # down-link decision scalars (encode token counts, packed
        # payload bytes) ride the header's sync
        if "down_meta" in packed:
            packed["down_meta"].copy_to_host_async()
            spec["down_meta"] = packed["down_meta"]
        if "payload_meta" in packed:
            packed["payload_meta"].copy_to_host_async()
            spec["payload_meta"] = packed["payload_meta"]
        if self._fanout:
            d, mx, mn, b = self._fan_probe(header, packed)
            for s in (mx, mn, b):
                s.copy_to_host_async()
            spec["fan_probe"] = (d, mx, mn, b)
            return spec
        if self._int_output:
            a_d, w_d, probes = self._int_probe(header, packed)
            for s in probes[1:]:
                s.copy_to_host_async()
            spec["int_probe"] = (a_d, w_d, probes)
            packed["mask"].copy_to_host_async()
            return spec
        if self._viewable:
            packed["mask"].copy_to_host_async()
            if self._identity_view or "span_start" not in packed:
                # filter-only and striped chains: the mask IS the whole
                # download — no descriptor speculation to arm
                return spec
            guess = self._spec_rows
            n_desc = packed["span_start"].shape[0]
            if (
                guess is not None
                and guess == self._spec_prev
                and guess <= n_desc
            ):
                st_s, ln_s = self._view_slices(packed, buf.width, guess)
                st_s.copy_to_host_async()
                ln_s.copy_to_host_async()
                spec["view"] = (guess, st_s, ln_s)
        elif "mask" in packed:
            packed["mask"].copy_to_host_async()
        return spec

    @staticmethod
    def _settle_device_at_exit() -> None:
        """Wait for the device before the interpreter tears the client
        down. A discarded dispatch is device work nobody fetches: its
        dispatch-time `copy_to_host_async` copies run when its results
        are ready, and jax's own exit hook destroys the client without
        waiting for them (SIGSEGV in `TpuRawBuffer::CopyToLiteralAsync`
        at the exit of an `explode-drain` run whose consumer had left
        with a slice on the chip: PERF.md §6, PR 33)."""
        try:
            jax.block_until_ready(jax.live_arrays())
        except Exception:  # noqa: BLE001 — exiting: nothing left to tell
            pass

    def discard_dispatch(self, handle) -> None:
        """Drop a speculative dispatch, restoring pre-dispatch carries."""
        import atexit

        # idempotent registration; runs before jax's exit hook (LIFO)
        atexit.unregister(self._settle_device_at_exit)
        atexit.register(self._settle_device_at_exit)
        self._gauge_release(handle)
        if self._sharded is not None:
            self._sharded.discard_dispatch(handle)
            return
        self._charge_unfetched_spec(handle)
        spec = handle[3] if len(handle) > 3 else None
        if (
            self.agg_configs
            and spec is not None
            and spec.get("epoch", self._heal_epoch) != self._heal_epoch
        ):
            # a heal already superseded this handle's carry lineage;
            # restoring its pre-dispatch futures would resurrect the
            # corrupt chain the heal rolled away from
            return
        self._device_carries = handle[0]

    def rollback_finished(self, handle) -> None:
        """Restore the carries a FINISHED dispatch started from: its
        slice declined after the fetch (the broker's record encode
        refused the output) and is re-run per record, which must start
        where the slice did. Nothing of the stream may be in flight: a
        caller that dispatched ahead discards that first."""
        if self._window is not None:
            self._window_bank.commit(*handle[3]["bank0"])
        if not self.agg_configs:
            return
        if self._sharded is not None:
            self._sharded._pending_carries = handle[0]
        else:
            self._device_carries = handle[0]

    def finish_buffer(self, buf: RecordBuffer, handle) -> RecordBuffer:
        """Phase 2: block on results and materialize the output buffer.

        Fan-out chains run with a learned capacity; a batch whose exact
        element total exceeds it retries once at the (bucketed) exact
        capacity — aggregate device carries are restored first so the
        retry cannot double-apply. Device-detected transform errors raise
        `TpuSpill` (carries restored) for the interpreter to re-run with
        exact error semantics.
        """
        try:
            return self._finish_buffer_inner(buf, handle)
        finally:
            # EVERY finish outcome (materialized output, spill, retry
            # exhaustion) retires the handle's HBM/live-handle gauges
            self._gauge_release(handle)

    def finish_buffer_deferred(self, buf: RecordBuffer, handle):
        """`finish_buffer` with the pure host-materialization half split
        off: blocks on downloads and resolves every failure ladder on
        the calling thread, then returns either the finished buffer or
        a zero-argument thunk (pure numpy over host arrays) the caller
        may run on the fetch worker — the overlapped stream loops'
        "fetch runs concurrently with the next batch's device phase"
        half. Exactly-once by construction: carries, heals, and retries
        are settled before the thunk exists."""
        try:
            return self._finish_buffer_inner(buf, handle, defer=True)
        finally:
            self._gauge_release(handle)

    def _fetch_or_recover(self, buf: RecordBuffer, handle, span, defer: bool):
        """The blocking half of a finish: the fetch, and on a failure
        the ladder that answers it (fan-out capacity retry, the encode
        heal, the bounded transient retry). Returns the output
        buffer, or the deferred split-back thunk."""
        prev_carries, header, packed, spec = handle
        try:
            out = self._fetch(buf, header, packed, spec, defer=defer)
        except _FanoutOverflow as o:
            # one re-run at the exact (bucketed) capacity where the total
            # passed it, and at the grown bound where a record passed that
            if o.total > o.cap:
                self._learn_cap(buf, o.total)
            self._learn_bound(o.over)
            self._device_carries = prev_carries
            cap = max(self._bucket_bytes(o.total, 1024), o.cap)
            header, packed = self._dispatch(buf, fanout_cap=cap, span=span)
            if span is not None:
                span.mark_dispatched()
            try:
                out = self._fetch(
                    buf, header, packed, {"span": span} if span else None
                )
            except _FanoutOverflow as e:  # pragma: no cover — total is exact
                self._device_carries = prev_carries
                raise TpuSpill(
                    f"fanout overflow after retry: {e.total}",
                    reason="fanout-overflow",
                )
        except TpuSpill:
            self._charge_unfetched_spec(handle)
            self._device_carries = prev_carries
            raise
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            # a program fault (lowering/compile error of a fetch-side
            # jit) is nobody's to heal: `_finish_retry` classifies it
            # deterministic, restores the carries and re-raises it
            heal = spec and not is_program_fault(e)
            if heal and spec.get("enc_used"):
                # async half of the ENCODE ladder: a device runtime
                # failure of an encode-armed batch surfaces when results
                # are consumed — latch encode off and re-run the batch
                # through the shared recovery re-dispatch (which owns
                # the carry snapshot + heal-epoch bookkeeping). Gated on
                # THIS batch's own enc_used, not the executor-wide latch:
                # under a pipelined loop, batch k's heal latches encode
                # off while batch k+1 (already dispatched encode-armed)
                # is still in flight, and k+1 must heal too
                self._enc_demote(e, where="fetch")
                try:
                    out = self._redispatch_refetch(buf, handle, span)
                except (TpuSpill, KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e2:
                    # the rerun failed too: hand off to the bounded
                    # transient retry (unrelated deterministic failures
                    # re-raise from there with carries restored)
                    out = self._finish_retry(buf, handle, span, e2)
            else:
                # transient device/fetch failure of an unencoded batch:
                # bounded retry against the handle's carry snapshot
                out = self._finish_retry(buf, handle, span, e)
        return out

    def _finish_buffer_inner(self, buf: RecordBuffer, handle,
                             defer: bool = False):
        if self._sharded is not None:
            return self._finish_sharded(buf, handle)
        spec = handle[3]
        if (
            self.agg_configs
            and spec is not None
            and spec.get("epoch", self._heal_epoch) != self._heal_epoch
        ):
            return self._finish_stale_epoch(buf, handle)
        span = spec.get("span") if spec else None
        t_f0 = time.perf_counter() if span is not None else 0.0
        d2h0 = span.phase("d2h") if span is not None else 0.0
        # `fetch` is computed by subtraction below, so its annotation is
        # the enclosing interval: this blocking half (``fluvio/wait`` and
        # ``fluvio/d2h`` nest inside it) and, deferred, the worker-side
        # split-back
        with annotate(span, "fetch"):
            out = self._fetch_or_recover(buf, handle, span, defer)

        def _complete(result):
            if span is not None:
                # fetch = host materialization time for this batch:
                # total minus the device wait (up to ready_t) minus the
                # blocking d2h copies recorded since this call began —
                # in deferred mode the clock stops when the worker-side
                # materialization finishes, so flight-recorder lanes
                # show the real overlap with the next batch's phases
                t_end = time.perf_counter()
                wait = 0.0
                if span.ready_t is not None and span.ready_t > t_f0:
                    wait = span.ready_t - t_f0
                span.add(
                    "fetch", (t_end - t_f0) - wait - (span.phase("d2h") - d2h0)
                )
                # records = INPUT records staged through this batch (same
                # semantic as the interpreter path, so per-path record
                # counters compare identical workloads)
                TELEMETRY.end_batch(span, records=buf.count)
            return result

        if callable(out):
            # deferred materialization: the recovery ladders above all
            # return finished buffers, so a thunk here is the pure
            # happy-path split-back
            def _deferred():
                with annotate(span, "fetch"):
                    return _complete(out())

            return _deferred
        return _complete(out)

    def _finish_stale_epoch(self, buf: RecordBuffer, handle) -> RecordBuffer:
        """Finish an aggregate dispatch whose carry lineage a heal
        invalidated while it was in flight.

        When nothing else has consumed the carry chain since the heal
        (the common pipelined case: stale handles finish in dispatch
        order), re-dispatch this batch from the healed tip — the repaired
        chain stays on device end to end. When later dispatches already
        advanced the chain past the gap, those results are poisoned too:
        restore the healed tip, invalidate them, and spill this batch to
        the interpreter (which re-syncs authoritative state afterwards).
        """
        self._charge_unfetched_spec(handle)
        if self._dispatch_seq == self._heal_dispatch_seq:
            header, packed = self._dispatch(
                buf, fanout_cap=self._fanout_cap(buf)
            )
            self._heal_carries = self._device_carries
            self._heal_dispatch_seq = self._dispatch_seq
            return self._fetch(buf, header, packed)
        self._heal_epoch += 1
        self._heal_dispatch_seq = -1
        if self._heal_carries is not None:
            self._device_carries = self._heal_carries
            self._heal_carries = None
        raise TpuSpill(
            "heal invalidated in-flight aggregate carry lineage",
            reason="heal-lineage",
        )

    def process_buffer(self, buf: RecordBuffer) -> RecordBuffer:
        """Array-in/array-out path (bench + broker stream path)."""
        return self.finish_buffer(buf, self.dispatch_buffer(buf))

    def process_stream(self, bufs):
        """Pipelined generator: batch k+1 dispatches while k downloads.

        The broker's consume loop shape: sustained throughput is bounded by
        max(compute, transfer), not their sum.
        """
        if (self.agg_configs and self._fanout) or self._window is not None:
            # serialized: fan-out overflow retry must roll carries back,
            # impossible once the next batch dispatched; a window bank
            # is committed at the fetch, which the next dispatch reads
            for buf in bufs:
                yield self.process_buffer(buf)
            return

        # two-phase pipeline through the delegating API (single-device OR
        # sharded mesh): finish_buffer handles overflow retry internally,
        # which is safe here — stateless chains have no carries to roll
        # back, and aggregate chains without fan-out cannot overflow.
        # Sharded aggregates pipeline too: carries chain through device
        # futures at dispatch time (ShardedChainExecutor._pending_carries)
        # One-batch lookahead: batch k dispatches immediately (the
        # device never idles behind an arrival), but k-1's results
        # yield only after k+1 arrives — immaterial for eager sources
        # (the bench, sharded pipelining, queue drains), one batch of
        # result latency on a sparse tailing source.
        # Fetch/compute overlap (effective_fetch_overlap): finish_buffer
        # splits into its blocking half (downloads + failure ladders, on
        # this thread) and a PURE materialization thunk that runs on the
        # shared fetch worker — batch k's host split-back proceeds while
        # batch k+1 dispatches and its device phase runs. One worker
        # keeps yields in dispatch order.
        overlap = effective_fetch_overlap() and self._sharded is None
        it = iter(bufs)
        cur = next(it, None)
        pending = None
        handle = None
        mat = None  # in-flight deferred materialization (Future)
        try:
            while cur is not None:
                handle = self.dispatch_buffer(cur)
                nxt = next(it, None)
                if pending is not None:
                    if overlap:
                        out = self.finish_buffer_deferred(
                            pending[0], pending[1]
                        )
                        if mat is not None:
                            yield mat.result()
                            mat = None
                        if callable(out):
                            mat = _fetch_mat_pool().submit(out)
                        else:
                            yield out
                    else:
                        yield self.finish_buffer(pending[0], pending[1])
                pending = (cur, handle)
                cur = nxt
            if pending is not None:
                out = (
                    self.finish_buffer_deferred(pending[0], pending[1])
                    if overlap
                    else self.finish_buffer(pending[0], pending[1])
                )
                if mat is not None:
                    yield mat.result()
                    mat = None
                yield out() if callable(out) else out
        except BaseException as e:
            # the stream dies with a dispatch still in flight (the batch
            # behind the one whose finish raised, or the one ahead of a
            # failed dispatch): nobody will finish it, so its staged
            # bytes leave the memory ledger and the live-handle gauge
            # here (idempotent; carries are the failure ladder's to
            # settle, not this release's)
            for h in (pending[1] if pending is not None else None, handle):
                if h is not None:
                    self._gauge_release(h)
            if isinstance(e, GeneratorExit):
                # consumer closed us mid-stream: no further yields allowed
                raise
            # a later batch's dispatch/finish failure must not swallow a
            # batch that ALREADY finished and whose pure materialization
            # is in flight on the worker — the serialized path had
            # yielded it one iteration earlier (delivered work is never
            # lost to a neighbor's error)
            if mat is not None:
                yield mat.result()
            raise

    def process(
        self, inp: SmartModuleInput, metrics: Optional[SmartModuleChainMetrics] = None
    ) -> SmartModuleOutput:
        try:
            buf = RecordBuffer.from_smartmodule_input(inp)
        except ValueError as e:
            # a record beyond even the striped layout's hard ceiling
            # (MAX_RECORD_WIDTH) cannot stage: spill to the interpreter
            # (same surface as a device-detected transform error), never
            # crash the chain. Records merely wider than the narrow
            # layout stage striped — or spill from _dispatch when the
            # chain is outside the stripeable subset.
            raise TpuSpill(str(e), reason="record-too-wide") from None
        grows = self.fanout_grows
        out = self.process_buffer(buf)
        if self.agg_configs:
            self._ensure_host_state()
        if metrics is not None:
            metrics.add_fuel_used(buf.count * max(len(self.stages), 1))
            metrics.add_fanout_grows(self.fanout_grows - grows)
        return SmartModuleOutput(successes=out.to_records())

    # -- state mirroring ----------------------------------------------------

    def _sync_instances(self) -> None:
        slot = 0
        for inst in self._instances:
            if inst.kind != SmartModuleKind.AGGREGATE:
                continue
            if slot >= len(self.carries):
                break
            acc, win, has = self.carries[slot]
            inst.accumulator = str(acc).encode("ascii")
            inst._window_start = win if (has and self.agg_configs[slot][1]) else None
            slot += 1

    def sync_state_from(self, instances: List) -> None:
        self._device_carries = None  # host state becomes authoritative
        if self._sharded is not None:
            self._sharded._pending_carries = None
        if self._window is not None:
            self._restore_window(instances[-1])
        slot = 0
        for inst in instances:
            if inst.kind != SmartModuleKind.AGGREGATE:
                continue
            if slot >= len(self.carries):
                break
            op, window_ms, _ = self.agg_configs[slot]
            neutral = _AGG_NEUTRAL[op]
            acc = (
                dsl.parse_int_prefix(inst.accumulator)
                if inst.accumulator
                else neutral
            )
            win = inst._window_start if inst._window_start is not None else 0
            has = True if not window_ms else inst._window_start is not None
            self.carries[slot] = (acc, win, has)
            slot += 1


class _StreamExecutor(TpuChainExecutor):
    """One stream of another executor's compiled chain
    (`TpuChainExecutor.open_stream`).

    It holds two things, the compiled executor and its own
    `StreamState`. Every method is the compiled class's own, run with
    this object as ``self``: a `StreamState` field resolves through the
    class properties to THIS stream's state, every other attribute is
    read from and written to the compiled executor, so capacity
    learning, link latches and byte counters stay shared while carries
    and heal lineage do not."""

    def __init__(self, compiled: TpuChainExecutor) -> None:
        self.__dict__["_compiled"] = compiled
        self.__dict__["state"] = StreamState(compiled.initial_carries())

    def __getattr__(self, name: str):
        # reached only for names this object does not hold itself
        return getattr(self.__dict__["_compiled"], name)

    def __setattr__(self, name: str, value) -> None:
        if isinstance(getattr(TpuChainExecutor, name, None), property):
            object.__setattr__(self, name, value)
        else:
            setattr(self._compiled, name, value)

    def open_stream(self) -> TpuChainExecutor:
        return self._compiled.open_stream()
