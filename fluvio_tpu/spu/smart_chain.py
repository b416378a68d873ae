"""SPU <-> SmartEngine bridge.

Capability parity: fluvio-spu/src/smartengine/ — building a chain from
`SmartModuleInvocation`s with Predefined-name resolution against the local
store (context.rs:34,63,95), lookback record readers over the replica
(context.rs:117-240), and the per-batch processing loop that feeds stored
batches through the chain and re-batches the output with offset fixup and
a max_bytes cutoff (batch.rs:41-140).
"""

from __future__ import annotations

import hashlib
import logging
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from fluvio_tpu.analysis.envreg import env_int
# dense staging cap for the coalesced fast path (bytes of padded values)
_MAX_STAGING_BYTES = int(env_int("FLUVIO_TPU_MAX_STAGING"))

# records per device dispatch on the stateless fast path; a 16 MB read
# slice of short records becomes ~4-15 concurrently-in-flight dispatches
_DISPATCH_CHUNK_ROWS = int(env_int("FLUVIO_TPU_DISPATCH_CHUNK"))


def _slice_columns(cols: dict, lo: int, hi: int) -> dict:
    """Record-range view [lo, hi) of merged aligned-decode columns.

    val_flat/val_off keep the decoder's 4-aligned form (from_flat adopts
    them zero-copy); key_flat/key_off are exact-packed. All slices are
    numpy views — chunking adds no copies to staging.
    """
    if lo == 0 and hi == cols["count"]:
        return cols
    v0, v1 = int(cols["val_off"][lo]), int(cols["val_off"][hi])
    k0, k1 = int(cols["key_off"][lo]), int(cols["key_off"][hi])
    return {
        "count": hi - lo,
        "val_flat": cols["val_flat"][v0:v1],
        "val_len": cols["val_len"][lo:hi],
        "val_off": cols["val_off"][lo : hi + 1] - v0,
        "key_flat": cols["key_flat"][k0:k1],
        "key_off": cols["key_off"][lo : hi + 1] - k0,
        "key_present": cols["key_present"][lo:hi],
        "off_delta": cols["off_delta"][lo:hi],
        "ts_delta": cols["ts_delta"][lo:hi],
    }


from fluvio_tpu.protocol.error import ErrorCode
from fluvio_tpu.resilience.policy import is_program_fault
from fluvio_tpu.protocol.record import Batch, RecordSet
from fluvio_tpu.schema.smartmodule import (
    SmartModuleInvocation,
    SmartModuleInvocationWasm,
)
from fluvio_tpu.smartengine.config import Lookback
from fluvio_tpu.smartengine.engine import (
    SmartModuleChainInstance,
    SmartModuleChainInitError,
)
from fluvio_tpu.smartmodule.types import (
    SmartModuleInput,
    SmartModuleRecord,
    SmartModuleTransformRuntimeError,
)
from fluvio_tpu.spu.context import GlobalContext
from fluvio_tpu.spu.replica import LeaderReplicaState
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.spans import timed
from fluvio_tpu.types import NO_TIMESTAMP


class SmartModuleResolutionError(Exception):
    def __init__(self, code: ErrorCode, message: str):
        super().__init__(message)
        self.code = code
        self.message = message


def resolve_invocation(
    invocation: SmartModuleInvocation, ctx: GlobalContext
) -> tuple[bytes, str]:
    """Predefined name -> payload bytes from the local store; AdHoc passes
    through (parity: context.rs:95)."""
    wasm = invocation.wasm
    if wasm.tag == SmartModuleInvocationWasm.ADHOC:
        return wasm.payload, invocation.name or "adhoc"
    payload = ctx.smartmodules.get(wasm.name)
    if payload is None:
        raise SmartModuleResolutionError(
            ErrorCode.SMARTMODULE_NOT_FOUND,
            f"SmartModule {wasm.name!r} not found in local store",
        )
    return payload, invocation.name or wasm.name


def dedup_to_invocation(topic_config: dict) -> Optional[SmartModuleInvocation]:
    """Topic ``Deduplication`` config -> filter SM invocation with lookback.

    Parity: fluvio-spu/src/smartengine/mod.rs:152 `dedup_to_invocation` —
    the dedup filter is a Predefined module named by
    ``deduplication.filter.transform.uses``, parameterised by the
    transform's ``with`` params plus the window bounds, and seeded from
    the log via Lookback(last=count, age=age).
    """
    dedup = topic_config.get("deduplication")
    if not dedup:
        return None
    bounds = dedup.get("bounds") or {}
    transform = (dedup.get("filter") or {}).get("transform") or {}
    uses = transform.get("uses", "")
    count = int(bounds.get("count") or 0)
    age_seconds = bounds.get("age_seconds")
    # bounds first, then the transform's `with` params (which may override),
    # matching the reference's insert order; `age` is in milliseconds there
    params = {"count": str(count)}
    if age_seconds is not None:
        params["age"] = str(int(age_seconds) * 1000)
    params.update(transform.get("with_params") or {})
    inv = SmartModuleInvocation(
        wasm=SmartModuleInvocationWasm.predefined(uses),
        params=params,
        lookback_last=count,
        name=f"dedup/{uses}",
    )
    if age_seconds is not None:
        inv.lookback_age_ms = int(age_seconds) * 1000
    return inv


def build_chain(
    invocations: List[SmartModuleInvocation],
    ctx: GlobalContext,
    version: Optional[int] = None,
) -> SmartModuleChainInstance:
    """Build + initialize a chain from wire invocations (context.rs:63)."""
    builder = ctx.engine.builder()
    for invocation in invocations:
        payload, name = resolve_invocation(invocation, ctx)
        config = invocation.to_config()
        if version is not None:
            config.version = version
        try:
            builder.add_smart_module(config, payload, name=name)
        except SmartModuleChainInitError:
            raise
        except Exception as e:  # noqa: BLE001 — artifact compile boundary
            raise SmartModuleResolutionError(
                ErrorCode.SMARTMODULE_INVALID,
                f"invalid SmartModule {name!r}: {e}",
            ) from e
    return builder.initialize()


_STREAM_CHAIN_CACHE_MAX = 32


def acquire_stream_chain(
    invocations: List[SmartModuleInvocation],
    ctx: GlobalContext,
    version: Optional[int] = None,
) -> SmartModuleChainInstance:
    """build_chain with an SPU-level cache of COMPILED chains.

    Every stream-fetch request builds its chain from wire invocations
    (matching the reference, which instantiates the wasm store per
    stream, engine.rs:135-185). For this engine that rebuild is not
    cheap: a fresh executor re-traces its jitted chain function and
    reloads the XLA executable for each shape bucket — hundreds of ms
    per stream even with the persistent compile cache hot, which
    dominated the broker end-to-end benchmark. What is compiled does
    not depend on the stream, so pure DSL chains on the TPU backend
    are built once per key:

    - a stateless chain is shared as it is: nothing crosses calls,
      dispatch handles are explicit, so interleaved slices from
      concurrent streams on one executor do not interact;
    - a stateful chain (aggregate carries) is shared by its compiled
      half only: the cache keeps the built chain and every stream gets
      `open_stream()` of it, i.e. its own interpreter instances and
      its own `StreamState` from the chain spec's seed (the key
      includes the invocation's accumulator). The cached chain itself
      never serves, so its state stays at the seed.

    Anything else — lookback-seeded (state comes from the replica),
    python-only, sharded-and-stateful (the sharded delegate keeps its
    own carry) — gets a fresh chain per stream exactly as before.
    Books `stream_chain_hits` / `stream_chain_builds` on the SPU's
    chain metrics.
    """
    key_parts = [str(version)]
    cacheable = True
    for inv in invocations:
        if inv.lookback() is not None:
            cacheable = False
            break
        payload = (
            inv.wasm.payload
            if inv.wasm.tag == SmartModuleInvocationWasm.ADHOC
            else ctx.smartmodules.get(inv.wasm.name)
        )
        if payload is None:  # unresolved predefined: let build_chain raise
            cacheable = False
            break
        if isinstance(payload, str):  # in-process adhoc sources
            payload = payload.encode()
        elif not isinstance(payload, (bytes, bytearray, memoryview)):
            cacheable = False  # in-process module object: no stable key
            break
        key_parts.append(
            "%d:%s:%s:%r" % (
                int(inv.kind),
                hashlib.sha256(payload).hexdigest(),
                inv.accumulator.hex(),
                sorted((inv.params or {}).items()),
            )
        )
    key = "|".join(key_parts)
    if cacheable:
        chain = ctx.stream_chains.get(key)
        if chain is not None:
            if getattr(chain, "_poisoned", None) is not None:
                # a fuel trap poisoned this chain (abandoned hook thread
                # or trapped stateful instance, its own or of a stream
                # opened from it); never serve it to new streams —
                # rebuild instead. A module that traps cleanly every
                # time pays chain build + its budget per stream,
                # matching the reference, where each stream instantiates
                # the wasm and burns fuel to the trap; only ABANDONED
                # threads escalate to the per-module quarantine.
                del ctx.stream_chains[key]
            else:
                ctx.stream_chains.move_to_end(key)
                ctx.metrics.smartmodule.add_stream_chain(hit=True)
                return _stream_of(chain)
    chain = build_chain(invocations, ctx, version)
    ctx.metrics.smartmodule.add_stream_chain(hit=False)
    TELEMETRY.add_chain_build(chain.chain_label)
    tpu = getattr(chain, "tpu_chain", None)
    if (
        cacheable
        and tpu is not None
        and chain.backend_in_use == "tpu"
        and not (tpu.stateful and tpu._sharded is not None)
    ):
        ctx.stream_chains[key] = chain
        while len(ctx.stream_chains) > _STREAM_CHAIN_CACHE_MAX:
            ctx.stream_chains.popitem(last=False)
        return _stream_of(chain)
    return chain


def _stream_of(cached: SmartModuleChainInstance) -> SmartModuleChainInstance:
    """What a stream gets of a cached chain: the chain itself when it
    holds no state, else a stream of it with a state of its own."""
    return cached.open_stream() if cached.tpu_chain.stateful else cached


async def ensure_dedup_chain(ctx: GlobalContext, leader: LeaderReplicaState) -> None:
    """Lazily attach the topic's dedup filter chain to a leader replica.

    Parity: Uninit<LeaderReplicaState>::init (replica_state.rs:392-405) —
    a replica whose topic config carries Deduplication gets a persistent
    chain (with one lookback seed from the log) that every produced record
    set is piped through. Init runs under the leader's write lock so no
    produce can append between the lookback seed and the chain attach;
    failures (e.g. the SmartModule not yet pushed by the SC) are retried
    on the next produce.
    """
    if leader.sm_chain is not None:
        return
    inv = dedup_to_invocation(ctx.replica_config(leader.topic, leader.partition))
    if inv is None:
        return
    async with leader._write_lock:
        if leader.sm_chain is not None:  # lost the init race
            return
        chain = build_chain([inv], ctx)
        await chain_look_back(chain, leader)
        leader.sm_chain_metrics = ctx.metrics.smartmodule
        leader.sm_chain = chain


def apply_chain(chain, records: RecordSet, metrics=None):
    """Run an in-memory record set through a chain, re-batching outputs.

    Shared by the produce-side transform (produce_handler.rs:215
    apply_smartmodules) and the leader's persistent dedup chain
    (replica_state.rs:344 transform). Returns (RecordSet, error): on a
    transform error the partial output is discarded and the produce fails.
    """
    out = RecordSet()
    for batch in records.batches:
        inp = SmartModuleInput.from_records(
            batch.memory_records(),
            base_offset=0,  # offsets not assigned until the log write
            base_timestamp=batch.header.first_timestamp,
        )
        output = chain.process(inp, metrics)
        if output.error is not None:
            return out, output.error
        if output.successes:
            out.add(
                Batch.from_records(
                    output.successes,
                    first_timestamp=(
                        batch.header.first_timestamp
                        if batch.header.first_timestamp != NO_TIMESTAMP
                        else None
                    ),
                )
            )
    return out, None


async def chain_look_back(
    chain: SmartModuleChainInstance, leader: LeaderReplicaState
) -> None:
    """Feed recent stored records to look_back hooks (context.rs:117-240)."""

    async def read_fn(lookback: Lookback) -> List[SmartModuleRecord]:
        if lookback.age_ms is not None:
            floor = int(time.time() * 1000) - lookback.age_ms
            records = leader.storage.read_last_records(
                lookback.last, min_timestamp=floor
            )
        else:
            records = leader.storage.read_last_records(lookback.last)
        return [SmartModuleRecord(rec) for rec in records]

    await chain.look_back(read_fn)


@dataclass
class BatchProcessResult:
    """Output of one pass over a raw slice."""

    records: RecordSet = field(default_factory=RecordSet)
    next_offset: int = 0  # where the consumer should continue
    error: Optional[SmartModuleTransformRuntimeError] = None


@dataclass
class PendingSlice:
    """A read slice on its way through the device: staged (`tpu_stage`:
    ``bufs``), dispatched (`tpu_dispatch`: ``chunks``), fetched
    (`tpu_fetch`: ``parts``), then materialized and encoded
    (`tpu_materialize`).

    ``chunks`` holds (RecordBuffer, dispatch handle) pairs in slice
    order. Stateless chains split a large slice into several dispatches
    (all in flight at once — see the chunking note in `tpu_stage`);
    stateful/fan-out chains always stage exactly one chunk."""

    batches: List[Batch]
    planned_next: int  # next offset assuming no max_bytes truncation
    total_raw: int
    base0: int
    ts0: int
    count: int  # staged input records across all chunks
    read_from: Optional[int] = None  # consume cursor (drop outputs below)
    bufs: List = field(default_factory=list)  # staged chunk buffers
    chunks: List[tuple] = field(default_factory=list)  # [(buf, handle)]
    # per chunk, after the fetch: its output buffer, the Future of its
    # split-back thunk on the fetch worker, or (the last chunk) the
    # thunk itself. None until then: the chunks' handles are live and a
    # discard has to drop them
    parts: Optional[List] = None
    # stateful chains: the carries the fetched slice left, as host values
    carries: Optional[List] = None
    # chunks currently counted in the inflight_queue_depth gauge (set at
    # dispatch; release is idempotent — fetch and discard both call it)
    tracked_depth: int = 0
    # the slice's causal flow record (telemetry/flow.py), carried from
    # arrival through dispatch to the serve that closes it; None when
    # flow tracing is off (the zero-cost seam)
    flow: Optional[object] = None

    def release_depth(self) -> None:
        if self.tracked_depth:
            TELEMETRY.gauge_add("inflight_queue_depth", -self.tracked_depth)
            self.tracked_depth = 0

    def discard(self, tpu) -> None:
        """Drop the slice wherever it stands (idempotent). Dispatched
        and not fetched: every handle is discarded, newest first, so a
        stateful chain is back at the carry this slice started from.
        Staged only, or already fetched: nothing of it is on the device."""
        self.release_depth()
        if self.parts is None:
            for _, handle in reversed(self.chunks):
                tpu.discard_dispatch(handle)
        self.chunks = []

    def rollback(self, tpu) -> None:
        """A FETCHED slice declines (`encode-failed`): its carries go
        back to where the slice started, for the per-record rerun."""
        if self.chunks:
            tpu.rollback_finished(self.chunks[0][1])


def _decline(metrics, reason: str):
    if metrics is not None:
        metrics.add_fallback(reason)
    TELEMETRY.add_decline(reason)
    return None


# --- admission seam (fluvio_tpu/admission) ----------------------------------
# One source of truth: admission.gate() owns the resolve-once state, so
# admission.reset_gate()/set_gate() affect the broker seam immediately.
# Only the import is cached here; with FLUVIO_ADMISSION off (the
# default) the per-slice cost is one resolved-flag check returning None
# — no controller, queue, lock, or gauge (the overhead gate tripwires
# this).
_GATE_FN = None


def _admission_gate():
    global _GATE_FN
    if _GATE_FN is None:
        from fluvio_tpu.admission import gate

        _GATE_FN = gate
    return _GATE_FN()


# --- partition seam (fluvio_tpu/partition) ----------------------------------
# Same shape as the admission seam: with FLUVIO_PARTITIONS unset the
# per-slice cost is one resolved-flag check returning None — no plan,
# mesh, or placement object (overhead-gate tripwired).
_PARTITION_GATE_FN = None


def _partition_gate():
    global _PARTITION_GATE_FN
    if _PARTITION_GATE_FN is None:
        from fluvio_tpu.partition import gate

        _PARTITION_GATE_FN = gate
    return _PARTITION_GATE_FN()


def _enter_partition_scope(topic, partition, tpu):
    """Enter the partition placement scope for one slice, or None.

    None when the gate is unarmed, no partition identity was supplied,
    or placement itself fails — a rule set that matches nothing for
    this topic is a CONFIG error surfaced loudly on its own typed
    decline reason, after which the slice serves unpartitioned instead
    of crashing the stream. BOTH the dispatch and the finish seam come
    through here: either can be the first to hit the bad rule."""
    pgate = _partition_gate()
    if pgate is None or partition is None or tpu is None:
        return None
    try:
        scope = pgate.scope(topic or "t", partition, tpu)
        scope.__enter__()
        return scope
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        logging.getLogger(__name__).error(
            "partition placement failed for %s/%s (%s: %s); "
            "serving unpartitioned",
            topic, partition, type(e).__name__, e,
        )
        # decline counter only — NOT _decline(): the slice still
        # serves fused, so it must not book a per-record fallback
        TELEMETRY.add_decline("partition-placement-error")
        return None


def admission_chain_sig(chain, topic=None, partition=None) -> str:
    tpu = getattr(chain, "tpu_chain", None)
    sig = (
        tpu._chain_sig
        if tpu is not None
        else getattr(chain, "chain_label", "") or "chain"
    )
    if partition is None:
        return sig
    # chain@partition identity: per-partition admission buckets and SLO
    # verdict families — a hot partition sheds without starving its
    # siblings (warm bookkeeping stays per-chain; the controller strips
    # the suffix for warm lookups)
    return f"{sig}@{topic or 't'}/{partition}"


def admission_check(chain, topic=None, partition=None, tenant=""):
    """The broker front door: one admission decision for one read slice.

    Returns None when admitted (or admission is disabled), else the
    typed ``Rejected`` decline. A health/credit shed means HOLD the
    slice — the stream handler sleeps ``retry_after_s`` and retries, so
    offsets never advance past unserved records (no loss, no
    duplicates) and no exception ever reaches the client. A
    ``breaker-open`` rejection is counted on the same decline surface
    but the caller proceeds: the existing breaker path serves the slice
    per-record, which is strictly better than stalling it.

    A shed happens BEFORE `tpu_stage_dispatch`, so a shed slice never
    constructs a dispatched `PendingSlice` — the
    ``inflight_queue_depth`` gauge must not move for it (regression-
    pinned in tests/test_admission.py).

    ``tenant`` attributes real sheds (not breaker-open, which the
    caller serves anyway) to the per-tenant accounting plane. The
    attribution happens HERE, not inside the gate: ``set_gate()``
    installs duck-typed controllers whose ``admit(chain, cost,
    breaker)`` contract predates tenancy and must keep working.
    """
    ctl = _admission_gate()
    if ctl is None:
        return None
    decision = ctl.admit(
        admission_chain_sig(chain, topic, partition),
        breaker=getattr(chain, "breaker", None),
    )
    if decision:
        return None
    if tenant and decision.reason != "breaker-open":
        TELEMETRY.add_tenant_shed(tenant)
    return decision


def admission_note_warm(chain, buckets) -> None:
    """Register AOT-warmed width buckets with the live controller (the
    serve gate's cold-chain shed lifts once the chain's buckets are
    warm)."""
    ctl = _admission_gate()
    if ctl is not None:
        ctl.note_warm(admission_chain_sig(chain), buckets)


def admission_require_warm(chain) -> None:
    ctl = _admission_gate()
    if ctl is not None:
        ctl.require_warm(admission_chain_sig(chain))


def tpu_stage(
    chain: SmartModuleChainInstance,
    batches: List[Batch],
    metrics=None,
    start_offset: Optional[int] = None,
    flow=None,
) -> Optional[PendingSlice]:
    """The host half of a slice's way in: stage a read slice into
    columnar chunk buffers through the native parser (no per-record
    Python objects). Touches no device and no device state, so the
    stream loop runs it for slice k+1 while slice k is still out on the
    device.

    Returns None (counting the decline reason) when the chain has no TPU
    executor, the native library is unavailable, a batch's slab
    disagrees with its header, or a staging guard trips — the caller
    falls back to the per-record path for this slice.
    """
    from fluvio_tpu.protocol.compression import Compression, decompress
    from fluvio_tpu.smartengine import native_backend
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

    tpu = getattr(chain, "tpu_chain", None)
    if tpu is None or not batches:
        return None
    breaker = getattr(chain, "breaker", None)
    if breaker is not None and not breaker.allow_fused():
        # chain breaker open: no fused slice attempt — the per-record
        # path (whose own breaker check routes each batch to the
        # interpreter AND counts the per-batch short-circuits) serves
        # the stream until probes re-promote; the decline reason below
        # records the slice-level event once
        return _decline(metrics, "breaker-open")
    t_stage0 = time.perf_counter() if TELEMETRY.enabled else 0.0
    glz_decode_s = 0.0
    # flow phases (one clock pair each, per SLICE): `wire_decode` is the
    # native decode loop and its guards, net of stored-batch
    # decompression, which it is cut from (`ph.less`)
    with timed(flow, "wire_decode") as ph:
        staged: List[tuple] = []
        total_raw = 0
        for batch in batches:
            raw = batch.raw_records
            if raw is None:
                return _decline(metrics, "no-raw-records")
            if batch.header.compression() != Compression.NONE:
                if TELEMETRY.enabled:
                    t_dc = time.perf_counter()
                    raw = decompress(batch.header.compression(), raw)
                    glz_decode_s += time.perf_counter() - t_dc
                    ph.less = glz_decode_s
                else:
                    raw = decompress(batch.header.compression(), raw)
            cols = native_backend.decode_record_columns_aligned(raw)
            if cols is None:
                return _decline(metrics, "no-native-decoder")
            if cols["count"] != batch.records_len() or cols["parsed"] != len(raw):
                return _decline(metrics, "malformed-slab")
            staged.append((batch, cols))
            total_raw += len(raw)
        # the per-record path's input-size guard (engine.py StoreMemoryExceeded)
        engine = getattr(chain, "engine", None)
        if engine is not None and total_raw > engine.store_max_memory:
            return _decline(metrics, "store-memory")  # per-record path raises

    with timed(flow, "stage"):
        # Coalesce the whole read slice into ONE device dispatch: per-batch
        # dispatches pay fixed host<->device round trips that dwarf a 16k-record
        # batch's compute. Offset deltas rebase to the first batch's base
        # offset; timestamp deltas rebase to its base timestamp.
        base0 = staged[0][0].base_offset
        ts0 = staged[0][0].header.first_timestamp
        ts_list = [b.header.first_timestamp for b, _ in staged]
        if any(t < 0 for t in ts_list) and any(t >= 0 for t in ts_list):
            # mixed absent/present base timestamps: rebase undefined
            return _decline(metrics, "mixed-base-timestamps")
        merged = {
            "count": sum(c["count"] for _, c in staged),
            # per-batch flats are 4-aligned (every record padded to 4), so a
            # straight concat preserves alignment for the whole slice
            "val_flat": np.concatenate([c["val_flat"] for _, c in staged]),
            "val_len": np.concatenate([c["val_len"] for _, c in staged]),
            "key_flat": np.concatenate([c["key_flat"] for _, c in staged]),
            "key_present": np.concatenate([c["key_present"] for _, c in staged]),
        }
        off_parts, ts_parts, val_offs, key_offs = [], [], [], []
        v_base = k_base = 0
        for b, c in staged:
            off_parts.append(c["off_delta"] + (b.base_offset - base0))
            ts_parts.append(
                c["ts_delta"] + (b.header.first_timestamp - ts0 if ts0 >= 0 else 0)
            )
            val_offs.append(c["val_off"][:-1] + v_base)
            key_offs.append(c["key_off"][:-1] + k_base)
            v_base += int(c["val_off"][-1])
            k_base += int(c["key_off"][-1])
        merged["off_delta"] = np.concatenate(off_parts)
        merged["ts_delta"] = np.concatenate(ts_parts)
        merged["val_off"] = np.concatenate(
            [np.concatenate(val_offs), np.array([v_base], dtype=np.int64)]
        )
        merged["key_off"] = np.concatenate(
            [np.concatenate(key_offs), np.array([k_base], dtype=np.int64)]
        )
        # Chunked dispatch (stateless chains): one huge slice is one device
        # call with ZERO overlap — host staging, device compute, and result
        # materialization run strictly serially. Splitting into fixed-size
        # record chunks and dispatching them ALL up front keeps every chunk
        # in flight while the first one downloads/encodes, so the slice's
        # wall time approaches max(host, device) instead of the sum. Equal
        # chunk sizes reuse one compiled shape bucket. Stateful chains chain
        # their carries through dispatch order (safe), but fan-out capacity
        # retries and aggregate delta-fetches are tuned for one dispatch —
        # keep those single-chunk.
        n_total = merged["count"]
        chunk_rows = _DISPATCH_CHUNK_ROWS
        stateless = not tpu.stateful and not tpu._fanout
        if stateless and n_total > chunk_rows * 3 // 2:
            bounds = list(range(0, n_total, chunk_rows)) + [n_total]
            if bounds[-1] == bounds[-2]:
                bounds.pop()
        else:
            bounds = [0, n_total]  # n_total == 0 still stages one empty chunk
        # whole-slice width guard BEFORE any dispatch: a too-wide record
        # declines the slice without leaving earlier chunks' device work
        # abandoned mid-flight. The bound is the CHAIN's: stripe-capable
        # chains stage wide records as striped segments (tpu/stripes.py) up
        # to the hard ceiling, others decline at the narrow layout width.
        if n_total and int(merged["val_len"].max()) > tpu.max_stageable_width():
            return _decline(metrics, "record-too-wide")
        # EVERY chunk builds (and passes its guards) before ANY dispatch:
        # a mid-loop decline (staging-cap depends on each chunk's local
        # padded width) must never abandon earlier chunks' in-flight device
        # work. The build pass is view-based numpy slicing (flat-backed
        # buffers are born in upload form), so the device idles ~ms per
        # slice for it — the invariant is worth more than the overlap.
        chunk_bufs: List = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            part = _slice_columns(merged, lo, hi)
            try:
                buf = RecordBuffer.from_flat(
                    part, base_offset=base0, base_timestamp=ts0
                )
            except ValueError:  # value beyond the hard ceiling: per-record path
                return _decline(metrics, "record-too-wide")
            # dense-amplification guard: one huge value would pad every
            # row of the DEVICE-side re-padded matrix (rows x width in
            # HBM) to its pow2 width — the host stays flat-backed either way
            if buf.rows * buf.width > _MAX_STAGING_BYTES:
                return _decline(metrics, "staging-cap")
            if tpu._fanout:
                # fan-out outputs inherit their source batch's rebase
                # deltas ("fresh" records, delta 0 relative to their own
                # batch); fan-out is always single-chunk so the staged
                # batch walk covers the whole slice
                rows = buf.offset_deltas.shape[0]
                fo = np.zeros(rows, dtype=np.int32)
                ft = np.zeros(rows, dtype=np.int64)
                pos = 0
                for b, c in staged:
                    n_b = c["count"]
                    fo[pos : pos + n_b] = b.base_offset - base0
                    if ts0 >= 0:
                        ft[pos : pos + n_b] = b.header.first_timestamp - ts0
                    pos += n_b
                buf.fresh_offset_deltas = fo
                buf.fresh_timestamp_deltas = ft
            chunk_bufs.append(buf)
    if TELEMETRY.enabled:
        # slice-level staging cost (native decode, column merge, chunk
        # builds), net of stored-batch decompression; the per-chunk
        # device work below books into its own spans
        TELEMETRY.add_phase("glz_decode", glz_decode_s)
        TELEMETRY.add_phase(
            "stage", time.perf_counter() - t_stage0 - glz_decode_s
        )
    return PendingSlice(
        batches=batches,
        bufs=chunk_bufs,
        planned_next=staged[-1][0].computed_last_offset(),
        total_raw=total_raw,
        base0=base0,
        ts0=ts0,
        count=n_total,
        read_from=start_offset,
        flow=flow,
    )


def tpu_dispatch(
    chain: SmartModuleChainInstance,
    pending: PendingSlice,
    metrics=None,
    topic: Optional[str] = None,
    partition: Optional[int] = None,
) -> Optional[PendingSlice]:
    """The device half of a slice's way in: every staged chunk goes out
    in ONE `dispatch_buffers` call, which returns without blocking on
    results. Returns ``pending`` with its ``chunks`` in flight, or None
    (counting the decline reason) when the dispatch failed for good."""
    from fluvio_tpu.smartengine.tpu.executor import TpuSpill

    tpu = chain.tpu_chain
    flow = pending.flow
    # executor-owned dispatch: a plain dispatch loop. A dispatch failure
    # that survived the executor's bounded retries (or a deterministic fault)
    # must not crash the stream handler: the slice declines to the
    # per-record path, whose own fused/spill/quarantine ladder decides
    # per batch (dispatch_buffers discarded any partial handles).
    # partitioned placement: this stream's dispatches run on its
    # partition's device group with the chain@partition identity on
    # spans/down-link telemetry (broker chains are per-stream so the
    # carries are already per-partition)
    pscope = _enter_partition_scope(topic, partition, tpu)
    try:
        # the chunks' BatchSpans are this phase's children: each carries
        # the slice's flow id
        with timed(flow, "dispatch"):
            pending.chunks = tpu.dispatch_buffers(
                pending.bufs, flow_id=flow.flow_id if flow is not None else 0
            )
    except TpuSpill:
        return _decline(metrics, "transform-error-spill")
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        if is_program_fault(e):
            raise  # compiler-refused program: never a per-record rerun
        logging.getLogger(__name__).warning(
            "fused slice dispatch failed (%s: %s); per-record fallback",
            type(e).__name__, e,
        )
        return _decline(metrics, "fused-error")
    finally:
        if pscope is not None:
            pscope.__exit__(None, None, None)
    # pipelined occupancy gauge: every dispatched chunk counts until its
    # fetch (tpu_fetch) or the slice's discard retires it
    if TELEMETRY.enabled:
        TELEMETRY.gauge_add("inflight_queue_depth", len(pending.chunks))
        pending.tracked_depth = len(pending.chunks)
    return pending


def tpu_stage_dispatch(
    chain: SmartModuleChainInstance,
    batches: List[Batch],
    metrics=None,
    start_offset: Optional[int] = None,
    topic: Optional[str] = None,
    partition: Optional[int] = None,
    flow=None,
) -> Optional[PendingSlice]:
    """`tpu_stage` then `tpu_dispatch`: a slice's whole way in."""
    pending = tpu_stage(chain, batches, metrics, start_offset, flow=flow)
    if pending is None:
        return None
    return tpu_dispatch(chain, pending, metrics, topic, partition)


def tpu_fetch(
    chain: SmartModuleChainInstance,
    pending: PendingSlice,
    metrics=None,
    topic: Optional[str] = None,
    partition: Optional[int] = None,
) -> bool:
    """The blocking half of a slice's way out (flow phase ``finish``):
    per chunk, the header sync, the count-sized slice programs, the
    downloads and every failure ladder (`finish_buffer_deferred`). When
    it returns, the slice's carries are settled: the stream loop
    dispatches the next slice only now, so the slice programs found an
    empty device queue and no slice is ever in flight ahead of a fetch
    that can roll a carry back. A chunk's pure split-back thunk goes to
    the shared fetch worker as it appears, the last chunk's stays with
    the slice; `tpu_materialize` joins the former and runs the latter.

    With the partition gate armed and a partition identity supplied,
    the fetch runs in the partition's placement scope so the fetch-side
    telemetry (down-* variants, enc-ratio declines) books per
    partition, matching the dispatch side.

    False (the decline counted, carries restored by the executor) when
    the device signalled a transform error or the fetch failed for
    good — the interpreter re-runs the slice for exact error semantics.
    """
    pscope = _enter_partition_scope(
        topic, partition, getattr(chain, "tpu_chain", None)
    )
    try:
        return _tpu_fetch_inner(chain, pending, metrics)
    finally:
        if pscope is not None:
            pscope.__exit__(None, None, None)


def _tpu_fetch_inner(
    chain: SmartModuleChainInstance, pending: PendingSlice, metrics=None
) -> bool:
    from fluvio_tpu.smartengine.tpu import executor as tpu_executor
    from fluvio_tpu.smartengine.tpu.executor import TpuSpill

    tpu = chain.tpu_chain
    # whatever the outcome below (outputs, spill, fused-error decline),
    # this slice's chunks leave the pipelined queue now
    pending.release_depth()
    overlap = tpu_executor.effective_fetch_overlap()
    # from here on the handles are the executor's to settle: a discard
    # of this slice must not touch them again. `finished` counts chunks
    # whose handles were consumed (the discard slices below must skip
    # them AND the one that raised).
    parts = pending.parts = []
    finished = 0
    last = len(pending.chunks) - 1
    grows = tpu.fanout_grows
    with timed(pending.flow, "finish"):
        try:
            for b, h in pending.chunks:
                out = (
                    tpu.finish_buffer_deferred(b, h)
                    if overlap
                    else tpu.finish_buffer(b, h)
                )
                # a chunk's split-back runs on the fetch worker under
                # the downloads of the chunks after it. The last chunk's
                # would run under the next slice's dispatch and slow it
                # (both hold the interpreter lock): `tpu_materialize`
                # runs that one itself, once the chip has its work
                parts.append(
                    tpu_executor._fetch_mat_pool().submit(out)
                    if callable(out) and finished < last
                    else out
                )
                finished += 1
            if tpu.agg_configs:
                # the carries this slice left, read before a later
                # dispatch replaces them by its futures; the host mirror
                # takes them once the slice has encoded
                pending.carries = tpu._download_carries()
            return True
        except TpuSpill:
            # later chunks' dispatch-time D2H copies still crossed the link;
            # discard them so the executor's byte accounting stays honest.
            # NOT counted as a telemetry spill here: the per-record rerun
            # re-enters chain.process, whose own TpuSpill handler counts one
            # spill per batch — counting the slice here too would inflate
            # spills_total for the single logical event (the slice-level
            # decline counter below already records it once)
            for _, h in pending.chunks[finished + 1 :]:
                tpu.discard_dispatch(h)
            _decline(metrics, "transform-error-spill")
            return False
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            # a device/fetch failure that survived the executor's bounded
            # retries: same containment as a spill — the per-record path
            # decides per batch (carries were rolled back by the executor)
            for _, h in pending.chunks[finished + 1 :]:
                tpu.discard_dispatch(h)
            if is_program_fault(e):
                raise  # compiler-refused program: never a per-record rerun
            logging.getLogger(__name__).warning(
                "fused slice finish failed (%s: %s); per-record fallback",
                type(e).__name__, e,
            )
            _decline(metrics, "fused-error")
            return False
        finally:
            # a chunk that re-ran at a grown fan-out bound, whatever the
            # slice's outcome
            if metrics is not None:
                metrics.add_fanout_grows(tpu.fanout_grows - grows)


def _split_back_of(part):
    """A fetched chunk's output buffer: joined from the fetch worker,
    split back here (the last chunk's thunk), or there already."""
    if hasattr(part, "result"):
        return part.result()
    return part() if callable(part) else part


def _decline_fetched(tpu, pending, ahead, metrics, reason: str):
    """A slice declines AFTER its fetch, with its carries advanced and
    perhaps a slice dispatched ``ahead`` of the decline: that one is
    discarded first (its handles restore the carries this slice left),
    then this slice's carries go back to where it started, so the
    per-record rerun counts nothing twice."""
    if ahead is not None:
        ahead.discard(tpu)
    pending.rollback(tpu)
    return _decline(metrics, reason)


def _encode_outputs(outbufs: List, max_bytes: int, resume: Optional[int]):
    """A fetched slice's output buffers, chunk after chunk, into ONE
    wire-format slab: per chunk the resume drop (rows whose offset delta
    is under ``resume``; survivor deltas are ascending and already
    rebased to the slice's base offset, so a consumer resuming mid-slice
    filters correctly) and one native pass that reads the form the
    buffer holds (`RecordBuffer.encode_into`). The slab carries the
    ``max_bytes`` cut (0 = none) from chunk to chunk.

    Returns (slab bytes, rows kept, the last kept row's offset delta
    when the cut fell — else None —, the encode forms taken), or None
    when there is something to encode and no native encoder."""
    from fluvio_tpu.smartengine import native_backend

    slab = None
    n_out, last_delta, cut_at = 0, 0, None
    forms = set()
    for outbuf in outbufs:
        n = outbuf.count
        deltas = outbuf.offset_deltas[:n]
        first = (
            0 if resume is None
            else int(np.searchsorted(deltas, resume, side="left"))
        )
        if first >= n:
            continue
        if slab is None:
            slab = native_backend.record_slab(max_bytes)
            if slab is None:
                return None
        kept, form = outbuf.encode_into(slab, first)
        forms.add(form)
        if kept:
            n_out += kept
            last_delta = int(deltas[first + kept - 1])
        if kept < n - first:
            cut_at = last_delta
            break
    return (slab.take() if n_out else b""), n_out, cut_at, forms


def tpu_materialize(
    chain: SmartModuleChainInstance,
    pending: PendingSlice,
    max_bytes: int,
    metrics=None,
    ahead: Optional[PendingSlice] = None,
) -> Optional[BatchProcessResult]:
    """The host half of a fetched slice's way out: join the split-back
    thunks and run the last one (flow phase ``materialize``), and
    re-assemble output batches
    at the byte level with the native encoder (``encode``). Nothing
    here waits for the device, so the stream loop runs it while the
    slice it dispatched ``ahead`` is out there; that slice is discarded
    here when this one's outcome makes it wrong (a ``max_bytes`` cut, a
    decline).

    Wire/offset semantics match `process_batches`: survivors keep their
    stored offsets rebased to the slice's first batch. Aggregate chains
    always deliver every processed batch — device carries have already
    advanced, so dropping computed outputs would double-count on
    refetch; stateless chains honor the max_bytes cutoff exactly like
    the per-record path. Returns None when the encoder refuses the
    output: the carries are back where this slice started, for the
    per-record rerun.
    """
    tpu = chain.tpu_chain
    base0, ts0 = pending.base0, pending.ts0
    result = BatchProcessResult()
    result.next_offset = pending.planned_next
    flow = pending.flow
    try:
        with timed(flow, "materialize"):
            outbufs = [_split_back_of(p) for p in pending.parts]
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        if is_program_fault(e):
            raise
        logging.getLogger(__name__).warning(
            "fused slice split-back failed (%s: %s); per-record fallback",
            type(e).__name__, e,
        )
        return _decline_fetched(tpu, pending, ahead, metrics, "fused-error")
    # `encode`: the chunks' one native pass each into the response slab,
    # then the response Batch
    with timed(flow, "encode"):
        stateless = not tpu.stateful and not tpu._fanout
        resume = None
        if (
            stateless
            and pending.read_from is not None
            and pending.read_from > base0
        ):
            # resuming mid-batch: outputs below the consume cursor were
            # already served in a previous (truncated) response — drop them
            # so the stream always advances
            resume = pending.read_from - base0
        # stateless chains honor max_bytes: keep the longest record prefix
        # whose encoded size fits (>= semantics: always keep one batch's
        # worth of progress by including at least the first record)
        encoded = _encode_outputs(
            outbufs, max_bytes if stateless else 0, resume
        )
        if encoded is None:
            return _decline_fetched(
                tpu, pending, ahead, metrics, "encode-failed"
            )
        raw_out, n_out, cut_at, forms = encoded
        if cut_at is not None:
            result.next_offset = base0 + cut_at + 1
            if ahead is not None:
                # the consume point moved: the slice dispatched ahead
                # read from the wrong offset
                ahead.discard(tpu)
        if n_out:
            out_batch = Batch(
                base_offset=base0,
                raw_records=raw_out,
                raw_record_count=n_out,
            )
            now = int(time.time() * 1000) if ts0 == NO_TIMESTAMP else ts0
            out_batch.header.first_timestamp = now
            out_batch.header.max_time_stamp = now
            # span the full consumed offset range so the consumer's next fetch
            # advances past every input record (incl. filtered-out ones)
            out_batch.header.last_offset_delta = result.next_offset - 1 - base0
            result.records.add(out_batch)
    # metrics only after the last possible fallback return: the per-record
    # path re-counts bytes_in when this path bails out
    if metrics is not None:
        metrics.add_bytes_in(pending.total_raw)
        metrics.add_fuel_used(pending.count * max(len(tpu.stages), 1))
        metrics.add_records_out(n_out)
        metrics.add_fastpath()
    # which form the slice's output took into the encoder: `enc-direct-*`
    # (the one native pass) or `enc-columns` (the general form)
    for form in sorted(forms):
        TELEMETRY.add_link_variant(form)
    if pending.carries is not None:
        tpu._ensure_host_state(pending.carries)
    # a clean fused slice counts toward the chain breaker's health —
    # half-open probes served through the slice path must be able to
    # re-promote the chain, not only per-record batches
    breaker = getattr(chain, "breaker", None)
    if breaker is not None:
        breaker.record_success()
    return result


def tpu_finish(
    chain: SmartModuleChainInstance,
    pending: PendingSlice,
    max_bytes: int,
    metrics=None,
    topic: Optional[str] = None,
    partition: Optional[int] = None,
) -> Optional[BatchProcessResult]:
    """`tpu_fetch` then `tpu_materialize`: a slice's whole way out.
    None when either half declined."""
    if not tpu_fetch(chain, pending, metrics, topic, partition):
        return None
    return tpu_materialize(chain, pending, max_bytes, metrics)


def _tpu_process_batches(
    chain: SmartModuleChainInstance,
    batches: List[Batch],
    max_bytes: int,
    metrics=None,
    start_offset: Optional[int] = None,
    topic: Optional[str] = None,
    partition: Optional[int] = None,
    flow=None,
) -> Optional[BatchProcessResult]:
    """Coalesced TPU fast path, one slice at a time: the four halves in
    a row (stage, dispatch, fetch, materialize).

    The stream-fetch handler's loop calls the halves itself, so that
    slice k+1 is out on the device while slice k is split back, encoded
    and sent. ``flow`` is the slice's flow record: a slice served here
    records the same phases.
    """
    pending = tpu_stage_dispatch(
        chain, batches, metrics, start_offset,
        topic=topic, partition=partition, flow=flow,
    )
    if pending is None:
        return None
    return tpu_finish(
        chain, pending, max_bytes, metrics,
        topic=topic, partition=partition,
    )


def process_batches(
    chain: SmartModuleChainInstance,
    batches: List[Batch],
    max_bytes: int,
    metrics=None,
    start_offset: Optional[int] = None,
    topic: Optional[str] = None,
    partition: Optional[int] = None,
    flow=None,
) -> BatchProcessResult:
    """Run stored batches through the chain, re-batch the outputs.

    Per input batch (parity: batch.rs:41-140): records -> SmartModuleInput
    (base offset/timestamp from the batch header) -> chain.process -> output
    Batch spanning the *input* batch's offset range, so consumers advance
    their offsets past filtered-out records. Survivors keep their stored
    offsets; batches re-served on a mid-batch resume are deduplicated by
    the consumer's cursor (the fast path additionally drops already-
    served outputs below ``start_offset``). Stops at max_bytes or on the
    first transform error (partial output kept, engine.rs:159-161).

    Chains with a TPU executor take `_tpu_process_batches`'s coalesced
    batch-level path when the native codecs are available.
    """
    fast = _tpu_process_batches(
        chain, batches, max_bytes, metrics, start_offset,
        topic=topic, partition=partition, flow=flow,
    )
    if fast is not None:
        return fast
    return process_batches_per_record(
        chain, batches, max_bytes, metrics, flow=flow
    )


def process_batches_per_record(
    chain: SmartModuleChainInstance,
    batches: List[Batch],
    max_bytes: int,
    metrics=None,
    flow=None,
) -> BatchProcessResult:
    """The interpreting per-batch loop (exact reference semantics);
    also the direct target for slices the fast path already declined —
    re-entering `process_batches` would re-stage and re-dispatch the
    failed slice and double-count the fallback metrics. Books ONE flow
    phase, ``interpret``, so a declined slice is not a hole in its
    flow."""
    with timed(flow, "interpret"):
        return _process_batches_per_record(chain, batches, max_bytes, metrics)


def _process_batches_per_record(
    chain: SmartModuleChainInstance,
    batches: List[Batch],
    max_bytes: int,
    metrics=None,
) -> BatchProcessResult:
    result = BatchProcessResult()
    total_bytes = 0
    for batch in batches:
        records = batch.memory_records()
        inp = SmartModuleInput.from_records(
            records,
            base_offset=batch.base_offset,
            base_timestamp=batch.header.first_timestamp,
        )
        output = chain.process(inp, metrics)
        result.next_offset = batch.computed_last_offset()
        if output.successes:
            # consume-path contract (parity with the TPU fast path and
            # fluvio-spu batch.rs): survivors keep their stored offsets
            out_batch = Batch.from_records(
                output.successes,
                base_offset=batch.base_offset,
                first_timestamp=(
                    batch.header.first_timestamp
                    if batch.header.first_timestamp != NO_TIMESTAMP
                    else None
                ),
                preserve_offsets=True,
            )
            # Cover the input batch's whole offset range: next fetch offset
            # is computed from last_offset_delta, which must reflect the
            # records consumed from the log, not the (possibly fewer or
            # more) records produced.
            out_batch.header.last_offset_delta = (
                batch.computed_last_offset() - 1 - batch.base_offset
            )
            total_bytes += out_batch.write_size()
            result.records.add(out_batch)
        if output.error is not None:
            result.error = output.error
            break
        if total_bytes >= max_bytes:
            break
    return result
