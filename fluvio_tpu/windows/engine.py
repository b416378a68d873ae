"""The windowed-state runtime: device-resident carry, delta-only D2H.

`WindowedRuntime` drives one stream through the fused window kernel:
the bank (state.py) never leaves the device between batches, and the
only thing that crosses the link down is the per-batch DELTA — closed
windows plus the (key, window) entries this batch touched — as packed
int columns riding the same down-* accounting the executor's packed
fetch uses. A full-state image ships only on consumer attach, failover
seed/migration (CarryReplica), and the emit-capacity overflow resync —
and an overflow resync still carries the batch's closed rows (their
final aggregates were evicted from the bank), never dropping closes.

Fault discipline matches the executor: `faults.maybe_fire` at the
stage/dispatch/device/fetch seams, transient faults retried ONCE
against the untouched carry (the bank commits only after the fetch
succeeded), then re-raised. Every batch books a `BatchSpan` on the
"windowed" path so BENCH_DETAIL's phase split shows where the wall
went; its host phases go through `telemetry/spans.py:timed` like the
executor's, and the span names the slice flow that caused it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

from fluvio_tpu.resilience import faults
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.spans import timed
from fluvio_tpu.windows.kernels import WindowJits
from fluvio_tpu.windows.spec import WindowCapacityError, WindowSpec
from fluvio_tpu.windows.state import ENTRY_BYTES, WindowStateBank

# fixed per-delta framing cost (header scalars + column descriptors);
# matches the executor's packed-fetch 64-byte framing constant
DELTA_FRAME_BYTES = 64


@dataclass
class WindowDelta:
    """One batch's downlink payload (already on host)."""

    kind: str  # "rows" (delta columns) | "resync" (full bank image)
    ids: np.ndarray
    accs: np.ndarray
    counts: np.ndarray
    closed: np.ndarray  # 1 = this row is a window close (rows kind)
    watermark: int
    n_open: int
    n_closed: int
    n_late: int
    delta_bytes: int
    full_bytes: int
    records: int
    # rows whose key fell outside the composite-id packing range
    # [0, KEY_STRIDE): dropped (never folded), counted for observability
    n_invalid: int = 0
    # filled by PartitionedWindowRuntime so replayed deltas can be
    # deduped by the serving ladder
    partition: Optional[Tuple[str, int]] = None
    offset: int = -1

    def row_count(self) -> int:
        return int(self.ids.shape[0])


def _fetch_bucket(n: int, cap: int) -> int:
    """Rows a fetch slices for ``n`` live ones: a power of two from 8
    (the executor's bucketed-jit discipline: XLA compiles each slice
    shape ONCE, a per-batch ``n`` would pay a tiny-op compile a batch),
    at most the column's length."""
    rows = 8
    while rows < n:
        rows *= 2
    return min(rows, cap)


def _full_state_bytes(records: int) -> int:
    """What the classic per-record emission ships for the same batch:
    one i64 result + i64 window id per record, a validity bitmap, and
    the packed-fetch framing — the denominator of the delta-vs-full
    downlink ratio."""
    return 16 * records + math.ceil(records / 8) + DELTA_FRAME_BYTES


class WindowedRuntime:
    """One stream's windowed-state engine (single-device path)."""

    def __init__(
        self,
        spec: WindowSpec,
        device=None,
        jits: Optional[WindowJits] = None,
    ):
        self.spec = spec
        self.jits = jits if jits is not None else WindowJits(spec)
        self.bank = WindowStateBank(spec, device=device)
        self.batches = 0
        self.d2h_bytes_total = 0

    @classmethod
    def from_params(cls, kind: str, window_ms, slide_ms=0, keyed=False,
                    device=None):
        return cls(
            WindowSpec.from_params(kind, window_ms, slide_ms, keyed),
            device=device,
        )

    # -- ingest --------------------------------------------------------------

    def process_buffer(self, buf, flow_id: int = 0) -> WindowDelta:
        """Fold one RecordBuffer; returns the batch's delta. Transient
        injected faults retry once against the identical carry (the
        bank is untouched until the fetch lands). ``flow_id`` names the
        slice flow that caused the batch on its span (0 = none; a
        buffer tagged with its flow names it itself)."""
        if not flow_id:
            flow = getattr(buf, "_flow", None)
            if flow is not None:
                flow_id = flow.batch_id
        for attempt in (0, 1):
            try:
                return self._process_once(buf, flow_id)
            except faults.InjectedFault as exc:
                if not exc.transient or attempt:
                    raise
                TELEMETRY.add_retry(exc.point)

    def _process_once(self, buf, flow_id: int = 0) -> WindowDelta:
        import jax.numpy as jnp

        span = TELEMETRY.begin_batch(
            "windowed", chain=self.spec.mode, flow_id=flow_id
        )
        with timed(span, "stage"):
            faults.maybe_fire("stage")
            values = buf.dense_values()
            n = values.shape[0]
            count = int(buf.count)
            # base_timestamp -1 is the buffer's "unset" sentinel
            base = max(int(buf.base_timestamp), 0)
            ts = np.asarray(buf.timestamp_deltas, dtype=np.int64) + base
            valid = np.arange(n, dtype=np.int64) < count
            lengths = np.asarray(buf.lengths, dtype=np.int32)
        return self._run(
            self.jits.update_values,
            (jnp.asarray(values), jnp.asarray(lengths),
             jnp.asarray(ts), jnp.asarray(valid)),
            count,
            span,
        )

    def ingest_arrays(self, contribs, keys, ts, count: Optional[int] = None
                      ) -> WindowDelta:
        """Pre-parsed seam for the striped/sharded split-backs (and
        tests): contribs/keys/ts int64 rows, already on host or
        device."""
        import jax.numpy as jnp

        contribs = jnp.asarray(contribs, dtype=jnp.int64)
        keys = jnp.asarray(keys, dtype=jnp.int64)
        ts = jnp.asarray(ts, dtype=jnp.int64)
        n = int(contribs.shape[0])
        count = n if count is None else int(count)
        valid = jnp.arange(n, dtype=jnp.int64) < count
        span = TELEMETRY.begin_batch("windowed", chain=self.spec.mode)
        return self._run(
            self.jits.update_arrays, (contribs, keys, ts, valid), count, span
        )

    def _run(self, update, batch_args, count: int, span) -> WindowDelta:
        import jax

        with timed(span, "dispatch"):
            faults.maybe_fire("dispatch")
            outs = update(*self.bank.arrays(), *batch_args)
        if span is not None:
            span.mark_dispatched()
        faults.maybe_fire("device")
        (header, nb_ids, nb_accs, nb_cnts,
         em_ids, em_accs, em_cnts, em_closed) = outs
        # first blocking sync: the scalar header (8 i64 = 64 bytes)
        with timed(span, "wait"):
            h = jax.device_get(header)
        if span is not None:
            span.mark_device_ready()
        faults.maybe_fire("fetch")
        (n_emit, n_open, n_closed, n_late, new_wm, bank_ovf, emit_ovf,
         n_invalid) = (int(x) for x in h)
        if bank_ovf:
            # the merged open set no longer fits the device bank: loud
            # failure BEFORE committing, so the carry stays valid
            TELEMETRY.add_decline("window-capacity")
            raise WindowCapacityError(
                f"{n_open} open windows exceed bank capacity "
                f"{self.spec.capacity} (raise FLUVIO_WINDOW_CAPACITY)"
            )
        emit_cols = int(em_ids.shape[0])
        resync = emit_ovf or not self.spec.delta_only
        if resync and n_closed > emit_cols:
            # the batch closed more windows than the emit columns hold:
            # their final aggregates exist ONLY there (a close evicts
            # the entry from the bank), so they cannot be delivered —
            # loud failure BEFORE committing, like the bank-capacity
            # path, instead of silently losing close events
            TELEMETRY.add_decline("window-capacity")
            raise WindowCapacityError(
                f"{n_closed} windows closed in one batch exceed emit "
                f"capacity {emit_cols} (raise FLUVIO_WINDOW_EMIT)"
            )
        self.bank.commit(
            nb_ids, nb_accs, nb_cnts, header[4], n_open, new_wm
        )
        # ONE bucketed device_get per batch: the emit columns' live
        # prefix (a resync ships only its closed rows, which the kernel
        # packs first and the guard above pinned within the columns)
        # and, on a resync, the open-state image of the bank just
        # committed — more changed rows than the emit columns hold, or
        # the FLUVIO_WINDOW_DELTA=0 escape hatch: correct, just not
        # delta-sized; the view folds the closes and replaces its open
        # table from the image
        n_cols = n_closed if resync else n_emit
        fetch_rows = (
            _fetch_bucket(n_cols, emit_cols) if n_cols or not resync else 0
        )
        parts = [em_ids[:fetch_rows], em_accs[:fetch_rows],
                 em_cnts[:fetch_rows]]
        if resync:
            image_rows = _fetch_bucket(n_open, self.spec.capacity)
            parts += [nb_ids[:image_rows], nb_accs[:image_rows],
                      nb_cnts[:image_rows]]
        else:
            parts.append(em_closed[:fetch_rows])
        # emit-buffer ledger window: the sliced device rows (3 i64 + 1
        # i32 verdict column a row) are live HBM until the host copy
        # below materializes
        row_bytes = ENTRY_BYTES if resync else ENTRY_BYTES + 4
        with timed(span, "d2h"):
            TELEMETRY.mem_acquire(
                "emit_buffer", ("emit", id(self)), fetch_rows * row_bytes
            )
            try:
                host = [np.asarray(a) for a in jax.device_get(parts)]
            finally:
                TELEMETRY.mem_release(("emit", id(self)))
        ids, accs, cnts = (a[:n_cols] for a in host[:3])
        if resync:
            ids, accs, cnts = (
                np.concatenate([c, image[:n_open]])
                for c, image in zip((ids, accs, cnts), host[3:])
            )
            closed = np.zeros((ids.shape[0],), dtype=np.int32)
            closed[:n_closed] = 1
            kind = "rows-resync"
            delta_bytes = (
                fetch_rows * ENTRY_BYTES
                + n_open * ENTRY_BYTES
                + DELTA_FRAME_BYTES
            )
        else:
            closed = host[3][:n_emit]
            kind = "rows"
            delta_bytes = fetch_rows * row_bytes + DELTA_FRAME_BYTES
        full_bytes = _full_state_bytes(count)
        self.batches += 1
        self.d2h_bytes_total += delta_bytes
        # -- telemetry (counters always-on; gauges gated inside) -------------
        TELEMETRY.add_windows_closed(n_closed)
        if n_closed:
            TELEMETRY.add_window_delta("close", n_closed)
        if kind == "rows":
            upserts = int(ids.shape[0]) - n_closed
            if upserts:
                TELEMETRY.add_window_delta("upsert", upserts)
        else:
            # closes riding the resync are already counted under "close"
            TELEMETRY.add_window_delta("resync", int(ids.shape[0]) - n_closed)
        if n_late:
            TELEMETRY.add_window_delta("late", n_late)
        if n_invalid:
            TELEMETRY.add_window_delta("invalid", n_invalid)
        TELEMETRY.add_window_downlink(delta_bytes, full_bytes)
        # window_state_bytes now republishes from the device-memory
        # ledger's window_bank owner — booked (always-on) inside
        # bank.commit above, gauge publication still capture-gated
        TELEMETRY.add_link_variant("down-packed")
        TELEMETRY.end_batch(span, records=count)
        return WindowDelta(
            kind="resync" if kind == "rows-resync" else "rows",
            ids=np.asarray(ids, dtype=np.int64),
            accs=np.asarray(accs, dtype=np.int64),
            counts=np.asarray(cnts, dtype=np.int64),
            closed=np.asarray(closed, dtype=np.int32),
            watermark=new_wm,
            n_open=n_open,
            n_closed=n_closed,
            n_late=n_late,
            delta_bytes=delta_bytes,
            full_bytes=full_bytes,
            records=count,
            n_invalid=n_invalid,
        )

    # -- attach / resync -----------------------------------------------------

    def resync_rows(self) -> Tuple[np.ndarray, int]:
        """Full-state image for a consumer attach: (rows, watermark)
        for `MaterializedView.resync`."""
        return self.bank.full_rows(), self.bank.watermark


def _fold_open(mirror: Dict[int, Tuple[int, int]], delta: WindowDelta
               ) -> None:
    """Fold one delta into a host open-table mirror (the open-side of
    `MaterializedView.apply_delta`): upserts overwrite, closes evict, a
    resync replaces the table from its open rows. Because every open
    bank entry shipped in the batch that last touched it, the mirror
    tracks the device bank's live entries exactly — which is what lets
    the replica publish ride rows the batch ALREADY fetched instead of
    a per-batch full-bank device_get."""
    if delta.kind == "resync":
        mirror.clear()
    for i, a, c, cl in zip(delta.ids, delta.accs, delta.counts,
                           delta.closed):
        if cl:
            mirror.pop(int(i), None)
        else:
            mirror[int(i)] = (int(a), int(c))


class PartitionedWindowRuntime:
    """Per-(topic, partition) window banks sharing ONE compiled
    `WindowJits`, with the carry riding the PR-13/18 CarryReplica
    exactly-once ladder: every committed batch publishes the bank
    snapshot + served-delta offset, so promotion/migration restores a
    bit-equal bank and the serving side can dedupe replayed deltas."""

    def __init__(self, spec: WindowSpec, replica=None,
                 jits: Optional[WindowJits] = None):
        self.spec = spec
        self.jits = jits if jits is not None else WindowJits(spec)
        self.replica = replica
        self._runtimes: Dict[Tuple[str, int], WindowedRuntime] = {}
        self._offsets: Dict[Tuple[str, int], int] = {}
        # host mirror of each bank's open entries, folded from served
        # deltas — the replica-publish source (no extra D2H per batch)
        self._mirrors: Dict[Tuple[str, int], Dict[int, Tuple[int, int]]] = {}

    @staticmethod
    def _replica_key(topic: str, partition: int) -> str:
        return f"window/{topic}/{partition}"

    def runtime(self, topic: str, partition: int, device=None
                ) -> WindowedRuntime:
        key = (topic, partition)
        rt = self._runtimes.get(key)
        if rt is None:
            rt = WindowedRuntime(self.spec, device=device, jits=self.jits)
            self._runtimes[key] = rt
        elif device is not None:
            rt.bank.to_device(device)
        return rt

    def process_buffer(self, topic: str, partition: int, buf,
                       device=None) -> WindowDelta:
        rt = self.runtime(topic, partition, device=device)
        delta = rt.process_buffer(buf)
        key = (topic, partition)
        offset = self._offsets.get(key, 0)
        delta.partition = key
        delta.offset = offset
        self._offsets[key] = offset + delta.records
        if self.replica is not None:
            # the publish derives from the delta the batch already
            # fetched: the mirror IS the bank's live entry set (sorted
            # by id, the bank's compaction order), so promotion seeds
            # bit-equal without re-shipping the full bank every batch
            mirror = self._mirrors.setdefault(key, {})
            _fold_open(mirror, delta)
            self.replica.publish(
                self._replica_key(topic, partition),
                self._offsets[key],
                [(i,) + mirror[i] for i in sorted(mirror)],
                inst_state=[("wm", delta.watermark)],
            )
        return delta

    # -- failover / migration ------------------------------------------------

    def seed(self, topic: str, partition: int, device=None) -> int:
        """Promotion seed: restore the bank from the replica's last
        committed snapshot; returns the committed offset replay should
        resume from (the exactly-once rewind point)."""
        if self.replica is None:
            raise RuntimeError("no CarryReplica bound for window seed")
        offset, carries, inst_state = self.replica.latest(
            self._replica_key(topic, partition)
        )
        wm = dict(inst_state or ()).get("wm", None)
        if wm is None:
            raise RuntimeError(
                f"window replica for {topic}/{partition} has no watermark"
            )
        rt = self.runtime(topic, partition, device=device)
        rt.bank.restore(list(carries or ()), int(wm))
        self._offsets[(topic, partition)] = int(offset)
        self._mirrors[(topic, partition)] = {
            int(i): (int(a), int(c)) for i, a, c in (carries or ())
        }
        return int(offset)

    def migrate(self, topic: str, partition: int, device) -> None:
        """Mid-window partition move: lazy device re-placement of the
        live carry (no host round-trip), same as the partition
        runtime's migration move. The replica snapshot published at
        the last commit is the rollback point."""
        rt = self._runtimes.get((topic, partition))
        if rt is not None:
            rt.bank.to_device(device)

    def snapshot(self, topic: str, partition: int):
        rt = self._runtimes.get((topic, partition))
        if rt is None:
            return [], None
        return rt.bank.snapshot()

    def state_bytes(self) -> int:
        return sum(
            rt.bank.state_bytes() for rt in self._runtimes.values()
        )
