"""Per-slice causal flow records for the flight recorder.

PR 10's cross-tenant batcher made per-slice causality invisible: one
dispatched batch serves N tenant slices, a shed "holds the slice" with
zero telemetry, and the PR-5 span ring only sees BATCHES. A
:class:`SliceFlow` is the missing per-slice walk: it is born when a
broker read slice (or an admission-pipeline submission) arrives, picks
up wall-positioned lifecycle phases as the slice moves —

- ``hold``        shed-held retry wait (admission backpressure),
- ``queue_wait``  admission fair-queue residence,
- ``batcher``     shape-bucket batcher residence (coalescing wait),
- ``serve``       arrival -> served end-to-end (recorded implicitly
                  from ``t0``/``t_end`` at close),

and the served path's own steps, each one clock pair per SLICE taken
where the work happens (`spans.timed(flow, phase)`), so the slice is
the root span of everything the serving task did for it —

- ``chain_acquire`` `acquire_stream_chain` for the stream this slice is
                  the first of: a cache hit (and, for a stateful chain,
                  a `StreamState` of its own) or a chain build, then
                  the chain's look-back seed from the replica. On the
                  flow of a stream's FIRST slice only; a stream that
                  opens on an empty log leaves a flow of this one phase,
- ``read``        `leader.read_records` + the shallow batch decode
                  (both stream loops of `spu/public_service.py`),
- ``wire_decode`` the native per-batch wire decode and its guards in
                  `tpu_stage`, net of stored-batch
                  decompression (that stays the ``glz_decode`` phase
                  histogram),
- ``stage``       column merge, chunk bounds, chunk buffer builds,
- ``dispatch``    wall of `dispatch_buffers`; the chunks' `BatchSpan`s
                  (each carrying this flow's id) are its children,
- ``finish``      wall of `tpu_fetch`: the blocking half of every
                  chunk (header sync, count-sized slice programs,
                  downloads, failure ladders). The stream loop
                  dispatches the NEXT slice after it,
- ``materialize`` the chunks' split-back: the join of the thunks that
                  ran on the fetch worker since ``finish``, and the last
                  chunk's (a single-chunk slice's only one), run here,
- ``encode``      per chunk the resume drop and one native pass from
                  the buffer's own form to the response slab (the
                  ``max_bytes`` cut inside it), then the `Batch` build,
- ``send``        `sink.send_response`,
- ``ack_wait``    until the consumer's ack reaches the pushed offset
                  (the consumer's own decode is inside it),
- ``interpret``   the per-record fallback pass, so a slice the fast
                  path declined is not a hole,

— one boolean, ``interleaved``: the stream loop dispatched the next
slice between this slice's ``finish`` and its ``materialize``, so the
device worked under this slice's host half (a stream's last slice has no
next one) —

and closes AFTER its ack wait (`end_flow` follows `_wait_for_ack` in
both stream loops): ``ack_wait`` belongs to the slice it waits for, so
``t0..t_end`` covers everything up to the consumer's ack. Completed flows
land in a bounded :class:`FlowRing` (capacity ``FLUVIO_SLICE_RING``)
and render as their own ``slice`` lane group in the Perfetto export,
connected to the batch spans they caused via Chrome-trace flow events
(``ph: s/t/f`` with a shared ``id`` — see telemetry/trace.py); a span
names its slice by ``flow_id``.

Cost contract: one object + a handful of clock reads per SLICE (never
per record, never per batch chunk); `PipelineTelemetry.begin_flow`
returns None when capture is off or ``FLUVIO_FLOW_TRACE=0``, and every
instrumentation site guards on that.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from fluvio_tpu.telemetry.spans import _BoundedRing

#: fixed slice-phase vocabulary (the registry's per-phase histograms
#: and the Prometheus ``slice_wait_seconds`` family key on it)
SLICE_PHASES = (
    "queue_wait", "batcher", "hold", "serve",
    "chain_acquire", "read", "wire_decode", "stage", "dispatch", "finish",
    "materialize", "encode", "send", "ack_wait", "interpret",
)


class SliceFlow:
    """One slice's causal walk through the serving pipeline.

    Not thread-safe; owned by the task driving the slice (ring
    insertion at `PipelineTelemetry.end_flow` is what synchronizes),
    exactly like `BatchSpan`.
    """

    #: lane-group key in the trace renderer (class attribute so the
    #: lane allocator treats flows as one track family)
    path = "slice"

    __slots__ = (
        "flow_id", "chain", "tenant", "t0", "t_end", "records", "phases",
        "decision", "holds", "cause", "sources", "batch_id", "interleaved",
        "_q_t0", "_b_t0",
    )

    def __init__(self, flow_id: int, chain: str = "", tenant: str = "") -> None:
        self.flow_id = flow_id
        self.chain = chain
        #: tenant label (topic-name prefix) — the soak scorer joins
        #: flow-ring records against the per-tenant counter families
        self.tenant = tenant
        self.t0 = time.perf_counter()
        self.t_end: Optional[float] = None
        self.records = 0
        #: wall-positioned phases: (name, start, seconds)
        self.phases: List[Tuple[str, float, float]] = []
        #: last admission outcome ("admit" or the shed reason)
        self.decision: Optional[str] = None
        self.holds = 0  # shed-then-retry cycles survived
        #: batcher flush cause + co-batched source count (coalesced
        #: flows only) — "which batch did this slice ride, and why"
        self.cause: Optional[str] = None
        self.sources = 0
        #: the ``flow_id`` the slice's batch spans carry: its own, or —
        #: when the batcher coalesced it behind another tenant's slice
        #: into ONE dispatch — the lead slice's (the renderer and the
        #: benchmark's readers join spans to flows on it)
        self.batch_id = flow_id
        #: the next slice went out to the device between this slice's
        #: ``finish`` and its ``materialize``
        self.interleaved = False
        self._q_t0: Optional[float] = None
        self._b_t0: Optional[float] = None

    # -- phase capture -------------------------------------------------------

    def add_phase(self, name: str, start: float, seconds: float) -> None:
        if seconds > 0.0:
            self.phases.append((name, start, seconds))

    def hold(self, seconds: float) -> None:
        """One shed-hold released: callers measure ``seconds`` against
        a clock read taken at the hold start, so now-seconds is it."""
        self.holds += 1
        self.add_phase("hold", time.perf_counter() - seconds, seconds)

    def note_queue(self) -> None:
        self._q_t0 = time.perf_counter()

    def end_queue(self) -> None:
        if self._q_t0 is not None:
            now = time.perf_counter()
            self.add_phase("queue_wait", self._q_t0, now - self._q_t0)
            self._q_t0 = None

    def note_batcher(self) -> None:
        self._b_t0 = time.perf_counter()

    def end_batcher(self, cause: str, sources: int) -> None:
        self.cause = cause
        self.sources = sources
        if self._b_t0 is not None:
            now = time.perf_counter()
            self.add_phase("batcher", self._b_t0, now - self._b_t0)
            self._b_t0 = None

    def close(self, records: int = 0) -> None:
        self.t_end = time.perf_counter()
        self.records = records

    # -- reads ---------------------------------------------------------------

    def phase_totals(self) -> Dict[str, float]:
        """{phase: total seconds} across this flow's recorded phases."""
        out: Dict[str, float] = {}
        for name, _start, s in self.phases:
            out[name] = out.get(name, 0.0) + s
        return out

    def serve_seconds(self) -> float:
        end = self.t_end if self.t_end is not None else time.perf_counter()
        return max(end - self.t0, 0.0)

    def to_dict(self) -> Dict:
        d: Dict = {
            "flow_id": self.flow_id,
            "records": self.records,
            "serve_ms": round(self.serve_seconds() * 1000, 3),
            "t0": round(self.t0, 6),
            "interleaved": self.interleaved,
        }
        if self.chain:
            d["chain"] = self.chain
        if self.tenant:
            d["tenant"] = self.tenant
        if self.decision:
            d["decision"] = self.decision
        if self.holds:
            d["holds"] = self.holds
        if self.cause:
            d["cause"] = self.cause
            d["sources"] = self.sources
        if self.t_end is not None:
            d["t_end"] = round(self.t_end, 6)
        totals = self.phase_totals()
        if totals:
            d["phases_ms"] = {
                k: round(v * 1000, 3) for k, v in totals.items()
            }
            # wall-positioned: readers and gap attribution need where a
            # phase sat, not only how long it was
            d["phases"] = [
                [name, round(start, 6), round(s, 6)]
                for name, start, s in self.phases
            ]
        return d


class FlowRing(_BoundedRing):
    """Bounded ring of completed `SliceFlow`s (same primitive as the
    span/event rings — one lock/slicing discipline for all three)."""

    def __init__(self, capacity: int = 512) -> None:
        super().__init__(capacity)
