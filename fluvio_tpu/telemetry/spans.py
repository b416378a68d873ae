"""Per-batch pipeline spans with fixed phase labels, in a bounded ring.

A `BatchSpan` is one batch's walk through the pipeline. Phases are a
FIXED vocabulary (indexes into one flat float list — no per-phase dict
allocation on the hot path):

- ``stage``        host staging: ragged flat build, column merge/slice
- ``h2d``          host-side link staging/enqueue (device array builds;
                   the physical transfer overlaps ``device``)
- ``dispatch``     jit call: trace lookup + async dispatch enqueue
- ``device``       dispatch-complete -> first result sync satisfied:
                   the batch's time QUEUED on the device plus its
                   compute. Batches in flight overlap, so across a
                   pipelined stream these intervals cover one another
                   and sum to more than the wall; it says how long a
                   batch was out, not what any thread did (no thread
                   does it, so it is the one phase with no
                   ``fluvio/<phase>`` annotation — the device plane of
                   the same profile shows it)
- ``wait``         the calling thread BLOCKED in the first result sync
                   of this batch (the header fetch in `_fetch_inner`):
                   exclusive on its thread, so over a stream it sums to
                   at most the wall — the number to read for "how long
                   did the host wait for the device"
- ``fetch``        host-side result materialization after download.
                   Computed by subtraction (finish wall - wait - d2h),
                   so its annotation ``fluvio/fetch`` is the ENCLOSING
                   finish interval it is cut from (``fluvio/wait`` and
                   ``fluvio/d2h`` nest inside it), plus the deferred
                   split-back where that runs on the fetch worker
- ``d2h``          blocking device->host copy time
- ``glz_decode``   host decompression of stored-batch compression on
                   the staging side
- ``spill``        interpreter re-run after a fused-path spill/decline

Overhead contract: begin/end is two monotonic clock reads; each phase
adds one clock pair. No per-record work anywhere.

One clock with the device trace: `timed(target, phase)` is the ONE
timing path of a phase — a `time.perf_counter` pair that books the
phase into its span (or slice flow) and, at the same pair, a
`jax.profiler.TraceAnnotation` named ``fluvio/<phase>`` with the flow
id as an argument. Outside a profiler session the annotation is an
atomic-flag check; inside one the profile's host plane carries the
program's phases on the trace's own clock beside the ``XLA Ops`` line.
"""

from __future__ import annotations

import contextlib
import functools
import sys
import time
from typing import Dict, List, Optional

from fluvio_tpu.analysis.lockwatch import make_lock

PHASES = (
    "stage",
    "h2d",
    "dispatch",
    "device",
    "fetch",
    "d2h",
    "glz_decode",
    "spill",
    "wait",
)
_PHASE_INDEX = {name: i for i, name in enumerate(PHASES)}

#: device-side scope vocabulary: the `jax.named_scope`s the chain
#: program opens at its stage boundaries (executor `_chain_fn*`, the
#: sharded `_local_step*`), so a profiler trace's device operations
#: carry the stage that emitted them. ``stage`` is a prefix: each
#: `stage.apply` is `stage<i>.<kind>` (`stage_scope`). Scopes nest
#: (``compact/pack``); the innermost one names the operation.
DEVICE_SCOPES = (
    "link_decode",  # reader-only word: no program opens it since the
                    # flat ships raw (PR 33); kept because the benchmark's
                    # recorded fixture (tests/benchmark) is reduced
                    # through this tuple
    "repad",        # ragged flat -> padded matrix (block fetch, row
                    # shift, byte unpack), derived meta columns
    "stage",        # stage<i>.<kind>, inner .aggregate_scan/.window_*/.group_*
    "compact",      # survivor compaction, mask, header
    "pack",         # byte-mode payload / descriptor stream packing
    "link_encode",  # down-link glz encode of the packed stream
)


#: version of the vocabulary above, carried in the NAME of every chain
#: program (`scoped_program`). jax's persistent compile cache keys a
#: program by its module with debug info stripped, and a scope is debug
#: info: an executable compiled before a scope existed (an older
#: checkout sharing the cache directory) is otherwise loaded as "the
#: same program" and profiles under the OLD names — or none. Bump it
#: whenever a scope is added, renamed or moved.
DEVICE_SCOPES_TAG = "s2"


def scoped_program(fn):
    """``fn`` under a name that ends in `DEVICE_SCOPES_TAG`, for
    `jax.jit`: the module name is part of the compile-cache key."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = (
        f"{fn.__name__}_{DEVICE_SCOPES_TAG}"
    )
    return program


def stage_scope(index: int, kind: str) -> str:
    """The device scope of one chain stage: position + the stage's
    kind (`filter`, `map`, `array_map`, `aggregate`, or a striped op
    kind) — never a module name or a parameter (no user data). An
    aggregate opens `stage<i>.aggregate_scan` inside its own scope
    around the carry chain and the scan, so its contribution (the
    field extraction and parse) keeps `stage<i>.aggregate`; the inner
    name has the stage form because a reader names an operation by the
    innermost path component that is one of these scopes. A window
    stage (`stage<i>.window`: field spans, parses, window assignment)
    opens `stage<i>.window_merge` (concat with the bank, one sort that
    carries the columns, prefix sums, compaction, close, new bank) and
    `stage<i>.window_top` (the per-window maximum) the same way, and a
    group stage (`stage<i>.group`: field spans, parses, the key)
    `stage<i>.group_merge` (concat with the table, the stable sort, the
    segmented scans, the new table) and `stage<i>.group_emit` (rows
    back in offset order, the output columns)."""
    return f"stage{index}.{kind}"


_TRACE_ANNOTATION = None


def annotate(target, phase: str):
    """A `jax.profiler.TraceAnnotation` ``fluvio/<phase>`` carrying
    ``target``'s flow id and NO clock of its own: for a phase that is
    booked elsewhere (``fetch``, computed by subtraction). A null
    context when ``target`` is None (capture off) or in a process that
    never imported jax (no profiler session can exist there, and
    telemetry must not be what imports it)."""
    global _TRACE_ANNOTATION
    if target is None:
        return contextlib.nullcontext()
    if _TRACE_ANNOTATION is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return contextlib.nullcontext()
        _TRACE_ANNOTATION = jax.profiler.TraceAnnotation
    return _TRACE_ANNOTATION(f"fluvio/{phase}", flow_id=target.flow_id)


class _TimedPhase:
    """One phase's clock pair: annotation and booking share it."""

    __slots__ = ("target", "name", "t0", "less", "_ann")

    def __init__(self, target, name: str) -> None:
        self.target = target
        self.name = name
        #: seconds inside the pair that belong to ANOTHER phase and are
        #: cut from this one at exit (stored-batch decompression inside
        #: the wire decode); the annotation stays the enclosing interval
        self.less = 0.0

    def __enter__(self) -> "_TimedPhase":
        self._ann = annotate(self.target, self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def rename(self, name: str) -> None:
        """Book under another phase than the one entered, where only
        the work itself decides which it was: the annotation keeps the
        name it was entered with and gains ``phase=<name>``. No program
        site needs it since the staged flat has one link form (PR 33);
        `tests/benchmark/test_tracing_readers.py` holds its contract."""
        self.name = name
        set_metadata = getattr(self._ann, "set_metadata", None)
        if set_metadata is not None:
            set_metadata(phase=name)

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self.t0 - self.less
        self._ann.__exit__(*exc)
        self.target.add_phase(self.name, self.t0, seconds)
        return False


class _NullPhase:
    """`timed(None, ...)`: capture is off, nothing is read or booked."""

    __slots__ = ()
    less = property(lambda self: 0.0, lambda self, seconds: None)

    def __enter__(self) -> "_NullPhase":
        return self

    def rename(self, name: str) -> None:
        pass

    def __exit__(self, *exc) -> bool:
        return False


_NULL_PHASE = _NullPhase()


def timed(target, phase: str):
    """Context manager over one phase of ``target`` (a `BatchSpan`, a
    `SliceFlow`, or None when capture is off): one `perf_counter` pair,
    booked with `target.add_phase(phase, start, seconds)` and entered as
    the ``fluvio/<phase>`` trace annotation at the same pair. A phase is
    booked when the block raises too — the time was spent."""
    if target is None:
        return _NULL_PHASE
    return _TimedPhase(target, phase)


class BatchSpan:
    """One batch's phase timings. Not thread-safe; owned by the thread
    driving the batch (ring insertion at `end` is what synchronizes)."""

    __slots__ = (
        "t0", "t_end", "phase_s", "phase_t0", "records", "path", "chain",
        "dispatch_end", "ready_t", "flow_id",
    )

    def __init__(
        self, path: str = "fused", chain: str = "", flow_id: int = 0
    ) -> None:
        self.t0 = time.perf_counter()
        self.t_end: Optional[float] = None
        self.phase_s: List[float] = [0.0] * len(PHASES)
        # first-add start time per phase (0.0 = never recorded): the
        # trace renderer places each phase's duration event at its real
        # wall position instead of reconstructing a serial layout
        self.phase_t0: List[float] = [0.0] * len(PHASES)
        self.records = 0
        self.path = path
        # chain identity (the executor's compact chain signature, e.g.
        # "filter+map"): keys the per-chain latency family the SLO
        # engine's windowed verdicts evaluate; "" = unattributed
        self.chain = chain
        # the slice flow that caused this dispatch (`SliceFlow.flow_id`;
        # 0 = none): the trace renderer and the benchmark's readers join
        # chunks to their slice by it
        self.flow_id = flow_id
        # set by mark_dispatched; the device phase measures from here
        self.dispatch_end: Optional[float] = None
        # when the first blocking result sync returned (finish-side
        # "fetch" accounting subtracts the wait up to this point)
        self.ready_t: Optional[float] = None

    def add(
        self, phase: str, seconds: float, start: Optional[float] = None
    ) -> None:
        if seconds > 0.0:
            i = _PHASE_INDEX[phase]
            if self.phase_s[i] == 0.0:
                # without a start, callers measure `seconds` against a
                # clock read taken just before this call, so now-seconds
                # is the start
                self.phase_t0[i] = (
                    start if start is not None
                    else time.perf_counter() - seconds
                )
            self.phase_s[i] += seconds

    # `timed(span, phase)` books through this name on spans and flows
    def add_phase(self, name: str, start: float, seconds: float) -> None:
        self.add(name, seconds, start)

    def mark_dispatched(self) -> None:
        self.dispatch_end = time.perf_counter()

    def mark_device_ready(self) -> None:
        """First blocking sync on this batch's results returned: the
        device span is dispatch-end -> now (monotone clock pair)."""
        now = time.perf_counter()
        if self.dispatch_end is not None:
            self.add("device", now - self.dispatch_end)
            self.dispatch_end = None  # a re-dispatch restarts the pair
        self.ready_t = now

    def phase(self, name: str) -> float:
        return self.phase_s[_PHASE_INDEX[name]]

    def to_dict(self) -> Dict:
        d = {
            "path": self.path,
            "records": self.records,
        }
        if self.chain:
            d["chain"] = self.chain
        if self.flow_id:
            d["flow_id"] = self.flow_id
        d |= {
            "e2e_ms": round(
                ((self.t_end if self.t_end is not None else time.perf_counter())
                 - self.t0) * 1000, 3,
            ),
            "t0": round(self.t0, 6),
        }
        if self.t_end is not None:
            d["t_end"] = round(self.t_end, 6)
        d["phases_ms"] = {
            name: round(s * 1000, 3)
            for name, s in zip(PHASES, self.phase_s)
            if s > 0.0
        }
        return d


class _BoundedRing:
    """Bounded ring: O(1) push, most recent ``capacity`` items retained
    in completion order, overwrites counted (``dropped``). One
    implementation for the span and instant-event rings — a fix to the
    slicing or lock discipline cannot land in one and miss the other."""

    def __init__(self, capacity: int) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._slots: List = [None] * capacity
        self._next = 0  # total pushes (monotone)
        self._lock = make_lock("telemetry.ring")

    def push(self, item) -> None:
        with self._lock:
            self._slots[self._next % self.capacity] = item
            self._next += 1

    def __len__(self) -> int:
        with self._lock:
            return min(self._next, self.capacity)

    @property
    def total(self) -> int:
        """Items ever pushed (wrapped ones included)."""
        with self._lock:
            return self._next

    @property
    def dropped(self) -> int:
        """Items the ring has overwritten (total − retained): nonzero
        means a dump/trace of this ring is missing history — detectable
        instead of silently lossy."""
        with self._lock:
            return max(self._next - self.capacity, 0)

    def stats(self) -> "tuple":
        """(total, retained, dropped) under ONE lock acquisition — the
        scrape-visible invariant total == retained + dropped can tear
        across separate property reads when a push lands between them."""
        with self._lock:
            total = self._next
            retained = min(total, self.capacity)
            return total, retained, total - retained

    def recent(self, limit: Optional[int] = None) -> List:
        """Most-recent-last list of retained items."""
        with self._lock:
            n = min(self._next, self.capacity)
            start = self._next - n
            items = [
                self._slots[i % self.capacity] for i in range(start, self._next)
            ]
        if limit is not None and limit < len(items):
            items = items[-limit:]
        return items


class SpanRing(_BoundedRing):
    """Bounded ring of completed `BatchSpan`s."""

    def __init__(self, capacity: int = 256) -> None:
        super().__init__(capacity)


class InstantEvent:
    """One point-in-time pipeline event (heal, spill, retry, breaker
    transition, compile, quarantine) for the flight recorder: the trace
    renders these as instant markers over the batch tracks."""

    __slots__ = ("t", "kind", "detail")

    def __init__(self, kind: str, detail: str = "") -> None:
        self.t = time.perf_counter()
        self.kind = kind
        self.detail = detail

    def to_dict(self) -> Dict:
        d = {"t": round(self.t, 6), "kind": self.kind}
        if self.detail:
            d["detail"] = self.detail
        return d


class EventRing(_BoundedRing):
    """Bounded ring of `InstantEvent`s."""

    def __init__(self, capacity: int = 512) -> None:
        super().__init__(capacity)
