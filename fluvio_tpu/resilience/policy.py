"""Recovery policy layer: fault classification, bounded retry with
exponential backoff + jitter, and the per-chain circuit breaker.

The classifier splits failures into two classes:

- **transient** — device/link/runtime errors that a clean re-run can
  plausibly clear (XLA RESOURCE_EXHAUSTED/INTERNAL, transfer failures,
  OS-level connection errors, `InjectedFault(transient=True)`). These
  are retried under `RetryPolicy` with the aggregate carry snapshot
  restored before every attempt.
- **deterministic** — anything else (lowering bugs, malformed data,
  `InjectedFault(transient=False)`). Retrying cannot help; the batch
  goes straight to the interpreter spill, and a batch that fails there
  too is quarantined (see `deadletter`).

The circuit breaker keeps a flapping device from degrading a stream one
spill at a time forever-after: M fused-path failures inside a sliding
window trip the chain to the interpreter path outright; after a cooldown
it half-opens and probe batches run fused again — P consecutive probe
passes re-promote the chain, one probe failure re-opens it.

Env knobs (all read at policy construction):

=============================  =======  ==================================
``FLUVIO_RETRY_MAX``           ``2``    retries after the first attempt
``FLUVIO_RETRY_BASE_MS``       ``2``    first backoff delay
``FLUVIO_RETRY_CAP_MS``        ``200``  backoff ceiling
``FLUVIO_RETRY_JITTER``        ``0.25`` fraction of the delay randomized
``FLUVIO_BREAKER_THRESHOLD``   ``5``    failures in window to trip open
``FLUVIO_BREAKER_WINDOW_S``    ``30``   sliding failure window
``FLUVIO_BREAKER_COOLDOWN_S``  ``5``    open -> half-open delay
``FLUVIO_BREAKER_PROBES``      ``2``    half-open passes to re-close
=============================  =======  ==================================
"""

from __future__ import annotations

import random
import time
from collections import deque
from typing import Callable, Optional

from fluvio_tpu.analysis.envreg import env_float, env_int
from fluvio_tpu.resilience.faults import InjectedFault

TRANSIENT = "transient"
DETERMINISTIC = "deterministic"

# substrings of XLA/runtime error text that mark a device-side failure
# worth retrying (the status-code vocabulary of absl::Status as jaxlib
# renders it, plus the transfer-manager phrasings)
_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "ABORTED",
    "INTERNAL",
    "out of memory",
    "transfer",
    "failed to enqueue",
)


# substrings that mark an XLA/Mosaic runtime-error as raised by the
# COMPILER (lowering, legalization, compile-time memory planning), not
# by an execution on the device
_PROGRAM_FAULT_MARKERS = ("mosaic", "compil", "lowering", "legaliz")


def is_program_fault(exc: BaseException) -> bool:
    """True when ``exc`` is what only tracing/lowering/compiling raises:
    the program is wrong for this backend, and no re-run, quieter rung
    or interpreter pass makes it right. Program faults propagate out of
    every heal ladder and the engine's interpreter demotion — the run
    stops with the compiler's own error. Device weather (execution-time
    runtime errors, decode mismatches, every `InjectedFault`) is not a
    program fault and heals exactly as before."""
    if isinstance(exc, InjectedFault):
        return False
    if isinstance(exc, (NotImplementedError, TypeError)):
        # includes the jax tracer/type errors (JAXTypeError subclasses)
        return True
    cls = type(exc)
    if cls.__module__.startswith("jax") and (
        "Lowering" in cls.__name__ or "Tracer" in cls.__name__
    ):
        return True
    if cls.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        msg = str(exc).lower()
        return any(m in msg for m in _PROGRAM_FAULT_MARKERS)
    return False


def classify(exc: BaseException) -> str:
    """``transient`` | ``deterministic`` for a fused-path failure."""
    if isinstance(exc, InjectedFault):
        return TRANSIENT if exc.transient else DETERMINISTIC
    if is_program_fault(exc):
        return DETERMINISTIC
    if isinstance(exc, (ConnectionError, TimeoutError, BrokenPipeError)):
        return TRANSIENT
    name = type(exc).__name__
    if name in ("XlaRuntimeError", "JaxRuntimeError"):
        msg = str(exc)
        # a trace/lowering error re-raised as runtime is deterministic;
        # the status-code vocabulary separates them
        if any(m in msg for m in _TRANSIENT_MARKERS):
            return TRANSIENT
        return DETERMINISTIC
    if isinstance(exc, OSError):
        return TRANSIENT
    if isinstance(exc, RuntimeError) and any(
        m in str(exc) for m in _TRANSIENT_MARKERS
    ):
        return TRANSIENT
    return DETERMINISTIC


class RetryPolicy:
    """Bounded retry with exponential backoff + jitter."""

    def __init__(
        self,
        max_retries: Optional[int] = None,
        base_ms: Optional[float] = None,
        cap_ms: Optional[float] = None,
        jitter: Optional[float] = None,
    ):
        self.max_retries = (
            max_retries
            if max_retries is not None
            else int(env_int("FLUVIO_RETRY_MAX"))
        )
        self.base_ms = (
            base_ms if base_ms is not None
            else float(env_float("FLUVIO_RETRY_BASE_MS"))
        )
        self.cap_ms = (
            cap_ms if cap_ms is not None
            else float(env_float("FLUVIO_RETRY_CAP_MS"))
        )
        self.jitter = (
            jitter if jitter is not None
            else float(env_float("FLUVIO_RETRY_JITTER"))
        )
        self._rng = random.Random()

    def should_retry(self, exc: BaseException, attempt: int) -> bool:
        """``attempt`` counts retries already taken (0 before the first)."""
        return attempt < self.max_retries and classify(exc) == TRANSIENT

    def backoff_s(self, attempt: int) -> float:
        d = min(self.cap_ms, self.base_ms * (2.0 ** attempt))
        if self.jitter:
            d *= 1.0 - self.jitter * self._rng.random()
        return d / 1000.0

    def sleep(self, attempt: int) -> None:
        time.sleep(self.backoff_s(attempt))


CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

_BREAKER_SEQ = [0]


class CircuitBreaker:
    """Per-chain fused-path circuit breaker.

    States: ``closed`` (fused path runs) -> ``open`` (every batch routes
    to the interpreter, no fused attempt) -> ``half_open`` (probe
    batches run fused) -> ``closed`` again after P probe passes, or back
    to ``open`` on a probe failure. Single-threaded per chain (chains
    process one slab at a time), so no lock.

    ``clock`` is injectable for tests; transitions report to the
    telemetry registry under this breaker's ``name``.
    """

    def __init__(
        self,
        threshold: Optional[int] = None,
        window_s: Optional[float] = None,
        cooldown_s: Optional[float] = None,
        probes: Optional[int] = None,
        name: Optional[str] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.threshold = (
            threshold if threshold is not None
            else int(env_int("FLUVIO_BREAKER_THRESHOLD"))
        )
        self.window_s = (
            window_s if window_s is not None
            else float(env_float("FLUVIO_BREAKER_WINDOW_S"))
        )
        self.cooldown_s = (
            cooldown_s if cooldown_s is not None
            else float(env_float("FLUVIO_BREAKER_COOLDOWN_S"))
        )
        self.probes = (
            probes if probes is not None
            else int(env_int("FLUVIO_BREAKER_PROBES"))
        )
        if name is None:
            _BREAKER_SEQ[0] += 1
            name = f"chain-{_BREAKER_SEQ[0]}"
        self.name = name
        self.clock = clock
        self.state = CLOSED
        self._failures: deque = deque()
        self._opened_at = 0.0
        self._probe_passes = 0
        self._report(CLOSED, transition=False)

    def _report(self, state: str, transition: bool = True) -> None:
        from fluvio_tpu.telemetry import TELEMETRY

        TELEMETRY.record_breaker(self.name, state, transition=transition)

    def _transition(self, state: str) -> None:
        self.state = state
        self._report(state)

    def allow_fused(self) -> bool:
        """Gate one batch's fused attempt; called before every dispatch."""
        if self.state == OPEN:
            if self.clock() - self._opened_at >= self.cooldown_s:
                self._probe_passes = 0
                self._transition(HALF_OPEN)
                return True
            return False
        return True

    def record_success(self) -> None:
        if self.state == HALF_OPEN:
            self._probe_passes += 1
            if self._probe_passes >= self.probes:
                self._failures.clear()
                self._transition(CLOSED)
        elif self._failures:
            # a closed breaker under mixed traffic: expire stale failures
            # so intermittent noise never accumulates to a trip
            self._expire()

    def record_failure(self) -> None:
        now = self.clock()
        if self.state == HALF_OPEN:
            self._opened_at = now
            self._transition(OPEN)
            return
        if self.state == OPEN:  # pragma: no cover — open short-circuits
            return
        self._failures.append(now)
        self._expire(now)
        if len(self._failures) >= self.threshold:
            self._opened_at = now
            self._failures.clear()
            self._transition(OPEN)

    def _expire(self, now: Optional[float] = None) -> None:
        now = self.clock() if now is None else now
        while self._failures and now - self._failures[0] > self.window_s:
            self._failures.popleft()
