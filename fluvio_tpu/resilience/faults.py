"""Fault-injection harness: a process-global registry of named fault
points threaded through the pipeline's failure seams.

Every seam the recovery layer guards is also a place chaos can strike on
demand — the SAME code path handles a real XLA transfer error and an
armed `InjectedFault`, so the chaos suite exercises exactly the
production recovery logic:

==============  ==========================================================
point           seam
==============  ==========================================================
``stage``       host-side columnar staging (flat build / slice decode)
``h2d``         staging the flat onto the device link
``dispatch``    the jitted chain call (trace/compile/enqueue)
``device``      first blocking sync on device results (header fetch)
``fetch``       the D2H download of result columns
``glz_encode``  the on-device result-encode path (down-link ladder armed)
``spill_rerun`` the interpreter re-run of a spilled batch
``socket_accept``  the SPU monitoring socket's per-client handler
==============  ==========================================================

Arming — programmatic::

    from fluvio_tpu.resilience import faults
    faults.inject("device", first=2)            # fire on the first 2 hits
    faults.inject("fetch", every=3)             # every 3rd hit
    faults.inject("h2d", prob=0.01, seed=7)     # 1% of hits, deterministic
    faults.inject("dispatch", first=1, exc=faults.InjectedFault(
        "dispatch", transient=False))           # deterministic-class fault

— or via the environment, before the process starts::

    FLUVIO_FAULTS="device:first=2;fetch:every=3,exc=deterministic"

Grammar: ``;``-separated entries, each ``point:field=value[,field=value]``
with exactly one trigger field (``every=N`` | ``first=K`` | ``prob=P``)
and optional ``exc=transient|deterministic`` (default transient) and
``seed=N`` (for ``prob``).

Hot-path contract: `maybe_fire(point)` is the seam call. With nothing
armed it is one module-global ``None`` check — the overhead gate in
``tests/test_telemetry_overhead.py`` pins it under 1% rps.
"""

from __future__ import annotations

import logging
import random
from typing import Dict, Optional

from fluvio_tpu.analysis.envreg import env_raw
from fluvio_tpu.analysis.lockwatch import make_lock

logger = logging.getLogger(__name__)

FAULT_POINTS = (
    "stage",
    "h2d",
    "dispatch",
    "device",
    "fetch",
    "glz_encode",
    "spill_rerun",
    "socket_accept",
)


class InjectedFault(RuntimeError):
    """The exception an armed fault point raises.

    ``transient`` drives the recovery classifier: transient faults are
    retried with backoff, deterministic ones go straight to the
    interpreter spill (and, failing that too, the quarantine).
    """

    def __init__(self, point: str, transient: bool = True, message: str = ""):
        super().__init__(
            message or f"injected fault at {point!r} "
            f"({'transient' if transient else 'deterministic'})"
        )
        self.point = point
        self.transient = transient


class FaultRule:
    """One armed fault point: trigger mode + exception template."""

    def __init__(
        self,
        point: str,
        every: Optional[int] = None,
        first: Optional[int] = None,
        prob: Optional[float] = None,
        exc=None,
        seed: Optional[int] = None,
    ):
        modes = [m for m in (every, first, prob) if m is not None]
        if len(modes) != 1:
            raise ValueError(
                f"fault point {point!r} needs exactly one of every/first/prob"
            )
        if every is not None and every < 1:
            raise ValueError("every must be >= 1")
        if first is not None and first < 1:
            raise ValueError("first must be >= 1")
        if prob is not None and not (0.0 <= prob <= 1.0):
            raise ValueError("prob must be in [0, 1]")
        self.point = point
        self.every = every
        self.first = first
        self.prob = prob
        self.exc = exc
        self.hits = 0
        self.fired = 0
        self._rng = random.Random(seed if seed is not None else 0xF1A7)

    def should_fire(self) -> bool:
        self.hits += 1
        if self.every is not None:
            return self.hits % self.every == 0
        if self.first is not None:
            return self.hits <= self.first
        return self._rng.random() < self.prob

    def make_exc(self) -> BaseException:
        if self.exc is None:
            return InjectedFault(self.point)
        if isinstance(self.exc, BaseException):
            # the armed instance is a TEMPLATE: raising the same object
            # repeatedly would mutate its __traceback__/__context__
            # across fires (garbled chains, cross-thread races) — build
            # a fresh copy per fire
            e = self.exc
            if isinstance(e, InjectedFault):
                return InjectedFault(e.point, transient=e.transient,
                                     message=str(e))
            try:
                return type(e)(*e.args)
            except Exception:  # pragma: no cover — exotic __init__
                return e
        if isinstance(self.exc, type) and issubclass(self.exc, BaseException):
            return self.exc(f"injected fault at {self.point!r}")
        if self.exc == "deterministic":
            return InjectedFault(self.point, transient=False)
        return InjectedFault(self.point)


class FaultRegistry:
    """Process-global map of armed fault points (thread-safe arming;
    firing reads a snapshot dict, so seams never take the lock)."""

    def __init__(self) -> None:
        self._lock = make_lock("faults.registry")
        self._rules: Dict[str, FaultRule] = {}

    @property
    def armed(self) -> bool:
        return bool(self._rules)

    def inject(
        self,
        point: str,
        every: Optional[int] = None,
        first: Optional[int] = None,
        prob: Optional[float] = None,
        exc=None,
        seed: Optional[int] = None,
    ) -> FaultRule:
        if point not in FAULT_POINTS:
            raise ValueError(
                f"unknown fault point {point!r} (one of {FAULT_POINTS})"
            )
        rule = FaultRule(point, every=every, first=first, prob=prob, exc=exc,
                         seed=seed)
        with self._lock:
            rules = dict(self._rules)
            rules[point] = rule
            self._rules = rules
        _refresh_armed()
        return rule

    def clear(self, point: Optional[str] = None) -> None:
        with self._lock:
            if point is None:
                self._rules = {}
            else:
                rules = dict(self._rules)
                rules.pop(point, None)
                self._rules = rules
        _refresh_armed()

    def rule(self, point: str) -> Optional[FaultRule]:
        return self._rules.get(point)

    def fire(self, point: str) -> None:
        rule = self._rules.get(point)
        if rule is not None and rule.should_fire():
            rule.fired += 1
            raise rule.make_exc()

    # -- env spec -----------------------------------------------------------

    def load_env_spec(self, spec: str) -> None:
        """Arm from a ``FLUVIO_FAULTS`` spec string (see module doc).

        All-or-nothing: every entry parses before ANY arms, so a
        malformed spec cannot leave a prefix of its faults live while
        the startup log claims the process runs un-armed."""
        parsed = []
        for entry in spec.split(";"):
            entry = entry.strip()
            if not entry:
                continue
            point, _, fields = entry.partition(":")
            point = point.strip()
            kwargs: Dict = {}
            for fld in fields.split(","):
                fld = fld.strip()
                if not fld:
                    continue
                key, _, val = fld.partition("=")
                key = key.strip()
                val = val.strip()
                if key == "every":
                    kwargs["every"] = int(val)
                elif key == "first":
                    kwargs["first"] = int(val)
                elif key == "prob":
                    kwargs["prob"] = float(val)
                elif key == "seed":
                    kwargs["seed"] = int(val)
                elif key == "exc":
                    if val not in ("transient", "deterministic"):
                        raise ValueError(
                            f"FLUVIO_FAULTS exc must be transient|deterministic,"
                            f" got {val!r}"
                        )
                    kwargs["exc"] = val
                else:
                    raise ValueError(f"unknown FLUVIO_FAULTS field {key!r}")
            if point not in FAULT_POINTS:
                raise ValueError(
                    f"unknown fault point {point!r} (one of {FAULT_POINTS})"
                )
            FaultRule(point, **kwargs)  # validate trigger fields now
            parsed.append((point, kwargs))
        for point, kwargs in parsed:
            self.inject(point, **kwargs)


FAULTS = FaultRegistry()

# seam fast path: None when nothing is armed, so `maybe_fire` costs one
# global load + is-None test per seam on the happy path
_ARMED: Optional[FaultRegistry] = None


def _refresh_armed() -> None:
    global _ARMED
    _ARMED = FAULTS if FAULTS.armed else None


def maybe_fire(point: str) -> None:
    """The seam call: raise the armed exception when ``point`` triggers."""
    if _ARMED is not None:
        _ARMED.fire(point)


def _load_from_env() -> None:
    spec = env_raw("FLUVIO_FAULTS") or ""
    if not spec:
        return
    try:
        FAULTS.load_env_spec(spec)
        logger.warning("FLUVIO_FAULTS armed: %s", spec)
    except ValueError as e:
        # a malformed chaos spec must never take a production broker
        # down — log loudly and run un-armed
        logger.error("ignoring malformed FLUVIO_FAULTS=%r: %s", spec, e)


_load_from_env()
