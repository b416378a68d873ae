"""Window specifications for the device-resident windowed-state engine.

One `WindowSpec` fixes everything the jitted update kernel needs
statically: the window geometry (tumbling when ``slide_ms ==
window_ms``, sliding when it divides it), the combine monoid, whether
records carry a per-key segment id, the allowed lateness, and the two
device capacities (state-bank entries and per-batch emit rows). The
spec is hashable so each distinct geometry compiles exactly one XLA
program per shape bucket — the same discipline as the executor's
bucketed chain jits.
"""

from __future__ import annotations

from dataclasses import dataclass

from fluvio_tpu.analysis.envreg import env_bool, env_int

# composite segment id: id = key * KEY_STRIDE + window_index. The
# window index is win_start // slide_ms (always >= 0), so keys up to
# 2^31 and window indices up to 2^31 pack into one sortable int64 —
# one argsort orders (key, window) pairs without tuple comparators.
KEY_STRIDE = 1 << 31
# sentinel id for unused bank slots / invalid rows: larger than any
# real composite id, so empties sort to the tail and one compaction
# drops them
EMPTY_ID = 1 << 62

# combine-op neutral elements (host ints — creating jax arrays at
# import time would force backend init, same rule as kernels._AGG_OPS)
INT64_MIN = -(2**63)
INT64_MAX = 2**63 - 1
OP_NEUTRAL = {"add": 0, "max": INT64_MIN, "min": INT64_MAX}

# AggregateProgram kind -> combine monoid (the windowed-sum model's
# vocabulary; fluvio_tpu/models/windowed_aggregate.py)
KIND_TO_OP = {"sum_int": "add", "max_int": "max", "min_int": "min"}


class WindowCapacityError(RuntimeError):
    """Live (open) windows exceed the device bank capacity — raise
    FLUVIO_WINDOW_CAPACITY or close windows faster (smaller lateness).
    Loud at the seam by design: silently dropping an open window would
    corrupt every later exactness pin."""


@dataclass(frozen=True)
class WindowSpec:
    """Static geometry of one windowed-state stream."""

    window_ms: int
    slide_ms: int = 0  # 0 -> tumbling (slide == window)
    op: str = "add"
    keyed: bool = False
    lateness_ms: int = -1  # -1 -> FLUVIO_WINDOW_LATENESS_MS
    capacity: int = 0  # 0 -> FLUVIO_WINDOW_CAPACITY
    emit_capacity: int = 0  # 0 -> FLUVIO_WINDOW_EMIT
    delta_only: bool = True  # FLUVIO_WINDOW_DELTA resolves this
    # a keyed TABLE (`dsl.GroupProgram`) gives ``op`` as a tuple, one
    # monoid per accumulator lane (the bank's ``accs`` is then
    # int64[lanes, capacity]), and keeps no per-entry count
    counted: bool = True

    def __post_init__(self):
        if self.window_ms <= 0:
            raise ValueError("window_ms must be positive")
        slide = self.slide_ms or self.window_ms
        if slide <= 0 or self.window_ms % slide:
            raise ValueError(
                f"slide_ms ({slide}) must divide window_ms "
                f"({self.window_ms})"
            )
        for op in self.op if isinstance(self.op, tuple) else (self.op,):
            if op not in OP_NEUTRAL:
                raise ValueError(f"unknown combine op {op!r}")
        object.__setattr__(self, "slide_ms", slide)
        if self.lateness_ms < 0:
            object.__setattr__(
                self, "lateness_ms", int(env_int("FLUVIO_WINDOW_LATENESS_MS"))
            )
        if self.capacity <= 0:
            object.__setattr__(
                self, "capacity", int(env_int("FLUVIO_WINDOW_CAPACITY"))
            )
        if self.emit_capacity <= 0:
            object.__setattr__(
                self, "emit_capacity", int(env_int("FLUVIO_WINDOW_EMIT"))
            )

    @property
    def fanout(self) -> int:
        """Windows each record belongs to (1 for tumbling)."""
        return self.window_ms // self.slide_ms

    @property
    def tumbling(self) -> bool:
        return self.slide_ms == self.window_ms

    @property
    def neutral(self):
        """The monoid's neutral; a tuple of them for a tuple of lanes."""
        if isinstance(self.op, tuple):
            return tuple(OP_NEUTRAL[op] for op in self.op)
        return OP_NEUTRAL[self.op]

    @property
    def entry_bytes(self) -> int:
        """Device bytes of one bank entry: id, lanes, count (int64)."""
        lanes = len(self.op) if isinstance(self.op, tuple) else 1
        return 8 * (1 + lanes + self.counted)

    @property
    def mode(self) -> str:
        base = "tumbling" if self.tumbling else "sliding"
        return f"{base}+keyed" if self.keyed else base

    def win_start(self, win_idx: int) -> int:
        return win_idx * self.slide_ms

    def describe(self) -> str:
        return (
            f"window[{self.mode} w={self.window_ms} s={self.slide_ms} "
            f"op={self.op} K={self.capacity} E={self.emit_capacity}]"
        )

    @classmethod
    def from_params(cls, kind: str, window_ms, slide_ms=0, keyed=False):
        """Spec from the windowed-aggregate model's param vocabulary."""
        op = KIND_TO_OP.get(str(kind))
        if op is None:
            raise ValueError(f"unknown windowed kind {kind!r}")
        return cls(
            window_ms=int(window_ms),
            slide_ms=int(slide_ms or 0),
            op=op,
            keyed=bool(keyed),
            delta_only=delta_enabled(),
        )


def delta_enabled() -> bool:
    """The FLUVIO_WINDOW_DELTA gate: delta-only emission (the default)
    vs full-state emission every batch (the debugging escape hatch,
    and the preflight's ``win-full`` variant)."""
    return env_bool("FLUVIO_WINDOW_DELTA")
