"""The device a run is on, and the table of its published peaks."""

from __future__ import annotations

import json
from pathlib import Path

# what `jax.devices()[0].platform` must say. The command has no option
# that lets it measure without the chip; only the benchmark's own tests
# steer this constant (monkeypatch) to rehearse the loops on the CPU.
REQUIRED_PLATFORM = "tpu"


class NoDevice(SystemExit):
    """Wrong platform or too few chips: exit non-zero, print no result."""


def require_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != REQUIRED_PLATFORM:
        raise NoDevice(
            f"benchmark: jax found platform {d0.platform!r} ({d0.device_kind}), "
            f"not {REQUIRED_PLATFORM!r}; nothing is measured without the chip"
        )
    if len(devs) < chips:
        raise NoDevice(
            f"benchmark: the cell asks for {chips} chip(s), jax sees {len(devs)}"
        )
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend has no
    `memory_stats`, as the CPU's)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    with open(Path(__file__).with_name("peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in peaks.json"
        )
    return table[device_kind]


def chips_of(obs) -> int:
    """The chips a run's readings are over: the cell's, which `run.py`
    puts in every run's observations; 1 where an observation states none
    (one written by hand)."""
    return int(obs.get("chips", 1))


def hbm_peak_of_run(obs) -> float:
    """HBM bytes a second the cell's chips move at their published peak:
    the one-chip peak times the chips. A roofline share divides bytes
    summed over every chip by it and by `busy_s`, the mean chip's busy
    time, so that four chips that each move a quarter of the bytes in
    the same time read as one chip would."""
    return peaks_for(obs["device_kind"])["hbm_bytes_per_s"] * chips_of(obs)
