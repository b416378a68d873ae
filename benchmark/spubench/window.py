"""What a run observed: program counters around the window, and the truth
verdict (`correct` part c) read from them.

The benchmark takes from the program only its spans and counters; every
reduction from them to a number is here or in a `layer_metrics/` reader.
"""

from __future__ import annotations

import time

# the executor's phase spans up to the dispatch: host work before the
# device has the batch
UP_PHASES = ("stage", "h2d", "dispatch")


def snapshot(broker) -> dict:
    """Monotone program counters, for a delta around the window."""
    from fluvio_tpu.telemetry import TELEMETRY

    counters = TELEMETRY.snapshot()["counters"]
    return {
        "t": time.perf_counter(),
        "slices": broker.slice_counts(),
        "phases": TELEMETRY.phase_totals(),
        "paths": TELEMETRY.path_records(),
        "compiles": TELEMETRY.compile_totals(),
        "heals": counters["heals"],
        "stripe_fallbacks": counters["stripe_fallbacks"],
        "spills": sum(counters["spills"].values()),
        "retries": sum(counters["retries"].values()),
        "quarantined": counters["quarantined"],
        "interp_records": counters["interp_instance"]["records"],
        # a slice the partition gate failed to place serves unplaced
        # (`spu/smart_chain.py:_enter_partition_scope`) and books this
        "placement_errors": counters["declines"].get(
            "partition-placement-error", 0),
    }


def delta(c0: dict, c1: dict) -> dict:
    """Window delta of two snapshots, flattened for the readers."""
    phases = {
        p: c1["phases"][p][1] - c0["phases"].get(p, (0, 0.0))[1]
        for p in c1["phases"]
    }
    return {
        "fastpath_slices": c1["slices"]["fastpath_slices"]
        - c0["slices"]["fastpath_slices"],
        "fallback_slices": c1["slices"]["fallback_slices"]
        - c0["slices"]["fallback_slices"],
        "phase_s": phases,
        "fused_records": sum(
            c1["paths"].get(p, 0) - c0["paths"].get(p, 0)
            for p in ("fused", "striped")
        ),
        "interpreter_records": c1["paths"].get("interpreter", 0)
        - c0["paths"].get("interpreter", 0),
        "compiles": c1["compiles"]["compiles"] - c0["compiles"]["compiles"],
        "compile_s": c1["compiles"]["seconds"] - c0["compiles"]["seconds"],
    }


# counters of the program that may not move in a run
STILL_COUNTERS = ("heals", "stripe_fallbacks", "spills", "retries",
                  "quarantined", "interp_records", "placement_errors")


def truth_faults(c_start: dict, c_end: dict) -> list:
    """`correct` part (c) over the WHOLE run (set-up and window): the fast
    path served every slice and nothing healed, spilled, interpreted or
    failed its placement."""
    faults = []
    s0, s1 = c_start["slices"], c_end["slices"]
    if s1["fastpath_slices"] - s0["fastpath_slices"] <= 0:
        faults.append("no slice took the fast path")
    if s1["fallback_slices"] != s0["fallback_slices"]:
        faults.append(f"slices fell back: {s1.get('fallback_reasons')}")
    for key in STILL_COUNTERS:
        if c_end[key] != c_start[key]:
            faults.append(f"{key} moved by {c_end[key] - c_start[key]}")
    if c_end["paths"].get("interpreter", 0) != c_start["paths"].get(
            "interpreter", 0):
        faults.append("records ran on the interpreter path")
    return faults


def idle_chips(reduced) -> list:
    """The cell's chips on which no operation ran in the traced span."""
    return [i for i, b in enumerate(reduced["busy_s_by_device"]) if b <= 0]


def placement_faults(broker, reduced=None) -> list:
    """`correct` for the cell's chips: a configuration that states a
    placement was served with the program's partition gate armed (the
    gate's own resolution logs and stays unarmed where it cannot build),
    and in a traced run every chip of the cell ran an operation."""
    faults = []
    if broker.placement is not None and broker.gate is None:
        faults.append("the configuration states a placement and the "
                      "program's partition gate is not armed")
    if reduced is not None and idle_chips(reduced):
        faults.append(f"no operation ran on chip(s) {idle_chips(reduced)} "
                      "of the cell in the traced span")
    return faults


def compared(obs: dict, c_start: dict, c_end: dict, broker=None,
             reduced=None) -> dict:
    """Each number `correct` rests on beside its limit, for the result
    line and the last lines of standard error. The comparison with the
    reference is exact, so every limit is 0 but the floors of one
    fast-path slice and, for a placed configuration, of an armed
    partition gate (``at_least``)."""
    s0, s1 = c_start["slices"], c_end["slices"]
    out = {
        # what the mode found against the reference: warm-up output not
        # byte-equal, a response's count or order off, a compile in a
        # drain window
        "reference_faults": {"value": len(obs["faults"]), "limit": 0},
        "failed_operations": {"value": int(obs["failed"]), "limit": 0},
        "fastpath_slices": {
            "value": s1["fastpath_slices"] - s0["fastpath_slices"],
            "limit": 1, "at_least": True},
        "fallback_slices": {
            "value": s1["fallback_slices"] - s0["fallback_slices"], "limit": 0},
        "interpreter_path_records": {
            "value": c_end["paths"].get("interpreter", 0)
            - c_start["paths"].get("interpreter", 0), "limit": 0},
    }
    for key in STILL_COUNTERS:
        out[key] = {"value": c_end[key] - c_start[key], "limit": 0}
    if broker is not None and broker.placement is not None:
        out["partition_gate_armed"] = {
            "value": int(broker.gate is not None), "limit": 1,
            "at_least": True}
    if reduced is not None:
        out["idle_chips"] = {"value": len(idle_chips(reduced)), "limit": 0}
    return out


def spans_between(t0: float, t1: float) -> list:
    """The executor's per-dispatch spans (as dicts) that ended inside
    [t0, t1] on perf_counter, the clock the benchmark stamps with. The
    program's ring keeps the most recent 4,096 (`SPAN_RING_CAPACITY`)."""
    from fluvio_tpu.telemetry import TELEMETRY

    return [
        s for s in TELEMETRY.spans_json()
        if "t_end" in s and t0 <= s["t_end"] <= t1
    ]


def phase_intervals(t0: float, t1: float) -> list:
    """[(phase, start, end)] of the executor's phase spans that touch
    [t0, t1]: what the host was doing, for attributing device-idle gaps.
    A phase's interval runs from its first start for its total time."""
    from fluvio_tpu.telemetry import TELEMETRY
    from fluvio_tpu.telemetry.spans import PHASES

    out = []
    for span in TELEMETRY.spans.recent():
        for name, start, secs in zip(PHASES, span.phase_t0, span.phase_s):
            if secs > 0.0 and start < t1 and start + secs > t0:
                out.append((name, start, start + secs))
    return out
