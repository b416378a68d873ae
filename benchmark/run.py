#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Opens the chip (no TPU, or fewer chips than the cell asks for: non-zero
exit, no result line, never a CPU fallback), builds the cell from the
files `BENCHMARK.json` names, warms up, measures for ``--seconds`` and
prints one JSON object as the last line of standard output. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics with the device's busy time from a profiler trace.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()   # `setup_s` is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(ROOT), str(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)

RUN_TIMEOUT_S = 1150.0   # a run that hangs is a failure, not a wait (a first run compiles)


def _read_metrics(cell, kind: str, entries: list, obs: dict) -> dict:
    """Each metric's own reader (`<kind>/<name>.py`) over what the run
    observed; a reader that finds nothing to read is left out."""
    from spubench import manifest

    out = {}
    for m in entries:
        value = manifest.load_plugin(cell.bench_dir, kind, m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


async def _run(session, mode) -> dict:
    session.adopt_loop(asyncio.get_running_loop())
    await session.start()
    try:
        return await asyncio.wait_for(mode.run(session), RUN_TIMEOUT_S)
    finally:
        await session.close()


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, t_process_start: float = None) -> dict:
    """Run one cell once and return the result object."""
    # the program's dead-letter store defaults to a fixed /tmp path; a
    # run keeps whatever it writes under its own TMPDIR
    os.environ.setdefault(
        "FLUVIO_DEADLETTER_DIR",
        os.path.join(tempfile.gettempdir(), "spubench-deadletter"),
    )
    from spubench import device, manifest
    from spubench.session import Session

    cell = manifest.load_cell(workload, root)
    dev = device.require_device(cell.chips)
    mode = manifest.load_plugin(cell.bench_dir, "modes", cell.traffic["mode"])
    session = Session(
        cell, seed, seconds, trace,
        T_PROCESS_START if t_process_start is None else t_process_start,
    )
    try:
        obs = asyncio.run(_run(session, mode))
        return _result(cell, session, dev, obs, workload, seed, trace)
    finally:
        session.cleanup()


def _result(cell, session, dev, obs, workload, seed, trace) -> dict:
    from spubench import device, window

    obs["setup_s"] = session.setup_s
    obs["device_kind"] = dev["kind"]
    obs["chips"] = cell.chips
    obs["shape"] = session.shape
    dev["memory_peak_bytes"] = device.memory_peak_bytes()
    obs["window_spans"] = window.spans_between(obs["t_open"], obs["t_close"])
    reduced = None
    if trace:
        tr = session.tracer
        if tr.t1 is not None:
            reduced = tr.reduce(window.phase_intervals(tr.t0, tr.t1))
            obs["trace_spans"] = window.spans_between(tr.t0, tr.t1)
        obs["trace"] = reduced
    faults = (list(obs["faults"])
              + window.truth_faults(session.c_start, obs["c_close"])
              + window.placement_faults(session.broker, reduced))
    result = {
        "correct": not faults,
        "attempted": int(obs["attempted"]),
        "failed": int(obs["failed"]),
        "device": dev,
        "workload": workload,
        "seed": seed,
        "window_s": obs["window_s"],
        "faults": faults,
        "counts": {
            k: obs[k] for k in ("records_in", "records_out", "bytes_out",
                                "responses") if k in obs
        } | {"samples": len(obs.get("ages_s", ()))} | obs["delta"]
        | obs.get("counts", {}),
    }
    if trace:
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
        result["metrics"] = _read_metrics(
            cell, "layer_metrics", cell.per_layer, obs
        )
    else:
        result["metrics"] = _read_metrics(
            cell, "end_to_end", cell.end_to_end, obs
        )
    result["compared"] = window.compared(
        obs, session.c_start, obs["c_close"], session.broker, reduced)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    if args.trace and "busy_s" not in result["device"]:
        print("benchmark: the trace shows no operation on the device",
              file=sys.stderr)
        return 1
    for f in result["faults"]:
        print(f"benchmark: NOT CORRECT: {f}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"benchmark: compared {name} {c['value']} limit "
              f"{'>= ' if c.get('at_least') else ''}{c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
