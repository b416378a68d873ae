#!/usr/bin/env python3
"""The sweep that sets a paced traffic mix's rate. Run once, on the chip.

    python3 benchmark/sweep_rate.py --workload ns-paced --rates 25,50,100,200,400 \
        --seconds 20 --seed 7

For each rate it copies the manifest and the benchmark's files into a
temporary checkout, writes the rate into the cell's traffic file there and
runs the cell once in a process of its own (this process never touches
JAX, so the chip is free for each child). A rate is SUSTAINED when the
consumer's lag at the window's end is no larger than at its middle and the
generator's p95 lateness is under one interval; the knee is the highest
sustained rate, and the cell's rate is four fifths of it, rounded down to
two figures. Nothing here is read by `run.py`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated batches/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--warm-max", type=int, default=None,
                    help="warm_max_batches for the sweep (default: the file's)")
    args = ap.parse_args(argv)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == args.workload)
    bench = manifest["paths"][0]
    env = dict(os.environ, PYTHONPATH=str(ROOT))
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        with tempfile.TemporaryDirectory(prefix="spubench-sweep-") as tmp:
            shutil.copy(ROOT / "BENCHMARK.json", tmp)
            shutil.copytree(ROOT / bench, Path(tmp) / bench,
                            ignore=shutil.ignore_patterns("__pycache__"))
            tf = Path(tmp) / bench / "traffic" / f"{cell['traffic']}.json"
            traffic = json.loads(tf.read_text())
            traffic["rate_batches_per_s"] = rate
            if args.warm_max is not None:
                traffic["warm_max_batches"] = args.warm_max
            tf.write_text(json.dumps(traffic))
            out = subprocess.run(
                [sys.executable, str(Path(tmp) / bench / "run.py"),
                 "--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", "0"],
                env=env, capture_output=True, text=True,
            )
        if out.returncode != 0 or not out.stdout.strip():
            print(json.dumps({"rate": rate, "rc": out.returncode,
                              "stderr": out.stderr[-600:]}), flush=True)
            continue
        r = json.loads(out.stdout.strip().splitlines()[-1])
        c = r["counts"]
        sustained = (
            r["failed"] == 0
            and c["lag_end_records"] <= c["lag_mid_records"]
            and c["gen_late_p95_ms"] < 1000.0 / rate
        )
        if sustained:
            knee = rate if knee is None else max(knee, rate)
        print(json.dumps({
            "rate": rate, "sustained": sustained, "correct": r["correct"],
            "failed": r["failed"], "attempted": r["attempted"],
            "age_p50_ms": r["metrics"].get("age_p50_ms", {}).get("value"),
            "age_p95_ms": r["metrics"].get("age_p95_ms", {}).get("value"),
            "setup_s": r["metrics"]["setup_s"]["value"],
            "lag_mid_records": c["lag_mid_records"],
            "lag_end_records": c["lag_end_records"],
            "gen_late_p95_ms": c["gen_late_p95_ms"],
            "interval_ms": 1000.0 / rate,
            "slices": c["fastpath_slices"],
            "max_slice_batches": c["max_slice_batches"],
            "compiles_in_window": c["compiles"],
            "faults": r["faults"],
        }), flush=True)
    print(json.dumps({"knee": knee,
                      "four_fifths": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
