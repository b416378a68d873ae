"""Least time the chips' HBM could take to move the traced span's staged
input and result buffers once each, over the mean chip's busy time.

Bytes come from shapes, by `spubench.shapes.span_bytes` (a copy of the
program's bucketing rules kept with the benchmark), summed over every
chip's dispatches; the peak is `spubench/peaks.json`'s, keyed by the
device kind, times the cell's chips (`device.hbm_peak_of_run`).
Bandwidth-bound by construction: these chains do byte scans, no matrix
work."""

from spubench.device import hbm_peak_of_run
from spubench.shapes import span_bytes


def read(obs):
    t = obs.get("trace")
    spans = obs.get("trace_spans") or ()
    if not t or not spans or t["busy_s"] <= 0:
        return None
    peak = hbm_peak_of_run(obs)
    moved = sum(span_bytes(s["records"], obs["shape"]) for s in spans)
    return 100.0 * (moved / peak) / t["busy_s"]
