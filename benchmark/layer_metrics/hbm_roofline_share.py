"""Least time the chip's HBM could take to move the traced span's staged
input and result buffers once each, over the device's busy time.

Bytes come from shapes, by `spubench.shapes.slice_bytes` (a copy of the
program's bucketing rules kept with the benchmark); the peak comes from
`spubench/peaks.json`, keyed by the device kind. Bandwidth-bound by
construction: these chains do byte scans, no matrix work."""

from spubench.device import peaks_for
from spubench.shapes import span_bytes


def read(obs):
    t = obs.get("trace")
    spans = obs.get("trace_spans") or ()
    if not t or not spans or t["busy_s"] <= 0:
        return None
    peak = peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    moved = sum(span_bytes(s["records"], obs["shape"]) for s in spans)
    return 100.0 * (moved / peak) / t["busy_s"]
