"""Share of served slices that took the fused fast path, from the SPU's
own slice counters (`ctx.metrics.smartmodule`), window delta."""


def read(obs):
    d = obs["delta"]
    total = d["fastpath_slices"] + d["fallback_slices"]
    return 100.0 * d["fastpath_slices"] / total if total else None
