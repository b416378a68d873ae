"""95th percentile of the same ages as `age_p50_ms` (all batches of the
window; the sample count is `counts.samples` on the result line)."""

from spubench.stats import percentile


def read(obs):
    p = percentile(obs.get("ages_s") or (), 0.95)
    return None if p is None else p * 1000.0
