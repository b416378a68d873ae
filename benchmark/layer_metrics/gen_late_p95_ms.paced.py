"""95th percentile of how late the generator started a write against its
schedule (a starved or blocked generator must not read as a fast SPU)."""

from spubench.stats import percentile


def read(obs):
    p = percentile(obs.get("late_s") or (), 0.95)
    return None if p is None else p * 1000.0
