"""Median over the window's dispatches of the sum of one dispatch's phase
spans (one executor span per dispatched slice; the program's span ring
holds the most recent 256, so the median is over those of the window)."""

import statistics


def read(obs):
    spans = obs.get("window_spans") or ()
    if not spans:
        return None
    return statistics.median(sum(s["phases_ms"].values()) for s in spans)
