"""Contributions the window stage DROPPED inside the window: late (to a
window already emitted) and invalid (a key outside the packing range)
rows, summed over the program's `window-drop` events (the running
counters are `TELEMETRY.window_counts()`'s ``late`` and ``invalid``).
A deployment whose disorder stays under the watermark's delay reads 0."""

from spubench.window_events import in_window


def read(obs):
    events = in_window(obs, "window-drop")
    if events is None:
        return None
    return sum(int(e.detail.rsplit(":", 1)[1]) for e in events)
