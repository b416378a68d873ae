"""Slices inside the window that outgrew their stream's window bank or
emit columns and were re-run under a doubled shape (each a compile): the
program's `window-grow` events stamped inside the window, read as
`stream_chain_builds` reads `chain-build`. The sizes are learned in the
warm-up and stay with the compiled chain, so a window reads 0."""

from spubench.window_events import in_window


def read(obs):
    events = in_window(obs, "window-grow")
    return None if events is None else len(events)
