"""Slices inside the window that outgrew their stream's session bank or
emit columns and were re-run under a doubled shape (each a compile): the
program's `session-grow` events stamped inside the window, read as
`group_table_growths` reads `group-grow`. The capacities are learned in
the warm-up and stay with the compiled chain, so a window reads 0."""

from spubench.session_bytes import events_in_window


def read(obs):
    events = events_in_window(obs, "session-grow")
    return None if events is None else len(events)
