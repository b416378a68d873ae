"""Slices inside the window that outgrew their stream's keyed table and
were re-run under a doubled capacity (each a compile): the program's
`group-grow` events stamped inside the window, read as
`window_bank_growths` reads `window-grow`. The capacity is learned in
the warm-up and stays with the compiled chain, so a window reads 0."""

from spubench.group_bytes import events_in_window


def read(obs):
    events = events_in_window(obs, "group-grow")
    return None if events is None else len(events)
