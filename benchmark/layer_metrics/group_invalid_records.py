"""Records the group stage of a keyed table DROPPED inside the window
for want of a key (auction or time out of range, a field missing): the
rows of the program's `group-drop` events (detail ``invalid:<rows>``;
the running counter is `TELEMETRY.group_counts()`'s ``invalid``). A
deployment whose keys are in range reads 0."""

from spubench.group_bytes import events_in_window


def read(obs):
    events = events_in_window(obs, "group-drop")
    if events is None:
        return None
    return sum(int(e.detail.rsplit(":", 1)[1]) for e in events)
