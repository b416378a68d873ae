"""The window stage's instant events inside a run's window.

The program dates what is rare with an instant event (`TELEMETRY.events`,
a ring): `window-grow` (a bank or emit capacity doubled: a re-run and a
compile) and `window-drop` (detail ``late:<rows>`` or ``invalid:<rows>``:
contributions dropped by a slice). The benchmark's window delta carries
neither counter, the ring dates each event."""


def in_window(obs, kind: str):
    """The events of ``kind`` stamped inside the window; None where the
    program books none (a parent commit), or where its event ring
    overwrote part of the window."""
    from fluvio_tpu.telemetry import TELEMETRY

    if not hasattr(TELEMETRY, "add_window_grow"):
        return None
    events = TELEMETRY.events.recent()
    if TELEMETRY.events.dropped and events and events[0].t > obs["t_open"]:
        return None
    return [e for e in events
            if e.kind == kind and obs["t_open"] <= e.t <= obs["t_close"]]
