"""Stream opens inside the window that BUILT their chain instead of
finding it in the SPU's stream-chain cache: the program's `chain-build`
instant events stamped inside the window (the running counter is
`stream_chain_builds` of the SPU's chain metrics; the benchmark's window
delta does not carry it, the event ring dates each build). None where
the program has no such counter, or where its event ring overwrote part
of the window."""


def read(obs):
    from fluvio_tpu.telemetry import TELEMETRY

    if "stream_chain_builds" not in obs["c_close"]["slices"]:
        return None
    events = TELEMETRY.events.recent()
    if TELEMETRY.events.dropped and events and events[0].t > obs["t_open"]:
        return None
    return sum(
        1 for e in events
        if e.kind == "chain-build" and obs["t_open"] <= e.t <= obs["t_close"]
    )
