"""What a stream's open costs before its first slice is read: the flow
phase `chain_acquire` (the SPU's `acquire_stream_chain`: a cache hit or
a chain build), mean over the stream opens whose first slice ended in
the window. None where no flow has the phase (a program without it)."""

from spubench.xplane_scopes import window_flows


def read(obs):
    opens = [
        f["phases_ms"]["chain_acquire"] for f in window_flows(obs) or ()
        if "chain_acquire" in f.get("phases_ms", {})
    ]
    return sum(opens) / len(opens) if opens else None
