"""How often the stream loop's order engages: the share (%) of the
window's served slice flows whose `interleaved` field is set, i.e. the
next slice was dispatched between this slice's `finish` and its
`materialize`, so the device worked under this slice's host half. A
pass's last slice has no next one. None where no flow carries the field
(a program without it)."""

from spubench.xplane_scopes import window_flows


def read(obs):
    served = [
        f for f in window_flows(obs) or ()
        if "interleaved" in f and "finish" in f.get("phases_ms", {})
    ]
    if not served:
        return None
    return 100.0 * sum(bool(f["interleaved"]) for f in served) / len(served)
