"""Least time the chip's HBM could take to move what the group stage
must (`spubench.group_bytes`: every padded row's key and seven
contributions read once, its eight answer columns written once) for the
traced span's dispatches, over the device's busy time under
`stage<i>.group_merge` + `stage<i>.group_emit`. Bandwidth-bound by
construction: a sort, scans, a scatter of positions and gathers of int64
columns.
The peak is the cell's chips' (`device.hbm_peak_of_run`): the bytes are
summed over every chip's dispatches, the busy time is the mean chip's."""

from spubench.device import hbm_peak_of_run
from spubench.group_bytes import group_bytes, work_scope_seconds
from spubench.xplane_scopes import reduce_run


def read(obs):
    r = reduce_run(obs)
    spans = obs.get("trace_spans") or ()
    if not r or not spans:
        return None
    busy = work_scope_seconds(r)
    if busy <= 0:
        return None
    peak = hbm_peak_of_run(obs)
    moved = sum(group_bytes(s["records"]) for s in spans)
    return 100.0 * (moved / peak) / busy
