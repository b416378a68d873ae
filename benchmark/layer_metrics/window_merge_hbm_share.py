"""Least time the chip's HBM could take to move what the window merge
must (`spubench.window_bytes`: every replicated row's id, accumulator
and count read once and written once) for the traced span's dispatches,
over the device's busy time under `stage<i>.window_merge`.
The replicas a record counts in are the cell's own (`window_replicas`,
which the event-time mode notes from the configuration's window and
slide): None where the run states none. Bandwidth-bound by
construction: a sort, scans and gathers of int64 columns.
The peak is the cell's chips' (`device.hbm_peak_of_run`): the bytes are
summed over every chip's dispatches, the busy time is the mean chip's."""

from spubench.device import hbm_peak_of_run
from spubench.window_bytes import merge_bytes, merge_scope_seconds
from spubench.xplane_scopes import reduce_run


def read(obs):
    r = reduce_run(obs)
    spans = obs.get("trace_spans") or ()
    replicas = obs.get("window_replicas")
    if not r or not spans or not replicas:
        return None
    busy = merge_scope_seconds(r)
    if busy <= 0:
        return None
    peak = hbm_peak_of_run(obs)
    moved = sum(merge_bytes(s["records"], replicas) for s in spans)
    return 100.0 * (moved / peak) / busy
