"""Least time the chip's HBM could take to move what the aggregate stage
must (`spubench.agg_bytes`: every staged row read once, one 8-byte
accumulator written a row) for the traced span's dispatches, over the
device's busy time under the aggregate stage's scopes. Bandwidth-bound
by construction: a byte scan for the field, an integer scan for the sum.
The peak is the cell's chips' (`device.hbm_peak_of_run`): the bytes are
summed over every chip's dispatches, the busy time is the mean chip's."""

from spubench.agg_bytes import agg_scope_seconds, agg_stage_bytes
from spubench.device import hbm_peak_of_run
from spubench.xplane_scopes import reduce_run


def read(obs):
    r = reduce_run(obs)
    spans = obs.get("trace_spans") or ()
    if not r or not spans:
        return None
    busy = agg_scope_seconds(r)
    if busy <= 0:
        return None
    peak = hbm_peak_of_run(obs)
    moved = sum(agg_stage_bytes(s["records"], obs["shape"]) for s in spans)
    return 100.0 * (moved / peak) / busy
