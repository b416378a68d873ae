"""Least time the chip's HBM could take to move what the aggregate stage
must (`spubench.agg_bytes`: every staged row read once, one 8-byte
accumulator written a row) for the traced span's dispatches, over the
device's busy time under the aggregate stage's scopes. Bandwidth-bound
by construction: a byte scan for the field, an integer scan for the sum."""

from spubench.agg_bytes import agg_scope_seconds, agg_stage_bytes
from spubench.device import peaks_for
from spubench.xplane_scopes import reduce_run


def read(obs):
    r = reduce_run(obs)
    spans = obs.get("trace_spans") or ()
    if not r or not spans:
        return None
    busy = agg_scope_seconds(r)
    if busy <= 0:
        return None
    peak = peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    moved = sum(agg_stage_bytes(s["records"], obs["shape"]) for s in spans)
    return 100.0 * (moved / peak) / busy
