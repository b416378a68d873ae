"""Device time of the aggregate stage: busy time under the scopes
`stage<i>.aggregate` and `stage<i>.aggregate_scan`, scaled as
`device_busy_ms_per_mrec` is. None where the trace has no such scope
(a chain without an aggregate, a program without the scopes)."""

from spubench.agg_bytes import agg_scope_seconds
from spubench.xplane_scopes import per_mrec, reduce_run


def read(obs):
    r = reduce_run(obs)
    if not r:
        return None
    seconds = agg_scope_seconds(r)
    return per_mrec(obs, seconds) if seconds > 0 else None
