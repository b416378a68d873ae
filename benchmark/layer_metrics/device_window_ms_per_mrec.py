"""Device time of the window stage: busy time under the scopes
`stage<i>.window`, `stage<i>.window_merge` and `stage<i>.window_top`,
scaled as `device_busy_ms_per_mrec` is. None where the trace has no
such scope (a chain without a window stage, a program without the
scopes)."""

from spubench.window_bytes import window_scope_seconds
from spubench.xplane_scopes import per_mrec, reduce_run


def read(obs):
    r = reduce_run(obs)
    if not r:
        return None
    seconds = window_scope_seconds(r)
    return per_mrec(obs, seconds) if seconds > 0 else None
