"""Device time of the group stage of a keyed table: busy time under the
scopes `stage<i>.group`, `stage<i>.group_merge` and `stage<i>.group_emit`,
scaled as `device_busy_ms_per_mrec` is. None where the trace has no
such scope (a chain without a group stage, a program without the
scopes)."""

from spubench.group_bytes import group_scope_seconds
from spubench.xplane_scopes import per_mrec, reduce_run


def read(obs):
    r = reduce_run(obs)
    if not r:
        return None
    seconds = group_scope_seconds(r)
    return per_mrec(obs, seconds) if seconds > 0 else None
