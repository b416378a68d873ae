"""Device time of the session stage of session windows: busy time under
the scopes `stage<i>.session`, `stage<i>.session_merge` and
`stage<i>.session_close`, scaled as `device_busy_ms_per_mrec` is. None
where the trace has no such scope (a chain without a session stage, a
program without the scopes)."""

from spubench.session_bytes import session_scope_seconds
from spubench.xplane_scopes import per_mrec, reduce_run


def read(obs):
    r = reduce_run(obs)
    if not r:
        return None
    seconds = session_scope_seconds(r)
    return per_mrec(obs, seconds) if seconds > 0 else None
