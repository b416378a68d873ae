"""Share of the device's busy time (traced span) in operations that carry
one of the program's scopes (`DEVICE_SCOPES`; the `tf_op` of the
operation's metadata in the run's own xplane)."""

from spubench.xplane_scopes import reduce_run


def read(obs):
    r = reduce_run(obs)
    if not r or not r["scope_s"] or r["busy_s"] <= 0:
        return None
    return 100.0 * sum(r["scope_s"].values()) / r["busy_s"]
