"""Share of the window's wall that lies under no phase of any slice flow:
what the program's own tracing of the serving task does not name (a
pass's stream re-open, scheduling between phases)."""

from spubench.xplane_scopes import unnamed_share


def read(obs):
    return unnamed_share(obs)
