"""Time the calling thread was BLOCKED on the device: the `wait` phase of
the window's dispatch spans (the first result sync of each chunk), per
million input records. Exclusive on its thread, so it cannot exceed the
wall."""

from spubench.xplane_scopes import span_phase_ms_per_mrec


def read(obs):
    return span_phase_ms_per_mrec(obs, "wait")
