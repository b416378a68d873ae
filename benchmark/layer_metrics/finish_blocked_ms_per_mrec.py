"""Host time the serving pass spends BLOCKED on a slice's results: the
flow phase `finish` (header sync, the count-sized slice programs, the
downloads, failure ladders), summed over the slice flows that ended in
the window, per million input records. Where the stream loop dispatches
the next slice before it (the order before ISSUE 31), the phase also
holds the wait behind that slice's device work and, in a program without
the `materialize` phase, the split-back."""

from spubench.xplane_scopes import flow_phase_ms_per_mrec


def read(obs):
    return flow_phase_ms_per_mrec(obs, ("finish",))
