"""Host time of the slice path BEFORE the executor has the slice: the
flow phases `read` (log read + shallow batch decode), `wire_decode`
(native record decode) and `stage` (column merge, chunk buffers), summed
over the slice flows that ended in the window, per million input records."""

from spubench.xplane_scopes import flow_phase_ms_per_mrec


def read(obs):
    return flow_phase_ms_per_mrec(obs, ("read", "wire_decode", "stage"))
