"""Host time of the slice path AFTER the executor returned the chunks:
the flow phase `encode` (output merge, resume drop, `max_bytes` cut,
`to_columns`, native record encode, response batch), per million input
records."""

from spubench.xplane_scopes import flow_phase_ms_per_mrec


def read(obs):
    return flow_phase_ms_per_mrec(obs, ("encode",))
