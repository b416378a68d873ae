"""How long the chip waited on the in-process consumer: device-idle time
of the traced span under a `fluvio/consume_*` annotation, where a phase
that holds its thread outranks one that awaits (the ranking of
`spubench/consume_spans.py:idle_by_phase`), scaled as
`device_busy_ms_per_mrec` is. None without the consumer's records or
this run's own trace."""

from spubench.consume_spans import idle_consume_seconds
from spubench.xplane_scopes import per_mrec


def read(obs):
    seconds = idle_consume_seconds(obs)
    return None if seconds is None else per_mrec(obs, seconds)
