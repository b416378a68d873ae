"""Host time of the executor's phases BEFORE the device has the batch
(stage, h2d, dispatch; `TELEMETRY.phase_totals()` window delta, host
clock), per million input records."""

from spubench.window import UP_PHASES


def read(obs):
    if not obs["records_in"]:
        return None
    s = sum(obs["delta"]["phase_s"].get(p, 0.0) for p in UP_PHASES)
    return s * 1e3 / (obs["records_in"] / 1e6)
