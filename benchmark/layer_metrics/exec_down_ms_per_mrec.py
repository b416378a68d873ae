"""Host time blocked on and after the device (the executor's `device`,
`fetch` and `d2h` phases; `device` is host time waiting for the first
result, not device time), window delta per million input records."""

from spubench.window import DOWN_PHASES


def read(obs):
    if not obs["records_in"]:
        return None
    s = sum(obs["delta"]["phase_s"].get(p, 0.0) for p in DOWN_PHASES)
    return s * 1e3 / (obs["records_in"] / 1e6)
