"""Trace-cache misses (compiles or persistent-cache loads) inside the
window: `TELEMETRY.compile_totals()["compiles"]`, window delta."""


def read(obs):
    return obs["delta"]["compiles"]
