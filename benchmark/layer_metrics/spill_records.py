"""Records that ran outside the fused paths (the interpreter path of
`TELEMETRY.path_records()`), window delta."""


def read(obs):
    return obs["delta"]["interpreter_records"]
