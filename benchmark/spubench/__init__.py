"""The benchmark's shared code: manifest, device, broker, window, trace.

Everything that belongs to ONE configuration, traffic mix, loop mode or
per-layer metric lives in a file of its own beside this package
(`configs/`, `traffic/`, `modes/`, `corpora/`, `references/`,
`layer_metrics/`) and is found by the name `BENCHMARK.json` gives it.
"""
