"""Bytes of response batches the benchmark's consumer received, per
second of window (10**6 bytes)."""


def read(obs):
    return obs["bytes_out"] / 1e6 / obs["window_s"]
