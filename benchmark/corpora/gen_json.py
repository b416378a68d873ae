"""`{"name":"<one of six names>-<i & 1023>","n":<0..99999>}` records.

The `gen_json` corpus of `chip_smoke.py` / `bench.py` (about 36 B a
record, a third of them match ``fluvio``), re-stated here in vectorised
form so that a million records cost a fraction of a second of set-up.
"""

import numpy as np

from spubench.ragged import concat_parts, digit_table, word_table

NAMES = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "redpanda", "flink"]


def generate(n: int, seed: int, names=NAMES, n_limit: int = 100000):
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(names), size=n)
    nums = rng.integers(0, n_limit, size=n)
    name_t, name_l = word_table(names)
    idx_t, idx_l = digit_table(1024)
    num_t, num_l = digit_table(n_limit)
    return concat_parts(n, [
        b'{"name":"',
        (name_t, name_l, picks),
        b"-",
        (idx_t, idx_l, np.arange(n, dtype=np.int64) & 1023),
        b'","n":',
        (num_t, num_l, nums),
        b"}",
    ])
