"""`["a<i & 255>","b<0..9999>",<0..9999>,<0..9999>,"x","y"]` records.

The `gen_arrays` corpus of `chip_smoke.py` / `bench.py`: a top-level JSON
array of six elements (four strings, two numbers), vectorised.
"""

import numpy as np

from spubench.ragged import concat_parts, digit_table

ELEMENTS_PER_RECORD = 6


def generate(n: int, seed: int, n_limit: int = 10000):
    rng = np.random.default_rng(seed)
    nums = rng.integers(0, n_limit, size=(n, 3))
    a_t, a_l = digit_table(256)
    num_t, num_l = digit_table(n_limit)
    return concat_parts(n, [
        b'["a',
        (a_t, a_l, np.arange(n, dtype=np.int64) & 255),
        b'","b',
        (num_t, num_l, nums[:, 0]),
        b'",',
        (num_t, num_l, nums[:, 1]),
        b",",
        (num_t, num_l, nums[:, 2]),
        b',"x","y"]',
    ])
