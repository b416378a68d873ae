"""Vectorised ragged-byte helpers shared by the corpus generators.

A corpus is (flat uint8, offsets int64[n+1]); record i is
``flat[off[i]:off[i+1]]``. `concat_parts` builds one from per-record
parts without a Python loop over records.
"""

from __future__ import annotations

import numpy as np


def digit_table(limit: int):
    """(table[limit, w] uint8, lens[limit]) of the decimal strings 0..limit-1."""
    width = len(str(limit - 1))
    vals = np.arange(limit, dtype=np.int64)
    lens = np.ones(limit, dtype=np.int64)
    for p in range(1, width):
        lens += vals >= 10 ** p
    table = np.zeros((limit, width), dtype=np.uint8)
    for j in range(width):
        # digit j of a number with `lens` digits sits at 10**(lens-1-j)
        exp = np.maximum(lens - 1 - j, 0)
        table[:, j] = (vals // 10 ** exp) % 10 + 0x30
    return table, lens


def word_table(words):
    """(table[k, w] uint8, lens[k]) of a list of ASCII words."""
    enc = [w.encode() for w in words]
    lens = np.array([len(e) for e in enc], dtype=np.int64)
    table = np.zeros((len(enc), int(lens.max())), dtype=np.uint8)
    for i, e in enumerate(enc):
        table[i, : len(e)] = np.frombuffer(e, dtype=np.uint8)
    return table, lens


def concat_parts(n: int, parts):
    """Ragged concat. Each part is ``bytes`` (the same for every record)
    or ``(table, lens, idx)``: record i takes ``table[idx[i], :lens[idx[i]]]``.
    Returns (flat, offsets)."""
    part_lens = []
    for p in parts:
        if isinstance(p, bytes):
            part_lens.append(np.full(n, len(p), dtype=np.int64))
        else:
            _, lens, idx = p
            part_lens.append(lens[idx])
    off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.sum(part_lens, axis=0), out=off[1:])
    flat = np.zeros(int(off[-1]), dtype=np.uint8)
    start = off[:-1].copy()
    for p, pl in zip(parts, part_lens):
        if isinstance(p, bytes):
            for j, c in enumerate(p):
                flat[start + j] = c
        else:
            table, _, idx = p
            for j in range(table.shape[1]):
                live = pl > j
                flat[start[live] + j] = table[idx[live], j]
        start += pl
    return flat, off


def to_values(flat: np.ndarray, off: np.ndarray):
    """The corpus as a list of `bytes` (what a host reference consumes)."""
    buf = flat.tobytes()
    o = off.tolist()
    return [buf[o[i]:o[i + 1]] for i in range(len(o) - 1)]
