"""Bytes a dispatched slice's device buffers occupy, from their shapes.

A copy of the program's staging rules (`smartengine/tpu/buffer.py`:
rows pad to a power of two from 8, value width to a power of two from 32
up to 128 and in eighths of a power of two above), kept here so that a
later change to the program cannot move the yardstick. The staged input
is a rows x width byte matrix plus a 4-byte length a row; the result is
counted at the same padded form: out rows x out width plus lengths,
where out rows is the input rows times the chain's fan-out (1 for a
filter or a map, whose result cannot outgrow its input).
"""

from __future__ import annotations

MIN_ROWS = 8
MIN_WIDTH = 32


def _next_pow2(n: int, floor: int) -> int:
    v = floor
    while v < n:
        v <<= 1
    return v


def bucket_width(max_len: int) -> int:
    v = _next_pow2(max(max_len, 1), MIN_WIDTH)
    if v <= 128:
        return v
    step = max(32, v >> 3)
    return ((max_len + step - 1) // step) * step


def span_bytes(records: int, shape: dict) -> int:
    """``shape``: max_in_len, max_out_len, fanout (outputs per input,
    rounded up) of the configuration's corpus and reference."""
    rows = _next_pow2(max(records, 1), MIN_ROWS)
    staged = rows * (bucket_width(shape["max_in_len"]) + 4)
    result = rows * shape["fanout"] * (bucket_width(shape["max_out_len"]) + 4)
    return staged + result
