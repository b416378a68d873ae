"""Host reference of `aggregate-field(field, combine)`: a running
reduction of one JSON field, the accumulator emitted as every output.

`json` and Python `int` only; calls no engine code. Upstream's aggregate
contract (fluvio-smartmodule-derive generator/aggregate.rs): the stream's
accumulator starts from the invocation's seed (empty = none), every
input record folds its field into it, and the output record carries the
new accumulator as its value at the input's own offset. Python integers
do not wrap, so a sum past 2**63 would differ from any fixed-width
implementation instead of agreeing with it by accident.
"""

import json

import numpy as np

OFFSETS = "exact"  # every output carries its input record's offset

COMBINES = {"add": lambda acc, x: acc + x, "max": max, "min": min}


def expect(values, field: str = "n", combine: str = "add", initial=b""):
    """-> (source input index of each output, output values as a list of
    bytes): one output per input. ``initial`` is the accumulator seed as
    the invocation carries it (decimal ASCII; empty = start from 0 under
    add, from the first record's own contribution under max and min)."""
    fold = COMBINES[combine]
    if isinstance(initial, str):
        initial = initial.encode()
    acc = int(initial) if initial else (0 if combine == "add" else None)
    out = []
    for v in values:
        x = int(json.loads(v)[field])
        acc = x if acc is None else fold(acc, x)
        out.append(str(acc).encode())
    return np.arange(len(values), dtype=np.int64), out
