"""Host reference of `regex-filter("fluvio")` -> `json-map(name)` upper-cased.

`re` + `json` only; calls no engine code. Record i of the log has offset
i; a record the filter drops emits nothing, one it keeps emits the
upper-cased value of its ``name`` field at the same offset.
"""

import json
import re

import numpy as np

OFFSETS = "exact"  # every output carries its input record's offset


def expect(values, regex: str = "fluvio", field: str = "name"):
    """-> (source input index of each output, output values as a list of
    bytes; a reference may also return them as (lengths, flat bytes))."""
    pat = re.compile(regex.encode())
    src, out = [], []
    for i, v in enumerate(values):
        if pat.search(v):
            src.append(i)
            out.append(json.loads(v)[field].upper().encode())
    return np.asarray(src, dtype=np.int64), out
