"""Host reference of `array-map-json`: one output per top-level element.

Written from the SmartModule's documented semantics (upstream
`array_map` JSON-array example: every element of the array becomes a
record of its own; strings lose their quotes, other elements keep their
JSON text), with `json` only; calls no engine code. The corpus is parsed
in one `json.loads` call (the records joined into one outer array), which
is the same parser over the same bytes at a third of the set-up time.

Offsets: an array_map output is a fresh record with offset delta 0, so
it reads as the base offset of the response batch that carries it. The
outputs of one pass are therefore compared as a value stream in order,
with non-decreasing offsets that never pass their input record.
"""

import json

import numpy as np

OFFSETS = "nondecreasing"


def _text(e) -> str:
    if type(e) is str:
        return e
    if type(e) is int:
        return str(e)
    return json.dumps(e, separators=(",", ":"))


def expect(values):
    """-> (source input index of each output, (value lengths, value bytes))."""
    parsed = json.loads(b"[" + b",".join(values) + b"]")
    if len(parsed) != len(values):
        raise ValueError("a record holds more than one JSON value")
    counts = np.zeros(len(parsed), dtype=np.int64)
    for i, arr in enumerate(parsed):
        if type(arr) is not list:
            raise ValueError(f"record {i} is not a JSON array")
        counts[i] = len(arr)
    texts = [_text(e) for arr in parsed for e in arr]
    joined = "".join(texts)
    if joined.isascii():   # one encode; a text's length is its byte length
        lens = np.fromiter(map(len, texts), dtype=np.int64, count=len(texts))
        flat = np.frombuffer(joined.encode(), dtype=np.uint8)
    else:
        enc = [t.encode() for t in texts]
        lens = np.fromiter(map(len, enc), dtype=np.int64, count=len(enc))
        flat = np.frombuffer(b"".join(enc), dtype=np.uint8)
    src = np.repeat(np.arange(len(parsed), dtype=np.int64), counts)
    return src, (lens, flat)
