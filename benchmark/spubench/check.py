"""The comparison that decides `correct`.

The reference (a file under `references/`) says, for a range of input
records, which output values the chain must emit and from which input
each comes. The received side is decoded ONCE into columns by the
program's native record decoder (one call per response batch, no Python
object per record) and compared as flat byte arrays.
"""

from __future__ import annotations

import numpy as np


class Reference:
    """Expected outputs of input records [lo, hi) of the log."""

    def __init__(self, module, values, lo: int, params: dict):
        src, out = module.expect(values, **params)
        self.lo = lo
        self.hi = lo + len(values)
        self.offsets_rule = module.OFFSETS
        self.src = src + lo                      # absolute input offsets
        if isinstance(out, tuple):               # (lengths, flat bytes)
            self.lens, self.flat = out
        else:                                    # a list of bytes
            self.lens = np.fromiter(map(len, out), dtype=np.int64,
                                    count=len(out))
            self.flat = np.frombuffer(b"".join(out), dtype=np.uint8)
        # cum[i] = outputs owed to inputs lo .. lo+i-1
        self.cum = np.zeros(len(values) + 1, dtype=np.int64)
        np.cumsum(np.bincount(src, minlength=len(values)), out=self.cum[1:])

    def count(self, a: int, b: int) -> int:
        """Outputs the reference states for inputs [a, b)."""
        return int(self.cum[b - self.lo] - self.cum[a - self.lo])


def decode_batches(batches) -> dict:
    """Response batches -> (absolute offsets, value lens, value flat)."""
    from fluvio_tpu.protocol.compression import Compression, decompress
    from fluvio_tpu.smartengine import native_backend

    offs, lens, flats = [], [], []
    for b in batches:
        raw = b.raw_records
        if raw is None:
            raise ValueError("response batch carries parsed records, not a slab")
        if b.header.compression() != Compression.NONE:
            raw = decompress(b.header.compression(), raw)
        cols = native_backend.decode_record_columns(raw)
        if cols is None:
            raise RuntimeError("the program's native record codec did not build")
        if cols["count"] != b.records_len() or cols["parsed"] != len(raw):
            raise ValueError("malformed record slab in a response batch")
        offs.append(cols["off_delta"] + b.base_offset)
        lens.append(np.diff(cols["val_off"]))
        flats.append(cols["val_flat"])
    if not offs:
        z = np.zeros(0, dtype=np.int64)
        return {"offsets": z, "lens": z, "flat": np.zeros(0, dtype=np.uint8)}
    return {
        "offsets": np.concatenate(offs),
        "lens": np.concatenate(lens),
        "flat": np.concatenate(flats),
    }


def compare(ref: Reference, a: int, b: int, batches) -> list:
    """Every fault found in ``batches`` as the served output of inputs
    [a, b); an empty list means equal to the reference."""
    got = decode_batches(batches)
    i0, i1 = int(ref.cum[a - ref.lo]), int(ref.cum[b - ref.lo])
    faults = []
    if len(got["offsets"]) != i1 - i0:
        return [f"{len(got['offsets'])} records out for inputs [{a},{b}), "
                f"the reference states {i1 - i0}"]
    if not np.array_equal(got["lens"], ref.lens[i0:i1]):
        faults.append("output value lengths differ from the reference")
    else:
        b0 = int(ref.lens[:i0].sum())
        if not np.array_equal(got["flat"], ref.flat[b0:b0 + got["flat"].size]):
            faults.append("output value bytes differ from the reference")
    src = ref.src[i0:i1]
    if ref.offsets_rule == "exact":
        if not np.array_equal(got["offsets"], src):
            faults.append("output offsets differ from their input records'")
    else:
        if np.any(np.diff(got["offsets"]) < 0):
            faults.append("output offsets decrease")
        if np.any(got["offsets"] > src) or np.any(got["offsets"] < a):
            faults.append("an output offset lies outside [slice start, its input]")
    return faults


def headers_in_order(batches, a: int, b: int) -> bool:
    """The in-window check that needs no record decode: batch base
    offsets do not decrease and every batch lies inside [a, b)."""
    last = a
    for bt in batches:
        if bt.base_offset < last or bt.computed_last_offset() > b:
            return False
        last = bt.base_offset
    return True
