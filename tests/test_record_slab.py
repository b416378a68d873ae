"""ISSUE-34: the native record writer's two direct entries (`RecordSlab.
append_flat`, `.append_ints`) and its general one (`.append_columns`)
against an encoder that shares none of their code: `protocol/record.py`'s
per-record `Record.encode`.

The numpy size passes that `tpu_materialize` used for the `max_bytes` cut
(`_varint_sizes`, `_encoded_record_sizes_at`, moved here from
`spu/smart_chain.py` when the cut moved into the encoder) are the oracle
for the rows kept.
"""

from __future__ import annotations

import numpy as np
import pytest

from fluvio_tpu.protocol.codec import ByteWriter
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import native_backend
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor
from fluvio_tpu.spu import smart_chain

pytestmark = pytest.mark.skipif(
    native_backend.load_library() is None, reason="no native toolchain"
)

INT64_MIN, INT64_MAX = -(2**63), 2**63 - 1


# -- the oracle: numpy's size rule, as `tpu_materialize` had it ---------------


def _varint_sizes(x: np.ndarray) -> np.ndarray:
    """Exact zigzag-varint encoded sizes, vectorized."""
    xi = x.astype(np.int64)
    u = ((xi << 1) ^ (xi >> 63)).view(np.uint64)
    nb = np.ones(len(u), dtype=np.int64)
    for k in range(1, 10):
        nb += (u >= np.uint64(1 << (7 * k))).astype(np.int64)
    return nb


def _encoded_record_sizes_at(
    vlens: np.ndarray, klens_raw: np.ndarray, deltas: np.ndarray, ts: np.ndarray
) -> np.ndarray:
    """Per-record wire sizes (parity: protocol.record.Record.write_size)."""
    vlens = vlens.astype(np.int64)
    klens_raw = klens_raw.astype(np.int64)
    has_key = klens_raw >= 0
    klens = np.maximum(klens_raw, 0)
    inner = (
        1  # attributes
        + _varint_sizes(ts)
        + _varint_sizes(deltas)
        + 1  # key tag
        + np.where(has_key, _varint_sizes(klens) + klens, 0)
        + _varint_sizes(vlens)
        + vlens
        + 1  # varint(0) header count
    )
    return _varint_sizes(inner) + inner


def _sizes(values, keys, offs, tss) -> np.ndarray:
    return _encoded_record_sizes_at(
        np.array([len(v) for v in values]),
        np.array([-1 if k is None else len(k) for k in keys]),
        np.asarray(offs), np.asarray(tss),
    )


def _numpy_keep(sizes: np.ndarray, max_bytes: int) -> int:
    """The rows the numpy rule keeps of a slice (at least one)."""
    if not len(sizes) or max_bytes <= 0:
        return len(sizes)
    keep = int(np.searchsorted(np.cumsum(sizes), max_bytes, side="left")) + 1
    return max(min(keep, len(sizes)), 1)


# -- the independent encoder ---------------------------------------------------


def _wire(records) -> bytes:
    w = ByteWriter()
    for r in records:
        r.encode(w)
    return w.bytes()


def _records(values, keys, offs, tss):
    return [
        Record(value=v, key=k, offset_delta=int(o), timestamp_delta=int(t))
        for v, k, o, t in zip(values, keys, offs, tss)
    ]


# -- building a flat-backed chunk the way a fetch leaves it --------------------


def _meta_columns(keys, offs, tss, rows):
    """Key matrix (junk beyond a key's length), -1 for a null key, and
    the delta columns, padded to ``rows``."""
    n = len(keys)
    kw = max([len(k) for k in keys if k is not None] + [1])
    kmat = np.full((rows, kw), 0xDD, np.uint8)
    klens = np.full(rows, -1, np.int32)
    for i, k in enumerate(keys):
        if k is not None:
            kmat[i, : len(k)] = np.frombuffer(k, np.uint8)
            klens[i] = len(k)
    od = np.zeros(rows, np.int32)
    od[:n] = offs
    td = np.zeros(rows, np.int64)
    td[:n] = tss
    return dict(keys=kmat, key_lengths=klens, offset_deltas=od,
                timestamp_deltas=td)


def _flat_chunk(values, keys, offs, tss, pad_rows=3):
    """A flat-backed `RecordBuffer`: 4-aligned flat (the 0-3 pad bytes
    filled with junk: only a record's own bytes may reach the wire),
    int32 starts / lengths, a key matrix with -1 for a null key, padding
    rows after the live ones."""
    n = len(values)
    rows = n + pad_rows
    lens = np.array([len(v) for v in values], np.int64)
    l4 = (lens + 3) & ~3
    starts64 = np.cumsum(l4) - l4
    flat = np.full(int(l4.sum()), 0xEE, np.uint8)
    for v, s in zip(values, starts64):
        flat[s : s + len(v)] = np.frombuffer(v, np.uint8)
    lengths = np.zeros(rows, np.int32)
    lengths[:n] = lens
    starts = np.full(rows, int(l4.sum()), np.int32)
    starts[:n] = starts64
    return RecordBuffer(
        values=None, lengths=lengths, count=n,
        _flat=flat, _starts=starts, _width=32, _rows=rows,
        **_meta_columns(keys, offs, tss, rows),
    )


def _int_chunk(ints, keys, offs, tss, pad_rows=2):
    n = len(ints)
    rows = n + pad_rows
    ex = TpuChainExecutor.__new__(TpuChainExecutor)  # the render only
    return RecordBuffer(
        values=None, lengths=None, count=n,
        _rows=rows, _ints=np.asarray(ints, np.int64),
        _render=ex._int_value_columns,
        **_meta_columns(keys, offs, tss, rows),
    )


def _corpus(n, seed):
    """n records covering the wire's corners: value lengths 0-3 pad bytes
    apart and across the 1/2-byte varint boundary, null / empty / present
    keys, negative and large timestamp deltas, offsets with gaps."""
    rng = np.random.default_rng(seed)
    vlen = [0, 1, 2, 3, 4, 5, 63, 64, 65, 127, 128, 200]
    values = [
        bytes(rng.integers(0, 256, vlen[(i + seed) % len(vlen)], np.uint8))
        for i in range(n)
    ]
    keys = [
        (None, b"", b"k", bytes(rng.integers(0, 256, 70, np.uint8)))[
            (i + seed) % 4
        ]
        for i in range(n)
    ]
    offs = np.cumsum(rng.integers(1, 90, n)) + 8100 * seed
    ts_corners = [0, -1, 1, -64, 63, 64, -(2**40), 2**40, INT64_MIN, INT64_MAX]
    tss = [ts_corners[(i + seed) % len(ts_corners)] for i in range(n)]
    return values, keys, offs, tss


def _encode_chunks(chunks, max_bytes, resume=None):
    """`tpu_materialize`'s own pass over a slice's chunks: (slab bytes,
    rows kept, forms, the offset delta a cut response resumes after)."""
    raw, kept, cut_at, forms = smart_chain._encode_outputs(
        chunks, max_bytes, resume
    )
    return raw, kept, forms, cut_at


# -- byte outputs ---------------------------------------------------------------


@pytest.mark.parametrize("n_chunks", [1, 2, 3, 4, 5, 6])
def test_flat_chunks_append_to_one_slab(n_chunks):
    per = 13
    values, keys, offs, tss = _corpus(per * n_chunks, seed=n_chunks)
    chunks = [
        _flat_chunk(values[a : a + per], keys[a : a + per],
                    offs[a : a + per], tss[a : a + per], pad_rows=a % 4)
        for a in range(0, per * n_chunks, per)
    ]
    raw, kept, forms, cut_at = _encode_chunks(chunks, 0)
    assert raw == _wire(_records(values, keys, offs, tss))
    assert kept == per * n_chunks and forms == {"enc-direct-bytes"}
    assert cut_at is None


@pytest.mark.parametrize("drop", ["none", "middle", "chunk-edge", "all"])
def test_flat_resume_drop(drop):
    per = 9
    values, keys, offs, tss = _corpus(per * 3, seed=7)
    chunks = [
        _flat_chunk(values[a : a + per], keys[a : a + per],
                    offs[a : a + per], tss[a : a + per])
        for a in range(0, per * 3, per)
    ]
    at = {"none": 0, "middle": 13, "chunk-edge": per, "all": per * 3}[drop]
    # a cursor between two survivors' offsets resumes at the later one
    resume = int(offs[at - 1]) + 1 if at else int(offs[0])
    raw, kept, _forms, _last = _encode_chunks(chunks, 0, resume)
    assert kept == per * 3 - at
    assert raw == _wire(_records(values[at:], keys[at:], offs[at:], tss[at:]))


def _cut_cases():
    values, keys, offs, tss = _corpus(14, seed=3)
    sizes = _sizes(values, keys, offs, tss)
    cum = np.cumsum(sizes)
    # every record boundary, one byte either side of it, and a budget
    # smaller than the first record
    budgets = sorted({1, int(sizes[0]) - 1} | {
        int(c) + d for c in cum for d in (-1, 0, 1)
    })
    return [(b, split) for b in budgets for split in (14, 5)]


@pytest.mark.parametrize("max_bytes,split", _cut_cases())
def test_flat_cut_at_every_record_boundary(max_bytes, split):
    values, keys, offs, tss = _corpus(14, seed=3)
    chunks = [
        _flat_chunk(values[a : a + split], keys[a : a + split],
                    offs[a : a + split], tss[a : a + split])
        for a in range(0, 14, split)
    ]
    sizes = _sizes(values, keys, offs, tss)
    keep = _numpy_keep(sizes, max_bytes)
    raw, kept, _forms, cut_at = _encode_chunks(chunks, max_bytes)
    assert kept == keep >= 1
    assert raw == _wire(_records(values, keys, offs, tss)[:keep])
    assert len(raw) == int(sizes[:keep].sum())
    # `next_offset` of a cut response: one past the last kept survivor
    assert cut_at == (offs[keep - 1] if keep < 14 else None)


def test_flat_cut_after_a_resume_drop():
    values, keys, offs, tss = _corpus(20, seed=5)
    chunks = [
        _flat_chunk(values[a : a + 10], keys[a : a + 10],
                    offs[a : a + 10], tss[a : a + 10])
        for a in (0, 10)
    ]
    sizes = _sizes(values, keys, offs, tss)
    drop = 6
    max_bytes = int(sizes[drop : drop + 7].sum())   # ends inside chunk two
    keep = _numpy_keep(sizes[drop:], max_bytes)
    raw, kept, _forms, cut_at = _encode_chunks(
        chunks, max_bytes, int(offs[drop])
    )
    assert kept == keep == 7
    assert raw == _wire(_records(values, keys, offs, tss)[drop : drop + keep])
    assert cut_at == offs[drop + keep - 1]


@pytest.mark.parametrize("what", ["empty-chunk", "all-empty-values"])
def test_flat_degenerate_chunks(what):
    if what == "empty-chunk":
        chunks = [_flat_chunk([], [], [], [], pad_rows=8)]
        assert _encode_chunks(chunks, 64)[:2] == (b"", 0)
        return
    values, keys = [b""] * 5, [None] * 5
    offs, tss = np.arange(5), [0] * 5
    raw, kept, _f, _l = _encode_chunks(
        [_flat_chunk(values, keys, offs, tss)], 0
    )
    assert kept == 5 and raw == _wire(_records(values, keys, offs, tss))


@pytest.mark.parametrize("fault", ["start", "length", "key-length", "rows"])
def test_a_row_outside_its_column_is_refused(fault):
    values, keys, offs, tss = _corpus(6, seed=1)
    c = _flat_chunk(values, keys, offs, tss)
    if fault == "start":
        c._starts[5] = len(c._flat)
        c.lengths[5] = 4
    elif fault == "length":
        c.lengths[2] = -1
    elif fault == "key-length":
        c.key_lengths[3] = c.keys.shape[1] + 1
    else:
        c.count = len(c.lengths) + 1
    with pytest.raises(ValueError):
        c.encode_into(native_backend.record_slab())


# -- int outputs ----------------------------------------------------------------


INT_CORNERS = [
    0, 1, -1, 9, 10, -9, -10, 99, 100, -99, -100, 999_999_999, 1_000_000_000,
    10**18 - 1, 10**18, -(10**18), INT64_MAX, INT64_MIN, INT64_MIN + 1,
    -(10**18) + 1, 10**17, 4_294_967_296, -4_294_967_296,
]


@pytest.mark.parametrize("value", INT_CORNERS)
def test_int_decimal_is_byte_equal(value):
    c = _int_chunk([value], [None], [7], [0])
    raw, kept, forms, _last = _encode_chunks([c], 0)
    assert forms == {"enc-direct-int"} and kept == 1
    assert raw == _wire([Record(value=str(value).encode(), offset_delta=7)])
    # and to the rendered form every other consumer gets
    mat, lens = TpuChainExecutor._ints_to_ascii_host(np.array([value], np.int64))
    assert mat[0, : lens[0]].tobytes() == str(value).encode()
    assert c.to_records()[0].value == str(value).encode()


@pytest.mark.parametrize("drop,max_bytes", [
    (0, 0), (5, 0), (0, 40), (9, 55), (len(INT_CORNERS), 0),
])
def test_int_column_with_keys_drop_and_cut(drop, max_bytes):
    n = len(INT_CORNERS)
    _v, keys, offs, tss = _corpus(n, seed=11)
    values = [str(v).encode() for v in INT_CORNERS]
    half = n // 2
    chunks = [
        _int_chunk(INT_CORNERS[a:b], keys[a:b], offs[a:b], tss[a:b])
        for a, b in ((0, half), (half, n))
    ]
    sizes = _sizes(values, keys, offs, tss)
    keep = _numpy_keep(sizes[drop:], max_bytes)
    resume = int(offs[drop]) if drop < n else int(offs[-1]) + 1
    raw, kept, _forms, _last = _encode_chunks(chunks, max_bytes, resume)
    assert kept == keep
    assert raw == _wire(_records(values, keys, offs, tss)[drop : drop + keep])


def test_int_backed_buffer_renders_on_demand():
    """Every consumer that is not the served encode gets today's rendered
    form from the same int column."""
    ints = np.array(INT_CORNERS, np.int64)
    n = len(ints)
    c = _int_chunk(ints, [None] * n, np.arange(n), [0] * n)
    assert c.lengths is None and c.values is None
    cols = _int_chunk(ints, [None] * n, np.arange(n), [0] * n).to_columns()
    want = [str(int(v)).encode() for v in ints]
    got = [
        cols["val_flat"][a:b].tobytes()
        for a, b in zip(cols["val_off"][:-1], cols["val_off"][1:])
    ]
    assert got == want
    dense = c.dense_values()
    assert dense.shape == (n + 2, 32) and c.width == 32
    assert [dense[i, : c.lengths[i]].tobytes() for i in range(n)] == want
    assert [r.value for r in c.to_records()] == want
    # once rendered it is a dense buffer, and encodes to the same bytes
    raw, _k, forms, _l = _encode_chunks([c], 0)
    assert forms == {"enc-columns"}
    assert raw == _wire([
        Record(value=v, offset_delta=i) for i, v in enumerate(want)
    ])


# -- the general form -------------------------------------------------------------


@pytest.mark.parametrize("max_bytes", [0, 1, 300, 10**6])
def test_dense_buffer_takes_the_columns_form(max_bytes):
    values, keys, offs, tss = _corpus(12, seed=2)
    recs = _records(values, keys, offs, tss)
    dense = RecordBuffer.from_records(recs)
    sizes = _sizes(values, keys, offs, tss)
    keep = _numpy_keep(sizes, max_bytes)
    raw, kept, forms, _last = _encode_chunks([dense], max_bytes)
    assert forms == {"enc-columns"} and kept == keep
    assert raw == _wire(recs[:keep])


def test_encode_record_columns_keeps_its_contract():
    values, keys, offs, tss = _corpus(12, seed=4)
    recs = _records(values, keys, offs, tss)
    c = RecordBuffer.from_records(recs).to_columns()
    raw = native_backend.encode_record_columns(
        c["val_flat"], c["val_off"], c["key_flat"], c["key_off"],
        c["key_present"], c["off_delta"], c["ts_delta"],
    )
    assert raw == _wire(recs)
    empty = RecordBuffer.from_records([]).to_columns()
    assert native_backend.encode_record_columns(
        empty["val_flat"], empty["val_off"], empty["key_flat"],
        empty["key_off"], empty["key_present"], empty["off_delta"],
        empty["ts_delta"],
    ) == b""
