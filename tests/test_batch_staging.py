"""Broker fast-path staging: native columnar codecs + pipelined batches.

Covers the stream-fetch hot loop's batch-level byte assembly: record
slabs -> RecordBuffer columns via the native parser, outputs back to
wire batches via the native encoder, and wire-level equivalence of
`process_batches` between the pipelined TPU path and the per-record
Python path (parity model: fluvio-spu/src/smartengine/batch.rs:41-140).
"""

from __future__ import annotations

import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.codec import ByteReader, ByteWriter
from fluvio_tpu.protocol.record import Batch, Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine import native_backend
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.spu.smart_chain import _tpu_process_batches, process_batches

native_available = native_backend.load_library() is not None
needs_native = pytest.mark.skipif(
    not native_available, reason="native library unavailable"
)


def _records(n, start=0, keyed=False):
    out = []
    for i in range(start, start + n):
        name = "fluvio" if i % 3 else "kafka"
        r = Record(value=f'{{"name":"{name}-{i}","n":{i}}}'.encode())
        if keyed and i % 2:
            r.key = f"k{i}".encode()
        r.timestamp_delta = i * 7
        out.append(r)
    return out


def _encode_records(records):
    w = ByteWriter()
    for i, r in enumerate(records):
        r.offset_delta = i
        r.encode(w)
    return w.bytes()


@needs_native
class TestNativeCodecs:
    def test_decode_matches_python(self):
        records = _records(17, keyed=True)
        raw = _encode_records(records)
        cols = native_backend.decode_record_columns(raw)
        assert cols["count"] == len(records)
        for i, rec in enumerate(records):
            v = cols["val_flat"][cols["val_off"][i] : cols["val_off"][i + 1]]
            assert v.tobytes() == rec.value
            if rec.key is not None:
                assert cols["key_present"][i]
                k = cols["key_flat"][cols["key_off"][i] : cols["key_off"][i + 1]]
                assert k.tobytes() == rec.key
            else:
                assert not cols["key_present"][i]
            assert cols["off_delta"][i] == i
            assert cols["ts_delta"][i] == rec.timestamp_delta

    def test_encode_matches_python(self):
        records = _records(11, keyed=True)
        expected = _encode_records(records)
        buf = RecordBuffer.from_records(records)
        cols = buf.to_columns()
        raw = native_backend.encode_record_columns(
            cols["val_flat"],
            cols["val_off"],
            cols["key_flat"],
            cols["key_off"],
            cols["key_present"],
            cols["off_delta"],
            cols["ts_delta"],
        )
        assert raw == expected

    def test_roundtrip_through_buffer(self):
        records = _records(9, keyed=True)
        raw = _encode_records(records)
        cols = native_backend.decode_record_columns(raw)
        buf = RecordBuffer.from_columns(cols, base_offset=5, base_timestamp=100)
        got = buf.to_records()
        for rec, orig in zip(got, records):
            assert rec.value == orig.value
            assert rec.key == orig.key
            assert rec.timestamp_delta == orig.timestamp_delta
        assert buf.base_offset == 5

    def test_empty_slab(self):
        cols = native_backend.decode_record_columns(b"")
        assert cols["count"] == 0

    def test_malformed_slabs_report_partial_parse(self):
        """Any truncation/garbage => parsed != len(raw), so the broker
        fast path falls back instead of silently dropping the tail."""
        records = _records(5, keyed=True)
        raw = _encode_records(records)
        cases = {
            "truncated final record": raw[:-3],
            "trailing garbage": raw + b"\x07\x01",
            "mid-varint cut": raw[: len(raw) - len(raw) // 3],
        }
        for label, bad in cases.items():
            cols = native_backend.decode_record_columns(bad)
            assert cols["parsed"] != len(bad), label
            # whatever did parse is whole records with intact values
            for i in range(cols["count"]):
                v = cols["val_flat"][cols["val_off"][i] : cols["val_off"][i + 1]]
                assert v.tobytes() == records[i].value, label

    def test_well_formed_slab_parses_to_end(self):
        records = _records(7, keyed=True)
        raw = _encode_records(records)
        cols = native_backend.decode_record_columns(raw)
        assert cols["parsed"] == len(raw)

    def test_malformed_slab_falls_back_to_per_record_path(self):
        """A batch whose slab is truncated but whose header still claims
        the full record count must not be served by the fast path."""
        from fluvio_tpu.spu import smart_chain

        records = _records(6)
        raw = _encode_records(records)
        batch = Batch(base_offset=0, raw_records=raw[:-2], raw_record_count=6)
        chain = _chain("tpu", ("regex-filter", {"regex": "fluvio"}))
        res = smart_chain._tpu_process_batches(chain, [batch], max_bytes=1 << 20)
        assert res is None  # declined -> per-record path decides


def _chain(backend, *specs):
    b = SmartEngine(backend=backend).builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize()


def _shallow_batches(record_groups, base_offsets, first_ts=5000):
    """Wire-encode batches then decode shallow (raw_records set)."""
    w = ByteWriter()
    for recs, base in zip(record_groups, base_offsets):
        b = Batch.from_records(recs, base_offset=base, first_timestamp=first_ts)
        b.encode(w)
    r = ByteReader(w.bytes())
    out = []
    while r.remaining() > 0:
        out.append(Batch.decode(r, parse_records=False))
    return out


def _wire(result):
    w = ByteWriter()
    for b in result.records.batches:
        b.encode(w)
    return w.bytes()


def _flat_records(result):
    """(value, key, abs_timestamp, abs_offset) per record across all
    output batches — offset parity between the fast and per-record paths
    is part of the contract (consumers resume by offset)."""
    out = []
    for b in result.records.batches:
        ts = b.header.first_timestamp
        for rec in b.memory_records():
            out.append(
                (rec.value, rec.key, ts + rec.timestamp_delta,
                 b.base_offset + rec.offset_delta)
            )
    return out


@needs_native
class TestPipelinedProcessBatches:
    def test_filter_map_equivalence(self):
        """The fast path coalesces the slice into one output batch; record
        content, timestamps, and the consumer's next offset must match the
        per-record path."""
        groups = [_records(40), _records(40, start=40), _records(13, start=80)]
        bases = [0, 40, 80]
        specs = (("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"}))

        tpu_chain = _chain("tpu", *specs)
        assert tpu_chain.tpu_chain is not None
        fast = _tpu_process_batches(
            tpu_chain, _shallow_batches(groups, bases), 10**9
        )
        assert fast is not None
        assert len(fast.records.batches) == 1

        py_chain = _chain("python", *specs)
        slow = process_batches(py_chain, _shallow_batches(groups, bases), 10**9)

        assert _flat_records(fast) == _flat_records(slow)
        assert fast.next_offset == slow.next_offset == 93
        # the coalesced batch spans the full consumed offset range
        b = fast.records.batches[0]
        assert b.base_offset == 0
        assert b.header.last_offset_delta == 92

    def test_aggregate_carry_across_batches(self):
        groups = [
            [Record(value=str(i).encode()) for i in range(10)],
            [Record(value=str(100 + i).encode()) for i in range(10)],
        ]
        bases = [0, 10]
        specs = (("aggregate-sum", None),)
        tpu_chain = _chain("tpu", *specs)
        fast = _tpu_process_batches(
            tpu_chain, _shallow_batches(groups, bases), 10**9
        )
        py_chain = _chain("python", *specs)
        slow = process_batches(py_chain, _shallow_batches(groups, bases), 10**9)
        assert _flat_records(fast) == _flat_records(slow)
        # host state mirrors device carries after the run
        expect = sum(range(10)) + sum(range(100, 110))
        assert tpu_chain.tpu_chain.carries[0][0] == expect

    def test_timestamp_rebase_across_batches(self):
        """Batches with different base timestamps coalesce with rebased
        deltas; absolute record timestamps are preserved."""
        g1 = [Record(value=b"fluvio-a")]
        g1[0].timestamp_delta = 5
        g2 = [Record(value=b"fluvio-b")]
        g2[0].timestamp_delta = 9
        w = ByteWriter()
        Batch.from_records(g1, base_offset=0, first_timestamp=1000).encode(w)
        Batch.from_records(g2, base_offset=1, first_timestamp=2000).encode(w)
        r = ByteReader(w.bytes())
        batches = []
        while r.remaining() > 0:
            batches.append(Batch.decode(r, parse_records=False))
        tpu_chain = _chain("tpu", ("regex-filter", {"regex": "fluvio"}))
        fast = _tpu_process_batches(tpu_chain, batches, 10**9)
        assert [t for _, _, t, _ in _flat_records(fast)] == [1005, 2009]

    def test_falls_back_without_tpu_chain(self):
        py_chain = _chain("python", ("regex-filter", {"regex": "x"}))
        assert py_chain.tpu_chain is None
        groups = [_records(4)]
        assert _tpu_process_batches(py_chain, _shallow_batches(groups, [0]), 10**9) is None

    def test_keyed_records_roundtrip(self):
        groups = [_records(16, keyed=True)]
        specs = (("regex-filter", {"regex": "fluvio"}),)
        tpu_chain = _chain("tpu", *specs)
        fast = _tpu_process_batches(tpu_chain, _shallow_batches(groups, [0]), 10**9)
        py_chain = _chain("python", *specs)
        slow = process_batches(py_chain, _shallow_batches(groups, [0]), 10**9)
        assert _flat_records(fast) == _flat_records(slow)

    def test_survivors_keep_stored_offsets(self):
        """Surviving records keep their absolute stored offsets, so a
        consumer resuming mid-slice never drops records that rebasing
        would have pushed below its requested offset."""
        groups = [_records(9), _records(9, start=9)]
        bases = [100, 109]
        tpu_chain = _chain("tpu", ("regex-filter", {"regex": "fluvio"}))
        fast = _tpu_process_batches(tpu_chain, _shallow_batches(groups, bases), 10**9)
        [batch] = fast.records.batches
        abs_offsets = [
            batch.base_offset + r.offset_delta for r in batch.memory_records()
        ]
        # survivors are the i % 3 != 0 records at stored offsets 100..117
        expect = [100 + i for i in range(18) if i % 3]
        assert abs_offsets == expect

    def test_stateless_max_bytes_trims_output(self):
        groups = [[Record(value=b"fluvio-" + bytes([65 + j]) * 40) for j in range(20)]]
        tpu_chain = _chain("tpu", ("regex-filter", {"regex": "fluvio"}))
        fast = _tpu_process_batches(
            tpu_chain, _shallow_batches(groups, [0]), max_bytes=120
        )
        [batch] = fast.records.batches
        n_kept = batch.records_len()
        assert 0 < n_kept < 20
        # next fetch resumes right after the last delivered record
        assert fast.next_offset == n_kept
        # parity: the per-record path stops after crossing max_bytes too
        sizes = [r.write_size() for r in groups[0]]
        total, expect_kept = 0, 0
        for s in sizes:
            total += s
            expect_kept += 1
            if total >= 120:
                break
        assert n_kept == expect_kept


@needs_native
class TestFastpathObservability:
    """Fallback/fastpath counters (review round 2 weak#6): a silent drop to
    the per-record loop is a ~100x cliff — it must be visible."""

    def test_fastpath_counts(self):
        from fluvio_tpu.smartengine.metrics import SmartModuleChainMetrics

        chain = _chain("tpu", ("regex-filter", {"regex": "fluvio"}))
        m = SmartModuleChainMetrics()
        batches = _shallow_batches([_records(8)], [0])
        process_batches(chain, batches, 1 << 20, m)
        d = m.to_dict()
        assert d["fastpath_slices"] == 1 and d["fallback_slices"] == 0

    def test_malformed_slab_counts_fallback_reason(self):
        from fluvio_tpu.smartengine.metrics import SmartModuleChainMetrics

        records = _records(5)
        raw = _encode_records(records)
        batch = Batch(base_offset=0, raw_records=raw[:-2], raw_record_count=5)
        chain = _chain("tpu", ("regex-filter", {"regex": "fluvio"}))
        m = SmartModuleChainMetrics()
        try:
            process_batches(chain, [batch], 1 << 20, m)
        except Exception:
            pass  # the per-record path raises on the corrupt slab
        d = m.to_dict()
        assert d["fallback_slices"] == 1
        assert d["fallback_reasons"] == {"malformed-slab": 1}


@needs_native
class TestAlignedDecode:
    """The v2 (aligned) decoder + flat-backed RecordBuffer: parity with
    the v1 path and the edge cases the padded matrix used to paper over."""

    def test_parity_with_v1(self):
        records = _records(23, keyed=True)
        raw = _encode_records(records)
        v1 = RecordBuffer.from_columns(
            native_backend.decode_record_columns(raw), 5, 100
        )
        v2 = RecordBuffer.from_flat(
            native_backend.decode_record_columns_aligned(raw), 5, 100
        )
        assert v2.values is None  # flat-backed until someone asks
        assert (v1.rows, v1.width) == (v2.rows, v2.width)
        assert np.array_equal(v1.dense_values(), v2.dense_values())
        assert np.array_equal(v1.lengths, v2.lengths)
        assert np.array_equal(v1.keys, v2.keys)
        assert np.array_equal(v1.key_lengths, v2.key_lengths)
        assert np.array_equal(v1.offset_deltas, v2.offset_deltas)
        assert [
            (r.value, r.key, r.offset_delta) for r in v1.to_records()
        ] == [(r.value, r.key, r.offset_delta) for r in v2.to_records()]

    def test_upload_form_matches_dense_derivation(self):
        records = _records(9)
        raw = _encode_records(records)
        v2 = RecordBuffer.from_flat(
            native_backend.decode_record_columns_aligned(raw)
        )
        dense = RecordBuffer.from_columns(
            native_backend.decode_record_columns(raw)
        )
        f2, s2 = v2.ragged_values()
        f1, s1 = dense.ragged_values()
        assert np.array_equal(f1, f2)
        assert np.array_equal(s1[: v2.count], s2[: v2.count])

    def test_tombstones_empty_values(self):
        records = [Record(key=b"k%d" % i, value=b"") for i in range(5)]
        raw = _encode_records(records)
        v2 = RecordBuffer.from_flat(
            native_backend.decode_record_columns_aligned(raw)
        )
        out = v2.to_records()  # dense_values on an empty flat must not crash
        assert [r.key for r in out] == [b"k0", b"k1", b"k2", b"k3", b"k4"]
        assert all(r.value == b"" for r in out)

    def test_empty_slab(self):
        cols = native_backend.decode_record_columns_aligned(b"")
        assert cols["count"] == 0 and cols["parsed"] == 0
        v2 = RecordBuffer.from_flat(cols)
        assert v2.count == 0
        assert v2.to_records() == []

    def test_malformed_slab_parity(self):
        records = _records(6)
        raw = _encode_records(records)
        v2 = native_backend.decode_record_columns_aligned(raw[:-2])
        v1 = native_backend.decode_record_columns(raw[:-2])
        assert v2["count"] == v1["count"] == 5
        assert v2["parsed"] == v1["parsed"] != len(raw[:-2])

    def test_tombstones_through_tpu_chain(self):
        """Empty-value records through the flat-backed fast path."""
        groups = [[Record(key=b"a", value=b""), Record(key=b"b", value=b"x")]]
        fast_chain = _chain("tpu", ("regex-filter", {"regex": ""}))
        slow_chain = _chain("python", ("regex-filter", {"regex": ""}))
        fast = process_batches(fast_chain, _shallow_batches(groups, [0]), 1 << 20)
        slow = process_batches(slow_chain, _shallow_batches(groups, [0]), 1 << 20)
        assert _flat_records(fast) == _flat_records(slow)

    def test_fuzz_random_shapes_parity(self):
        rng = np.random.default_rng(31)
        for trial in range(20):
            n = int(rng.integers(1, 40))
            records = []
            for i in range(n):
                vlen = int(rng.integers(0, 120))
                v = bytes(rng.integers(0, 256, size=vlen, dtype=np.uint8))
                r = Record(value=v)
                if rng.random() < 0.5:
                    klen = int(rng.integers(0, 20))
                    r.key = bytes(rng.integers(0, 256, size=klen, dtype=np.uint8))
                r.timestamp_delta = int(rng.integers(0, 10000))
                records.append(r)
            raw = _encode_records(records)
            v1 = RecordBuffer.from_columns(
                native_backend.decode_record_columns(raw)
            )
            v2 = RecordBuffer.from_flat(
                native_backend.decode_record_columns_aligned(raw)
            )
            a = [(r.value, r.key, r.offset_delta, r.timestamp_delta)
                 for r in v1.to_records()]
            b = [(r.value, r.key, r.offset_delta, r.timestamp_delta)
                 for r in v2.to_records()]
            assert a == b, trial
            f1, s1 = v1.ragged_values()
            f2, s2 = v2.ragged_values()
            assert np.array_equal(f1, f2) and np.array_equal(
                s1[:n], s2[:n]
            ), trial


@needs_native
class TestChunkedDispatch:
    """Stateless slices split into several concurrent device dispatches
    (smart_chain._DISPATCH_CHUNK_ROWS); output must be bit-identical to
    the single-dispatch and per-record paths."""

    def _run_chunked(self, groups, bases, specs, chunk, max_bytes=10**9):
        import fluvio_tpu.spu.smart_chain as sm

        old = sm._DISPATCH_CHUNK_ROWS
        sm._DISPATCH_CHUNK_ROWS = chunk
        try:
            chain = _chain("tpu", *specs)
            return _tpu_process_batches(
                chain, _shallow_batches(groups, bases), max_bytes
            )
        finally:
            sm._DISPATCH_CHUNK_ROWS = old

    def test_multi_chunk_equivalence(self):
        groups = [_records(40, keyed=True), _records(40, start=40),
                  _records(13, start=80, keyed=True)]
        bases = [0, 40, 80]
        specs = (("regex-filter", {"regex": "fluvio"}),
                 ("json-map", {"field": "name"}))
        fast = self._run_chunked(groups, bases, specs, chunk=16)
        assert fast is not None
        slow = process_batches(
            _chain("python", *specs), _shallow_batches(groups, bases), 10**9
        )
        assert _flat_records(fast) == _flat_records(slow)
        assert fast.next_offset == slow.next_offset

    def test_chunk_boundary_sizes(self):
        """Counts around the 1.5x-chunk threshold and exact multiples."""
        specs = (("regex-filter", {"regex": "fluvio"}),)
        for n in (15, 16, 24, 25, 32, 48):
            groups, bases = [_records(n)], [0]
            fast = self._run_chunked(groups, bases, specs, chunk=16)
            slow = process_batches(
                _chain("python", *specs), _shallow_batches(groups, bases), 10**9
            )
            assert _flat_records(fast) == _flat_records(slow), n

    def test_chunked_max_bytes_truncation(self):
        """max_bytes cutoff over a merged multi-chunk output matches the
        single-dispatch fast path's record-prefix semantics exactly
        (the per-record path trims at batch granularity instead)."""
        groups, bases = [_records(60)], [0]
        specs = (("regex-filter", {"regex": "fluvio"}),)
        chunked = self._run_chunked(groups, bases, specs, chunk=16,
                                    max_bytes=700)
        single = self._run_chunked(groups, bases, specs, chunk=10**6,
                                   max_bytes=700)
        assert _flat_records(chunked) == _flat_records(single)
        assert chunked.next_offset == single.next_offset
        # and the cutoff actually trimmed the slice
        assert chunked.next_offset < 60

    def test_zero_record_slice(self):
        """A slice whose batches carry zero records stages one empty
        chunk and completes (regression: _MergedOut([]) crash)."""
        from fluvio_tpu.spu.smart_chain import tpu_stage_dispatch, tpu_finish

        chain = _chain("tpu", ("regex-filter", {"regex": "fluvio"}))
        batches = _shallow_batches([[]], [0])
        pending = tpu_stage_dispatch(chain, batches)
        assert pending is not None and len(pending.chunks) == 1
        result = tpu_finish(chain, pending, 10**9)
        assert result is not None
        assert not result.records.batches


FILTER_SRC = b"""
@smartmodule.filter(dsl=dsl.FilterProgram(
    predicate=dsl.RegexMatch(arg=dsl.Value(), pattern="@param:field=regex")))
def f(record):
    import re
    return re.search(params["regex"].encode(), record.value) is not None
"""

AGG_SRC = b"""
@smartmodule.aggregate(dsl=dsl.AggregateProgram(
    contribution=dsl.ParseInt(arg=dsl.Value()), combine="add"))
def agg(acc, record):
    return str(int(acc or b"0") + int(record.value)).encode()
"""


class TestStreamChainCache:
    @staticmethod
    def _ctx():
        from fluvio_tpu.spu import SpuConfig
        from fluvio_tpu.spu.context import GlobalContext

        return GlobalContext(SpuConfig(id=1))

    @staticmethod
    def _inv(src, kind, params=None, lookback_last=0):
        from fluvio_tpu.schema.smartmodule import (
            SmartModuleInvocation, SmartModuleInvocationWasm,
        )

        return [SmartModuleInvocation(
            wasm=SmartModuleInvocationWasm.adhoc(src),
            kind=kind,
            params=params or {},
            lookback_last=lookback_last,
        )]

    def test_stateless_chain_shared(self):
        from fluvio_tpu.schema.smartmodule import SmartModuleInvocationKind
        from fluvio_tpu.spu.smart_chain import acquire_stream_chain

        ctx = self._ctx()
        k = SmartModuleInvocationKind.FILTER
        inv = self._inv(FILTER_SRC, k, {"regex": "fluvio"})
        c1 = acquire_stream_chain(inv, ctx, version=23)
        c2 = acquire_stream_chain(inv, ctx, version=23)
        assert c1 is c2
        # different params -> different chain
        inv2 = self._inv(FILTER_SRC, k, {"regex": "kafka"})
        assert acquire_stream_chain(inv2, ctx, version=23) is not c1

    def test_stateful_chain_not_shared(self):
        from fluvio_tpu.schema.smartmodule import SmartModuleInvocationKind
        from fluvio_tpu.spu.smart_chain import acquire_stream_chain

        ctx = self._ctx()
        inv = self._inv(AGG_SRC, SmartModuleInvocationKind.AGGREGATE)
        assert acquire_stream_chain(inv, ctx) is not acquire_stream_chain(inv, ctx)

    def test_lookback_chain_not_shared(self):
        from fluvio_tpu.schema.smartmodule import SmartModuleInvocationKind
        from fluvio_tpu.spu.smart_chain import acquire_stream_chain

        ctx = self._ctx()
        inv = self._inv(
            FILTER_SRC, SmartModuleInvocationKind.FILTER,
            {"regex": "fluvio"}, lookback_last=5,
        )
        assert acquire_stream_chain(inv, ctx) is not acquire_stream_chain(inv, ctx)

    def test_poisoned_chain_evicted_from_cache(self):
        """A cached chain that a fuel trap poisoned must never be served
        to a new stream: the cache hit drops the entry and rebuilds
        (ADVICE r4 medium)."""
        from fluvio_tpu.schema.smartmodule import SmartModuleInvocationKind
        from fluvio_tpu.spu.smart_chain import acquire_stream_chain

        ctx = self._ctx()
        inv = self._inv(
            FILTER_SRC, SmartModuleInvocationKind.FILTER, {"regex": "fluvio"}
        )
        c1 = acquire_stream_chain(inv, ctx, version=23)
        c1._poisoned = object()  # what an abandoned fuel trap sets
        c2 = acquire_stream_chain(inv, ctx, version=23)
        assert c2 is not c1
        assert c2._poisoned is None
        # the fresh chain replaced the poisoned entry in the cache
        assert acquire_stream_chain(inv, ctx, version=23) is c2
