"""Sharded (multi-device) chain execution — equivalence vs single device.

Exercises `fluvio_tpu.parallel` (make_record_mesh / shard_buffer_arrays /
sharded_chain_step) on the 8-device virtual CPU mesh the conftest forces.
Every test asserts bit-equality of the sharded run against the plain
single-device jit of the same fused chain: GSPMD is allowed to insert
collectives (the aggregate prefix scan and the compaction cumsum cross
shards) but never to change results.

Rigor model: the reference's multi-"node"-in-one-process replication
tests (fluvio-spu/src/replication/test.rs:736).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.parallel import (
    RECORD_AXIS,
    make_record_mesh,
    shard_buffer_arrays,
    sharded_chain_step,
)
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

N_DEV = 8

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < N_DEV, reason=f"needs {N_DEV} virtual devices"
)


def _chain(*specs):
    """specs: (module-name, params) pairs -> TpuChainExecutor."""
    b = SmartEngine(backend="tpu").builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    chain = b.initialize()
    assert chain.tpu_chain is not None, "chain must lower to TPU"
    return chain.tpu_chain


def _buffer(values, timestamps=None, rows=None, base_timestamp=1000):
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
        if timestamps is not None:
            r.timestamp_delta = timestamps[i]
    buf = RecordBuffer.from_records(
        records, base_offset=0, base_timestamp=base_timestamp
    )
    if rows is not None and buf.values.shape[0] != rows:
        raise AssertionError(
            f"buffer rows {buf.values.shape[0]} != expected {rows}"
        )
    return buf


def _arrays(buf):
    return {
        "values": jnp.asarray(buf.values),
        "lengths": jnp.asarray(buf.lengths),
        "keys": jnp.asarray(buf.keys),
        "key_lengths": jnp.asarray(buf.key_lengths),
        "offset_deltas": jnp.asarray(buf.offset_deltas),
        "timestamp_deltas": jnp.asarray(buf.timestamp_deltas),
    }


def _carries(executor):
    return tuple(
        (jnp.int64(acc), jnp.int64(win), jnp.asarray(has))
        for acc, win, has in executor.carries
    )


def _run_single(executor, buf, carries):
    return jax.jit(executor._chain_fn)(
        _arrays(buf), jnp.int32(buf.count), jnp.int64(buf.base_timestamp), carries
    )


def _run_sharded(executor, buf, mesh, carries):
    with mesh:
        sharded = shard_buffer_arrays(_arrays(buf), mesh)
        run = sharded_chain_step(executor, mesh)
        return run(
            sharded, jnp.int32(buf.count), jnp.int64(buf.base_timestamp), carries
        )


def _assert_equal(single, sharded):
    s_header, s_packed, s_carries = single
    m_header, m_packed, m_carries = sharded
    np.testing.assert_array_equal(np.asarray(s_header), np.asarray(m_header))
    assert set(s_packed.keys()) == set(m_packed.keys())
    for k in s_packed:
        np.testing.assert_array_equal(
            np.asarray(s_packed[k]), np.asarray(m_packed[k]),
            err_msg=f"packed column {k}",
        )
    for i, (ca, cb) in enumerate(zip(s_carries, m_carries)):
        for j, (a, b) in enumerate(zip(ca, cb)):
            assert np.asarray(a) == np.asarray(b), f"carry {i}[{j}]"


def _north_star_values(n):
    out = []
    for i in range(n):
        name = "fluvio" if i % 3 else "kafka"
        out.append(f'{{"name":"{name}-{i}","n":{i}}}'.encode())
    return out


def test_mesh_construction():
    mesh = make_record_mesh(N_DEV)
    assert mesh.axis_names == (RECORD_AXIS,)
    assert mesh.devices.size == N_DEV


def test_north_star_chain_sharded_equivalence():
    """regex-filter + json-map + aggregate-count: sharded == single."""
    ex_a = _chain(
        ("regex-filter", {"regex": "fluvio"}),
        ("json-map", {"field": "name"}),
        ("aggregate-count", None),
    )
    ex_b = _chain(
        ("regex-filter", {"regex": "fluvio"}),
        ("json-map", {"field": "name"}),
        ("aggregate-count", None),
    )
    buf = _buffer(_north_star_values(64))
    mesh = make_record_mesh(N_DEV)
    single = _run_single(ex_a, buf, _carries(ex_a))
    sharded = _run_sharded(ex_b, buf, mesh, _carries(ex_b))
    _assert_equal(single, sharded)
    assert int(np.asarray(single[0])[0]) > 0


def test_uneven_count_across_shards():
    """count=37 over 64 rows: the last shards hold only padding."""
    ex_a = _chain(("regex-filter", {"regex": "fluvio"}), ("aggregate-sum", None))
    ex_b = _chain(("regex-filter", {"regex": "fluvio"}), ("aggregate-sum", None))
    values = [f'fluvio {i}'.encode() for i in range(37)] + [b""] * 27
    buf = _buffer(values)
    buf.count = 37
    mesh = make_record_mesh(N_DEV)
    single = _run_single(ex_a, buf, _carries(ex_a))
    sharded = _run_sharded(ex_b, buf, mesh, _carries(ex_b))
    _assert_equal(single, sharded)
    # sanity: sum carry reflects only the 37 live rows
    assert int(np.asarray(sharded[2][0][0])) == 0  # "fluvio N" parses as 0


def test_all_filtered_shards():
    """No record matches: zero outputs, carries keep prior state."""
    ex_a = _chain(("regex-filter", {"regex": "nomatch"}), ("aggregate-count", None))
    ex_b = _chain(("regex-filter", {"regex": "nomatch"}), ("aggregate-count", None))
    buf = _buffer([f"record-{i}".encode() for i in range(64)])
    mesh = make_record_mesh(N_DEV)
    single = _run_single(ex_a, buf, _carries(ex_a))
    sharded = _run_sharded(ex_b, buf, mesh, _carries(ex_b))
    _assert_equal(single, sharded)
    assert int(np.asarray(sharded[0])[0]) == 0


def test_windowed_aggregate_sharded():
    """Window boundaries crossing shard boundaries: the segmented scan's
    resets must propagate across devices identically."""
    ex_a = _chain(("windowed-sum", {"kind": "sum_int", "window_ms": "100"}),)
    ex_b = _chain(("windowed-sum", {"kind": "sum_int", "window_ms": "100"}),)
    values = [str(i + 1).encode() for i in range(64)]
    # timestamps step 40ms: windows of 100ms close mid-shard and across shards
    timestamps = [i * 40 for i in range(64)]
    buf = _buffer(values, timestamps=timestamps, base_timestamp=1_000_000)
    mesh = make_record_mesh(N_DEV)
    single = _run_single(ex_a, buf, _carries(ex_a))
    sharded = _run_sharded(ex_b, buf, mesh, _carries(ex_b))
    _assert_equal(single, sharded)


def test_carry_continuity_across_sharded_batches():
    """Two consecutive sharded process calls: batch 2 consumes batch 1's
    carries; the whole sequence must match the single-device sequence."""
    ex_a = _chain(("aggregate-sum", None))
    ex_b = _chain(("aggregate-sum", None))
    buf1 = _buffer([str(i).encode() for i in range(64)])
    buf2 = _buffer([str(100 + i).encode() for i in range(64)])
    mesh = make_record_mesh(N_DEV)

    s1 = _run_single(ex_a, buf1, _carries(ex_a))
    s2 = _run_single(ex_a, buf2, s1[2])
    m1 = _run_sharded(ex_b, buf1, mesh, _carries(ex_b))
    m2 = _run_sharded(ex_b, buf2, mesh, m1[2])
    _assert_equal(s1, m1)
    _assert_equal(s2, m2)
    # running sum after both batches: sum(0..63) + sum(100..163)
    expect = sum(range(64)) + sum(range(100, 164))
    assert int(np.asarray(m2[2][0][0])) == expect


def test_windowed_carry_continuity_sharded():
    """Windowed aggregate state crossing a sharded process-call boundary:
    batch 2 continues the window batch 1 ended in."""
    ex_a = _chain(("windowed-sum", {"kind": "sum_int", "window_ms": "1000"}),)
    ex_b = _chain(("windowed-sum", {"kind": "sum_int", "window_ms": "1000"}),)
    # batch 1 ends inside window [0,1000); batch 2 starts there then rolls over
    buf1 = _buffer(
        [b"1"] * 64, timestamps=[i * 10 for i in range(64)], base_timestamp=0
    )
    buf2 = _buffer(
        [b"1"] * 64, timestamps=[640 + i * 10 for i in range(64)], base_timestamp=0
    )
    mesh = make_record_mesh(N_DEV)
    s1 = _run_single(ex_a, buf1, _carries(ex_a))
    s2 = _run_single(ex_a, buf2, s1[2])
    m1 = _run_sharded(ex_b, buf1, mesh, _carries(ex_b))
    m2 = _run_sharded(ex_b, buf2, mesh, m1[2])
    _assert_equal(s1, m1)
    _assert_equal(s2, m2)


def _engine_chain(mesh_devices, *specs, pallas=None):
    """Chain through the PUBLIC config surface (SmartEngine mesh_devices)."""
    b = SmartEngine(backend="tpu", mesh_devices=mesh_devices).builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize()


class TestShardedEngineMode:
    """shard_map engine mode: config-selected, pallas active per shard,
    bit-equal to the single-device executor through the full dispatch
    path (ragged staging on the single side, sharded puts on the other)."""

    def _run_both(self, specs, values, timestamps=None, base_ts=1000):
        from fluvio_tpu.smartmodule import SmartModuleInput

        single = _engine_chain(0, *specs)
        sharded = _engine_chain(N_DEV, *specs)
        assert sharded.tpu_chain._sharded is not None, "mesh mode not engaged"

        def records():
            from fluvio_tpu.protocol.record import Record

            out = []
            for i, v in enumerate(values):
                r = Record(value=v)
                r.offset_delta = i
                if timestamps:
                    r.timestamp_delta = timestamps[i]
                out.append(r)
            return out

        a = single.process(SmartModuleInput.from_records(records(), 0, base_ts))
        b = sharded.process(SmartModuleInput.from_records(records(), 0, base_ts))
        ka = [(r.value, r.key, r.offset_delta, r.timestamp_delta) for r in a.successes]
        kb = [(r.value, r.key, r.offset_delta, r.timestamp_delta) for r in b.successes]
        assert ka == kb
        return single, sharded, ka

    def test_north_star_chain_config_selected(self):
        _, sharded, out = self._run_both(
            [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})],
            _north_star_values(200),
        )
        assert len(out) > 0
        assert sharded.tpu_chain._viewable  # descriptor mode survives sharding

    def test_pallas_kernels_active_per_shard(self, monkeypatch):
        """The sharded trace must invoke the pallas span kernel (GSPMD
        tracing can't; shard_map can)."""
        import fluvio_tpu.smartengine.tpu.pallas_kernels as pk

        monkeypatch.setenv("FLUVIO_TPU_PALLAS", "interpret")
        calls = {"n": 0}
        orig = pk.json_get_span_pallas

        def spy(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(pk, "json_get_span_pallas", spy)
        self._run_both(
            [("json-map", {"field": "name"})], _north_star_values(64)
        )
        assert calls["n"] > 0

    def test_aggregate_cross_shard_carry(self):
        single, sharded, out = self._run_both(
            [("aggregate-sum", None)],
            [str(i).encode() for i in range(100)],
        )
        assert out[-1][0] == str(sum(range(100))).encode()
        # carries identical after the run
        sharded.tpu_chain._ensure_host_state()
        single.tpu_chain._ensure_host_state()
        assert sharded.tpu_chain.carries == single.tpu_chain.carries

    def test_windowed_aggregate_across_shards(self):
        self._run_both(
            [("windowed-sum", {"kind": "sum_int", "window_ms": "100"})],
            [str(i + 1).encode() for i in range(96)],
            timestamps=[i * 40 for i in range(96)],
            base_ts=1_000_000,
        )

    def test_carry_continuity_across_batches(self):
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartmodule import SmartModuleInput

        single = _engine_chain(0, ("aggregate-field", {"field": "n", "combine": "max"}))
        sharded = _engine_chain(N_DEV, ("aggregate-field", {"field": "n", "combine": "max"}))
        for lo in (0, 50):
            values = [
                f'{{"n":{(i * 37) % 91}}}'.encode() for i in range(lo, lo + 50)
            ]
            recs = lambda: [Record(value=v) for v in values]  # noqa: E731
            a = single.process(SmartModuleInput.from_records(recs()))
            b = sharded.process(SmartModuleInput.from_records(recs()))
            assert [r.value for r in a.successes] == [r.value for r in b.successes]

    def test_broker_fast_path_through_sharded_mode(self, tmp_path):
        """SPU config selects the mesh; the stream-fetch fast path runs
        through the sharded executor."""
        import asyncio

        from fluvio_tpu.protocol.codec import ByteReader, ByteWriter
        from fluvio_tpu.protocol.record import Batch, Record
        from fluvio_tpu.smartengine import native_backend
        from fluvio_tpu.spu.smart_chain import process_batches

        if native_backend.load_library() is None:
            pytest.skip("no native toolchain")
        chain = _engine_chain(
            N_DEV,
            ("regex-filter", {"regex": "fluvio"}),
            ("json-map", {"field": "name"}),
        )
        assert chain.tpu_chain._sharded is not None
        records = [Record(value=v) for v in _north_star_values(48)]
        w = ByteWriter()
        for i, r in enumerate(records):
            r.offset_delta = i
            r.encode(w)
        batch = Batch(base_offset=0, raw_records=w.bytes(), raw_record_count=48)
        batch.header.first_timestamp = 1000
        batch.header.last_offset_delta = 47
        fast = process_batches(chain, [batch], 1 << 20)
        slow_chain = _engine_chain(
            0,
            ("regex-filter", {"regex": "fluvio"}),
            ("json-map", {"field": "name"}),
        )
        slow = process_batches(slow_chain, [batch], 1 << 20)
        flat = lambda res: [  # noqa: E731
            (r.value, b.base_offset + r.offset_delta)
            for b in res.records.batches
            for r in b.memory_records()
        ]
        assert flat(fast) == flat(slow)


class TestShardedLinkDiet:
    """The sharded path must keep the single-device H2D diet (ragged
    flat upload, device re-pad, derived-column synthesis) — review round 3
    weak #3: the old dense upload was a rows x width blowup."""

    def _bytes_for(self, specs, values, timestamps=None):
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartmodule import SmartModuleInput

        out = {}
        for mesh in (0, N_DEV):
            chain = _engine_chain(mesh, *specs)
            recs = []
            for i, v in enumerate(values):
                r = Record(value=v)
                r.offset_delta = i
                if timestamps:
                    r.timestamp_delta = timestamps[i]
                recs.append(r)
            res = chain.process(SmartModuleInput.from_records(recs, 0, 1000))
            assert res.error is None
            ex = chain.tpu_chain
            out[mesh] = (ex.h2d_bytes_total, [
                (r.value, r.key, r.offset_delta) for r in res.successes
            ])
        assert out[0][1] == out[N_DEV][1]  # equivalence rides along
        return out[0][0], out[N_DEV][0]

    def test_h2d_within_budget_of_single_device(self):
        h1, h8 = self._bytes_for(
            [("regex-filter", {"regex": "fluvio"}),
             ("json-map", {"field": "name"})],
            _north_star_values(4000),
        )
        assert h8 <= h1 * 1.2 + 4096, (h1, h8)

    def test_h2d_budget_with_keys_and_timestamps(self):
        values = _north_star_values(2000)
        ts = [(i * 7) % 50_000 for i in range(len(values))]
        h1, h8 = self._bytes_for(
            [("regex-filter", {"regex": "fluvio"})], values, timestamps=ts
        )
        assert h8 <= h1 * 1.2 + 4096, (h1, h8)


class TestShardedFanout:
    """array_map under the mesh: per-shard capacity scatter, exact
    totals in the stacked headers, one bigger-capacity retry on
    overflow (review round 3 weak #4)."""

    def _values(self, n):
        return [
            f'["a{i & 7}","b{i}",{i},{i * 3},"x","y"]'.encode()
            for i in range(n)
        ]

    def _run_both(self, values):
        from fluvio_tpu.smartmodule import SmartModuleInput
        from fluvio_tpu.protocol.record import Record

        def records():
            out = []
            for i, v in enumerate(values):
                r = Record(value=v)
                r.offset_delta = i
                out.append(r)
            return out

        single = _engine_chain(0, ("array-map-json", None))
        sharded = _engine_chain(N_DEV, ("array-map-json", None))
        assert sharded.tpu_chain._sharded is not None, "mesh mode not engaged"
        a = single.process(SmartModuleInput.from_records(records(), 0, 1000))
        b = sharded.process(SmartModuleInput.from_records(records(), 0, 1000))
        assert a.error is None and b.error is None
        ka = [(r.value, r.key, r.offset_delta) for r in a.successes]
        kb = [(r.value, r.key, r.offset_delta) for r in b.successes]
        assert ka == kb
        return ka

    def test_array_map_sharded_equivalence(self):
        out = self._run_both(self._values(300))
        assert len(out) == 300 * 6  # 6 elements per record

    def test_uneven_rows_across_shards(self):
        out = self._run_both(self._values(37))
        assert len(out) == 37 * 6

    def test_capacity_overflow_retries(self):
        """A skewed corpus (one shard's records explode far more) must
        trip the per-shard capacity and succeed via the retry."""
        from fluvio_tpu.smartmodule import SmartModuleInput
        from fluvio_tpu.protocol.record import Record

        # shard 0's rows carry 40-element arrays; the rest 1-element
        n = 64
        heavy = "[" + ",".join(str(i) for i in range(40)) + "]"
        values = [
            heavy.encode() if i < n // N_DEV else b"[1]" for i in range(n)
        ]
        sharded = _engine_chain(N_DEV, ("array-map-json", None))
        ex = sharded.tpu_chain
        assert ex._sharded is not None
        records = []
        for i, v in enumerate(values):
            r = Record(value=v)
            r.offset_delta = i
            records.append(r)
        out = sharded.process(SmartModuleInput.from_records(records, 0, 1000))
        assert out.error is None
        expect = (n // N_DEV) * 40 + (n - n // N_DEV)
        assert len(out.successes) == expect
        # the skew must actually have tripped the capacity retry — if a
        # later headroom change makes the first dispatch fit, this test
        # stops covering the retry branch
        assert ex._sharded.fanout_retries == 1
        # and the learned ratio prevents a second retry for the same skew
        out2 = sharded.process(SmartModuleInput.from_records(records, 0, 1000))
        assert len(out2.successes) == expect
        assert ex._sharded.fanout_retries == 1

    def _run_combo_both(self, values):
        """explode -> count through single-device and mesh engines."""
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartmodule import SmartModuleInput

        specs = (("array-map-json", None), ("aggregate-count", None))
        single = _engine_chain(0, *specs)
        sharded = _engine_chain(N_DEV, *specs)
        assert sharded.tpu_chain._sharded is not None, "combo refused to shard"

        def records():
            out = []
            for i, v in enumerate(values):
                r = Record(value=v)
                r.offset_delta = i
                out.append(r)
            return out

        a = single.process(SmartModuleInput.from_records(records(), 0, 1000))
        b = sharded.process(SmartModuleInput.from_records(records(), 0, 1000))
        assert a.error is None and b.error is None
        ka = [(r.value, r.key, r.offset_delta) for r in a.successes]
        kb = [(r.value, r.key, r.offset_delta) for r in b.successes]
        assert ka == kb
        single.tpu_chain._ensure_host_state()
        sharded.tpu_chain._ensure_host_state()
        assert sharded.tpu_chain.carries == single.tpu_chain.carries
        return sharded, kb

    def test_fanout_aggregate_combo_sharded(self):
        """explode -> count shards and stays bit-equal to single-device,
        including the cross-shard carry (review round 4 missing #2)."""
        sharded, out = self._run_combo_both(self._values(300))
        assert len(out) == 300 * 6
        assert out[-1][0] == str(300 * 6).encode()  # running count
        assert sharded.tpu_chain._sharded.fanout_retries == 0

    def test_fanout_aggregate_overflow_rolls_back_carries(self):
        """A capacity overflow abandons a dispatch whose aggregate
        carries already advanced: the retry must chain from the
        snapshot, never double-count."""
        n = 64
        heavy = "[" + ",".join(str(i) for i in range(40)) + "]"
        values = [
            heavy.encode() if i < n // N_DEV else b"[1]" for i in range(n)
        ]
        sharded, out = self._run_combo_both(values)
        # the skew must actually have tripped the capacity retry
        assert sharded.tpu_chain._sharded.fanout_retries == 1
        expect = (n // N_DEV) * 40 + (n - n // N_DEV)
        assert out[-1][0] == str(expect).encode()
        # carry state after the retry equals the exact element total
        assert sharded.tpu_chain.carries[0][0] == expect


class TestShardedAggregateStream:
    def test_stream_pipelines_with_carry_continuity(self):
        """process_stream over a sharded windowed aggregate: pipelined
        dispatch-ahead must produce the same outputs as one-at-a-time
        process_buffer (carries chain through dispatch futures)."""
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
        from fluvio_tpu.protocol.record import Record

        def bufs():
            out = []
            for b in range(4):
                recs = []
                for i in range(48):
                    r = Record(value=str(b * 48 + i).encode())
                    r.offset_delta = i
                    r.timestamp_delta = (b * 48 + i) * 13
                    recs.append(r)
                out.append(RecordBuffer.from_records(recs, base_timestamp=1_000_000))
            return out

        ser = _engine_chain(N_DEV, ("windowed-sum", {"kind": "sum_int", "window_ms": "200"}))
        pip = _engine_chain(N_DEV, ("windowed-sum", {"kind": "sum_int", "window_ms": "200"}))
        assert pip.tpu_chain._sharded is not None
        serial = [
            [(r.value, r.offset_delta) for r in out.to_records()]
            for out in map(ser.tpu_chain.process_buffer, bufs())
        ]
        piped = [
            [(r.value, r.offset_delta) for r in out.to_records()]
            for out in pip.tpu_chain.process_stream(iter(bufs()))
        ]
        assert serial == piped
        ser.tpu_chain._ensure_host_state()
        pip.tpu_chain._ensure_host_state()
        assert ser.tpu_chain.carries == pip.tpu_chain.carries

    def test_discard_dispatch_rolls_back_carries(self):
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
        from fluvio_tpu.protocol.record import Record

        chain = _engine_chain(N_DEV, ("aggregate-sum", None))
        ex = chain.tpu_chain
        assert ex._sharded is not None

        def buf(vals):
            recs = []
            for i, v in enumerate(vals):
                r = Record(value=v)
                r.offset_delta = i
                recs.append(r)
            return RecordBuffer.from_records(recs)

        out1 = ex.process_buffer(buf([b"1", b"2", b"3"]))
        # speculative dispatch that gets discarded must not advance state
        h = ex.dispatch_buffer(buf([b"100", b"100", b"100"]))
        ex.discard_dispatch(h)
        out2 = ex.process_buffer(buf([b"4"]))
        assert out2.to_records()[-1].value == b"10"  # 1+2+3+4
