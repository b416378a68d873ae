"""Engine flight recorder (ISSUE-5): Perfetto trace export, JIT-compile
telemetry, and device-memory/queue gauges.

Covers the acceptance surfaces:

- trace round-trip: the rendered document is valid Chrome-trace JSON,
  batch-event parity with `spans_json()`, overlapping batches land on
  distinct tracks (the pipelined overlap is visible), phases sit at
  their recorded wall positions,
- the continuous `FLUVIO_TRACE` file sink stays valid JSON after every
  append and respects its rotation bound,
- compile events on a forced fresh shape bucket (counts, seconds,
  trace-cache hit accounting, DFA table builds) and the recompile-storm
  decline,
- gauge up/down correctness across dispatch/finish/discard including
  the sharded path, the dead-letter occupancy gauge, and the pipelined
  queue-depth release idempotence,
- `SpanRing.dropped` through snapshot + Prometheus,
- the monitoring socket's ``trace`` mode and the `fluvio-tpu trace`
  CLI.
"""

from __future__ import annotations

import asyncio
import json
import os

import jax
import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.telemetry import (
    TELEMETRY,
    PipelineTelemetry,
    TraceFileSink,
    render_prometheus,
    render_trace,
)
from fluvio_tpu.telemetry.spans import PHASES, BatchSpan, InstantEvent, SpanRing
from fluvio_tpu.telemetry import memory as memory_mod
from fluvio_tpu.telemetry import trace as trace_mod


@pytest.fixture(autouse=True)
def _fresh_registry():
    # the memory ledger behind `hbm_staged_bytes` survives
    # `TELEMETRY.reset()` by design: drop it too, or a handle an earlier
    # test FILE on this worker abandoned in flight keeps its bytes booked
    # and the gauge never reads 0 here (tests/test_memory.py's convention)
    memory_mod.reset_engine()
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.trace_sink = None
    TELEMETRY.reset()
    memory_mod.reset_engine()


def _span(t0: float, dur: float, path: str = "fused", records: int = 8):
    s = BatchSpan(path)
    s.t0 = t0
    s.t_end = t0 + dur
    s.records = records
    return s


def _chain(*specs):
    b = SmartEngine(backend="tpu").builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    chain = b.initialize()
    assert chain.backend_in_use == "tpu"
    return chain


def _buf(n: int = 64, tag: str = "fluvio"):
    records = [
        Record(value=f'{{"name":"{tag}-{i}","n":{i}}}'.encode())
        for i in range(n)
    ]
    for i, r in enumerate(records):
        r.offset_delta = i
    return RecordBuffer.from_records(records)


# ---------------------------------------------------------------------------
# trace document
# ---------------------------------------------------------------------------


class TestTraceDocument:
    def test_round_trip_parity_and_overlap_tracks(self):
        # two overlapping fused batches (the pipelined shape) + one after
        a = _span(100.0, 0.010)
        stage, device = PHASES.index("stage"), PHASES.index("device")
        a.phase_s[stage] = 0.002
        a.phase_t0[stage] = 100.0
        a.phase_s[device] = 0.006
        a.phase_t0[device] = 100.003
        b = _span(100.005, 0.010)
        c = _span(100.020, 0.005, path="striped")
        for s in (a, b, c):
            TELEMETRY.spans.push(s)
        doc = json.loads(json.dumps(render_trace()))
        events = doc["traceEvents"]
        batches = [e for e in events if e.get("cat") == "batch"]
        # event parity: one batch envelope per retained span
        assert len(batches) == len(TELEMETRY.spans_json()) == 3
        # the overlapping pair occupies two DISTINCT tracks
        fused_tids = {
            e["tid"] for e in batches if e["args"]["path"] == "fused"
        }
        assert len(fused_tids) == 2
        # striped batches live in their own track family
        striped = [e for e in batches if e["args"]["path"] == "striped"]
        assert striped and striped[0]["tid"] not in fused_tids
        # phases are duration events at their recorded wall positions
        phases = {e["name"]: e for e in events if e.get("cat") == "phase"}
        assert phases["stage"]["dur"] == pytest.approx(2000, rel=0.01)
        assert phases["device"]["ts"] > phases["stage"]["ts"]
        # the envelope spans its phases
        env = [e for e in batches if e["ts"] == 0.0][0]
        assert env["dur"] == pytest.approx(10000, rel=0.01)

    def test_instant_events_render_as_markers(self):
        TELEMETRY.end_batch(TELEMETRY.begin_batch(), records=4)
        TELEMETRY.add_heal()
        TELEMETRY.add_spill("transform-error")
        TELEMETRY.add_retry("fetch")
        TELEMETRY.record_breaker("chain-a", "open")
        TELEMETRY.add_compile("ragged", "sig w=32", 0.5, True)
        doc = render_trace()
        marks = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
        kinds = {e["name"] for e in marks}
        assert {"heal", "spill", "retry", "breaker", "compile"} <= kinds
        spill = [e for e in marks if e["name"] == "spill"][0]
        assert spill["args"]["detail"] == "transform-error"

    def test_empty_registry_renders_valid_doc(self):
        doc = json.loads(json.dumps(render_trace()))
        assert doc["traceEvents"]  # metadata only, still loadable


# ---------------------------------------------------------------------------
# continuous file sink
# ---------------------------------------------------------------------------


class TestTraceFileSink:
    def test_file_always_valid_json_with_event_parity(self, tmp_path):
        path = str(tmp_path / "t.json")
        sink = TraceFileSink(path, max_bytes=1 << 20)
        assert not os.path.exists(path)  # lazy: no file until a write
        n = 17
        for i in range(n):
            sink.on_span(_span(10.0 + i * 0.01, 0.005, records=i))
            sink.flush()
            # the on-disk content is valid JSON after EVERY write
            data = json.load(open(path))
        sink.on_event(InstantEvent("heal"))
        sink.flush()  # force the coalesced tail out before asserting
        data = json.load(open(path))
        batches = [e for e in data if e.get("cat") == "batch"]
        assert len(batches) == n
        assert any(e.get("ph") == "i" and e["name"] == "heal" for e in data)
        sink.close()

    def test_reopen_never_truncates_prior_recording(self, tmp_path):
        """A second sink on the same path (engine restart, or a scraper
        process importing the package with FLUVIO_TRACE still set) must
        never truncate the existing recording: an idle sink leaves it
        byte-identical, a writing sink rotates it aside first (its time
        base belongs to the other run — appending would overlay two
        timelines on one track)."""
        path = str(tmp_path / "t.json")
        first = TraceFileSink(path, max_bytes=1 << 20)
        first.on_span(_span(10.0, 0.005, records=1))
        first.close()
        kept = json.load(open(path))
        assert any(e.get("cat") == "batch" for e in kept)
        # a sink that never writes leaves the file byte-identical
        idle = TraceFileSink(path, max_bytes=1 << 20)
        raw_before = open(path, "rb").read()
        idle.close()
        assert open(path, "rb").read() == raw_before
        # a sink that DOES write starts its own generation; the first
        # recording survives rotated to <path>.1
        second = TraceFileSink(path, max_bytes=1 << 20)
        second.on_span(_span(20.0, 0.005, records=2))
        second.close()
        data = json.load(open(path))
        assert [e["args"]["records"] for e in data if e.get("cat") == "batch"] == [2]
        rotated = json.load(open(path + ".1"))
        assert [e["args"]["records"] for e in rotated if e.get("cat") == "batch"] == [1]

    def test_rotation_bound_respected(self, tmp_path):
        path = str(tmp_path / "t.json")
        bound = 4096
        sink = TraceFileSink(path, max_bytes=bound)
        for i in range(200):
            sink.on_span(_span(10.0 + i * 0.01, 0.005))
        sink.flush()
        # one coalesced write may overshoot before rotation triggers;
        # the bound holds within a batch's worth of slack
        assert os.path.getsize(path) <= bound + 4096
        assert os.path.exists(path + ".1")
        json.load(open(path))
        json.load(open(path + ".1"))
        sink.close()

    def test_env_install_streams_completed_spans(self, tmp_path, monkeypatch):
        path = str(tmp_path / "env.json")
        monkeypatch.setenv("FLUVIO_TRACE", path)
        sink = trace_mod.install_env_sink()
        assert sink is not None and TELEMETRY.trace_sink is sink
        TELEMETRY.end_batch(TELEMETRY.begin_batch(), records=3)
        TELEMETRY.add_heal()
        sink.flush()
        data = json.load(open(path))
        assert any(e.get("cat") == "batch" for e in data)
        assert any(e.get("name") == "heal" for e in data)
        sink.close()

    def test_env_install_noop_without_var(self, monkeypatch):
        monkeypatch.delenv("FLUVIO_TRACE", raising=False)
        assert trace_mod.install_env_sink() is None

    def test_failed_append_rolls_back_to_valid_json(self, tmp_path):
        path = str(tmp_path / "t.json")
        sink = TraceFileSink(path, max_bytes=1 << 20)
        sink.on_span(_span(10.0, 0.005))
        sink.flush()
        before = json.load(open(path))
        # one torn write (disk blip): the file must roll back to its
        # pre-append bracket, not leave a half-chunk for later appends
        real_write = sink._f.write
        calls = {"n": 0}

        def torn_write(data):
            if calls["n"] == 0:
                calls["n"] += 1
                real_write(data[: len(data) // 2])
                raise OSError("disk blip")
            return real_write(data)

        sink._f.write = torn_write
        sink.on_span(_span(20.0, 0.005))
        sink.flush()
        assert json.load(open(path)) == before  # rolled back, valid
        # the disk recovers: later appends keep working on a valid file
        sink.on_span(_span(30.0, 0.005))
        sink.flush()
        data = json.load(open(path))
        assert len([e for e in data if e.get("cat") == "batch"]) == 2
        sink.close()


# ---------------------------------------------------------------------------
# compile telemetry
# ---------------------------------------------------------------------------


class TestCompileTelemetry:
    def test_fresh_shape_bucket_records_compile_event(self):
        chain = _chain(
            ("regex-filter", {"regex": "fluvio"}),
            ("json-map", {"field": "name"}),
        )
        ex = chain.tpu_chain
        ex.process_buffer(_buf(64))
        snap = TELEMETRY.snapshot()
        assert snap["compile"]["by_kind"].get("ragged", 0) >= 1
        assert snap["compile"]["latency"]["count"] >= 1
        compiles = [
            e for e in TELEMETRY.events_json() if e["kind"] == "compile"
        ]
        assert compiles and "ragged" in compiles[0]["detail"]
        assert "w=" in compiles[0]["detail"]  # shape bucket rides along
        # warm re-run: trace-cache hits move, the compile count does not
        before = snap["compile"]["by_kind"]["ragged"]
        hits0 = snap["compile"]["jit_cache_hits"]
        ex.process_buffer(_buf(64))
        snap2 = TELEMETRY.snapshot()
        assert snap2["compile"]["by_kind"]["ragged"] == before
        assert snap2["compile"]["jit_cache_hits"] > hits0

    def test_dfa_table_build_records_compile_event(self):
        from fluvio_tpu.ops.regex_dfa import compile_regex_cached

        compile_regex_cached.cache_clear()
        compile_regex_cached("flu(vio|x)+")
        snap = TELEMETRY.snapshot()
        assert snap["compile"]["by_kind"].get("dfa_table") == 1
        compile_regex_cached("flu(vio|x)+")  # lru hit: no new event
        assert (
            TELEMETRY.snapshot()["compile"]["by_kind"]["dfa_table"] == 1
        )

    def test_recompile_storm_counts_decline(self, monkeypatch):
        from fluvio_tpu.telemetry import registry

        monkeypatch.setattr(registry, "COMPILE_STORM_N", 2)
        for i in range(4):
            TELEMETRY.add_compile("ragged", f"sig{i}", 0.01)
        snap = TELEMETRY.snapshot()
        assert snap["counters"]["declines"].get("recompile-storm", 0) == 2
        kinds = [e["kind"] for e in TELEMETRY.events_json()]
        assert "recompile-storm" in kinds

    def test_disabled_telemetry_keeps_seams_silent(self):
        TELEMETRY.enabled = False
        chain = _chain(("regex-filter", {"regex": "fluvio"}))
        chain.tpu_chain.process_buffer(_buf(32))
        snap = TELEMETRY.snapshot()
        assert snap["compile"]["by_kind"] == {}
        assert snap["gauges"] == {}
        assert snap["events_total"] == 0

    def test_prometheus_renders_compile_series(self):
        TELEMETRY.add_compile("ragged", "sig", 0.25, False)
        TELEMETRY.add_compile("striped", "sig2", 0.5, True)
        text = render_prometheus()
        assert 'fluvio_tpu_compiles_total{kind="ragged"} 1' in text
        assert 'fluvio_tpu_compiles_total{kind="striped"} 1' in text
        assert "fluvio_tpu_compile_latency_seconds_count 2" in text
        assert "fluvio_tpu_persistent_cache_hits_total 1" in text
        assert "fluvio_tpu_persistent_cache_misses_total 1" in text


# ---------------------------------------------------------------------------
# gauges
# ---------------------------------------------------------------------------


class TestGauges:
    def test_dispatch_finish_up_down(self):
        chain = _chain(
            ("regex-filter", {"regex": "fluvio"}),
            ("json-map", {"field": "name"}),
        )
        ex = chain.tpu_chain
        buf = _buf(64)
        handle = ex.dispatch_buffer(buf)
        assert TELEMETRY.gauge_value("live_batch_handles") == 1
        staged = TELEMETRY.gauge_value("hbm_staged_bytes")
        assert staged > 0
        ex.finish_buffer(buf, handle)
        assert TELEMETRY.gauge_value("live_batch_handles") == 0
        assert TELEMETRY.gauge_value("hbm_staged_bytes") == 0

    def test_discard_releases(self):
        chain = _chain(("regex-filter", {"regex": "fluvio"}))
        ex = chain.tpu_chain
        handle = ex.dispatch_buffer(_buf(32))
        assert TELEMETRY.gauge_value("live_batch_handles") == 1
        ex.discard_dispatch(handle)
        assert TELEMETRY.gauge_value("live_batch_handles") == 0
        assert TELEMETRY.gauge_value("hbm_staged_bytes") == 0

    def test_pipelined_stream_peaks_then_drains(self):
        chain = _chain(("regex-filter", {"regex": "fluvio"}))
        ex = chain.tpu_chain
        buf = _buf(32)
        peaks = []
        for out in ex.process_stream(iter([buf] * 4)):
            peaks.append(TELEMETRY.gauge_value("live_batch_handles"))
        # the two-phase loop keeps one batch in flight while yielding
        assert max(peaks) >= 1
        assert TELEMETRY.gauge_value("live_batch_handles") == 0
        assert TELEMETRY.gauge_value("hbm_staged_bytes") == 0

    @pytest.mark.skipif(
        len(jax.devices()) < 8, reason="needs 8 virtual devices"
    )
    def test_sharded_dispatch_finish_up_down(self):
        chain = _chain(("regex-filter", {"regex": "fluvio"}))
        ex = chain.tpu_chain
        ex.enable_sharded(8)
        buf = _buf(64)
        handle = ex.dispatch_buffer(buf)
        assert TELEMETRY.gauge_value("live_batch_handles") == 1
        assert TELEMETRY.gauge_value("hbm_staged_bytes") > 0
        ex.finish_buffer(buf, handle)
        assert TELEMETRY.gauge_value("live_batch_handles") == 0
        assert TELEMETRY.gauge_value("hbm_staged_bytes") == 0
        assert TELEMETRY.snapshot()["compile"]["by_kind"].get("sharded", 0) >= 1

    def test_deadletter_occupancy_gauge(self, tmp_path):
        from fluvio_tpu.resilience.deadletter import quarantine_batch
        from fluvio_tpu.smartmodule.types import SmartModuleInput

        inp = SmartModuleInput.from_records([Record(value=b"poison")])
        d = str(tmp_path / "dl")
        for i in range(3):
            quarantine_batch(
                [{"name": "f"}], inp, ValueError("a"), ValueError("b"),
                directory=d,
            )
        assert TELEMETRY.gauge_value("deadletter_entries") == 3
        # eviction keeps the gauge at the bound, not the write count
        for i in range(4):
            quarantine_batch(
                [{"name": "f"}], inp, ValueError("a"), ValueError("b"),
                directory=d, max_entries=2,
            )
        assert TELEMETRY.gauge_value("deadletter_entries") == 2

    def test_queue_depth_release_is_idempotent(self):
        from fluvio_tpu.spu.smart_chain import PendingSlice

        ps = PendingSlice(
            batches=[], chunks=[], planned_next=0, total_raw=0,
            base0=0, ts0=0, count=0,
        )
        TELEMETRY.gauge_add("inflight_queue_depth", 2)
        ps.tracked_depth = 2
        ps.release_depth()
        ps.release_depth()  # double release must not go negative
        assert TELEMETRY.gauge_value("inflight_queue_depth") == 0

    def test_disabled_telemetry_zero_cost_gauges(self):
        TELEMETRY.enabled = False
        TELEMETRY.gauge_add("hbm_staged_bytes", 100)
        TELEMETRY.gauge_set("deadletter_entries", 5)
        TELEMETRY.enabled = True
        assert TELEMETRY.snapshot()["gauges"] == {}


# ---------------------------------------------------------------------------
# span-ring dropped count
# ---------------------------------------------------------------------------


class TestSpanRingDropped:
    def test_dropped_through_snapshot_and_prometheus(self):
        t = PipelineTelemetry(ring_capacity=4)
        for i in range(7):
            t.end_batch(t.begin_batch(), records=1)
        assert t.spans.dropped == 3
        snap = t.snapshot()
        assert snap["spans_dropped"] == 3
        assert snap["spans_retained"] == 4 and snap["spans_total"] == 7
        text = render_prometheus(telemetry=t)
        assert "fluvio_tpu_spans_dropped_total 3" in text

    def test_unwrapped_ring_reports_zero(self):
        ring = SpanRing(8)
        for i in range(5):
            ring.push(_span(1.0 + i, 0.1))
        assert ring.dropped == 0 and ring.total == 5


# ---------------------------------------------------------------------------
# export surfaces: monitoring socket + CLI
# ---------------------------------------------------------------------------


class _Ctx:
    def __init__(self):
        from fluvio_tpu.spu.metrics import SpuMetrics

        self.metrics = SpuMetrics()


def _with_server(tmp_path, fn):
    from fluvio_tpu.spu.monitoring import MonitoringServer

    async def run():
        server = MonitoringServer(_Ctx(), str(tmp_path / "m.sock"))
        await server.start()
        try:
            return await fn(server)
        finally:
            await server.stop()

    return asyncio.run(run())


class TestTraceExportSurfaces:
    def _populate(self):
        span = TELEMETRY.begin_batch()
        span.add("stage", 0.001)
        TELEMETRY.end_batch(span, records=16)
        TELEMETRY.add_compile("ragged", "sig w=64", 0.3, True)

    def test_monitoring_socket_trace_mode(self, tmp_path):
        from fluvio_tpu.spu.monitoring import read_trace

        self._populate()
        doc = _with_server(tmp_path, lambda s: read_trace(s.path))
        assert doc["displayTimeUnit"] == "ms"
        names = {e.get("name") for e in doc["traceEvents"]}
        assert "compile" in names
        assert any(
            e.get("cat") == "batch" for e in doc["traceEvents"]
        )

    def test_cli_trace_writes_perfetto_file(self, tmp_path):
        import argparse

        from fluvio_tpu.cli.trace import trace as trace_cmd

        self._populate()
        out_path = str(tmp_path / "out.json")

        def run(server):
            args = argparse.Namespace(out=out_path, path=server.path)
            return trace_cmd(args)

        rc = _with_server(tmp_path, run)
        assert rc == 0
        doc = json.load(open(out_path))
        assert any(e.get("cat") == "batch" for e in doc["traceEvents"])

    def test_metrics_table_renders_compile_and_gauges(self):
        from fluvio_tpu.cli.metrics import render_metrics_table

        self._populate()
        TELEMETRY.gauge_add("live_batch_handles", 1)
        table = render_metrics_table({"telemetry": TELEMETRY.snapshot()})
        assert "jit compiles" in table and "ragged" in table
        assert "gauges" in table and "live_batch_handles" in table
