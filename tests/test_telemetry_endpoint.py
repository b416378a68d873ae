"""Metrics export surfaces: Prometheus exposition over the monitoring
socket, JSON/Prometheus/CLI snapshot parity, and the span dump.

ISSUE-2 acceptance: the Prometheus endpoint and the `fluvio-tpu metrics`
CLI must render the SAME snapshot, the exposition must be valid
text-format, and every declared series must be present.
"""

import asyncio
import json
import re

import pytest

from fluvio_tpu.cli.metrics import render_metrics_table
from fluvio_tpu.spu.metrics import SpuMetrics
from fluvio_tpu.spu.monitoring import (
    MonitoringServer,
    read_metrics,
    read_prometheus,
    read_spans,
)
from fluvio_tpu.telemetry import TELEMETRY, render_prometheus


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


class _Ctx:
    def __init__(self):
        self.metrics = SpuMetrics()


def _populate():
    """Drive representative traffic into every counter family."""
    span = TELEMETRY.begin_batch(chain="filter+map")
    span.add("stage", 0.002)
    span.add("dispatch", 0.001)
    span.add("device", 0.010)
    span.add("d2h", 0.003)
    TELEMETRY.end_batch(span, records=128)
    ispan = TELEMETRY.begin_batch(path="interpreter")
    TELEMETRY.end_batch(ispan, records=16)
    TELEMETRY.add_heal()
    TELEMETRY.add_stripe_fallback()
    TELEMETRY.add_spill("transform-error")
    TELEMETRY.add_decline("no-raw-records")
    TELEMETRY.add_interp_instance(0.004, 16)
    ctx = _Ctx()
    ctx.metrics.inbound.add(128, 4096)
    ctx.metrics.outbound.add(64, 2048)
    ctx.metrics.smartmodule.add_bytes_in(4096)
    ctx.metrics.smartmodule.add_fastpath()
    ctx.metrics.smartmodule.add_fallback("no-raw-records")
    return ctx


# a sample line is `name value` or `name{labels} value` with a float/int
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r" (?:[-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|\+Inf|-Inf|NaN)$"
)

DECLARED_SERIES = [
    "fluvio_tpu_batch_latency_seconds",
    "fluvio_tpu_phase_seconds",
    "fluvio_tpu_chain_e2e_latency_seconds",
    "fluvio_tpu_slo_verdict",
    "fluvio_tpu_batch_records_total",
    "fluvio_tpu_glz_heals_total",
    "fluvio_tpu_stripe_fallbacks_total",
    "fluvio_tpu_spills_total",
    "fluvio_tpu_declines_total",
    "fluvio_tpu_interp_instance_calls_total",
    "fluvio_tpu_interp_instance_seconds_total",
    "fluvio_tpu_interp_instance_records_total",
    "fluvio_tpu_spu_inbound_records_total",
    "fluvio_tpu_spu_inbound_bytes_total",
    "fluvio_tpu_spu_outbound_records_total",
    "fluvio_tpu_spu_outbound_bytes_total",
    "fluvio_tpu_smartmodule_bytes_in_total",
    "fluvio_tpu_smartmodule_fastpath_slices_total",
    "fluvio_tpu_smartmodule_fallback_slices_total",
    "fluvio_tpu_smartmodule_fallback_reasons_total",
]


def _sample_value(text: str, name: str, labels: str = "") -> float:
    target = f"{name}{labels} "
    for line in text.splitlines():
        if line.startswith(target):
            return float(line.split(" ")[-1])
    raise AssertionError(f"no sample {target!r}")


class TestExpositionFormat:
    def test_text_format_validity_and_declared_series(self):
        ctx = _populate()
        text = render_prometheus(spu_metrics=ctx.metrics.to_dict())
        assert text.endswith("\n")
        helped, typed = set(), set()
        for line in text.splitlines():
            if not line:
                continue
            if line.startswith("# HELP "):
                helped.add(line.split(" ")[2])
                continue
            if line.startswith("# TYPE "):
                parts = line.split(" ")
                assert parts[3] in ("counter", "gauge", "histogram")
                typed.add(parts[2])
                continue
            assert _SAMPLE_RE.match(line), f"invalid exposition line: {line!r}"
        for series in DECLARED_SERIES:
            assert series in typed, f"series {series} missing TYPE"
            assert series in helped, f"series {series} missing HELP"
            base = series.replace("_total", "")
            assert any(
                l.startswith(series) or l.startswith(base)
                for l in text.splitlines()
                if not l.startswith("#")
            ), f"series {series} has no samples"

    def test_every_histogram_family_emits_sum_count_with_parity(self):
        """SLO-PR satellite: every latency family must expose ``_sum``
        and ``_count`` (scrapers cannot compute true means from buckets
        alone), and both must agree exactly with the JSON snapshot's
        totals for the same instant."""
        _populate()
        TELEMETRY.add_compile("ragged", "sig", 0.25)
        text = render_prometheus()
        snap = TELEMETRY.snapshot()
        # discover every declared histogram family from the exposition
        families = [
            line.split(" ")[2]
            for line in text.splitlines()
            if line.startswith("# TYPE ") and line.endswith(" histogram")
        ]
        assert set(families) >= {
            "fluvio_tpu_batch_latency_seconds",
            "fluvio_tpu_phase_seconds",
            "fluvio_tpu_chain_e2e_latency_seconds",
            "fluvio_tpu_compile_latency_seconds",
        }
        for family in families:
            sums = [
                l for l in text.splitlines()
                if l.startswith(f"{family}_sum")
            ]
            counts = [
                l for l in text.splitlines()
                if l.startswith(f"{family}_count")
            ]
            assert sums and counts, f"{family} missing _sum/_count"
            assert len(sums) == len(counts)
        # exact parity against the snapshot totals (count is integral,
        # sum within the snapshot's own rounding)
        for path, b in snap["batches"].items():
            assert b["count"] == _sample_value(
                text,
                "fluvio_tpu_batch_latency_seconds_count",
                f'{{path="{path}"}}',
            )
            assert _sample_value(
                text,
                "fluvio_tpu_batch_latency_seconds_sum",
                f'{{path="{path}"}}',
            ) == pytest.approx(b["sum_s"], abs=1e-5)
        for phase, h in snap["phases"].items():
            assert h["count"] == _sample_value(
                text, "fluvio_tpu_phase_seconds_count",
                f'{{phase="{phase}"}}',
            )
            assert _sample_value(
                text, "fluvio_tpu_phase_seconds_sum",
                f'{{phase="{phase}"}}',
            ) == pytest.approx(h["sum_s"], abs=1e-5)
        for chain, h in snap["chains"].items():
            assert h["count"] == _sample_value(
                text, "fluvio_tpu_chain_e2e_latency_seconds_count",
                f'{{chain="{chain}"}}',
            )
            assert _sample_value(
                text, "fluvio_tpu_chain_e2e_latency_seconds_sum",
                f'{{chain="{chain}"}}',
            ) == pytest.approx(h["sum_s"], abs=1e-5)
        comp = snap["compile"]["latency"]
        assert comp["count"] == _sample_value(
            text, "fluvio_tpu_compile_latency_seconds_count"
        )
        assert _sample_value(
            text, "fluvio_tpu_compile_latency_seconds_sum"
        ) == pytest.approx(comp["sum_s"], abs=1e-5)

    def test_histogram_invariants(self):
        ctx = _populate()
        text = render_prometheus(spu_metrics=ctx.metrics.to_dict())
        # +Inf cumulative bucket equals the series count, per label set
        count = _sample_value(
            text, "fluvio_tpu_batch_latency_seconds_count", '{path="fused"}'
        )
        inf = _sample_value(
            text,
            "fluvio_tpu_batch_latency_seconds_bucket",
            '{path="fused",le="+Inf"}',
        )
        assert count == inf == 1
        # cumulative buckets are monotone non-decreasing
        pat = re.compile(
            r'fluvio_tpu_phase_seconds_bucket\{phase="device",le="([^"]+)"\} (\S+)'
        )
        cums = [float(m.group(2)) for m in pat.finditer(text)]
        assert cums and cums == sorted(cums)


class TestSnapshotParity:
    def test_prom_json_and_cli_render_the_same_snapshot(self):
        ctx = _populate()
        data = ctx.metrics.to_dict()
        text = render_prometheus(spu_metrics=data)
        tel = data["telemetry"]
        # counts agree between the JSON snapshot and the exposition
        assert tel["batches"]["fused"]["count"] == _sample_value(
            text, "fluvio_tpu_batch_latency_seconds_count", '{path="fused"}'
        )
        assert tel["batches"]["interpreter"]["records"] == _sample_value(
            text, "fluvio_tpu_batch_records_total", '{path="interpreter"}'
        )
        assert tel["counters"]["heals"] == _sample_value(
            text, "fluvio_tpu_glz_heals_total"
        )
        assert tel["counters"]["spills"]["transform-error"] == _sample_value(
            text, "fluvio_tpu_spills_total", '{reason="transform-error"}'
        )
        assert data["inbound"]["records"] == _sample_value(
            text, "fluvio_tpu_spu_inbound_records_total"
        )
        # the CLI table renders the same snapshot dict: every counter the
        # satellites added must be visible in the human surface
        table = render_metrics_table(data)
        assert "no-raw-records" in table       # fallback_reasons
        assert "glz_heals" in table and "stripe_fallbacks" in table
        assert "spill[transform-error]" in table
        assert "decline[no-raw-records]" in table
        assert "device" in table               # phase table
        assert "fastpath_slices" in table

    def test_cli_table_handles_empty_snapshot(self):
        ctx = _Ctx()
        table = render_metrics_table(ctx.metrics.to_dict())
        assert "smartmodule" in table and "pipeline events" in table


class TestMonitoringSocket:
    def _roundtrip(self, tmp_path, fn):
        async def run():
            ctx = _populate()
            server = MonitoringServer(ctx, str(tmp_path / "m.sock"))
            await server.start()
            try:
                return await fn(server)
            finally:
                await server.stop()

        return asyncio.run(run())

    def test_prom_scrape_over_socket(self, tmp_path):
        text = self._roundtrip(
            tmp_path, lambda s: read_prometheus(s.path)
        )
        assert "fluvio_tpu_batch_latency_seconds_bucket" in text
        assert _sample_value(text, "fluvio_tpu_glz_heals_total") == 1

    def test_json_includes_telemetry_and_matches_prom(self, tmp_path):
        async def both(server):
            return await read_metrics(server.path), await read_prometheus(
                server.path
            )

        data, text = self._roundtrip(tmp_path, both)
        assert data["telemetry"]["counters"]["heals"] == _sample_value(
            text, "fluvio_tpu_glz_heals_total"
        )
        assert (
            data["telemetry"]["batches"]["fused"]["count"]
            == _sample_value(
                text,
                "fluvio_tpu_batch_latency_seconds_count",
                '{path="fused"}',
            )
        )

    def test_span_dump_over_socket(self, tmp_path):
        spans = self._roundtrip(tmp_path, lambda s: read_spans(s.path))
        assert len(spans) == 2
        fused = [s for s in spans if s["path"] == "fused"]
        assert fused and fused[0]["records"] == 128
        assert fused[0]["phases_ms"]["device"] == pytest.approx(10.0)

    def test_legacy_client_without_mode_line_gets_json(self, tmp_path):
        async def legacy(server):
            reader, writer = await asyncio.open_unix_connection(server.path)
            try:
                return json.loads(await reader.read())
            finally:
                writer.close()

        data = self._roundtrip(tmp_path, legacy)
        assert data["inbound"]["records"] == 128
        assert "telemetry" in data


class TestConcurrentScrapeChaos:
    """ISSUE-7 chaos satellite: monitoring-socket ``prom``/``trace``
    scrapes racing live batch dispatch AND trace-sink rotation. Every
    scrape must parse (valid exposition text / valid trace JSON) and
    the span-ring bookkeeping must reconcile exactly — a race that
    tears a counter shows up as a dropped-span undercount."""

    def test_scrapes_race_dispatch_and_rotation(self, tmp_path):
        import threading

        from fluvio_tpu.models import lookup
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
        from fluvio_tpu.spu.monitoring import read_trace
        from fluvio_tpu.telemetry.trace import TraceFileSink

        b = SmartEngine(backend="tpu").builder()
        for name, params in (
            ("regex-filter", {"regex": "fluvio"}),
            ("json-map", {"field": "name"}),
        ):
            b.add_smart_module(SmartModuleConfig(params=params), lookup(name))
        chain = b.initialize()
        assert chain.backend_in_use == "tpu"
        records = [
            Record(value=f'{{"name":"fluvio-{i}","n":{i}}}'.encode())
            for i in range(128)
        ]
        for i, r in enumerate(records):
            r.offset_delta = i
        buf = RecordBuffer.from_records(records)
        # warm outside the race so the chaos window is steady-state
        for out in chain.tpu_chain.process_stream(iter([buf] * 2)):
            pass
        TELEMETRY.reset()

        # tiny rotation bound (floors to 4KiB) + per-span flush: the
        # sink rotates constantly while scrapes hold the registry lock
        sink = TraceFileSink(str(tmp_path / "chaos.json"), max_bytes=1)
        sink.FLUSH_INTERVAL_S = 0.0
        sink.BATCH_EVENTS = 1
        TELEMETRY.trace_sink = sink
        stop = threading.Event()
        errors = []
        batches = [0]

        def traffic():
            try:
                while not stop.is_set():
                    for out in chain.tpu_chain.process_stream(iter([buf])):
                        pass
                    batches[0] += 1
            except Exception as e:  # noqa: BLE001 — surfaced to the assert
                errors.append(repr(e))

        async def chaos():
            ctx = _Ctx()
            server = MonitoringServer(ctx, str(tmp_path / "m.sock"))
            await server.start()
            t = threading.Thread(target=traffic)
            t.start()
            try:
                for _ in range(12):
                    text = await read_prometheus(server.path)
                    for line in text.splitlines():
                        if line and not line.startswith("#"):
                            assert _SAMPLE_RE.match(line), line
                    doc = await read_trace(server.path)
                    assert isinstance(doc["traceEvents"], list)
                    # LIVE reconciliation: the snapshot's span triple is
                    # read under one ring-lock acquisition, so it must
                    # balance even while dispatch is mid-push
                    live = TELEMETRY.snapshot()
                    assert live["spans_total"] == (
                        live["spans_retained"] + live["spans_dropped"]
                    )
            finally:
                stop.set()
                t.join()
                await server.stop()

        try:
            asyncio.run(chaos())
        finally:
            TELEMETRY.trace_sink = None
            sink.close()
        assert not errors, errors[:3]
        assert batches[0] > 0
        # no dropped-span undercount: every batch span is accounted for
        # either retained in the ring or counted as dropped
        snap = TELEMETRY.snapshot()
        assert snap["spans_total"] == batches[0]
        assert snap["spans_total"] == (
            snap["spans_retained"] + snap["spans_dropped"]
        )
        # whichever sink generations survived the rotation storm must
        # be valid JSON documents
        for p in (tmp_path / "chaos.json", tmp_path / "chaos.json.1"):
            if p.exists():
                json.loads(p.read_text())
