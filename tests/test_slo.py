"""Chain-level SLO engine: windowed time-series mechanics, the
``FLUVIO_SLO`` grammar, burn-rate verdict flips under fault injection
and recompile storms (with deterministic recovery — injectable clock,
no wall-time sleeps), breach instant events on the flight-recorder
timeline, breach-triggered profiler captures (exactly one per
cooldown), and the health surfaces (socket mode, CLI, table renderer,
``metrics --watch``).
"""

from __future__ import annotations

import asyncio
import json
import os

import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.resilience import faults
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.telemetry import TELEMETRY, SloEngine, TimeSeries
from fluvio_tpu.telemetry import slo as slo_mod
from fluvio_tpu.telemetry.slo import (
    DEFAULT_RULES,
    ENGINE_CHAIN,
    parse_slo_spec,
    rules_from_env,
    summarize,
)


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Clean registry + global engine per test; faults disarmed."""
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    slo_mod.reset_engine()
    faults.FAULTS.clear()
    yield
    faults.FAULTS.clear()
    slo_mod.reset_engine()
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


def _engine(clock, window_s=10.0, capacity=6, **kw) -> SloEngine:
    ts = TimeSeries(window_s=window_s, capacity=capacity, clock=clock)
    return SloEngine(timeseries=ts, clock=clock, profile_dir=kw.pop(
        "profile_dir", ""
    ), **kw)


def _slow_batch(chain="filter+map", e2e_s=5.0, records=8) -> None:
    """Record one batch whose e2e exceeds the default 2 s p99 target."""
    span = TELEMETRY.begin_batch(chain=chain)
    span.t0 -= e2e_s
    TELEMETRY.end_batch(span, records=records)


def _fast_batch(chain="filter+map") -> None:
    span = TELEMETRY.begin_batch(chain=chain)
    TELEMETRY.end_batch(span, records=1)


def build_chain(specs):
    b = SmartEngine(backend="tpu").builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize()


def make_buf(values):
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
    return RecordBuffer.from_records(records)


# ---------------------------------------------------------------------------
# Time-series mechanics
# ---------------------------------------------------------------------------


class TestTimeSeries:
    def test_no_delta_until_two_snapshots(self):
        clk = FakeClock()
        ts = TimeSeries(window_s=10, capacity=4, clock=clk)
        assert ts.delta(1) is None
        ts.maybe_tick()  # baseline
        assert ts.delta(1) is None
        clk.advance(10)
        assert ts.maybe_tick() == 1
        assert ts.delta(1) is not None

    def test_window_delta_isolates_recent_observations(self):
        clk = FakeClock()
        ts = TimeSeries(window_s=10, capacity=4, clock=clk)
        ts.maybe_tick()
        _slow_batch("c1", e2e_s=1.0)
        clk.advance(10)
        ts.maybe_tick()
        d = ts.delta(1)
        assert d.chain_hists()["c1"].count == 1
        # next window is idle: the delta must read exactly zero
        clk.advance(10)
        ts.maybe_tick()
        assert "c1" not in ts.delta(1).chain_hists()
        # ...but the 2-window delta still holds the observation
        assert ts.delta(2).chain_hists()["c1"].count == 1

    def test_reader_gap_keeps_activity_in_the_short_window(self):
        clk = FakeClock()
        ts = TimeSeries(window_s=10, capacity=4, clock=clk)
        ts.maybe_tick()
        _slow_batch("c1")
        clk.advance(35)  # 3 whole windows elapsed with no reader
        assert ts.maybe_tick() == 3
        # ONE entry spanning the gap: the short window covers everything
        # since the reader last looked — a sparse scraper still catches
        # a fresh burn — and rates divide by the TRUE duration
        d = ts.delta(1)
        assert d.chain_hists()["c1"].count == 1
        # the stamp is the SAMPLE instant, so the delta divides by the
        # true 35 s span — not a boundary-aligned 30 s that would
        # overstate rates
        assert d.duration_s == pytest.approx(35.0)
        # the next tick moves the activity out of the short window
        clk.advance(10)
        ts.maybe_tick()
        assert "c1" not in ts.delta(1).chain_hists()
        assert ts.delta(4).chain_hists()["c1"].count == 1

    def test_ring_capacity_bounds_history(self):
        clk = FakeClock()
        ts = TimeSeries(window_s=10, capacity=3, clock=clk)
        ts.maybe_tick()
        for _ in range(10):
            clk.advance(10)
            ts.maybe_tick()
        assert ts.retained_windows() == 3
        # a huge gap jumps straight to the last capacity+1 boundaries
        clk.advance(10 * 500)
        ts.maybe_tick()
        assert ts.retained_windows() == 3

    def test_disabled_telemetry_never_captures(self, monkeypatch):
        TELEMETRY.enabled = False
        clk = FakeClock()
        ts = TimeSeries(window_s=10, capacity=4, clock=clk)
        monkeypatch.setattr(
            TELEMETRY, "timeseries_sample",
            lambda: (_ for _ in ()).throw(AssertionError("sampled while off")),
        )
        assert ts.maybe_tick() == 0
        clk.advance(100)
        assert ts.maybe_tick() == 0
        ts.force_tick()
        assert ts.retained_windows() == 0


# ---------------------------------------------------------------------------
# FLUVIO_SLO grammar
# ---------------------------------------------------------------------------


class TestGrammar:
    def test_defaults_cover_the_documented_rule_set(self):
        names = {r.name for r in DEFAULT_RULES}
        assert names == {
            "e2e_p99", "spill_ratio", "error_rate", "compile_budget",
            "recompile_rate", "queue_depth", "hbm_staged",
            "consumer_lag", "record_age_p99", "hbm_headroom",
        }
        # hbm_headroom stays dormant until FLUVIO_MEM_BUDGET arms it
        by_name = {r.name: r for r in DEFAULT_RULES}
        assert not by_name["hbm_headroom"].enabled

    def test_target_and_warn_overrides(self):
        rules = {
            r.name: r
            for r in parse_slo_spec("e2e_p99:target_ms=250;queue_depth:target=16,warn=0.5")
        }
        assert rules["e2e_p99"].target == pytest.approx(0.25)
        assert rules["queue_depth"].target == 16
        assert rules["queue_depth"].warn_ratio == 0.5
        # untouched rules keep their defaults
        assert rules["spill_ratio"].target == 0.05

    def test_off_disables_a_rule(self):
        rules = {r.name: r for r in parse_slo_spec("spill_ratio:off=1")}
        assert not rules["spill_ratio"].enabled
        assert rules["e2e_p99"].enabled

    def test_malformed_spec_raises(self):
        with pytest.raises(ValueError):
            parse_slo_spec("no_such_rule:target=1")
        with pytest.raises(ValueError):
            parse_slo_spec("e2e_p99:bogus_field=1")
        with pytest.raises(ValueError):
            parse_slo_spec("e2e_p99:target")

    def test_env_loader_falls_back_on_garbage(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_SLO", "e2e_p99:target_ms=100")
        rules = {r.name: r for r in rules_from_env()}
        assert rules["e2e_p99"].target == pytest.approx(0.1)
        monkeypatch.setenv("FLUVIO_SLO", "garbage!!!")
        assert rules_from_env() == DEFAULT_RULES

    def test_disabled_rule_never_evaluates(self):
        clk = FakeClock()
        eng = _engine(clk, rules=parse_slo_spec("e2e_p99:off=1"))
        eng.evaluate()
        _slow_batch()
        clk.advance(10)
        doc = eng.evaluate()
        assert "filter+map" not in doc["chains"]
        assert "e2e_p99" not in doc["targets"]


# ---------------------------------------------------------------------------
# Burn-rate verdicts: flip to breach, deterministic recovery
# ---------------------------------------------------------------------------


class TestVerdicts:
    def test_e2e_p99_breach_and_recovery(self):
        clk = FakeClock()
        eng = _engine(clk, capacity=4)
        assert eng.evaluate()["verdict"] == "ok"
        _slow_batch("filter+map", e2e_s=5.0)
        clk.advance(10)
        doc = eng.evaluate()
        entry = doc["chains"]["filter+map"]
        assert entry["verdict"] == "breach"
        ev = entry["rules"]["e2e_p99"]
        # named evidence: which window, observed vs target
        assert ev["observed"] > ev["target"] == 2.0
        assert ev["window_s"] == pytest.approx(10.0)
        assert doc["verdict"] == "breach"
        # recovery: clean traffic, windows age out deterministically
        verdicts = []
        for _ in range(6):
            _fast_batch("filter+map")
            clk.advance(10)
            verdicts.append(
                eng.evaluate()["chains"]["filter+map"]["verdict"]
            )
        # short window goes clean immediately -> warn (budget consumed,
        # not burning); once the slow batch ages out of the long window
        # the verdict returns to ok — monotone, no flapping back
        assert verdicts[0] == "warn"
        assert verdicts[-1] == "ok"
        assert "breach" not in verdicts

    def test_queue_depth_ceiling_is_instantaneous(self):
        clk = FakeClock()
        eng = _engine(clk)
        eng.evaluate()
        TELEMETRY.gauge_set("inflight_queue_depth", 500)
        clk.advance(10)
        doc = eng.evaluate()
        assert doc["chains"][ENGINE_CHAIN]["rules"]["queue_depth"][
            "verdict"
        ] == "breach"
        TELEMETRY.gauge_set("inflight_queue_depth", 2)
        clk.advance(10)
        doc = eng.evaluate()
        assert doc["chains"][ENGINE_CHAIN]["rules"]["queue_depth"][
            "verdict"
        ] == "ok"

    def test_fault_injection_flips_error_rate_to_breach(self):
        """The PR-3 fault registry drives the differential: injected
        device faults produce real retries through the real executor,
        and the SLO engine must read them as an error-rate breach —
        then recover once the injection clears."""
        clk = FakeClock()
        eng = _engine(clk, capacity=4)
        eng.evaluate()
        chain = build_chain([("regex-filter", {"regex": "fluvio"})])
        assert chain.backend_in_use == "tpu"
        buf = make_buf([b'{"name":"fluvio"}'] * 32)
        chain.tpu_chain.process_buffer(buf)  # warm compile outside window
        faults.FAULTS.inject("device", first=2)
        try:
            chain.tpu_chain.process_buffer(buf)
        finally:
            faults.FAULTS.clear()
        assert sum(TELEMETRY.retries.values()) >= 1
        clk.advance(10)
        doc = eng.evaluate()
        ev = doc["chains"][ENGINE_CHAIN]["rules"]["error_rate"]
        assert ev["verdict"] == "breach", ev
        # recovery: clean batches only, fault cleared
        for _ in range(6):
            chain.tpu_chain.process_buffer(buf)
            clk.advance(10)
            doc = eng.evaluate()
        assert doc["chains"][ENGINE_CHAIN]["rules"]["error_rate"][
            "verdict"
        ] == "ok"

    def test_recompile_storm_flips_compile_rules_to_breach(self):
        clk = FakeClock()
        eng = _engine(clk, capacity=4)
        eng.evaluate()
        # an injected storm: 20 compiles, 0.5 s each, inside one window
        for i in range(20):
            TELEMETRY.add_compile("ragged", f"sig{i}", 0.5)
        clk.advance(10)
        doc = eng.evaluate()
        rules = doc["chains"][ENGINE_CHAIN]["rules"]
        # 20 compiles / 10 s = 120/min >> 8/min target
        assert rules["recompile_rate"]["verdict"] == "breach"
        # 10 s of compile wall in a 10 s window >> 0.25 s/s budget
        assert rules["compile_budget"]["verdict"] == "breach"
        # storm ends: verdicts age back out
        for _ in range(6):
            clk.advance(10)
            doc = eng.evaluate()
        rules = doc["chains"][ENGINE_CHAIN]["rules"]
        assert rules["recompile_rate"]["verdict"] == "ok"
        assert rules["compile_budget"]["verdict"] == "ok"

    def test_spill_ratio_reads_interpreter_share(self):
        clk = FakeClock()
        eng = _engine(clk)
        eng.evaluate()
        for _ in range(8):
            span = TELEMETRY.begin_batch(path="interpreter", chain="py")
            TELEMETRY.end_batch(span, records=1)
        for _ in range(2):
            _fast_batch()
        clk.advance(10)
        doc = eng.evaluate()
        ev = doc["chains"][ENGINE_CHAIN]["rules"]["spill_ratio"]
        assert ev["verdict"] == "breach"
        assert ev["observed"] == pytest.approx(0.8)

    def test_breach_emits_flight_recorder_instant_event(self):
        from fluvio_tpu.telemetry import render_trace

        clk = FakeClock()
        eng = _engine(clk)
        eng.evaluate()
        _slow_batch("filter+map")
        clk.advance(10)
        eng.evaluate()
        events = TELEMETRY.events_json()
        breaches = [e for e in events if e["kind"] == "slo-breach"]
        assert breaches and "e2e_p99" in breaches[0]["detail"]
        # the transition is ONE event — a second evaluation in breach
        # must not re-fire it
        clk.advance(0.5)
        eng.evaluate()
        events = TELEMETRY.events_json()
        assert len([e for e in events if e["kind"] == "slo-breach"]) == len(
            breaches
        )
        # Perfetto-visible: the instant event renders into the trace doc
        doc = render_trace()
        names = [e.get("name") for e in doc["traceEvents"]]
        assert "slo-breach" in names
        # and the breach counter keys chain/rule
        assert TELEMETRY.snapshot()["counters"]["slo_breaches"] == {
            "filter+map/e2e_p99": 1
        }

    def test_summarize_compacts_the_document(self):
        clk = FakeClock()
        eng = _engine(clk)
        eng.evaluate()
        _slow_batch("filter+map")
        clk.advance(10)
        s = summarize(eng.evaluate())
        assert s["verdict"] == "breach"
        assert s["breached_chains"] == ["filter+map"]
        assert s["rules"]["e2e_p99"]["target"] == 2.0
        assert s["rules"]["e2e_p99"]["verdict"] == "breach"


# ---------------------------------------------------------------------------
# Breach-triggered device profiling
# ---------------------------------------------------------------------------


def _artifact_bytes(root: str) -> int:
    return sum(
        os.path.getsize(os.path.join(r, f))
        for r, _, fs in os.walk(root)
        for f in fs
    )


class TestBreachProfiling:
    def test_capture_once_per_cooldown_with_nonempty_artifact(self, tmp_path):
        clk = FakeClock()
        eng = _engine(
            clk, profile_dir=str(tmp_path), profile_cooldown_s=60.0
        )
        eng.evaluate()
        _slow_batch("chain-a")
        _slow_batch("chain-b")
        clk.advance(10)
        doc = eng.evaluate()
        # the capture runs on a worker thread (the monitoring event
        # loop must never stall on a jit compile); join it for the
        # artifact assertions
        eng.join_profile_capture()
        # two chains breached in one evaluation: the cooldown still
        # bounds capture to exactly ONE bounded jax.profiler window
        assert len(eng.profile_captures) == 1
        assert doc["profile_captures"] == eng.profile_captures
        assert _artifact_bytes(eng.profile_captures[0]) > 0
        # a fresh breach inside the cooldown: no second capture
        _slow_batch("chain-c")
        clk.advance(10)
        eng.evaluate()
        eng.join_profile_capture()
        assert len(eng.profile_captures) == 1
        # past the cooldown, a new breach transition captures again.
        # chain-d is fresh, so its breach is a transition.
        clk.advance(60)
        eng.timeseries.maybe_tick()
        _slow_batch("chain-d")
        clk.advance(10)
        eng.evaluate()
        eng.join_profile_capture()
        assert len(eng.profile_captures) == 2
        assert _artifact_bytes(eng.profile_captures[1]) > 0

    def test_no_profile_dir_means_no_capture(self):
        clk = FakeClock()
        eng = _engine(clk, profile_dir="")
        eng.evaluate()
        _slow_batch()
        clk.advance(10)
        doc = eng.evaluate()
        assert doc["verdict"] == "breach"
        assert eng.profile_captures == []
        assert "profile_captures" not in doc


# ---------------------------------------------------------------------------
# Surfaces: socket health mode, CLI, watch
# ---------------------------------------------------------------------------


class _Ctx:
    def __init__(self):
        from fluvio_tpu.spu.metrics import SpuMetrics

        self.metrics = SpuMetrics()


class TestHealthSurfaces:
    def _roundtrip(self, tmp_path, fn):
        from fluvio_tpu.spu.monitoring import MonitoringServer

        async def run():
            server = MonitoringServer(_Ctx(), str(tmp_path / "h.sock"))
            await server.start()
            try:
                return await fn(server)
            finally:
                await server.stop()

        return asyncio.run(run())

    def test_health_mode_over_socket(self, tmp_path):
        from fluvio_tpu.spu.monitoring import read_health

        _fast_batch("filter+map")
        doc = self._roundtrip(tmp_path, lambda s: read_health(s.path))
        assert doc["enabled"] is True
        assert doc["verdict"] in ("ok", "warn", "breach")
        assert ENGINE_CHAIN in doc["chains"]
        assert "e2e_p99" in doc["targets"]

    def test_cli_health_exit_codes_and_formats(self, capsys):
        from fluvio_tpu.cli import main

        # ok: in-process evaluation, table format
        rc = main(["health", "--local"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "overall: ok" in out
        # breach: install a fake-clock engine as the process-global one
        clk = FakeClock()
        slo_mod._ENGINE = _engine(clk)
        slo_mod._ENGINE.evaluate()
        _slow_batch("filter+map")
        clk.advance(10)
        rc = main(["health", "--local", "--format", "json"])
        assert rc == 1  # nonzero on breach: the deploy-gate contract
        doc = json.loads(capsys.readouterr().out)
        assert doc["verdict"] == "breach"
        assert doc["chains"]["filter+map"]["rules"]["e2e_p99"][
            "verdict"
        ] == "breach"

    def test_render_health_table_carries_evidence(self):
        from fluvio_tpu.cli.health import render_health_table

        clk = FakeClock()
        eng = _engine(clk)
        eng.evaluate()
        _slow_batch("filter+map")
        clk.advance(10)
        table = render_health_table(eng.evaluate())
        assert "overall: breach" in table
        assert "filter+map" in table and "e2e_p99" in table
        assert "2000ms" in table  # target rendered in ms
        # disabled telemetry renders an honest notice, not a verdict
        assert "FLUVIO_TELEMETRY=0" in render_health_table(
            {"enabled": False}
        )

    def test_metrics_watch_redraws_and_exits_after_count(self, tmp_path, capsys):
        from fluvio_tpu.cli import main
        from fluvio_tpu.spu.monitoring import MonitoringServer

        _fast_batch("filter+map")

        async def run():
            server = MonitoringServer(_Ctx(), str(tmp_path / "w.sock"))
            await server.start()
            try:
                from fluvio_tpu.cli.metrics import metrics as metrics_fn
                from fluvio_tpu.cli import build_parser

                args = build_parser().parse_args(
                    ["metrics", "--path", server.path, "--watch", "0.01",
                     "--watch-count", "2"]
                )
                return await metrics_fn(args)
            finally:
                await server.stop()

        rc = asyncio.run(run())
        assert rc == 0
        out = capsys.readouterr().out
        # two redraws, each behind an ANSI clear-home
        assert out.count("\x1b[2J\x1b[H") == 2
        assert out.count("pipeline events") == 2

    def test_metrics_watch_honors_format_and_rejects_zero(
        self, tmp_path, capsys
    ):
        from fluvio_tpu.cli import main
        from fluvio_tpu.spu.monitoring import MonitoringServer

        async def run(fmt_args):
            server = MonitoringServer(_Ctx(), str(tmp_path / "w2.sock"))
            await server.start()
            try:
                from fluvio_tpu.cli import build_parser
                from fluvio_tpu.cli.metrics import metrics as metrics_fn

                args = build_parser().parse_args(
                    ["metrics", "--path", server.path, "--watch", "0.01",
                     "--watch-count", "1"] + fmt_args
                )
                return await metrics_fn(args)
            finally:
                await server.stop()

        assert asyncio.run(run(["--format", "json"])) == 0
        out = capsys.readouterr().out
        assert '"telemetry"' in out  # json body, not the table
        assert "pipeline events" not in out
        # --watch 0 is a usage error, not a silent one-shot
        rc = main(["metrics", "--watch", "0"])
        assert rc == 1
        assert "--watch" in capsys.readouterr().err

    def test_chain_identity_rides_spans_and_snapshot(self):
        """End-to-end: a real fused chain labels its spans with the
        executor signature and the snapshot grows the per-chain family
        the SLO engine windows."""
        chain = build_chain(
            [("regex-filter", {"regex": "fluvio"}),
             ("json-map", {"field": "name"})],
        )
        buf = make_buf(
            [b'{"name":"fluvio-%d"}' % i for i in range(32)]
            + [b'{"name":"kafka"}'] * 32
        )
        chain.tpu_chain.process_buffer(buf)
        spans = TELEMETRY.spans.recent()
        assert spans and spans[-1].chain == "filter+map"
        assert spans[-1].to_dict()["chain"] == "filter+map"
        snap = TELEMETRY.snapshot()
        assert snap["chains"]["filter+map"]["count"] == 1
        # interpreter reruns of the same chain land in the SAME family
        assert chain.chain_label == "filter+map"
