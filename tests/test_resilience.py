"""Chaos suite for the resilience layer (ISSUE-3).

For every fault point the harness can arm, the pipeline must produce
results byte-identical to the fault-free run — retries, heals, and
spills are invisible to the consumer. On top of the zero-divergence
smoke (tier-1, CPU-only, fast): aggregate carry exactness across
mid-stream retries and heal+retry interleavings, circuit-breaker
open/half-open/close transitions at configured thresholds, the
poison-batch quarantine round-trip, the monitoring socket's client-gone
containment, and KeyboardInterrupt/SystemExit propagation through every
recovery ladder.
"""

import asyncio
import json
import os

import pytest

from fluvio_tpu.cli.metrics import render_metrics_table
from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.resilience import faults
from fluvio_tpu.resilience.deadletter import load_entry, quarantine_batch
from fluvio_tpu.resilience.faults import FaultRegistry, InjectedFault
from fluvio_tpu.resilience.policy import (
    CLOSED,
    DETERMINISTIC,
    HALF_OPEN,
    OPEN,
    TRANSIENT,
    CircuitBreaker,
    RetryPolicy,
    classify,
)
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartmodule.types import SmartModuleInput
from fluvio_tpu.telemetry import TELEMETRY, render_prometheus

# the transient fault points the generic chaos smoke can arm on the
# headline chain (glz_encode/spill_rerun/socket_accept have their own
# dedicated tests — they need the result encoder / a forced spill / a socket)
GENERIC_POINTS = ("stage", "h2d", "dispatch", "device", "fetch")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # retries must not sleep in the suite; chains built inside each test
    # pick the knob up at construction
    monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
    faults.FAULTS.clear()
    TELEMETRY.reset()
    yield
    faults.FAULTS.clear()
    TELEMETRY.reset()


def _build(backend="tpu", modules=(("regex-filter", {"regex": "fluvio"}),
                                   ("json-map", {"field": "name"}))):
    b = SmartEngine(backend=backend).builder()
    for name, params in modules:
        cfg = SmartModuleConfig(params=dict(params))
        if name.startswith("aggregate"):
            cfg.initial_data = b"0"
        b.add_smart_module(cfg, lookup(name))
    chain = b.initialize()
    if backend == "tpu":
        assert chain.backend_in_use == "tpu"
    return chain


def _slabs(n=3, rows=96, agg=False):
    out = []
    for k in range(n):
        if agg:
            recs = [
                Record(value=b"%d" % (k * 100 + i), offset_delta=i)
                for i in range(rows)
            ]
        else:
            names = ("fluvio", "kafka", "fluvio-tpu", "pulsar")
            recs = [
                Record(
                    value=b'{"name":"%s-%d","n":%d}'
                    % (names[(k + i) % 4].encode(), i, i),
                    offset_delta=i,
                )
                for i in range(rows)
            ]
        out.append(SmartModuleInput.from_records(recs))
    return out


def _run(chain, slabs):
    outs = []
    for s in slabs:
        out = chain.process(s)
        assert out.error is None
        outs.append([(r.key, r.value) for r in out.successes])
    return outs


# ---------------------------------------------------------------------------
# harness: spec grammar + trigger modes + classifier
# ---------------------------------------------------------------------------


class TestFaultHarness:
    def test_env_spec_grammar(self):
        reg = FaultRegistry()
        reg.load_env_spec(
            "device:first=2;fetch:every=3,exc=deterministic;h2d:prob=0.5,seed=1"
        )
        assert reg.rule("device").first == 2
        assert reg.rule("fetch").every == 3
        assert reg.rule("fetch").exc == "deterministic"
        assert reg.rule("h2d").prob == 0.5

    @pytest.mark.parametrize(
        "spec",
        [
            "bogus-point:first=1",
            "device:first=1,every=2",     # two trigger modes
            "device:exc=weird,first=1",
            "device:nope=3",
        ],
    )
    def test_env_spec_rejects_malformed(self, spec):
        with pytest.raises(ValueError):
            FaultRegistry().load_env_spec(spec)

    def test_trigger_modes(self):
        reg = FaultRegistry()
        rule = reg.inject("device", first=2)
        fired = 0
        for _ in range(5):
            try:
                reg.fire("device")
            except InjectedFault:
                fired += 1
        assert fired == 2 and rule.hits == 5
        reg.clear()
        reg.inject("device", every=3)
        fired = [False] * 6
        for i in range(6):
            try:
                reg.fire("device")
            except InjectedFault:
                fired[i] = True
        assert fired == [False, False, True, False, False, True]

    def test_env_entry_point_arms_global_registry(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_FAULTS", "device:first=1")
        faults._load_from_env()
        assert faults.FAULTS.rule("device").first == 1
        with pytest.raises(InjectedFault):
            faults.maybe_fire("device")
        faults.FAULTS.clear()

    def test_malformed_env_spec_never_crashes_startup(self, monkeypatch):
        # a broken chaos spec on a production broker must log, not raise
        monkeypatch.setenv("FLUVIO_FAULTS", "not-a-point:first=1")
        faults._load_from_env()
        assert not faults.FAULTS.armed

    def test_env_spec_arms_all_or_nothing(self):
        # a malformed SECOND entry must not leave the first one live
        # while the error log claims the process runs un-armed
        reg = FaultRegistry()
        with pytest.raises(ValueError):
            reg.load_env_spec("device:first=1;fetch:evry=3")
        assert not reg.armed
        with pytest.raises(ValueError):
            reg.load_env_spec("device:first=1;bogus-point:first=1")
        assert not reg.armed

    def test_unarmed_seam_is_noop(self):
        faults.FAULTS.clear()
        assert not faults.FAULTS.armed
        faults.maybe_fire("device")  # must not raise

    def test_instance_template_yields_fresh_exception_per_fire(self):
        reg = FaultRegistry()
        reg.inject(
            "device", every=1,
            exc=InjectedFault("device", transient=False),
        )
        raised = []
        for _ in range(2):
            try:
                reg.fire("device")
            except InjectedFault as e:
                raised.append(e)
        assert raised[0] is not raised[1], "template must be copied per fire"
        assert all(not e.transient for e in raised)

    def test_classifier(self):
        assert classify(InjectedFault("device")) == TRANSIENT
        assert classify(InjectedFault("device", transient=False)) == DETERMINISTIC
        assert classify(RuntimeError("RESOURCE_EXHAUSTED: hbm oom")) == TRANSIENT
        assert classify(ConnectionResetError()) == TRANSIENT
        assert classify(ValueError("bad lowering")) == DETERMINISTIC
        assert classify(RuntimeError("plain bug")) == DETERMINISTIC

    def test_retry_policy_backoff_monotone_and_capped(self):
        p = RetryPolicy(max_retries=3, base_ms=2, cap_ms=8, jitter=0.0)
        assert [p.backoff_s(a) for a in range(4)] == [
            0.002, 0.004, 0.008, 0.008
        ]
        assert p.should_retry(InjectedFault("x"), 2)
        assert not p.should_retry(InjectedFault("x"), 3)
        assert not p.should_retry(InjectedFault("x", transient=False), 0)


# ---------------------------------------------------------------------------
# chaos smoke (tier-1): one transient fault per point, zero divergence
# ---------------------------------------------------------------------------


class TestChaosZeroDivergence:
    @pytest.mark.parametrize("point", GENERIC_POINTS)
    def test_transient_fault_is_invisible(self, point):
        slabs = _slabs()
        ref = _run(_build("python"), slabs)
        chain = _build("tpu")
        faults.FAULTS.inject(point, first=1)
        got = _run(chain, slabs)
        faults.FAULTS.clear()
        assert got == ref
        assert TELEMETRY.snapshot()["counters"]["retries"].get(point, 0) >= 1
        # the fused path recovered — no spill, breaker stays closed
        assert chain.breaker.state == CLOSED

    def test_deterministic_fault_spills_to_interpreter(self):
        slabs = _slabs()
        ref = _run(_build("python"), slabs)
        chain = _build("tpu")
        faults.FAULTS.inject("device", first=1, exc="deterministic")
        got = _run(chain, slabs)
        faults.FAULTS.clear()
        assert got == ref
        counters = TELEMETRY.snapshot()["counters"]
        assert counters["spills"].get("fused-error") == 1
        assert not counters["retries"], "deterministic faults must not retry"


# ---------------------------------------------------------------------------
# aggregate carries: retries and heals can never double-count
# ---------------------------------------------------------------------------


class TestCarrySafety:
    AGG = (("aggregate-sum", {}),)

    def _acc(self, chain):
        chain.tpu_chain._ensure_host_state()
        return chain.tpu_chain.carries[0][0]

    def test_carry_exact_across_mid_stream_retry(self):
        slabs = _slabs(n=4, agg=True)
        py = _build("python", self.AGG)
        ref = _run(py, slabs)
        chain = _build("tpu", self.AGG)
        # every=3: the device seam fires mid-stream (slab 3), after the
        # carry chain already holds two slabs of state
        faults.FAULTS.inject("device", every=3)
        got = _run(chain, slabs)
        faults.FAULTS.clear()
        assert got == ref
        assert str(self._acc(chain)).encode() == py.instances[0].accumulator
        assert TELEMETRY.snapshot()["counters"]["retries"].get("device", 0) >= 1

    def test_carry_exact_across_heal_retry_interleaving(self, monkeypatch):
        # encode heal (the result encoder latches off, batch
        # re-dispatches) AND a transient fetch fault on the same stream:
        # the carry chain must come out exact. The aggregate is NOT last,
        # so the chain ships byte-mode payloads the encoder arms on.
        monkeypatch.setenv("FLUVIO_RESULT_COMPRESS", "on")
        mods = self.AGG + (("regex-filter", {"regex": "[0-9]"}),)
        slabs = [
            SmartModuleInput.from_records(
                [
                    Record(value=b"%06d" % ((i * (k + 1)) & 63, ), offset_delta=i)
                    for i in range(4000)
                ]
            )
            for k in range(3)
        ]
        py = _build("python", mods)
        ref = _run(py, slabs)
        chain = _build("tpu", mods)
        assert chain.tpu_chain._enc_variant == "xla"
        faults.FAULTS.inject("glz_encode", first=1)
        faults.FAULTS.inject("fetch", first=1)
        got = _run(chain, slabs)
        faults.FAULTS.clear()
        assert got == ref
        assert str(self._acc(chain)).encode() == py.instances[0].accumulator
        counters = TELEMETRY.snapshot()["counters"]
        assert counters["heals"] >= 1, "glz_encode fault should have healed"
        assert chain.tpu_chain._enc_variant == "off", "heal latches encode off"
        assert counters["retries"].get("fetch", 0) >= 1

    def test_sharded_retry_zero_divergence(self):
        # the multi-device engine mode retries through the same policy:
        # dispatch-side faults re-stage (carries commit post-call) and
        # device/fetch-side faults re-dispatch from the handle snapshot
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs the virtual multi-device mesh")
        py = _build("python", self.AGG)
        slabs = _slabs(n=3, agg=True)
        ref = _run(py, slabs)
        b = SmartEngine(backend="tpu", mesh_devices=4).builder()
        cfg = SmartModuleConfig(params={})
        cfg.initial_data = b"0"
        b.add_smart_module(cfg, lookup("aggregate-sum"))
        chain = b.initialize()
        assert chain.tpu_chain._sharded is not None
        faults.FAULTS.inject("dispatch", first=1)
        faults.FAULTS.inject("device", first=1)
        got = _run(chain, slabs)
        faults.FAULTS.clear()
        assert got == ref
        assert str(self._acc(chain)).encode() == py.instances[0].accumulator
        retries = TELEMETRY.snapshot()["counters"]["retries"]
        assert retries.get("dispatch", 0) >= 1
        assert retries.get("device", 0) >= 1

    def test_pipelined_stateless_stream_retry(self):
        # the broker's pipelined two-phase loop (dispatch k+1 while k
        # fetches): a transient fetch fault mid-stream must not change
        # any yielded batch
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

        chain = _build("tpu")
        ex = chain.tpu_chain
        bufs = [
            RecordBuffer.from_smartmodule_input(s) for s in _slabs(n=4)
        ]
        ref = [
            [r.value for r in out.to_records()]
            for out in ex.process_stream(iter(bufs))
        ]
        faults.FAULTS.inject("fetch", every=2)
        got = [
            [r.value for r in out.to_records()]
            for out in ex.process_stream(iter(bufs))
        ]
        faults.FAULTS.clear()
        assert got == ref


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def _breaker(self, **kw):
        t = [0.0]
        kw.setdefault("threshold", 3)
        kw.setdefault("window_s", 10.0)
        kw.setdefault("cooldown_s", 5.0)
        kw.setdefault("probes", 2)
        br = CircuitBreaker(clock=lambda: t[0], **kw)
        return br, t

    def test_opens_at_threshold_within_window(self):
        br, t = self._breaker()
        for _ in range(2):
            br.record_failure()
        assert br.state == CLOSED
        br.record_failure()
        assert br.state == OPEN
        assert not br.allow_fused()

    def test_window_expiry_forgives_old_failures(self):
        br, t = self._breaker()
        br.record_failure()
        br.record_failure()
        t[0] = 11.0  # past the window
        br.record_failure()
        assert br.state == CLOSED

    def test_half_open_probe_cycle(self):
        br, t = self._breaker()
        for _ in range(3):
            br.record_failure()
        assert br.state == OPEN
        t[0] = 4.9
        assert not br.allow_fused(), "cooldown not elapsed"
        t[0] = 5.1
        assert br.allow_fused()
        assert br.state == HALF_OPEN
        br.record_success()
        assert br.state == HALF_OPEN, "needs P probe passes"
        br.record_success()
        assert br.state == CLOSED
        trans = TELEMETRY.snapshot()["counters"]["breaker"]["transitions"]
        assert trans == {"open": 1, "half_open": 1, "closed": 1}

    def test_probe_failure_reopens(self):
        br, t = self._breaker()
        for _ in range(3):
            br.record_failure()
        t[0] = 6.0
        assert br.allow_fused()
        br.record_failure()
        assert br.state == OPEN
        t[0] = 10.0
        assert not br.allow_fused(), "cooldown restarts from the reopen"

    def test_expected_spills_do_not_trip_the_breaker(self, monkeypatch):
        # TpuSpill demotions are often data-dependent (a record that
        # errors under exact semantics, a too-wide batch) — device
        # health is what the breaker guards, so spills must not open it
        # and demote CLEAN batches to interpreter speed
        from fluvio_tpu.smartengine.tpu.executor import TpuSpill

        chain = _build("tpu")
        chain.breaker.threshold = 2

        def spill(inp, metrics=None):
            raise TpuSpill("record errors under exact semantics")

        monkeypatch.setattr(chain.tpu_chain, "process", spill)
        slabs = _slabs(n=1)
        ref = _run(_build("python"), slabs)
        for _ in range(4):  # well past the threshold
            assert _run(chain, slabs) == ref
        assert chain.breaker.state == CLOSED

    def test_chain_demotes_and_repromotes(self):
        slabs = _slabs(n=1)
        ref = _run(_build("python"), slabs)
        chain = _build("tpu")
        br = chain.breaker
        br.threshold, br.window_s, br.cooldown_s, br.probes = 2, 100.0, 50.0, 1
        t = [0.0]
        br.clock = lambda: t[0]

        rule = faults.FAULTS.inject("device", every=1, exc="deterministic")
        assert _run(chain, slabs) == ref  # interpreter rerun, failure 1
        assert br.state == CLOSED
        assert _run(chain, slabs) == ref  # failure 2 -> trips
        assert br.state == OPEN
        hits_when_open = rule.hits
        # open: the fused path is not even attempted — the device seam
        # must not record another hit, output still exact
        assert _run(chain, slabs) == ref
        assert rule.hits == hits_when_open
        assert TELEMETRY.snapshot()["counters"]["breaker"]["short_circuits"] >= 1
        # cooldown elapses, fault cleared: the probe passes and the
        # chain re-promotes to fused
        # while open, the rerun takes the same ladder as a spill: the
        # spill_rerun seam is reachable and transient faults there retry
        # instead of condemning the batch
        faults.FAULTS.clear()
        faults.FAULTS.inject("spill_rerun", first=1)
        assert _run(chain, slabs) == ref
        assert (
            TELEMETRY.snapshot()["counters"]["retries"].get("spill_rerun", 0)
            >= 1
        )
        assert TELEMETRY.snapshot()["counters"]["quarantined"] == 0
        faults.FAULTS.clear()
        t[0] = 51.0
        assert _run(chain, slabs) == ref
        assert br.state == CLOSED
        snap = TELEMETRY.snapshot()["counters"]["breaker"]
        assert snap["states"][br.name] == CLOSED
        assert snap["transitions"].get("open", 0) >= 1
        assert snap["transitions"].get("half_open", 0) >= 1


# ---------------------------------------------------------------------------
# poison-batch quarantine
# ---------------------------------------------------------------------------


class TestQuarantine:
    def _arm_poison(self):
        faults.FAULTS.inject("device", every=1, exc="deterministic")
        faults.FAULTS.inject("spill_rerun", every=1, exc="deterministic")

    def test_round_trip_and_stream_advances(self, monkeypatch, tmp_path):
        monkeypatch.setenv("FLUVIO_DEADLETTER_DIR", str(tmp_path))
        slabs = _slabs(n=2)
        chain = _build("tpu")
        self._arm_poison()
        out = chain.process(slabs[0])
        # the stream advances: empty output, NO error
        assert out.error is None and not out.successes
        assert TELEMETRY.snapshot()["counters"]["quarantined"] == 1
        # disarm: the very next slab processes normally on the same chain
        faults.FAULTS.clear()
        ok = chain.process(slabs[1])
        assert ok.error is None and ok.successes
        # the dead-letter entry is replayable: chain spec + exact records
        files = sorted(os.listdir(tmp_path))
        assert len(files) == 1
        spec, inp = load_entry(str(tmp_path / files[0]))
        assert [m["name"] for m in spec] == ["regex-filter", "json-map"]
        entry = json.loads((tmp_path / files[0]).read_text())
        assert "device" in entry["errors"]["fused"]
        assert "spill_rerun" in entry["errors"]["interpreter"]
        replay = _build("python").process(inp)
        ref = _build("python").process(slabs[0])
        assert [r.value for r in replay.successes] == [
            r.value for r in ref.successes
        ]

    def test_dead_letter_dir_is_bounded(self, monkeypatch, tmp_path):
        monkeypatch.setenv("FLUVIO_DEADLETTER_DIR", str(tmp_path))
        monkeypatch.setenv("FLUVIO_DEADLETTER_MAX", "2")
        chain = _build("tpu")
        self._arm_poison()
        for s in _slabs(n=3, rows=8):
            chain.process(s)
        faults.FAULTS.clear()
        assert TELEMETRY.snapshot()["counters"]["quarantined"] == 3
        files = sorted(os.listdir(tmp_path))
        assert len(files) == 2, "oldest entry must be evicted"

    def test_unserializable_chain_spec_never_crashes(self, tmp_path):
        # params are not validated as str->str; a bytes value must not
        # let the quarantine itself blow up the stream
        inp = _slabs(n=1, rows=2)[0]
        path = quarantine_batch(
            [{"name": "m", "kind": "filter", "params": {"pat": b"\xff\x00"}}],
            inp,
            RuntimeError("fused"),
            RuntimeError("interp"),
            directory=str(tmp_path),
        )
        assert path is not None, "repr-degraded spec should still write"
        spec, _ = load_entry(path)
        assert spec[0]["name"] == "m"
        assert not [
            n for n in os.listdir(tmp_path) if n.endswith(".tmp")
        ], "no debris"

    def test_unwritable_dir_still_counts(self, monkeypatch):
        monkeypatch.setenv(
            "FLUVIO_DEADLETTER_DIR", "/proc/definitely/not/writable"
        )
        chain = _build("tpu")
        self._arm_poison()
        out = chain.process(_slabs(n=1)[0])
        faults.FAULTS.clear()
        assert out.error is None
        assert TELEMETRY.snapshot()["counters"]["quarantined"] == 1

    def test_quarantine_rolls_back_half_advanced_aggregate(
        self, monkeypatch, tmp_path
    ):
        # an interpreter rerun that mutates an accumulator BEFORE it
        # fails must contribute nothing: the quarantined batch is
        # reported as never-processed, so replaying its dead-letter
        # entry later must not double-count
        monkeypatch.setenv("FLUVIO_DEADLETTER_DIR", str(tmp_path))
        chain = _build("tpu", (("aggregate-sum", {}),))
        inst = chain.instances[0]

        def evil_process(inp, metrics=None):
            inst.accumulator = b"999999"  # half-advanced, then dies
            raise RuntimeError("interpreter dies mid-batch")

        inst.process = evil_process  # instance attr shadows the method
        faults.FAULTS.inject("device", every=1, exc="deterministic")
        slabs = _slabs(n=2, agg=True)
        out = chain.process(slabs[0])
        faults.FAULTS.clear()
        del inst.process
        assert out.error is None and not out.successes
        assert TELEMETRY.snapshot()["counters"]["quarantined"] == 1
        assert inst.accumulator == b"0", "snapshot must roll back"
        # the next batch aggregates from the UNpoisoned base
        got = chain.process(slabs[1])
        py = _build("python", (("aggregate-sum", {}),))
        ref = py.process(slabs[1])
        assert [r.value for r in got.successes] == [
            r.value for r in ref.successes
        ]

    def test_quarantine_batch_direct(self, tmp_path):
        inp = _slabs(n=1, rows=4)[0]
        path = quarantine_batch(
            [{"name": "m", "kind": "filter", "params": {}}],
            inp,
            RuntimeError("fused boom"),
            RuntimeError("interp boom"),
            directory=str(tmp_path),
        )
        spec, inp2 = load_entry(path)
        assert spec[0]["name"] == "m"
        assert [r.value for r in inp2.into_records()] == [
            r.value for r in inp.into_records()
        ]


# ---------------------------------------------------------------------------
# counter surfaces: snapshot / Prometheus / CLI table
# ---------------------------------------------------------------------------


class TestCounterSurfaces:
    def _populate(self):
        TELEMETRY.add_retry("device")
        TELEMETRY.add_retry("fetch")
        TELEMETRY.add_quarantine()
        TELEMETRY.record_breaker("chain-t", OPEN)
        TELEMETRY.add_breaker_short_circuit()

    def test_all_three_families_in_prometheus(self):
        self._populate()
        text = render_prometheus()
        assert 'fluvio_tpu_retries_total{point="device"} 1' in text
        assert "fluvio_tpu_quarantined_total 1" in text
        assert 'fluvio_tpu_breaker_transitions_total{state="open"} 1' in text
        assert 'fluvio_tpu_breaker_state{chain="chain-t"} 2' in text
        assert "fluvio_tpu_breaker_short_circuits_total 1" in text

    def test_all_three_families_in_cli_table_and_json(self):
        self._populate()
        snap = {"telemetry": TELEMETRY.snapshot()}
        table = render_metrics_table(snap)
        assert "retry[device]" in table
        assert "quarantined" in table
        assert "breaker_to[open]" in table
        assert "breaker state" in table and "chain-t" in table
        counters = snap["telemetry"]["counters"]
        assert counters["retries"] == {"device": 1, "fetch": 1}
        assert counters["quarantined"] == 1
        assert counters["breaker"]["states"]["chain-t"] == OPEN

    def test_families_scrape_over_socket(self, tmp_path):
        from fluvio_tpu.spu.metrics import SpuMetrics
        from fluvio_tpu.spu.monitoring import MonitoringServer, read_prometheus

        self._populate()

        class _Ctx:
            metrics = SpuMetrics()

        async def run():
            server = MonitoringServer(_Ctx(), str(tmp_path / "m.sock"))
            await server.start()
            try:
                return await read_prometheus(server.path)
            finally:
                await server.stop()

        text = asyncio.run(run())
        for family in (
            "fluvio_tpu_retries_total",
            "fluvio_tpu_quarantined_total",
            "fluvio_tpu_breaker_transitions_total",
        ):
            assert family in text


# ---------------------------------------------------------------------------
# monitoring socket: client-gone containment
# ---------------------------------------------------------------------------


class TestMonitoringSocket:
    def test_client_gone_does_not_kill_accept_loop(self, tmp_path):
        from fluvio_tpu.spu.metrics import SpuMetrics
        from fluvio_tpu.spu.monitoring import MonitoringServer, read_metrics

        class _Ctx:
            metrics = SpuMetrics()

        async def run():
            server = MonitoringServer(_Ctx(), str(tmp_path / "m.sock"))
            await server.start()
            try:
                # client 1 hits an armed accept fault (stands in for a
                # mid-write disconnect: same except path)
                faults.FAULTS.inject("socket_accept", first=1)
                reader, writer = await asyncio.open_unix_connection(server.path)
                try:
                    writer.write(b"json\n")
                    await writer.drain()
                    await reader.read()
                except ConnectionError:
                    pass  # the server dropped us — that's the scenario
                finally:
                    writer.close()
                # client 2 disconnects without reading its payload
                _, w2 = await asyncio.open_unix_connection(server.path)
                w2.write(b"prom\n")
                w2.close()
                await asyncio.sleep(0.05)
                # client 3: the server must still answer
                return await read_metrics(server.path)
            finally:
                await server.stop()

        data = asyncio.run(run())
        assert "telemetry" in data or "smartmodule" in data
        declines = TELEMETRY.snapshot()["counters"]["declines"]
        assert declines.get("client-gone", 0) >= 1


# ---------------------------------------------------------------------------
# operator interrupts propagate through every recovery ladder
# ---------------------------------------------------------------------------


class TestInterruptPropagation:
    @pytest.mark.parametrize("point", ["dispatch", "device", "fetch"])
    def test_keyboard_interrupt_is_never_swallowed(self, point):
        chain = _build("tpu")
        faults.FAULTS.inject(point, first=1, exc=KeyboardInterrupt)
        with pytest.raises(KeyboardInterrupt):
            chain.process(_slabs(n=1)[0])
        faults.FAULTS.clear()
        counters = TELEMETRY.snapshot()["counters"]
        assert not counters["retries"], "interrupts must not be retried"
        assert not counters["spills"], "interrupts must not become spills"

    def test_system_exit_propagates_from_spill_rerun(self):
        chain = _build("tpu")
        faults.FAULTS.inject("device", first=1, exc="deterministic")
        faults.FAULTS.inject("spill_rerun", first=1, exc=SystemExit)
        with pytest.raises(SystemExit):
            chain.process(_slabs(n=1)[0])
        faults.FAULTS.clear()
        assert TELEMETRY.snapshot()["counters"]["quarantined"] == 0
