"""ISSUE-20: the device-memory accounting plane.

Covers the per-owner HBM ledger end to end:

- ledger arithmetic: acquire/release balance, re-acquire-as-resize,
  idempotent release, typed-owner rejection, process vs per-config
  peaks, and the gauge aliases (``hbm_staged_bytes`` /
  ``window_state_bytes``) republished FROM the ledger;
- leak detection: transient entries older than
  ``FLUVIO_MEM_LEAK_TTL_S`` flag ONCE (``memory_leaks_total`` counter +
  ``mem-leak`` flight-recorder instant), persistent owners are exempt,
  ``assert_drained`` pins quiesce, and a deliberately-stranded release
  on the REAL executor seam is detected;
- the chaos matrix: every generic fault point through the fused,
  sharded, partitioned, and windowed paths quiesces to zero transient
  bytes (retries and quarantine both retire their staged bookings);
- the budget chaos pin: an unbounded keyed-window workload grows the
  bank past ``FLUVIO_MEM_BUDGET`` -> ``hbm_headroom`` breach -> the
  admission controller sheds with a typed ``Rejected`` (no OOM) ->
  windows close, headroom recovers, the held slice serves -> the
  view/oracle tables agree (exactly-once);
- surfaces: registry snapshot ``memory`` section, ``memory_snapshot``
  document + disabled short-circuit, Prometheus families, the
  monitoring socket ``memory`` mode + ``read_memory``, the
  ``fluvio-tpu memory`` CLI exit-code contract, and the
  ``telemetry.memory`` lock-vocabulary pin.
"""

import asyncio
import json

import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.resilience import faults
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartmodule.types import SmartModuleInput
from fluvio_tpu.telemetry import TELEMETRY, SloEngine, TimeSeries
from fluvio_tpu.telemetry import memory as memory_mod
from fluvio_tpu.telemetry import slo as slo_mod
from fluvio_tpu.telemetry.memory import MemoryLedger, memory_snapshot
from fluvio_tpu.windows import (
    HostWindowReference,
    MaterializedView,
    WindowJits,
    WindowSpec,
    WindowedRuntime,
)

# the transient fault points the generic chaos smoke can arm (the same
# matrix test_resilience.py pins for bit-equality; here the pin is the
# ledger: transient owners drain to zero through every recovery ladder)
GENERIC_POINTS = ("stage", "h2d", "dispatch", "device", "fetch")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
    faults.FAULTS.clear()
    TELEMETRY.reset()
    memory_mod.reset_engine()
    slo_mod.reset_engine()
    yield
    faults.FAULTS.clear()
    memory_mod.reset_engine()
    slo_mod.reset_engine()
    TELEMETRY.reset()


# -- pipeline harness (test_resilience.py shapes) ---------------------------


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


def _slabs(n=3, rows=96):
    out = []
    names = ("fluvio", "kafka", "fluvio-tpu", "pulsar")
    for k in range(n):
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


def _drained():
    """Quiesce pin: the ledger exists (the seams booked through it)
    and every transient owner is back to zero."""
    eng = memory_mod.peek()
    assert eng is not None, "no ledger was ever minted — seams inactive?"
    eng.assert_drained()
    by = eng.owner_bytes()
    for owner in memory_mod.TRANSIENT_OWNERS:
        assert by[owner] == 0, (owner, by)
    return eng


# -- windowed harness (test_windows.py shapes) ------------------------------

_JITS = {}


def _wspec(**kw):
    kw.setdefault("window_ms", 100)
    kw.setdefault("slide_ms", 0)
    kw.setdefault("op", "add")
    kw.setdefault("keyed", True)
    kw.setdefault("lateness_ms", 0)
    kw.setdefault("capacity", 512)
    kw.setdefault("emit_capacity", 256)
    kw.setdefault("delta_only", True)
    return WindowSpec(**kw)


def _wruntime(spec):
    jits = _JITS.get(spec)
    if jits is None:
        jits = _JITS[spec] = WindowJits(spec)
    return WindowedRuntime(spec, jits=jits)


def _cols(batch):
    keys = np.array([k for k, _, _ in batch], dtype=np.int64)
    contribs = np.array([c for _, c, _ in batch], dtype=np.int64)
    ts = np.array([t for _, _, t in batch], dtype=np.int64)
    return contribs, keys, ts


def _ingest(rt, view, ref, batch):
    delta = rt.ingest_arrays(*_cols(batch))
    view.apply_delta(delta)
    ref.process_batch(batch)
    assert rt.bank.snapshot() == ref.bank_entries()
    return delta


def _pack(values, ts):
    """Raw records -> RecordBuffer (the process_buffer seam — the one
    with the transient-retry ladder)."""
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer, bucket_width

    n = len(values)
    width = bucket_width(max(len(v) for v in values))
    rows = 8
    while rows < n:
        rows *= 2
    arr = np.zeros((rows, width), dtype=np.uint8)
    lengths = np.zeros(rows, dtype=np.int32)
    for i, v in enumerate(values):
        arr[i, : len(v)] = np.frombuffer(v, dtype=np.uint8)
        lengths[i] = len(v)
    tcol = np.zeros(rows, dtype=np.int64)
    tcol[:n] = np.asarray(ts, dtype=np.int64)
    return RecordBuffer.from_arrays(
        arr, lengths, count=n, timestamp_deltas=tcol
    )


def _ingest_buf(rt, view, ref, batch):
    vals = [str(c).encode() for _, c, _ in batch]
    ts = [s for _, _, s in batch]
    delta = rt.process_buffer(_pack(vals, ts))
    view.apply_delta(delta)
    ref.process_batch(batch)
    assert rt.bank.snapshot() == ref.bank_entries()
    return delta


# ---------------------------------------------------------------------------
# Ledger arithmetic
# ---------------------------------------------------------------------------


class TestLedger:
    def test_acquire_release_balance(self):
        clk = {"t": 100.0}
        led = MemoryLedger(clock=lambda: clk["t"])
        led.acquire("staged_batch", ("b", 1), 1000)
        led.acquire("emit_buffer", ("g", 1), 200)
        assert led.total_bytes() == 1200
        by = led.owner_bytes()
        assert by["staged_batch"] == 1000 and by["emit_buffer"] == 200
        led.release(("b", 1))
        led.release(("g", 1))
        assert led.total_bytes() == 0
        # the high watermark survives the drain
        assert led.peak_bytes() == 1200

    def test_reacquire_is_a_resize(self):
        led = MemoryLedger(clock=lambda: 0.0)
        led.acquire("window_bank", ("w", 1), 1000)
        led.acquire("window_bank", ("w", 1), 400)
        assert led.owner_bytes()["window_bank"] == 400
        assert led.owner_entries()["window_bank"] == 1
        # a resize can even move the booking between owners atomically
        led.acquire("carry_bank", ("w", 1), 64)
        by = led.owner_bytes()
        assert by["window_bank"] == 0 and by["carry_bank"] == 64

    def test_unknown_owner_fails_loud(self):
        with pytest.raises(ValueError, match="unknown memory owner"):
            MemoryLedger(clock=lambda: 0.0).acquire("typo", "k", 1)

    def test_release_is_idempotent(self):
        led = MemoryLedger(clock=lambda: 0.0)
        led.acquire("staged_batch", "k", 10)
        led.release("k")
        led.release("k")  # finish + discard on the recovery ladder
        assert led.total_bytes() == 0

    def test_config_peak_resets_to_current(self):
        led = MemoryLedger(clock=lambda: 0.0)
        led.acquire("window_bank", "w", 500)
        led.acquire("staged_batch", "b", 300)
        led.release("b")
        assert led.config_peak_bytes() == 800
        led.reset_peak()
        # the new config inherits the still-resident bank, not the
        # retired staging spike
        assert led.config_peak_bytes() == 500
        assert led.peak_bytes() == 800

    def test_gauge_aliases_republish_from_the_ledger(self):
        led = MemoryLedger(clock=lambda: 0.0)
        led.acquire("staged_batch", "b", 1000)
        led.acquire("shard_staging", "s", 500)
        led.acquire("window_bank", "w", 480)
        gauges = TELEMETRY.snapshot()["gauges"]
        assert gauges["device_memory_bytes"] == 1980
        assert gauges["device_memory_peak_bytes"] == 1980
        # pre-ledger scrape names stay live as ledger aliases
        assert gauges["hbm_staged_bytes"] == 1500
        assert gauges["window_state_bytes"] == 480


# ---------------------------------------------------------------------------
# Leak detection
# ---------------------------------------------------------------------------


class TestLeakDetection:
    def test_ttl_flags_a_transient_entry_once(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_MEM_LEAK_TTL_S", "5")
        clk = {"t": 100.0}
        led = MemoryLedger(clock=lambda: clk["t"])
        led.acquire("staged_batch", ("b", 7), 4096)
        assert led.scan() == []  # fresh: nothing to flag
        clk["t"] += 10.0
        flagged = led.scan()
        assert [(f[0], f[2]) for f in flagged] == [("staged_batch", 4096)]
        assert TELEMETRY.memory_leak_counts() == {"staged_batch": 1}
        assert any(
            e.get("kind") == "mem-leak" for e in TELEMETRY.events_json()
        ), TELEMETRY.events_json()
        # flagged ONCE: a second scan is silent, the entry stays listed
        clk["t"] += 10.0
        assert led.scan() == []
        assert TELEMETRY.memory_leak_counts() == {"staged_batch": 1}
        (leaked,) = led.leaked_entries()
        assert leaked["owner"] == "staged_batch"
        assert leaked["bytes"] == 4096
        led.release(("b", 7))
        assert led.leaked_entries() == []

    def test_persistent_owners_exempt_from_ttl(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_MEM_LEAK_TTL_S", "5")
        clk = {"t": 0.0}
        led = MemoryLedger(clock=lambda: clk["t"])
        led.acquire("window_bank", "w", 100)
        led.acquire("carry_bank", "c", 100)
        led.acquire("compile_cache", "x", 100)
        clk["t"] += 1e6  # an idle engine, far past any TTL
        assert led.scan() == []
        assert TELEMETRY.memory_leak_counts() == {}

    def test_assert_drained_contract(self):
        led = MemoryLedger(clock=lambda: 0.0)
        led.assert_drained()
        led.acquire("window_bank", "w", 100)  # persistent: still clean
        led.assert_drained()
        led.acquire("staged_batch", ("b", 1), 64)
        with pytest.raises(AssertionError, match="staged_batch"):
            led.assert_drained()
        led.release(("b", 1))
        led.assert_drained()

    def test_stranded_release_on_the_real_seam_is_detected(
        self, monkeypatch
    ):
        """The deliberately-injected missing release: break the
        executor's release seam, run a real batch, and the TTL scan
        must convict the stranded staged booking."""
        monkeypatch.setenv("FLUVIO_MEM_LEAK_TTL_S", "0")
        chain = _build()
        ex = chain.tpu_chain
        monkeypatch.setattr(
            type(ex), "_gauge_release", lambda self, handle: None
        )
        _run(chain, _slabs(n=1))
        eng = memory_mod.peek()
        assert eng is not None
        flagged = eng.scan()
        assert flagged and all(
            f[0] in memory_mod.TRANSIENT_OWNERS for f in flagged
        ), flagged
        assert sum(TELEMETRY.memory_leak_counts().values()) >= 1
        with pytest.raises(AssertionError):
            eng.assert_drained()


# ---------------------------------------------------------------------------
# Chaos matrix: ledger balance through every recovery ladder
# ---------------------------------------------------------------------------


class TestChaosLedgerBalance:
    @pytest.mark.parametrize("point", GENERIC_POINTS)
    def test_fused_transient_fault_drains(self, point):
        expected = _run(_build(), _slabs())
        chain = _build()
        faults.FAULTS.inject(point, first=1)
        got = _run(chain, _slabs())
        faults.FAULTS.clear()
        assert got == expected
        _drained()

    def test_fused_deterministic_fault_drains(self):
        # no blind retry: the batch quarantines/errors, and the
        # recovery ladder still retires every staged booking
        chain = _build()
        faults.FAULTS.inject(
            "device", first=1,
            exc=faults.InjectedFault("device", transient=False),
        )
        for s in _slabs():
            chain.process(s)  # outcome (error/quarantine) is ISSUE-3's pin
        faults.FAULTS.clear()
        _drained()

    @pytest.mark.parametrize("point", GENERIC_POINTS)
    def test_sharded_transient_fault_drains(self, point):
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs the virtual multi-device mesh")
        b = SmartEngine(backend="tpu", mesh_devices=4).builder()
        cfg = SmartModuleConfig(params={})
        cfg.initial_data = b"0"
        b.add_smart_module(cfg, lookup("aggregate-sum"))
        chain = b.initialize()
        assert chain.tpu_chain._sharded is not None
        slabs = [
            SmartModuleInput.from_records(
                [
                    Record(value=b"%d" % (k * 100 + i), offset_delta=i)
                    for i in range(64)
                ]
            )
            for k in range(2)
        ]
        faults.FAULTS.inject(point, first=1)
        for s in slabs:
            out = chain.process(s)
            assert out.error is None
        faults.FAULTS.clear()
        eng = _drained()
        # the sharded path books under its own owner class
        assert eng.owner_bytes()["staged_batch"] == 0

    def test_partitioned_carry_bank_books_and_retires(self):
        from fluvio_tpu.partition.placement import (
            parse_placement_rules,
            plan_placement,
        )
        from fluvio_tpu.partition.runtime import PartitionRuntime
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

        chain = _build(
            modules=(
                ("regex-filter", {"regex": "fluvio"}),
                ("aggregate-field", {"field": "n", "combine": "add"}),
            )
        )
        plan = plan_placement(parse_placement_rules(".*=spread"), [], 2)
        rt = PartitionRuntime(chain.tpu_chain, plan, chain=chain)

        def _buf(vals):
            return RecordBuffer.from_smartmodule_input(
                SmartModuleInput.from_records(
                    [
                        Record(
                            value=json.dumps(
                                {"n": v, "name": f"fluvio-{v}"}
                            ).encode()
                        )
                        for v in vals
                    ]
                )
            )

        rt.process("t", 0, _buf([1, 2]))
        rt.process("t", 1, _buf([10]))
        eng = _drained()
        assert eng.owner_bytes()["carry_bank"] > 0
        assert eng.owner_entries()["carry_bank"] == 2
        # promotion installs a host snapshot: the old device-resident
        # bank is garbage, and its booking retires with it
        rt.seed_partition("t", 0, rt.carry_snapshot("t", 0))
        assert eng.owner_entries()["carry_bank"] == 1

    @pytest.mark.parametrize("point", ("stage", "dispatch", "device",
                                       "fetch"))
    def test_windowed_transient_fault_drains(self, point):
        spec = _wspec(keyed=False)
        rt, view, ref = (
            _wruntime(spec), MaterializedView(spec),
            HostWindowReference(spec),
        )
        batches = (
            [(0, 5, 10), (0, 7, 40)],
            [(0, 3, 120), (0, 9, 150)],
            [(0, 1, 260)],
        )
        for i, batch in enumerate(batches):
            if i == 1:
                faults.FAULTS.inject(point, first=1)
            _ingest_buf(rt, view, ref, batch)
        faults.FAULTS.clear()
        assert view.table() == ref.table()
        eng = _drained()
        # the bank booking tracks the live state size exactly, and the
        # emit-buffer fetch windows all retired
        assert eng.owner_bytes()["window_bank"] == rt.bank.state_bytes()
        assert eng.owner_bytes()["emit_buffer"] == 0


# ---------------------------------------------------------------------------
# The budget chaos pin: growth -> breach -> typed shed -> drain ->
# recovery, exactly-once
# ---------------------------------------------------------------------------


class TestHeadroomShedding:
    BUDGET = 2_000  # bytes — 83 bank entries

    def _controller(self, clk):
        from dataclasses import replace

        from fluvio_tpu.admission import AdmissionController

        rules = tuple(
            replace(r, target=float(self.BUDGET), enabled=True)
            if r.name == "hbm_headroom"
            else replace(r, enabled=False)
            for r in slo_mod.DEFAULT_RULES
        )
        ts = TimeSeries(window_s=1.0, capacity=4, clock=lambda: clk["t"])
        eng = SloEngine(
            timeseries=ts, rules=rules, clock=lambda: clk["t"]
        )
        ctl = AdmissionController(
            slo_engine=eng, clock=lambda: clk["t"], refresh_s=0.0,
            tokens=1e9, refill=1e9,
        )
        return ctl, eng

    def test_budget_breach_sheds_then_recovers_exactly_once(
        self, monkeypatch
    ):
        from fluvio_tpu.admission import Rejected

        monkeypatch.setenv("FLUVIO_MEM_BUDGET", str(self.BUDGET))
        clk = {"t": 1000.0}
        ctl, eng = self._controller(clk)
        spec = _wspec(keyed=True)
        rt, view, ref = (
            _wruntime(spec), MaterializedView(spec),
            HostWindowReference(spec),
        )
        key = "winchain@t/0"

        # the unbounded keyed workload: 120 distinct keys land in one
        # window -> 120 live bank entries -> 2888 bytes > the budget
        growth = [(k, k + 1, 10 + (k % 7)) for k in range(120)]
        _ingest(rt, view, ref, growth)
        ledger = memory_mod.peek()
        assert ledger is not None
        assert ledger.total_bytes() > self.BUDGET
        # the instantaneous floor already reads breach on the document
        assert memory_snapshot()["verdict"] == "breach"

        eng.timeseries.force_tick()
        clk["t"] += 1.0
        d = ctl.admit(key)
        assert isinstance(d, Rejected) and not d
        assert d.reason == "breach-shed"
        assert d.retry_after_s is not None
        assert TELEMETRY.admission.get("breach-shed", 0) >= 1
        # the breach landed on the engine-wide headroom rule
        assert any(
            k.startswith("_engine/hbm_headroom")
            for k in TELEMETRY.slo_breaches
        ), TELEMETRY.slo_breaches

        # the held slice: NOT ingested while shed (the broker holds it;
        # offsets do not advance, so nothing is lost or duplicated)
        held = [(k, 1000 + k, 5010 + k) for k in range(8)]

        # drain: event time advances on the admitted stream, the 120
        # windows close and emit, the bank shrinks under the budget
        _ingest(rt, view, ref, [(0, 1, 5000)])
        assert ledger.total_bytes() < self.BUDGET

        clk["t"] += 1.0
        d2 = ctl.admit(key)
        assert d2.admitted, d2
        _ingest(rt, view, ref, held)  # served exactly once, post-shed
        assert memory_snapshot()["verdict"] == "ok"

        # close everything out: the materialized view and the host
        # oracle agree bit-for-bit — exactly-once across the shed
        _ingest(rt, view, ref, [(0, 0, 9000)])
        assert view.table() == ref.table()
        _drained()


# ---------------------------------------------------------------------------
# Surfaces: snapshot section, memory document, prom, socket, CLI, locks
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_registry_snapshot_memory_section(self):
        memory_mod.engine().acquire("staged_batch", "b", 700)
        memory_mod.engine().acquire("window_bank", "w", 300)
        snap = TELEMETRY.snapshot()
        mem = snap["memory"]
        assert mem["owners"] == {"staged_batch": 700, "window_bank": 300}
        assert mem["total_bytes"] == 1000
        assert mem["peak_bytes"] == 1000
        assert mem["leaks"] == {}

    def test_memory_snapshot_document_shape(self):
        memory_mod.engine().acquire("staged_batch", "b", 512)
        doc = memory_snapshot()
        assert doc["enabled"] is True
        assert doc["verdict"] == "ok"
        assert set(doc["owners"]) == set(memory_mod.OWNERS)
        assert doc["owners"]["staged_batch"] == {"bytes": 512, "entries": 1}
        assert doc["total_bytes"] == 512
        assert doc["budget_bytes"] == 0
        assert doc["leaks_total"] == 0
        recon = doc["reconcile"]
        assert recon["ledger_bytes"] == 512
        # CPU backend: either no allocator stats (honest "unavailable")
        # or real ones with the delta attributed
        assert "backend" in recon or "backend_bytes" in recon

    def test_memory_snapshot_disabled_short_circuit(self):
        TELEMETRY.enabled = False
        try:
            doc = memory_snapshot()
        finally:
            TELEMETRY.enabled = True
        assert doc == {
            "enabled": False, "verdict": "disabled", "owners": {},
        }

    def test_budget_floor_flips_the_verdict(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_MEM_BUDGET", "1000")
        slo_mod.reset_engine()
        memory_mod.engine().acquire("window_bank", "w", 4096)
        doc = memory_snapshot()
        assert doc["verdict"] == "breach"
        assert doc["budget_bytes"] == 1000

    def test_prometheus_families_render(self):
        from fluvio_tpu.telemetry import render_prometheus

        memory_mod.engine().acquire("staged_batch", "b", 1000)
        memory_mod.engine().acquire("window_bank", "w", 480)
        TELEMETRY.add_memory_leak("emit_buffer", "stranded")
        text = render_prometheus()
        assert (
            'fluvio_tpu_device_memory_bytes{owner="staged_batch"} 1000'
            in text
        )
        assert (
            'fluvio_tpu_device_memory_bytes{owner="window_bank"} 480'
            in text
        )
        assert "fluvio_tpu_device_memory_peak_bytes 1480" in text
        assert (
            'fluvio_tpu_memory_leaks_total{owner="emit_buffer"} 1' in text
        )
        # the aliases keep their scrape names
        assert "fluvio_tpu_hbm_staged_bytes 1000" in text
        assert "fluvio_tpu_window_state_bytes 480" in text

    def test_socket_memory_mode_roundtrip(self, tmp_path):
        from fluvio_tpu.spu.monitoring import MonitoringServer, read_memory

        memory_mod.engine().acquire("carry_bank", "c", 2048)

        class _Ctx:
            class metrics:
                @staticmethod
                def to_dict(include_telemetry=True):
                    return {}

        loop = asyncio.new_event_loop()
        server = MonitoringServer(_Ctx(), path=str(tmp_path / "m.sock"))

        async def run():
            await server.start()
            try:
                return await read_memory(server.path)
            finally:
                await server.stop()

        try:
            doc = loop.run_until_complete(run())
        finally:
            loop.close()
        assert doc["enabled"] is True
        assert doc["owners"]["carry_bank"]["bytes"] == 2048
        assert doc["verdict"] == "ok"

    def test_cli_table_and_rc(self):
        from fluvio_tpu.cli.memory import memory_rc, render_memory_table

        memory_mod.engine().acquire("staged_batch", "b", 1500)
        doc = memory_snapshot()
        table = render_memory_table(doc)
        assert "memory verdict: ok" in table
        assert "staged_batch" in table and "1.5kB" in table
        assert memory_rc(doc) == 0
        assert memory_rc({**doc, "verdict": "breach"}) == 1
        assert memory_rc({**doc, "leaks_total": 2}) == 1
        disabled = render_memory_table({"enabled": False})
        assert "FLUVIO_TELEMETRY=0" in disabled

    def test_cli_exit_codes_local(self, capsys, monkeypatch):
        from fluvio_tpu.cli import main

        # clean ledger: rc 0, table names the owner
        memory_mod.engine().acquire("window_bank", "w", 4096)
        rc = main(["memory", "--local"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "window_bank" in out and "memory verdict: ok" in out

        # over budget: the floor flips the verdict -> rc 1
        monkeypatch.setenv("FLUVIO_MEM_BUDGET", "1000")
        slo_mod.reset_engine()
        rc = main(["memory", "--local", "--format", "json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert doc["verdict"] == "breach"

        # a flagged leak alone also gates the rollout
        monkeypatch.delenv("FLUVIO_MEM_BUDGET")
        slo_mod.reset_engine()
        TELEMETRY.add_memory_leak("staged_batch", "stranded")
        rc = main(["memory", "--local"])
        capsys.readouterr()
        assert rc == 1

    def test_memory_lock_in_static_vocabulary(self):
        from fluvio_tpu.analysis.concurrency import analyze_package

        names = set(analyze_package().locks)
        assert "telemetry.memory" in names, sorted(
            n for n in names if "telemetry" in n
        )
        assert "telemetry.memory_singleton" in names
