"""ISSUE-15: slice flow tracing + streaming lag/record-age engine.

Covers the new observability layer end to end:

- flow-event round-trip parity: every served slice's flow chain is
  connected arrival -> serve in the rendered Perfetto doc (``ph:
  s/t/f`` with one id per slice), including a coalesced multi-tenant
  batch and a shed-then-retry slice;
- lag/record-age differentials against hand-computed offsets with a
  fake clock;
- the chaos pin: backlog on one partition -> ``consumer_lag`` SLO
  breach -> admission sheds only that ``chain@topic/partition``
  (siblings unaffected) -> drain -> verdict ages out and serving
  resumes — both in-process against the real executor and through the
  real broker (SPU server over TCP);
- the monitoring socket ``lag`` mode + `read_lag`, and the
  ``fluvio-tpu lag`` CLI exit-code contract;
- lock-vocabulary pinning for the new ``telemetry.lag`` lock.
"""

import asyncio
import json

import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.telemetry import TELEMETRY, SloEngine, TimeSeries
from fluvio_tpu.telemetry import lag as lag_mod
from fluvio_tpu.telemetry.slo import parse_slo_spec
from fluvio_tpu.telemetry.trace import render_trace


@pytest.fixture(autouse=True)
def _clean_telemetry():
    TELEMETRY.reset()
    lag_mod.reset_engine()
    yield
    TELEMETRY.reset()
    lag_mod.reset_engine()


class FakeLeader:
    """hw()/leo() stand-in for a replica (the lag join's only surface)."""

    def __init__(self, leo: int = 0):
        self._leo = leo

    def leo(self) -> int:
        return self._leo

    def hw(self) -> int:
        return self._leo


def _filter_chain(regex: str = "keep"):
    b = SmartEngine(backend="tpu").builder()
    b.add_smart_module(
        SmartModuleConfig(params={"regex": regex}), lookup("regex-filter")
    )
    chain = b.initialize()
    assert chain.backend_in_use == "tpu"
    return chain


def _buf(n: int, tag: str = "keep") -> RecordBuffer:
    records = [Record(value=f"{tag}-{i}".encode()) for i in range(n)]
    for i, r in enumerate(records):
        r.offset_delta = i
    return RecordBuffer.from_records(records)


def _flow_chains(doc: dict) -> dict:
    """{flow id: set of ph values} for every flow event in a trace doc."""
    out: dict = {}
    for ev in doc["traceEvents"]:
        if ev.get("cat") == "flow":
            out.setdefault(ev["id"], []).append(ev)
    return out


def _assert_connected(doc: dict, flow_id: int, want_batch_step: bool = True):
    """A flow chain is CONNECTED when its id carries an ``s`` (arrival)
    and an ``f`` (serve), and — when it rode a dispatch — at least one
    ``t`` step bound to a batch track (tid outside the slice family)."""
    chains = _flow_chains(doc)
    assert flow_id in chains, f"flow {flow_id} missing from the doc"
    evs = chains[flow_id]
    phs = {e["ph"] for e in evs}
    assert {"s", "f"} <= phs, (flow_id, phs)
    if want_batch_step:
        steps = [e for e in evs if e["ph"] == "t"]
        assert steps, f"flow {flow_id} has no batch-track step"
        # slice lanes live at rank 3 (tid 301..399); batch tracks below
        assert any(e["tid"] < 301 for e in steps), steps
    # the arrival precedes the serve on the timeline
    s = next(e for e in evs if e["ph"] == "s")
    f = next(e for e in evs if e["ph"] == "f")
    assert s["ts"] <= f["ts"]


# ---------------------------------------------------------------------------
# Flow-event round-trip parity
# ---------------------------------------------------------------------------


class TestFlowTraceParity:
    def test_coalesced_multi_tenant_batch_flows_connected(self):
        """Two tenant slices of one chain coalesce into ONE dispatched
        batch; BOTH flow chains must stay connected arrival -> the
        shared batch -> serve in the rendered doc, and both records
        must name the coalesce (cause + sources=2)."""
        from fluvio_tpu.admission import AdmissionPipeline

        chain = _filter_chain()
        ex = chain.tpu_chain
        pipe = AdmissionPipeline(
            dispatch=lambda flush: ex.process_buffer(flush.buffer)
        )
        sig = ex._chain_sig
        pipe.register_chain(sig)
        for tag in ("tenant-a", "tenant-b"):
            d = pipe.submit(sig, _buf(4, f"keep-{tag}"))
            assert d.admitted
        pipe.pump()
        flushes = pipe.batcher.flush_all()
        assert len(flushes) == 1 and len(flushes[0].items) == 2

        flows = TELEMETRY.flows.recent()
        assert len(flows) == 2
        doc = render_trace()
        for fl in flows:
            assert fl.sources == 2
            assert fl.cause == "shutdown"
            _assert_connected(doc, fl.flow_id)
            totals = fl.phase_totals()
            assert "queue_wait" in totals and "batcher" in totals

    def test_shed_then_retry_flow_records_hold_and_connects(self):
        """A flow that survives shed-hold cycles keeps ONE id across
        the retries, counts its holds, and still renders a connected
        chain once it serves."""
        flow = TELEMETRY.begin_flow("filter@t/0")
        assert flow is not None
        flow.decision = "breach-shed"
        flow.hold(0.004)
        flow.hold(0.003)
        flow.decision = "admit"
        chain = _filter_chain()
        # the span names its slice: the renderer joins on the id
        span = TELEMETRY.begin_batch(
            chain=chain.tpu_chain._chain_sig, flow_id=flow.flow_id
        )
        chain.tpu_chain.process_buffer(_buf(4))
        TELEMETRY.end_batch(span, records=4)
        TELEMETRY.end_flow(flow, records=4)

        assert flow.holds == 2
        doc = render_trace()
        _assert_connected(doc, flow.flow_id)
        # the hold phases render at wall positions on the slice lane
        holds = [
            e
            for e in doc["traceEvents"]
            if e.get("cat") == "slice-phase" and e.get("name") == "hold"
        ]
        assert len(holds) == 2
        # holds are booked by the handler's release path, not end_flow
        # (no double-count): the slice histogram must NOT have them
        assert TELEMETRY.snapshot()["slices"].get("hold") is None

    def test_flow_ring_bounded_and_counted(self):
        for i in range(8):
            TELEMETRY.end_flow(TELEMETRY.begin_flow(f"c{i}"), records=1)
        snap = TELEMETRY.snapshot()
        assert snap["flows_total"] == 8
        assert snap["flows_dropped"] == 0
        assert snap["slices"]["serve"]["count"] == 8

    def test_continuous_sink_streams_flows(self, tmp_path):
        from fluvio_tpu.telemetry import TraceFileSink

        sink = TraceFileSink(str(tmp_path / "t.json"), 1 << 20)
        TELEMETRY.trace_sink = sink
        try:
            flow = TELEMETRY.begin_flow("c@t/0")
            flow.hold(0.001)
            TELEMETRY.end_flow(flow, records=3)
            sink.flush()
        finally:
            TELEMETRY.trace_sink = None
            sink.close()
        doc = json.loads((tmp_path / "t.json").read_text())
        cats = {e.get("cat") for e in doc}
        assert "slice" in cats and "flow" in cats
        phs = {e["ph"] for e in doc if e.get("cat") == "flow"}
        assert {"s", "f"} <= phs

    def test_flow_disarmed_by_env_flag(self, monkeypatch):
        monkeypatch.setattr(TELEMETRY, "flow_trace", False)
        assert TELEMETRY.begin_flow("c") is None
        # end_flow(None) is the documented no-op seam
        TELEMETRY.end_flow(None, records=5)
        assert TELEMETRY.snapshot()["flows_total"] == 0


# ---------------------------------------------------------------------------
# Lag / record-age differentials
# ---------------------------------------------------------------------------


class TestLagEngine:
    def test_lag_join_vs_hand_computed_offsets(self):
        eng = lag_mod.engine()
        leader = FakeLeader(1000)
        eng.track("c@t/0", leader)
        # nothing committed yet: lag == the whole log
        eng.sample()
        assert TELEMETRY.lag_families()[0]["c@t/0"] == 1000.0
        eng.note_commit("c@t/0", 400)
        eng.sample()
        assert TELEMETRY.lag_families()[0]["c@t/0"] == 600.0
        # commits are monotone: a stale ack cannot move lag backwards
        eng.note_commit("c@t/0", 150)
        eng.sample()
        assert TELEMETRY.lag_families()[0]["c@t/0"] == 600.0
        # the log grows while the consumer stalls: lag grows
        leader._leo = 1600
        eng.sample()
        assert TELEMETRY.lag_families()[0]["c@t/0"] == 1200.0
        # fully drained
        eng.note_commit("c@t/0", 1600)
        eng.sample()
        assert TELEMETRY.lag_families()[0]["c@t/0"] == 0.0

    def test_record_age_histogram_vs_fake_clock(self, monkeypatch):
        import time as time_mod

        now = {"t": 10_000.0}
        monkeypatch.setattr(time_mod, "time", lambda: now["t"])
        # a batch appended at t=9_990s served at t=10_000s is 10s old
        age = lag_mod.serve_age_s(int(9_990.0 * 1000))
        assert age == pytest.approx(10.0)
        lag_mod.note_serve("c@t/0", 32, age)
        _, served, ages = TELEMETRY.lag_families()
        assert served["c@t/0"] == 32
        h = ages["c@t/0"]
        assert h.count == 1
        # the log-bucketed histogram brackets the true value
        assert 8.0 <= h.percentile(99) <= 12.5
        # unstamped batches (NO_TIMESTAMP) produce no observation
        assert lag_mod.serve_age_s(-1) is None
        assert lag_mod.serve_age_s(None) is None

    def test_dead_leader_unregisters(self):
        eng = lag_mod.engine()
        eng.track("gone@t/0", FakeLeader(10))  # only ref: collectable
        import gc

        gc.collect()
        eng.sample()
        assert "gone@t/0" not in eng.snapshot()

    def test_windowed_slo_observation_per_partition(self):
        """The consumer_lag / record_age_p99 rules observe per
        chain@topic/partition from the time-series window."""
        clk = {"t": 100.0}
        ts = TimeSeries(window_s=1.0, capacity=8, clock=lambda: clk["t"])
        eng = SloEngine(
            timeseries=ts,
            rules=parse_slo_spec("consumer_lag:target=50"),
            clock=lambda: clk["t"],
        )
        leader = FakeLeader(500)
        lag_mod.engine().track("c@t/0", leader)
        lag_mod.engine().note_commit("c@t/0", 490)  # lag 10: ok
        ts.force_tick()
        clk["t"] += 1.0
        doc = eng.evaluate()
        ev = doc["chains"]["c@t/0"]["rules"]["consumer_lag"]
        assert ev["verdict"] == "ok" and ev["observed"] == 10.0
        leader._leo = 800  # backlog injected: lag 310 > 50
        clk["t"] += 1.0
        doc = eng.evaluate()
        ev = doc["chains"]["c@t/0"]["rules"]["consumer_lag"]
        assert ev["verdict"] == "breach" and ev["observed"] == 310.0
        # record-age: a served slice 120s old breaches the 60s default
        lag_mod.note_serve("c@t/0", 4, 120.0)
        clk["t"] += 1.0
        doc = eng.evaluate()
        ev = doc["chains"]["c@t/0"]["rules"]["record_age_p99"]
        assert ev["verdict"] in ("warn", "breach")
        assert ev["observed"] > 60.0

    def test_record_age_target_ms_grammar(self):
        rules = {
            r.name: r
            for r in parse_slo_spec("record_age_p99:target_ms=500")
        }
        assert rules["record_age_p99"].target == pytest.approx(0.5)

    def test_lag_lock_in_static_vocabulary(self):
        """The new lag-engine lock is a canonical make_lock so the
        FLV2xx analyzer and the runtime lockwatch share its name."""
        from fluvio_tpu.analysis.concurrency import analyze_package

        names = set(analyze_package().locks)
        assert "telemetry.lag" in names, sorted(
            n for n in names if "telemetry" in n
        )


# ---------------------------------------------------------------------------
# The chaos pin: backlog -> breach -> shed (that partition only) ->
# drain -> recovery, through the real executor pipeline
# ---------------------------------------------------------------------------


class TestLagKeyedShedding:
    @staticmethod
    def _serve(ex, flow, n: int = 8) -> None:
        """Serve one admitted slice: the dispatch's span carries the
        flow's id (the id join the trace renderer draws arrows on)."""
        buf = _buf(n)
        ex.finish_buffer(buf, ex.dispatch_buffer(buf, flow_id=flow.flow_id))
        TELEMETRY.end_flow(flow, records=n)

    def _controller(self, clk):
        from fluvio_tpu.admission import AdmissionController

        ts = TimeSeries(window_s=1.0, capacity=4, clock=lambda: clk["t"])
        eng = SloEngine(
            timeseries=ts,
            rules=parse_slo_spec(
                "consumer_lag:target=100;e2e_p99:off=1;spill_ratio:off=1;"
                "error_rate:off=1;compile_budget:off=1;recompile_rate:off=1;"
                "queue_depth:off=1;hbm_staged:off=1;record_age_p99:off=1"
            ),
            clock=lambda: clk["t"],
        )
        ctl = AdmissionController(
            slo_engine=eng, clock=lambda: clk["t"], refresh_s=0.0,
            tokens=1e9, refill=1e9,
        )
        return ctl, eng

    def test_breach_sheds_only_the_hot_partition_then_recovers(self):
        clk = {"t": 1000.0}
        ctl, eng = self._controller(clk)
        chain = _filter_chain()
        ex = chain.tpu_chain
        sig = ex._chain_sig
        hot, cold = f"{sig}@t/0", f"{sig}@t/1"
        hot_leader, cold_leader = FakeLeader(10_000), FakeLeader(64)
        leng = lag_mod.engine()
        leng.track(hot, hot_leader)
        leng.track(cold, cold_leader)
        leng.note_commit(hot, 10)    # backlog: lag 9_990 >> 100
        leng.note_commit(cold, 60)   # healthy sibling: lag 4
        eng.timeseries.force_tick()
        clk["t"] += 1.0

        # the hot partition sheds; its sibling serves untouched
        d_hot = ctl.admit(hot)
        d_cold = ctl.admit(cold)
        assert not d_hot and d_hot.reason == "breach-shed"
        assert d_cold.admitted
        # serve the admitted sibling through the REAL pipeline with a
        # connected flow record
        flow = TELEMETRY.begin_flow(cold)
        flow.decision = "admit"
        self._serve(ex, flow)

        # the held hot slice keeps retrying and keeps shedding
        clk["t"] += 1.0
        d_hot = ctl.admit(hot)
        assert not d_hot and d_hot.reason == "breach-shed"
        assert TELEMETRY.admission.get("breach-shed", 0) >= 2

        # drain the backlog (the consumer group catches up): the join
        # reads lag 0 on the next tick and the verdict ages out
        leng.note_commit(hot, 10_000)
        clk["t"] += 1.0
        d_hot = ctl.admit(hot)
        assert d_hot.admitted, d_hot
        flow = TELEMETRY.begin_flow(hot)
        flow.decision = "admit"
        flow.hold(0.002)  # the hold it survived
        self._serve(ex, flow)

        # every SERVED slice's flow chain is connected in the doc
        doc = render_trace()
        for fl in TELEMETRY.flows.recent():
            _assert_connected(doc, fl.flow_id)
        # and the breach landed on the slo-breach counter under its key
        assert any(
            k.startswith(f"{hot}/consumer_lag")
            for k in TELEMETRY.slo_breaches
        ), TELEMETRY.slo_breaches

    def test_two_tenants_coalesce_while_third_sheds_on_breach(self):
        """ISSUE-17 chaos pin: tenants A and B ride ONE cached chain
        and their slices COALESCE into a single batcher flush, while
        tenant C — same chain, hot partition in consumer_lag breach —
        is shed with tenant attribution. Once the hot backlog drains C
        serves too; the commit ledger closes per key (exactly-once)
        and every served slice's flow chain renders connected."""
        from fluvio_tpu.admission import AdmissionPipeline

        clk = {"t": 1000.0}
        ctl, eng = self._controller(clk)
        chain = _filter_chain()
        ex = chain.tpu_chain
        sig = ex._chain_sig
        shared, hot = f"{sig}@shared/0", f"{sig}@hot/0"

        # keep strong refs: the engine tracks leaders by weakref
        shared_leader, hot_leader = FakeLeader(8), FakeLeader(10_000)
        leng = lag_mod.engine()
        leng.track(shared, shared_leader)
        leng.track(hot, hot_leader)
        leng.note_commit(shared, 0)
        leng.note_commit(hot, 10)  # residual backlog: lag 9_990 >> 100
        eng.timeseries.force_tick()
        clk["t"] += 1.0

        committed = {shared: 0, hot: 10}

        def dispatch(flush):
            # the serving side of the ledger: process the coalesced
            # buffer, ack its positions, attribute per-tenant goodput
            # through the flow records the slices rode in on
            out = ex.process_buffer(flush.buffer)
            n = int(flush.buffer.count)
            committed[flush.chain] += n
            lag_mod.note_commit(flush.chain, committed[flush.chain])
            lag_mod.note_serve(flush.chain, n, 0.001)
            for buf in flush.items:
                fl = getattr(buf, "_flow", None)
                if fl is not None and fl.tenant:
                    TELEMETRY.add_tenant_served(fl.tenant, int(buf.count))
            return out

        pipe = AdmissionPipeline(dispatch=dispatch, controller=ctl)
        pipe.register_chain(shared)
        pipe.register_chain(hot)

        # tenants A and B: admitted onto the same chain key
        da = pipe.submit(shared, _buf(4, "keep-a"), tenant="ta")
        db = pipe.submit(shared, _buf(4, "keep-b"), tenant="tb")
        assert da.admitted and db.admitted
        # tenant C: same cached chain, hot partition — breach-shed,
        # and the shed lands on C's tenant counter
        dc = pipe.submit(hot, _buf(4, "keep-c"), tenant="tc")
        assert not dc and dc.reason == "breach-shed"
        _, shed_t, _, _ = TELEMETRY.tenant_families()
        assert shed_t.get("tc") == 1, shed_t

        pipe.pump()
        flushes = pipe.batcher.flush_all()
        assert len(flushes) == 1 and len(flushes[0].items) == 2, (
            "tenant A and B slices must coalesce into ONE flush"
        )
        snap1 = lag_mod.lag_snapshot()["partitions"]
        assert snap1[shared]["lag"] == 0
        assert snap1[shared]["served_records"] == 8  # == offered (leo)

        # the backlog drains out-of-band down to a 4-record tail; the
        # next verdict join reads lag 4 (under target) and C re-admits
        leng.note_commit(hot, 9_996)
        committed[hot] = 9_996
        clk["t"] += 1.0
        dc = pipe.submit(hot, _buf(4, "keep-c"), tenant="tc")
        assert dc.admitted, dc
        pipe.pump()
        flushes = pipe.batcher.flush_all()
        assert len(flushes) == 1

        # exactly-once on the commit ledger: both keys fully acked by
        # position, and C's served tail closes the hot backlog
        parts = lag_mod.lag_snapshot()["partitions"]
        assert parts[shared]["lag"] == 0
        assert parts[hot]["lag"] == 0
        assert parts[hot]["served_records"] == 4
        served_t, shed_t, _, _ = TELEMETRY.tenant_families()
        assert served_t == {"ta": 4, "tb": 4, "tc": 4}, served_t
        assert shed_t == {"tc": 1}, shed_t
        adm = TELEMETRY.admission
        assert adm.get("admit") == 3 and adm.get("breach-shed") == 1, adm

        # every served slice's flow chain is connected in the doc, the
        # coalesced pair names both sources, and tenants ride the flows
        flows = TELEMETRY.flows.recent()
        assert len(flows) == 3
        doc = render_trace()
        by_tenant = {}
        for fl in flows:
            _assert_connected(doc, fl.flow_id)
            by_tenant[fl.tenant] = fl
        assert set(by_tenant) == {"ta", "tb", "tc"}
        assert by_tenant["ta"].sources == 2
        assert by_tenant["tb"].sources == 2
        assert by_tenant["tc"].sources == 1
        # the breach landed on the slo-breach counter under C's key
        assert any(
            k.startswith(f"{hot}/consumer_lag")
            for k in TELEMETRY.slo_breaches
        ), TELEMETRY.slo_breaches

    def test_zero_cost_when_telemetry_off(self, monkeypatch):
        """The acceptance tripwire: with FLUVIO_TELEMETRY=0 the flow
        and lag seams do NOTHING — no flow objects, no ring pushes, no
        lag-engine registration, no sampler install."""
        from fluvio_tpu.telemetry import flow as flow_module

        prior = TELEMETRY.enabled
        TELEMETRY.enabled = False
        try:
            def tripwire(*a, **k):
                raise AssertionError("flow/lag seam touched while off")

            monkeypatch.setattr(flow_module.SliceFlow, "__init__", tripwire)
            monkeypatch.setattr(TELEMETRY.flows, "push", tripwire)
            monkeypatch.setattr(
                lag_mod.LagEngine, "track", tripwire
            )
            assert TELEMETRY.begin_flow("c") is None
            TELEMETRY.end_flow(None)
            TELEMETRY.add_slice_phase("hold", 1.0)
            TELEMETRY.add_record_age("c", 1.0)
            TELEMETRY.set_consumer_lag("c", 5)
            TELEMETRY.add_served("c", 5)
            lag_mod.track_stream("c", FakeLeader(5))
            lag_mod.note_commit("c", 1)
            lag_mod.note_serve("c", 1, 1.0)
            TELEMETRY.refresh_lag()
            assert TELEMETRY.lag_sampler is None
        finally:
            TELEMETRY.enabled = prior


# ---------------------------------------------------------------------------
# The REAL broker: backlog -> lag breach -> the stream handler HOLDS
# (held_slices visible) -> drain -> recovery, over real TCP
# ---------------------------------------------------------------------------


FILTER_SM = b"""
@smartmodule.filter(dsl=dsl.FilterProgram(
    predicate=dsl.Contains(arg=dsl.Value(), literal=b"keep")))
def fil(record):
    return b"keep" in record.value
"""


class TestBrokerLagLoop:
    def test_lag_breach_holds_stream_then_drain_resumes(self, tmp_path):
        """The acceptance loop through the real pipeline: produce a
        backlog whose consumer_lag breaches the (tight) SLO target ->
        the admission gate sheds and the stream handler HOLDS the slice
        (held_slices gauge up, no error, no loss) -> the backlog drains
        (the consumer group catches up out-of-band) -> the verdict ages
        out on the next join and serving resumes, delivering every
        record exactly once — with the served slices' flow chains
        connected in the exported Perfetto doc and the hold booked on
        admission_hold_seconds."""
        from fluvio_tpu import admission as admission_pkg
        from fluvio_tpu.admission import AdmissionController
        from fluvio_tpu.client import ConsumerConfig, Fluvio, Offset
        from fluvio_tpu.schema.smartmodule import (
            SmartModuleInvocation,
            SmartModuleInvocationKind,
            SmartModuleInvocationWasm,
        )
        from fluvio_tpu.spu import SpuConfig, SpuServer
        from fluvio_tpu.storage.config import ReplicaConfig

        loop = asyncio.new_event_loop()
        config = SpuConfig(
            id=5001,
            public_addr="127.0.0.1:0",
            log_base_dir=str(tmp_path),
            replication=ReplicaConfig(base_dir=str(tmp_path)),
        )
        config.smart_engine.backend = "auto"
        server = SpuServer(config)

        # window small enough that every admission refresh ticks, so
        # the second slice's verdict already sees the joined backlog
        slo_eng = SloEngine(
            timeseries=TimeSeries(window_s=1e-4, capacity=4),
            rules=parse_slo_spec(
                "consumer_lag:target=4;e2e_p99:off=1;spill_ratio:off=1;"
                "error_rate:off=1;compile_budget:off=1;recompile_rate:off=1;"
                "queue_depth:off=1;hbm_staged:off=1;record_age_p99:off=1"
            ),
        )
        ctl = AdmissionController(
            slo_engine=slo_eng, refresh_s=0.0, tokens=1e9, refill=1e9
        )
        admission_pkg.set_gate(ctl)

        values = [
            (b"keep-%d" % i if i % 2 == 0 else b"drop-%d" % i)
            for i in range(20)
        ]

        async def run():
            await server.start()
            server.ctx.create_replica("topic", 0)
            client = await Fluvio.connect(server.public_addr)
            producer = await client.topic_producer("topic")
            # one flushed round per pair -> many stored batches, so the
            # small-max_bytes consume reads the backlog in MANY slices
            # (the hold must strike mid-stream, not after one big read)
            for i in range(0, len(values), 2):
                futs = [
                    await producer.send(None, v) for v in values[i:i + 2]
                ]
                await producer.flush()
                for f in futs:
                    await f.wait()
            await producer.close()

            cfg = ConsumerConfig(
                disable_continuous=True,
                max_bytes=64,  # ~one stored batch per read slice
                smartmodules=[
                    SmartModuleInvocation(
                        wasm=SmartModuleInvocationWasm.adhoc(FILTER_SM),
                        kind=SmartModuleInvocationKind.FILTER,
                    )
                ],
            )
            consumer = await client.partition_consumer("topic", 0)

            got = []

            async def consume():
                async for rec in consumer.stream(Offset.beginning(), cfg):
                    got.append(rec.value)

            task = asyncio.ensure_future(consume())
            # the stream must end up HELD: residual lag > target (4) at
            # a verdict refresh -> breach-shed -> held_slices up
            for _ in range(3000):
                if (
                    TELEMETRY.admission.get("breach-shed", 0) >= 1
                    and TELEMETRY.gauge_value("held_slices") >= 1
                ):
                    break
                await asyncio.sleep(0.01)
            assert TELEMETRY.admission.get("breach-shed", 0) >= 1, (
                TELEMETRY.admission
            )
            assert TELEMETRY.gauge_value("held_slices") >= 1
            keeps = [v for v in values if b"keep" in v]
            assert len(got) < len(keeps), "held stream served everything"
            # the lag engine's key is the chain@topic/partition
            # identity, and the joined residual lag is over the target
            lags, _, _ = TELEMETRY.lag_families()
            (key,) = [k for k in lags if k.endswith("@topic/0")]
            assert lags[key] > 4
            # drain: the consumer group catches up out-of-band; the
            # next join reads lag 0 and the verdict ages out
            lag_mod.note_commit(key, len(values))
            await asyncio.wait_for(task, timeout=60)
            await client.close()
            return got

        try:
            got = loop.run_until_complete(run())
        finally:
            admission_pkg.reset_gate()
            loop.run_until_complete(server.stop())
            loop.close()
        # exactly-once delivery despite the held slices
        assert got == [v for v in values if b"keep" in v]
        # the hold released onto the histogram + the gauge came back
        assert TELEMETRY.gauge_value("held_slices") == 0
        snap = TELEMETRY.snapshot()
        assert snap["slices"]["hold"]["count"] >= 1
        # record age + served rate landed for the stream's key
        lags, served, ages = TELEMETRY.lag_families()
        (key,) = [k for k in served if k.endswith("@topic/0")]
        assert served[key] == len(got)
        assert ages[key].count >= 1
        # every SERVED slice's flow chain is connected in the doc
        served_flows = [
            f for f in TELEMETRY.flows.recent() if f.records > 0
        ]
        assert served_flows, "no completed slice flows recorded"
        doc = render_trace()
        for fl in served_flows:
            _assert_connected(doc, fl.flow_id)
        # and at least one of them survived a shed-then-retry hold
        assert any(f.holds >= 1 for f in served_flows), [
            f.to_dict() for f in served_flows
        ]


    def test_tail_consumer_seeds_committed_at_start_offset(self, tmp_path):
        """Regression: a consumer starting NEAR THE TAIL of a deep log
        must not report the whole log as lag before its first ack — the
        handler seeds the committed cursor at the resolved start
        offset, so the near-tail backlog stays under the SLO target and
        nothing sheds."""
        from fluvio_tpu import admission as admission_pkg
        from fluvio_tpu.admission import AdmissionController
        from fluvio_tpu.client import ConsumerConfig, Fluvio, Offset
        from fluvio_tpu.schema.smartmodule import (
            SmartModuleInvocation,
            SmartModuleInvocationKind,
            SmartModuleInvocationWasm,
        )
        from fluvio_tpu.spu import SpuConfig, SpuServer
        from fluvio_tpu.storage.config import ReplicaConfig

        loop = asyncio.new_event_loop()
        config = SpuConfig(
            id=5001,
            public_addr="127.0.0.1:0",
            log_base_dir=str(tmp_path),
            replication=ReplicaConfig(base_dir=str(tmp_path)),
        )
        config.smart_engine.backend = "auto"
        server = SpuServer(config)
        slo_eng = SloEngine(
            timeseries=TimeSeries(window_s=1e-4, capacity=4),
            rules=parse_slo_spec(
                "consumer_lag:target=4;e2e_p99:off=1;spill_ratio:off=1;"
                "error_rate:off=1;compile_budget:off=1;recompile_rate:off=1;"
                "queue_depth:off=1;hbm_staged:off=1;record_age_p99:off=1"
            ),
        )
        ctl = AdmissionController(
            slo_engine=slo_eng, refresh_s=0.0, tokens=1e9, refill=1e9
        )
        admission_pkg.set_gate(ctl)
        values = [b"keep-%d" % i for i in range(20)]

        async def run():
            await server.start()
            server.ctx.create_replica("topic", 0)
            client = await Fluvio.connect(server.public_addr)
            producer = await client.topic_producer("topic")
            futs = [await producer.send(None, v) for v in values]
            await producer.flush()
            for f in futs:
                await f.wait()
            await producer.close()
            cfg = ConsumerConfig(
                disable_continuous=True,
                smartmodules=[
                    SmartModuleInvocation(
                        wasm=SmartModuleInvocationWasm.adhoc(FILTER_SM),
                        kind=SmartModuleInvocationKind.FILTER,
                    )
                ],
            )
            consumer = await client.partition_consumer("topic", 0)
            got = []
            async for rec in consumer.stream(Offset.absolute(18), cfg):
                got.append(rec.value)
            await client.close()
            return got

        try:
            got = loop.run_until_complete(asyncio.wait_for(run(), 120))
        finally:
            admission_pkg.reset_gate()
            loop.run_until_complete(server.stop())
            loop.close()
        # only the near-tail records, no shed, no false breach
        assert got == values[18:]
        assert TELEMETRY.admission.get("breach-shed", 0) == 0, (
            TELEMETRY.admission
        )

    def test_disconnect_while_held_releases_and_books_the_hold(
        self, tmp_path
    ):
        """ISSUE-17 regression pin (live server): the client
        disconnects WHILE its slice is shed-held. The stream handler's
        exit path must release the hold through the same path as a
        re-admit — ``held_slices`` returns to 0 (no gauge leak) AND
        the held duration lands on ``admission_hold_seconds`` (the
        bare gauge decrement used to lose the observation), with the
        tenant held counter keeping the attribution."""
        from fluvio_tpu import admission as admission_pkg
        from fluvio_tpu.admission import AdmissionController
        from fluvio_tpu.client import ConsumerConfig, Fluvio, Offset
        from fluvio_tpu.schema.smartmodule import (
            SmartModuleInvocation,
            SmartModuleInvocationKind,
            SmartModuleInvocationWasm,
        )
        from fluvio_tpu.spu import SpuConfig, SpuServer
        from fluvio_tpu.storage.config import ReplicaConfig

        loop = asyncio.new_event_loop()
        config = SpuConfig(
            id=5002,
            public_addr="127.0.0.1:0",
            log_base_dir=str(tmp_path),
            replication=ReplicaConfig(base_dir=str(tmp_path)),
        )
        config.smart_engine.backend = "auto"
        server = SpuServer(config)
        slo_eng = SloEngine(
            timeseries=TimeSeries(window_s=1e-4, capacity=4),
            rules=parse_slo_spec(
                "consumer_lag:target=4;e2e_p99:off=1;spill_ratio:off=1;"
                "error_rate:off=1;compile_budget:off=1;recompile_rate:off=1;"
                "queue_depth:off=1;hbm_staged:off=1;record_age_p99:off=1"
            ),
        )
        ctl = AdmissionController(
            slo_engine=slo_eng, refresh_s=0.0, tokens=1e9, refill=1e9
        )
        admission_pkg.set_gate(ctl)
        values = [b"keep-%d" % i for i in range(20)]

        async def run():
            await server.start()
            # tenant = topic-name prefix: the held attribution below
            # must land on "acme"
            server.ctx.create_replica("acme.orders", 0)
            client = await Fluvio.connect(server.public_addr)
            producer = await client.topic_producer("acme.orders")
            for i in range(0, len(values), 2):
                futs = [
                    await producer.send(None, v) for v in values[i:i + 2]
                ]
                await producer.flush()
                for f in futs:
                    await f.wait()
            await producer.close()

            cfg = ConsumerConfig(
                disable_continuous=True,
                max_bytes=64,  # many slices: the hold strikes mid-stream
                smartmodules=[
                    SmartModuleInvocation(
                        wasm=SmartModuleInvocationWasm.adhoc(FILTER_SM),
                        kind=SmartModuleInvocationKind.FILTER,
                    )
                ],
            )
            consumer = await client.partition_consumer("acme.orders", 0)

            async def consume():
                async for _ in consumer.stream(Offset.beginning(), cfg):
                    pass

            task = asyncio.ensure_future(consume())
            for _ in range(3000):
                if (
                    TELEMETRY.admission.get("breach-shed", 0) >= 1
                    and TELEMETRY.gauge_value("held_slices") >= 1
                ):
                    break
                await asyncio.sleep(0.01)
            assert TELEMETRY.admission.get("breach-shed", 0) >= 1, (
                TELEMETRY.admission
            )
            assert TELEMETRY.gauge_value("held_slices") >= 1

            # the generator-driven disconnect: the client goes away
            # while the server still holds the shed slice
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
            await client.close()

            # the handler notices the dead connection on its next
            # retry tick and must release the hold on its way out
            for _ in range(3000):
                if TELEMETRY.gauge_value("held_slices") == 0:
                    break
                await asyncio.sleep(0.01)

        try:
            loop.run_until_complete(asyncio.wait_for(run(), 120))
        finally:
            admission_pkg.reset_gate()
            loop.run_until_complete(server.stop())
            loop.close()
        # no leak: the gauge came back without a drain or a re-admit
        assert TELEMETRY.gauge_value("held_slices") == 0
        # and the hold DURATION was booked on the way out — the exit
        # path must go through the same release as a re-admit, not a
        # bare gauge decrement that loses the observation
        snap = TELEMETRY.snapshot()
        hold = snap["slices"].get("hold")
        assert hold is not None and hold["count"] >= 1, snap["slices"]
        _, _, held_t, _ = TELEMETRY.tenant_families()
        assert held_t.get("acme", 0) >= 1, held_t


# ---------------------------------------------------------------------------
# Surfaces: socket lag mode, read_lag, CLI exit codes
# ---------------------------------------------------------------------------


class TestLagSurfaces:
    def test_socket_lag_mode_roundtrip(self, tmp_path):
        from fluvio_tpu.spu.monitoring import MonitoringServer, read_lag

        eng = lag_mod.engine()
        leader = FakeLeader(300)
        eng.track("c@t/0", leader)
        eng.note_commit("c@t/0", 100)
        lag_mod.note_serve("c@t/0", 100, 0.5)

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
                return await read_lag(server.path)
            finally:
                await server.stop()

        try:
            doc = loop.run_until_complete(run())
        finally:
            loop.close()
        assert doc["enabled"] is True
        entry = doc["partitions"]["c@t/0"]
        assert entry["committed"] == 100
        assert entry["hw"] == 300
        assert entry["lag"] == 200
        assert entry["served_records"] == 100
        assert entry["age_count"] == 1
        assert "consumer_lag" in doc["targets"]

    def test_lag_snapshot_disabled_verdict(self):
        prior = TELEMETRY.enabled
        TELEMETRY.enabled = False
        try:
            doc = lag_mod.lag_snapshot()
        finally:
            TELEMETRY.enabled = prior
        assert doc == {
            "enabled": False, "verdict": "disabled", "partitions": {},
        }

    def test_cli_exit_codes_and_formats(self, capsys):
        from fluvio_tpu.cli import main
        from fluvio_tpu.telemetry import slo as slo_mod

        # healthy: rc 0, table names the partition
        eng = lag_mod.engine()
        leader = FakeLeader(100)
        eng.track("c@t/0", leader)
        eng.note_commit("c@t/0", 90)
        slo_mod.reset_engine()
        try:
            rc = main(["lag", "--local"])
            out = capsys.readouterr().out
            assert rc == 0
            assert "c@t/0" in out and "lag verdict: ok" in out

            # breach: a backlogged partition flips the verdict -> rc 1
            leader._leo = 1_000_000
            slo_mod.reset_engine()
            ts = slo_mod.engine().timeseries
            ts.force_tick()
            import time as _t

            _t.sleep(0.01)
            ts.force_tick()
            rc = main(["lag", "--local", "--format", "json"])
            out = capsys.readouterr().out
            doc = json.loads(out)
            assert doc["verdict"] == "breach"
            assert rc == 1
            assert (
                doc["slo"]["c@t/0"]["consumer_lag"] == "breach"
            )
        finally:
            slo_mod.reset_engine()

    def test_prometheus_families_render(self):
        from fluvio_tpu.telemetry import render_prometheus

        eng = lag_mod.engine()
        leader = FakeLeader(50)  # keep the weakref'd leader alive
        eng.track("c@t/0", leader)
        lag_mod.note_serve("c@t/0", 10, 0.25)
        flow = TELEMETRY.begin_flow("c@t/0")
        TELEMETRY.end_flow(flow, records=10)
        TELEMETRY.add_slice_phase("hold", 0.1)
        TELEMETRY.gauge_add("held_slices", 1)
        text = render_prometheus()
        # the scrape re-joined lag without anyone calling sample()
        assert 'fluvio_tpu_consumer_lag{key="c@t/0"} 50' in text
        assert 'fluvio_tpu_record_age_seconds_count{key="c@t/0"} 1' in text
        assert 'fluvio_tpu_served_records_total{key="c@t/0"} 10' in text
        assert 'fluvio_tpu_slice_wait_seconds_count{phase="serve"} 1' in text
        assert "fluvio_tpu_admission_hold_seconds_count 1" in text
        assert "fluvio_tpu_held_slices 1" in text
        TELEMETRY.gauge_add("held_slices", -1)


# ---------------------------------------------------------------------------
# PartitionOffsets wiring: the partition tier joins the same engine
# ---------------------------------------------------------------------------


class TestPartitionOffsetsLag:
    def test_attach_and_advance_feed_the_join(self):
        from fluvio_tpu.partition.runtime import PartitionOffsets

        offsets = PartitionOffsets()
        leader = FakeLeader(500)
        offsets.attach_leader("t/3", leader)
        offsets.advance("t/3", 200)
        lag_mod.engine().sample()
        lags, _, _ = TELEMETRY.lag_families()
        assert lags["t/3"] == 300.0
        # PartitionOffsets.lag (leo-based) agrees with the engine join
        assert offsets.lag("t/3") == 300
