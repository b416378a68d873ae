"""ISSUE-35: keyed sliding event-time windows served per stream.

CPU, small sizes, through a real `SpuServer` socket: a stream that
carries a `dsl.WindowProgram` (NEXmark Q5, hot items) equals the plain
per-record reference `benchmark/references/nexmark_q5.py` byte for
byte and by its offset rule, over several slices and both swap
patterns of `modes/drain_eventtime.py`; a stream's bank starts empty
and is never another stream's; bank and emit capacities grow from 1,024
under a retried slice; the interpreter states the same rows; a late bid
is dropped and counted alike by the reference, the interpreter and the
fused path.
"""

from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from spubench import check, manifest  # noqa: E402
from spubench.broker import Broker, encode_batches, invocations  # noqa: E402
from spubench.ragged import to_values  # noqa: E402

from fluvio_tpu.resilience.faults import FAULTS  # noqa: E402
from fluvio_tpu.schema.spu import StreamFetchRequest  # noqa: E402
from fluvio_tpu.smartengine.tpu import executor as tpu_executor  # noqa: E402
from fluvio_tpu.smartengine.tpu import window_stage  # noqa: E402
from fluvio_tpu.smartmodule import dsl  # noqa: E402
from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

CONFIG = json.loads(
    (BENCH / "configs" / "fluvio-nexmark-q5-1p.json").read_text())
REF = manifest.load_plugin(BENCH, "references", "nexmark_q5")
GEN = manifest.load_plugin(BENCH, "corpora", "gen_nexmark_bids")
MODE = manifest.load_plugin(BENCH, "modes", "drain_eventtime")

N = 4096 + 200            # eight stored batches and a short one
PER_BATCH = 512
# 92 bids/s: 4,296 bids are 46.7 s of event time, about twenty windows
# close; a stored batch is 5.6 s, so a swapped pair is over the 4 s delay
# at the configuration's lateness: the tests that must see no late bid
# run the query with 12 s of lateness, the late test with 4 s
RATE = 100
PARAMS = {"window_ms": 10000, "slide_ms": 2000, "lateness_ms": 12000}


def _chain(lateness_ms=PARAMS["lateness_ms"]):
    (step,) = CONFIG["chain"]
    assert "lateness_ms=4000" in step["adhoc"]
    return invocations([step | {"adhoc": step["adhoc"].replace(
        "lateness_ms=4000", f"lateness_ms={lateness_ms}")}])


def _engine_chain(*sources, backend="tpu", **engine):
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig

    b = SmartEngine(backend=backend, **engine).builder()
    for src in sources:
        b.add_smart_module(SmartModuleConfig(), src)
    return b.initialize()


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    FAULTS.clear()
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


def _corpus(order=None, n=N):
    params = CONFIG["corpus"]["params"] | {"first_event_rate": RATE}
    flat, off = GEN.generate(n, [20261003, 0], **params)
    if order is not None:
        bounds = [(b * PER_BATCH, min((b + 1) * PER_BATCH, n)) for b in order]
        lens = np.concatenate([off[a + 1:b + 1] - off[a:b] for a, b in bounds])
        flat = np.concatenate([flat[off[a]:off[b]] for a, b in bounds])
        off = np.concatenate([[0], np.cumsum(lens)])
    return flat, off, to_values(flat, off)


class _Stream:
    """One stream fetch, acked response by response."""

    def __init__(self, broker, chain, max_bytes, start=0, end=N):
        self.broker, self.chain, self.max_bytes = broker, chain, max_bytes
        self.start, self.cur, self.end = start, start, end
        self.responses = []          # (first input, next input, batches)

    async def open(self):
        self._stream = await self.broker.socket.create_stream(
            StreamFetchRequest(
                topic="bench", partition=0, fetch_offset=self.start,
                max_bytes=self.max_bytes, smartmodules=self.chain,
            )
        )
        return self

    async def step(self):
        from fluvio_tpu.schema.spu import OffsetUpdate, UpdateOffsetsRequest

        response = await self._stream.next()
        part = response.partition
        assert part.error_code == 0, part.error_message
        self.responses.append(
            (self.cur, part.next_filter_offset, part.records.batches))
        self.cur = part.next_filter_offset
        await self.broker.socket.send_async(UpdateOffsetsRequest(offsets=[
            OffsetUpdate(offset=self.cur, session_id=response.stream_id)
        ]))

    async def drain(self):
        while self.cur < self.end:
            await self.step()
        await self._stream.close()
        return self

    @property
    def batches(self):
        return [b for _, _, bs in self.responses for b in bs]


def _serve(tmp_path, corpus, body):
    flat, off, values = corpus

    async def run():
        broker = Broker(CONFIG, str(tmp_path / "log"))
        await broker.start()
        try:
            for b in encode_batches(flat, off, 0, len(values), PER_BATCH):
                await broker.write([b])
            return await body(broker)
        finally:
            await broker.stop()

    return asyncio.run(run())


def _two_batches(corpus):
    return int(corpus[1][2 * PER_BATCH]) + 20 * 2 * PER_BATCH


def _held_to_reference(stream, values, lo=0, params=PARAMS):
    """`spubench.check`'s own comparison of a whole stream, and of each
    response's count, against the plain reference over ``values[lo:]``."""
    ref = check.Reference(REF, values[lo:], lo, params)
    assert check.compare(ref, lo, lo + len(values[lo:]), stream.batches) == []
    for a, b, batches in stream.responses:
        assert sum(x.records_len() for x in batches) == ref.count(a, b)
        assert check.headers_in_order(batches, a, b)
    return ref


@pytest.mark.parametrize("order", [
    None, [1, 0, 2, 3, 5, 4, 6, 7, 8], [1, 0, 3, 2, 5, 4, 7, 6, 8],
], ids=["in-order", "swap-some", "swap-all"])
def test_window_stream_equals_reference(tmp_path, order):
    corpus = _corpus(order)

    async def body(broker):
        s = await _Stream(broker, _chain(), _two_batches(corpus)).open()
        return await s.drain(), broker.slice_counts()

    stream, counts = _serve(tmp_path, corpus, body)
    assert len(stream.responses) >= 4
    assert counts["fastpath_slices"] == len(stream.responses)
    assert counts["fallback_slices"] == 0
    ref = _held_to_reference(stream, corpus[2])
    assert len(ref.lens) >= 15                       # windows do close
    assert any(not bs for _, _, bs in stream.responses)   # ... not in every slice
    assert REF.fold(corpus[2], **PARAMS)[2] == 0           # and no bid is late
    # the same rows whatever the order
    assert ref.flat.tobytes() == check.Reference(
        REF, _corpus()[2], 0, PARAMS).flat.tobytes()
    assert TELEMETRY.path_records().get("interpreter", 0) == 0
    assert TELEMETRY.link_variant_counts()["win-top"] == len(stream.responses)
    closed, deltas, _, _ = TELEMETRY.window_counts()
    assert closed == deltas["close"] > len(ref.lens)
    assert "late" not in deltas and "invalid" not in deltas


def test_reopened_stream_starts_from_an_empty_bank(tmp_path):
    corpus = _corpus()
    lo = 4 * PER_BATCH

    async def body(broker):
        mb = _two_batches(corpus)
        whole = await (await _Stream(broker, _chain(), mb).open()).drain()
        late = await (await _Stream(broker, _chain(), mb, start=lo).open()).drain()
        return whole, late

    whole, late = _serve(tmp_path, corpus, body)
    _held_to_reference(whole, corpus[2])
    ref = _held_to_reference(late, corpus[2], lo=lo)
    # it counted from its own first record: its first rows are smaller
    # than the whole stream's for the same windows
    full = check.Reference(REF, corpus[2], 0, PARAMS)
    assert 0 < len(ref.lens) < len(full.lens)
    assert ref.flat.tobytes() != full.flat.tobytes()[-len(ref.flat):]


def test_concurrent_streams_do_not_share_a_bank(tmp_path):
    corpus = _corpus()

    async def body(broker):
        mb = _two_batches(corpus)
        a = await _Stream(broker, _chain(), mb).open()
        await a.step()        # a's chain is built and cached before b asks
        await a.step()
        b = await _Stream(broker, _chain(), mb).open()
        while min(a.cur, b.cur) < N:
            for s in (b, a):
                if s.cur < N:
                    await s.step()
        chains = list(broker.server.ctx.stream_chains.values())
        return a, b, broker.slice_counts(), chains

    a, b, counts, chains = _serve(tmp_path, corpus, body)
    _held_to_reference(a, corpus[2])
    _held_to_reference(b, corpus[2])
    assert counts["stream_chain_builds"] == 1 and counts["stream_chain_hits"] == 1
    assert counts["fallback_slices"] == 0
    # the cached chain itself never served: it holds no bank
    (cached,) = chains
    assert cached.tpu_chain.stateful and cached.tpu_chain._window_bank is None


def _grow_events():
    return [e.detail for e in TELEMETRY.events.recent() if e.kind == "window-grow"]


def test_bank_grows_from_1024_under_a_retried_slice(tmp_path, monkeypatch):
    monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
    corpus = _corpus()
    assert window_stage.WINDOW_CAPACITY_START == 1024

    async def body(broker):
        mb = _two_batches(corpus)
        # the first fetch of the stream fails transiently: the retry
        # re-dispatches the slice, whose header then reports the overflow
        FAULTS.inject("device", first=1)
        first = await (await _Stream(broker, _chain(), mb).open()).drain()
        grown = _grow_events()
        c0 = TELEMETRY.compile_totals()["compiles"]
        second = await (await _Stream(broker, _chain(), mb).open()).drain()
        (cached,) = broker.server.ctx.stream_chains.values()
        return (first, second, grown, cached.tpu_chain,
                TELEMETRY.compile_totals()["compiles"] - c0,
                broker.slice_counts())

    first, second, grown, tpu, compiles, counts = _serve(tmp_path, corpus, body)
    _held_to_reference(first, corpus[2])
    _held_to_reference(second, corpus[2])
    assert TELEMETRY.snapshot()["counters"]["retries"] == {"device": 1}
    assert counts["fallback_slices"] == 0
    assert grown and grown[0].startswith("bank 1024->")
    # the learned sizes stayed with the compiled chain: the second stream
    # grew nothing and compiled nothing
    assert tpu._window.capacity > 1024 and _grow_events() == grown
    assert compiles == 0


def _interpret(values, lateness_ms=PARAMS["lateness_ms"], backend="python",
               per=PER_BATCH):
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    (step,) = CONFIG["chain"]
    chain = _engine_chain(step["adhoc"].replace(
        "lateness_ms=4000", f"lateness_ms={lateness_ms}"), backend=backend)
    assert chain.backend_in_use == backend
    out = []
    for lo in range(0, len(values), per):
        records = [Record(value=v) for v in values[lo:lo + per]]
        for i, r in enumerate(records):
            r.offset_delta = i
        got = chain.process(SmartModuleInput.from_records(records, lo, 1_000_000))
        assert got.error is None, got.error
        # fresh records: they read as their batch's base offset
        assert all(r.offset_delta == 0 for r in got.successes)
        out.append([r.value for r in got.successes])
    return chain, out


def test_python_backend_states_the_reference():
    values = _corpus([1, 0, 2, 3, 5, 4, 6, 7, 8])[2]
    _chain_, out = _interpret(values)
    ref = check.Reference(REF, values, 0, PARAMS)
    assert b"".join(v for part in out for v in part) == ref.flat.tobytes()
    # ... attributed to the stored batch that holds the closing record
    assert [len(part) for part in out] == [
        ref.count(lo, min(lo + PER_BATCH, N)) for lo in range(0, N, PER_BATCH)]


def test_late_bid_is_dropped_and_counted_alike(tmp_path):
    """Disorder forced over the delay: the first stored batch arrives
    after seven others (39 s late against a 4 s delay), in a later slice
    than the one that closed its windows, where the per-slice rule and
    the per-record rule agree."""
    corpus = _corpus([1, 2, 3, 4, 5, 6, 7, 0, 8])
    params = PARAMS | {"lateness_ms": 4000}
    src, want, late = REF.fold(corpus[2], **params)
    assert late == 5 * PER_BATCH and want          # every window of every bid

    async def body(broker):
        s = _Stream(broker, _chain(4000), _two_batches(corpus))
        return await (await s.open()).drain(), broker.slice_counts()

    stream, counts = _serve(tmp_path, corpus, body)
    _held_to_reference(stream, corpus[2], params=params)
    assert counts["fallback_slices"] == 0
    assert TELEMETRY.window_counts()[1]["late"] == late
    drops = [e.detail for e in TELEMETRY.events.recent() if e.kind == "window-drop"]
    assert drops == [f"late:{late}"]
    TELEMETRY.reset()
    _c, out = _interpret(corpus[2], lateness_ms=4000)
    assert [v for part in out for v in part] == want
    assert TELEMETRY.window_counts()[1]["late"] == late


def test_interpreter_takes_over_mid_stream_and_hands_back():
    """A slice the fused path cannot finish is re-run by the
    interpreter from the device bank, and the next slice runs fused
    from what the interpreter left."""
    from fluvio_tpu.resilience.faults import InjectedFault

    values = _corpus()[2]
    _c, want = _interpret(values)
    TELEMETRY.reset()
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    (step,) = CONFIG["chain"]
    chain = _engine_chain(step["adhoc"].replace(
        "lateness_ms=4000", f"lateness_ms={PARAMS['lateness_ms']}"))
    got = []
    for n, lo in enumerate(range(0, N, PER_BATCH)):
        if n == 4:
            FAULTS.inject("device", first=1,
                          exc=InjectedFault("device", transient=False))
        records = [Record(value=v) for v in values[lo:lo + PER_BATCH]]
        out = chain.process(SmartModuleInput.from_records(records, lo, 1_000_000))
        assert out.error is None, out.error
        got.append([r.value for r in out.successes])
    assert got == want
    assert TELEMETRY.snapshot()["counters"]["spills"] == {"fused-error": 1}
    assert TELEMETRY.path_records()["interpreter"] == PER_BATCH


def test_any_int_contribution_folds_in_64_bits():
    """Sum of prices per (window, auction), every row emitted: the
    fused chain against the interpreter, with sums past 2**31."""
    src = CONFIG["chain"][0]["adhoc"].replace(
        'dsl.ParseInt(arg=dsl.Const(data=b"1"))',
        'dsl.ParseInt(arg=dsl.JsonGet(arg=dsl.Value(), key="price"))',
    ).replace('emit="top"', 'emit="all"').replace(
        "lateness_ms=4000", "lateness_ms=12000")
    assert "price" in src and '"all"' in src
    values = _corpus()[2]
    rows = {}
    for backend in ("python", "tpu"):
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartmodule import SmartModuleInput

        chain = _engine_chain(src, backend=backend)
        out = []
        for lo in range(0, N, 2 * PER_BATCH):
            records = [Record(value=v) for v in values[lo:lo + 2 * PER_BATCH]]
            got = chain.process(SmartModuleInput.from_records(records, lo, 1))
            assert got.error is None, got.error
            out += [r.value for r in got.successes]
        rows[backend] = out
    assert rows["tpu"] == rows["python"] and len(rows["tpu"]) > 1000
    assert max(json.loads(v)["num"] for v in rows["tpu"]) > 2**31
    # the composite id's key range is the DSL's
    from fluvio_tpu.windows.spec import KEY_STRIDE

    assert dsl.WINDOW_KEY_LIMIT == KEY_STRIDE


def test_window_chain_is_one_device_and_its_rows_the_chains_output():
    """`enable_sharded` refuses a window chain with a message (a
    stream's bank lives on one device) instead of mis-serving it, and a
    program after the window's is not lowered."""
    from fluvio_tpu.smartengine.engine import EngineError

    window = CONFIG["chain"][0]["adhoc"]
    tpu = _engine_chain(window).tpu_chain
    assert tpu.stateful and tpu._window is tpu.stages[-1]
    with pytest.raises(ValueError, match="window chain cannot be sharded"):
        tpu.enable_sharded(2)
    assert tpu._sharded is None
    with pytest.raises(EngineError, match="window chain cannot be sharded"):
        _engine_chain(window, mesh_devices=2)
    upper = ("smartmodule.map(dsl=dsl.MapProgram("
             "value=dsl.Upper(arg=dsl.Value())))(None)")
    assert _engine_chain(upper, window).tpu_chain._window is not None
    with pytest.raises(EngineError, match="DSL program"):
        _engine_chain(window, upper)


# -- the corpus and the mode ---------------------------------------------------


def test_corpus_equals_its_per_record_form():
    n, seed = 3000, [2**31 + 7, 0]
    c = GEN.draws(n, seed)
    want = [
        b'{"auction":%d,"bidder":%d,"price":%d,"dateTime":%d,"extra":"%s"}' % (
            c["auction"][i], c["bidder"][i], c["price"][i], c["dateTime"][i],
            c["letters"][i, :c["extra_len"][i]].tobytes())
        for i in range(n)
    ]
    flat, off = GEN.generate(n, seed)
    assert to_values(flat, off) == want
    lens = np.diff(off)
    assert 80 <= lens.min() and lens.max() <= 120 and 95 < lens.mean() < 105
    # the stream the generator's defaults describe: 46 bids of 50 events
    # at 10,000 events/s, half of the bids to the hot auction
    assert c["dateTime"][0] == 1436918400000
    assert c["dateTime"][2999] - c["dateTime"][0] == (2999 // 46 * 50 + 4 + 2999 % 46) // 10
    hot = 1000 + (np.arange(n) // 46 * 3 + 2) // 100 * 100
    assert 0.45 < (c["auction"] == hot).mean() < 0.60
    assert to_values(*GEN.generate(50, 5)) == to_values(*GEN.generate(50, 5))
    assert to_values(*GEN.generate(50, 5)) != to_values(*GEN.generate(50, 6))


def test_mode_serves_the_same_batches_with_disorder_under_the_delay():
    from types import SimpleNamespace

    n = 6 * PER_BATCH + 100
    cfg = CONFIG | {"stored_batch_records": PER_BATCH}

    def batches(seed):
        s = SimpleNamespace(config=cfg, seed=seed)
        v = to_values(*MODE._generate(s, GEN, n))
        return [tuple(v[i:i + PER_BATCH]) for i in range(0, n, PER_BATCH)]

    a, b, a2 = batches(2**31 + 5), batches(11), batches(2**31 + 5)
    assert a == a2 and a != b
    assert sorted(a) == sorted(b) and a[-1] == b[-1] and len(a[-1]) == 100
    plain = to_values(*GEN.generate(
        n, [cfg["corpus"]["base_seed"], 0], **cfg["corpus"]["params"]))
    for got in (a, b):
        for pair in range(3):          # a pair swaps or stays; nothing else moves
            here = [plain[i:i + PER_BATCH][0] for i in
                    (2 * pair * PER_BATCH, (2 * pair + 1) * PER_BATCH)]
            assert sorted(x[0] for x in got[2 * pair:2 * pair + 2]) == sorted(here)
    # at the configuration's own geometry two stored batches are 3.56 s
    # of event time, under the watermark's 4 s
    per = CONFIG["stored_batch_records"]
    t = GEN.draws(2 * per, [1, 0], **CONFIG["corpus"]["params"])["dateTime"]
    assert t[-1] - t[0] < CONFIG["reference"]["params"]["lateness_ms"]
    assert MODE.event_order(CONFIG, 3, 62 * per + 576)[-1] == (62 * per, 62 * per + 576)


def test_window_slice_stages_its_flat_once_and_once_more_per_rerun(
    tmp_path, monkeypatch
):
    """A window slice's flat goes up once, in its dispatch; a re-run of
    the slice (bank growth) stages afresh, the first dispatch having
    donated the uploaded array."""
    corpus = _corpus()
    staged = []
    real = tpu_executor.TpuChainExecutor._stage_flat

    def stage_flat(flat, bucket):
        staged.append(bucket)
        return real(flat, bucket)

    monkeypatch.setattr(tpu_executor.TpuChainExecutor, "_stage_flat",
                        staticmethod(stage_flat))

    async def body(broker):
        s = await _Stream(broker, _chain(), _two_batches(corpus)).open()
        return await s.drain()

    stream = _serve(tmp_path, corpus, body)
    _held_to_reference(stream, corpus[2])
    slices = len(stream.responses)
    # one staging a slice, and one more for each slice re-run under a
    # larger shape
    assert len(staged) == slices + len(_grow_events()) and _grow_events()
