"""ISSUE-39: a keyed table that answers every record, served per stream.

CPU, small sizes, through a real `SpuServer` socket: a stream that
carries a `dsl.GroupProgram` (NEXmark Q17, auction statistics) equals
the plain per-record reference `benchmark/references/nexmark_q17.py`
byte for byte and offset for offset: one slice, several slices of one
stream (the table carried), two streams interleaved (never shared), a
table that grows through three doublings inside a stream, a retried, a
discarded and a rolled-back slice (the table it started from), keys that
cross a UTC midnight, a hot key that takes half the rows, the price
ranks at their edges, an invalid record; the interpreter states the same
rows, takes a stream over from the device table and hands it back.
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

from test_stream_window import _Stream  # noqa: E402  (one stream fetch, acked)

from spubench import check, manifest  # noqa: E402
from spubench.broker import Broker, encode_batches, invocations  # noqa: E402
from spubench.ragged import to_values  # noqa: E402

from fluvio_tpu.protocol.record import Record  # noqa: E402
from fluvio_tpu.resilience.faults import FAULTS  # noqa: E402
from fluvio_tpu.smartengine.tpu import window_stage  # noqa: E402
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer  # noqa: E402
from fluvio_tpu.smartmodule import SmartModuleInput, dsl  # noqa: E402
from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

CONFIG = json.loads(
    (BENCH / "configs" / "fluvio-nexmark-q17-1p.json").read_text())
SOURCE = CONFIG["chain"][0]["adhoc"]
REF = manifest.load_plugin(BENCH, "references", "nexmark_q17")
GEN = manifest.load_plugin(BENCH, "corpora", "gen_nexmark_bids")
PARAMS = CONFIG["reference"]["params"]

N = 4096 + 200            # eight stored batches and a short one
PER_BATCH = 512
DAY_MS = 86_400_000
# 92 bids/s: 4,296 bids are 46.7 s of event time, and the base time is
# 20 s before a UTC midnight, so the stream's keys cross it
RATE = 100
BASE_MS = CONFIG["corpus"]["params"]["base_time_ms"] + DAY_MS - 20_000


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    FAULTS.clear()
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


def _chain():
    return invocations(CONFIG["chain"])


def _engine_chain(*sources, backend="tpu", **engine):
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig

    b = SmartEngine(backend=backend, **engine).builder()
    for src in sources:
        b.add_smart_module(SmartModuleConfig(), src)
    return b.initialize()


def _flat(values):
    lens = np.fromiter(map(len, values), dtype=np.int64, count=len(values))
    off = np.zeros(len(values) + 1, dtype=np.int64)
    np.cumsum(lens, out=off[1:])
    return np.frombuffer(b"".join(values), dtype=np.uint8), off, list(values)


def _corpus(order=None, n=N):
    params = CONFIG["corpus"]["params"] | {
        "first_event_rate": RATE, "base_time_ms": BASE_MS}
    values = to_values(*GEN.generate(n, [20261003, 0], **params))
    if order is not None:
        values = [v for b in order
                  for v in values[b * PER_BATCH:(b + 1) * PER_BATCH]]
    return _flat(values)


def _bid(auction, price, t=BASE_MS):
    return b'{"auction":%d,"bidder":1,"price":%d,"dateTime":%d,"extra":"xx"}' % (
        auction, price, t)


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


def _held_to_reference(stream, values, lo=0):
    """`spubench.check`'s own comparison of a whole stream, and of each
    response's count, against the plain reference over ``values[lo:]``."""
    ref = check.Reference(REF, values[lo:], lo, PARAMS)
    assert ref.offsets_rule == "exact"
    assert check.compare(ref, lo, lo + len(values[lo:]), stream.batches) == []
    for a, b, batches in stream.responses:
        assert sum(x.records_len() for x in batches) == ref.count(a, b)
        assert check.headers_in_order(batches, a, b)
    return ref


def _drain_one(tmp_path, corpus, max_bytes):
    async def body(broker):
        s = await _Stream(broker, _chain(), max_bytes, end=len(corpus[2])).open()
        return await s.drain(), broker.slice_counts()

    return _serve(tmp_path, corpus, body)


# -- through the socket ---------------------------------------------------------


@pytest.mark.parametrize("order,slices", [
    (None, "one"), (None, "several"),
    ([1, 0, 2, 3, 5, 4, 6, 7, 8], "several"),
    ([1, 0, 3, 2, 5, 4, 7, 6, 8], "several"),
], ids=["one-slice", "in-order", "swap-some", "swap-all"])
def test_group_stream_equals_reference(tmp_path, order, slices):
    corpus = _corpus(order)
    mb = 64 << 20 if slices == "one" else _two_batches(corpus)
    stream, counts = _drain_one(tmp_path, corpus, mb)
    assert (len(stream.responses) == 1) == (slices == "one")
    assert counts["fastpath_slices"] == len(stream.responses)
    assert counts["fallback_slices"] == 0
    ref = _held_to_reference(stream, corpus[2])
    # every bid answered, at its own offset; a slice is never cut
    assert len(ref.lens) == N and ref.src.tolist() == list(range(N))
    rows = [json.loads(v) for v in REF.fold(corpus[2], **PARAMS)[1]]
    # the keys cross a UTC midnight, and while an auction is the hot one
    # (for 100 auctions' time, 1,533 bids) it takes half the bids
    assert {r["day"] for r in rows} == {"2015-07-15", "2015-07-16"}
    assert max(r["total_bids"] for r in rows) > 1533 * 0.4
    assert max(r["sum_price"] for r in rows) > 2**31
    # disorder changes which row a bid sees, never whether it is answered
    if order is not None:
        assert ref.flat.tobytes() != check.Reference(
            REF, _corpus()[2], 0, PARAMS).flat.tobytes()
    assert TELEMETRY.path_records().get("interpreter", 0) == 0
    variants = TELEMETRY.link_variant_counts()
    assert variants["grp-mixed"] == variants["enc-direct-rows"] == len(
        stream.responses)
    got = TELEMETRY.group_counts()
    assert got["rows"] == N and got["invalid"] == 0
    assert got["keys"] >= len({(r["auction"], r["day"]) for r in rows})


def test_reopened_stream_starts_from_an_empty_table(tmp_path):
    corpus = _corpus()
    lo = 4 * PER_BATCH

    async def body(broker):
        mb = _two_batches(corpus)
        whole = await (await _Stream(broker, _chain(), mb).open()).drain()
        late = await (await _Stream(broker, _chain(), mb, start=lo).open()).drain()
        return whole, late

    whole, late = _serve(tmp_path, corpus, body)
    full = _held_to_reference(whole, corpus[2])
    ref = _held_to_reference(late, corpus[2], lo=lo)
    # it counted from its own first record
    assert ref.flat.tobytes() != full.flat.tobytes()[-len(ref.flat):]


def test_concurrent_streams_do_not_share_a_table(tmp_path):
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
    # the cached chain itself never served: it holds no table
    (cached,) = chains
    assert cached.tpu_chain.stateful and cached.tpu_chain._window_bank is None


def _grow_events():
    return [e.detail for e in TELEMETRY.events.recent() if e.kind == "group-grow"]


def test_table_grows_three_times_under_a_retried_slice(tmp_path, monkeypatch):
    """4,096 bids of 4,096 auctions in slices of 1,024: the table starts
    at half a slice's rows and outgrows its capacity in three slices of
    the stream; the first slice's fetch is retried besides."""
    monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
    monkeypatch.setattr(window_stage, "WINDOW_CAPACITY_START", 64)
    n = 4096
    corpus = _flat([_bid(7 + i, 100 + i, BASE_MS + i) for i in range(n)])

    async def body(broker):
        mb = _two_batches(corpus)
        FAULTS.inject("device", first=1)
        first = await (await _Stream(broker, _chain(), mb, end=n).open()).drain()
        grown = _grow_events()
        c0 = TELEMETRY.compile_totals()["compiles"]
        second = await (await _Stream(broker, _chain(), mb, end=n).open()).drain()
        (cached,) = broker.server.ctx.stream_chains.values()
        return (first, second, grown, cached.tpu_chain,
                TELEMETRY.compile_totals()["compiles"] - c0,
                broker.slice_counts())

    first, second, grown, tpu, compiles, counts = _serve(tmp_path, corpus, body)
    _held_to_reference(first, corpus[2])
    _held_to_reference(second, corpus[2])
    assert TELEMETRY.snapshot()["counters"]["retries"] == {"device": 1}
    assert counts["fallback_slices"] == 0
    assert len(first.responses) == 4
    assert [g.split(" emit")[0] for g in grown] == [
        "bank 512->1024", "bank 1024->2048", "bank 2048->4096"]
    # the learned capacity stayed with the compiled chain: the second
    # stream grew nothing and compiled nothing
    assert tpu._window.capacity == 4096 and _grow_events() == grown
    assert compiles == 0


def test_an_invalid_record_yields_no_row_and_is_counted(tmp_path):
    values = [_bid(5, 100), _bid(5, 50)] * 300
    values[3] = b'{"bidder":1,"price":7,"dateTime":%d,"extra":""}' % BASE_MS
    values[40] = _bid(2**31, 9)                      # the key's range
    values[41] = _bid(2**31 - 1, 9)
    values[77] = b'{"auction":5,"bidder":1,"price":7,"extra":""}'
    values[78] = _bid(5, 9, t=-1)
    corpus = _flat(values)
    stream, counts = _drain_one(tmp_path, corpus, 64 << 20)
    assert counts["fallback_slices"] == 0
    ref = _held_to_reference(stream, corpus[2])
    assert REF.fold(values, **PARAMS)[2] == 4 and len(ref.lens) == 596
    assert sorted(set(range(600)) - set(ref.src.tolist())) == [3, 40, 77, 78]
    assert TELEMETRY.group_counts() == {"rows": 596, "keys": 2, "invalid": 4}
    drops = [e.detail for e in TELEMETRY.events.recent() if e.kind == "group-drop"]
    assert drops == ["invalid:4"]


# -- at the engine's surface ----------------------------------------------------


def _buffers(values, per=PER_BATCH):
    out = []
    for lo in range(0, len(values), per):
        records = [Record(value=v, offset_delta=i)
                   for i, v in enumerate(values[lo:lo + per])]
        out.append(RecordBuffer.from_records(records, base_offset=lo))
    return out


def _values_of(buf):
    return [r.value for r in buf.to_records()]


@pytest.mark.parametrize("prices", [
    (9_999, 10_000, 999_999, 1_000_000),
    (10_000, 9_999, 1_000_000, 999_999, 0, 10_001, 1_000_001),
], ids=["ascending", "mixed"])
def test_price_ranks_min_and_max_at_their_edges(prices):
    values = [_bid(3, p) for p in prices] + [_bid(4, prices[0])]
    want = REF.fold(values, **PARAMS)[1]
    rows = [json.loads(v) for v in want]
    last = rows[len(prices) - 1]
    assert last["rank1_bids"] == sum(p < 10_000 for p in prices)
    assert last["rank2_bids"] == sum(10_000 <= p < 1_000_000 for p in prices)
    assert last["rank3_bids"] == sum(p >= 1_000_000 for p in prices)
    assert (last["min_price"], last["max_price"]) == (min(prices), max(prices))
    assert last["avg_price"] == sum(prices) // len(prices)
    stream = _engine_chain(SOURCE).tpu_chain.open_stream()
    assert _values_of(stream.process_buffer(_buffers(values, 64)[0])) == want
    _chain_, out = _interpret(values)
    assert [v for part in out for v in part] == want


def test_a_discarded_and_a_rolled_back_slice_leave_the_table():
    values = _corpus()[2]
    want = REF.fold(values, **PARAMS)[1]
    stream = _engine_chain(SOURCE).tpu_chain.open_stream()
    bufs = _buffers(values, 2 * PER_BATCH)
    got = _values_of(stream.process_buffer(bufs[0]))
    table = stream._window_bank
    ids, keys = table.ids, table.occupancy
    # dispatched ahead and dropped: the table was never committed
    stream.discard_dispatch(stream.dispatch_buffer(bufs[1]))
    assert table.ids is ids and table.occupancy == keys
    # fetched, then declined by its caller: back to where it started
    handle = stream.dispatch_buffer(bufs[1])
    declined = _values_of(stream.finish_buffer(bufs[1], handle))
    assert table.ids is not ids and table.occupancy > keys
    stream.rollback_finished(handle)
    assert table.ids is ids and table.occupancy == keys
    for buf in bufs[1:]:
        got += _values_of(stream.process_buffer(buf))
    assert got == want and declined == want[1024:2048]
    # a stream of the chain opened since starts empty
    other = _engine_chain(SOURCE).tpu_chain.open_stream()
    assert _values_of(other.process_buffer(bufs[1])) == REF.fold(
        values[1024:2048], **PARAMS)[1]


def _interpret(values, backend="python", per=PER_BATCH):
    chain = _engine_chain(SOURCE, backend=backend)
    assert chain.backend_in_use == backend
    out = []
    for lo in range(0, len(values), per):
        records = [Record(value=v, offset_delta=i)
                   for i, v in enumerate(values[lo:lo + per])]
        got = chain.process(SmartModuleInput.from_records(records, lo, 1_000_000))
        assert got.error is None, got.error
        out.append([(r.offset_delta, r.value) for r in got.successes])
    return chain, [[v for _, v in part] for part in out]


def test_python_backend_states_the_reference():
    values = _corpus([1, 0, 2, 3, 5, 4, 6, 7, 8])[2]
    _chain_, out = _interpret(values)
    assert [v for part in out for v in part] == REF.fold(values, **PARAMS)[1]
    assert [len(part) for part in out] == [
        min(PER_BATCH, N - lo) for lo in range(0, N, PER_BATCH)]


def test_interpreter_takes_over_mid_stream_and_hands_back():
    """A slice the fused path cannot finish is re-run by the
    interpreter from the device table, and the next slice runs fused
    from what the interpreter left."""
    from fluvio_tpu.resilience.faults import InjectedFault

    values = _corpus()[2]
    want = REF.fold(values, **PARAMS)[1]
    chain = _engine_chain(SOURCE)
    got = []
    for n, lo in enumerate(range(0, N, PER_BATCH)):
        if n == 4:
            FAULTS.inject("device", first=1,
                          exc=InjectedFault("device", transient=False))
        records = [Record(value=v) for v in values[lo:lo + PER_BATCH]]
        out = chain.process(SmartModuleInput.from_records(records, lo, 1_000_000))
        assert out.error is None, out.error
        got += [r.value for r in out.successes]
    assert got == want
    assert TELEMETRY.snapshot()["counters"]["spills"] == {"fused-error": 1}
    assert TELEMETRY.path_records()["interpreter"] == PER_BATCH


def test_group_chain_is_one_device_and_its_rows_the_chains_output():
    """`enable_sharded` refuses a banked chain; a filter or a map may
    precede the group stage (rows it drops are not answered), a
    fan-out, an aggregate or a window may not, and nothing follows."""
    from fluvio_tpu.smartengine.engine import EngineError

    tpu = _engine_chain(SOURCE).tpu_chain
    assert tpu.stateful and tpu._window is tpu.stages[-1]
    assert tpu._window.kind == "group" and len(tpu._window.ops) == 7
    with pytest.raises(ValueError, match="cannot be sharded"):
        tpu.enable_sharded(2)
    upper = ("smartmodule.map(dsl=dsl.MapProgram("
             "value=dsl.Upper(arg=dsl.Value())))(None)")
    only7 = ("smartmodule.filter(dsl=dsl.FilterProgram(predicate=dsl.Contains("
             "arg=dsl.Value(), literal=b'\"auction\":7,')))(None)")
    explode = "smartmodule.array_map(dsl=dsl.ArrayMapProgram())(None)"
    with pytest.raises(EngineError, match="DSL program"):
        _engine_chain(SOURCE, upper)
    with pytest.raises(EngineError, match="DSL program"):
        _engine_chain(explode, SOURCE)
    with pytest.raises(EngineError, match="DSL program"):
        _engine_chain(SOURCE, SOURCE)
    values = [_bid(7, 10), _bid(8, 20), _bid(7, 30), _bid(9, 1), _bid(7, 2)]
    filtered = _engine_chain(only7, SOURCE)
    assert filtered.tpu_chain._window is not None
    records = [Record(value=v, offset_delta=i) for i, v in enumerate(values)]
    out = filtered.process(SmartModuleInput.from_records(records, 0, 1))
    assert [r.offset_delta for r in out.successes] == [0, 2, 4]
    assert [r.value for r in out.successes] == REF.fold(
        [values[0], values[2], values[4]], **PARAMS)[1]


def test_program_round_trips_and_renders_one_way():
    (program,) = [
        p for p in _engine_chain(SOURCE, backend="python").instances[0]
        ._dsl_programs.values()]
    assert isinstance(program, dsl.GroupProgram)
    assert dsl.Expr.from_json(json.loads(json.dumps(program.to_json()))) == program
    assert [c.name for c in program.columns] == [
        "total_bids", "rank1_bids", "rank2_bids", "rank3_bids", "min_price",
        "max_price", "avg_price", "sum_price"]
    assert len(dsl.group_accumulators(program)) == 7
    assert dsl.group_row_bytes(program, 7, 16631, [2, 1, 1, 0, 5, 20000, 20005]) == (
        b'{"auction":7,"day":"2015-07-15","total_bids":2,"rank1_bids":1,'
        b'"rank2_bids":1,"rank3_bids":0,"min_price":5,"max_price":20000,'
        b'"avg_price":10002,"sum_price":20005}')
    assert dsl.group_key(program, _bid(7, 1, t=DAY_MS * 3 + 5), None) == (7, 3)
    assert dsl.group_key(program, b'{"price":1}', None) is None
    from fluvio_tpu.windows.spec import KEY_STRIDE

    assert dsl.WINDOW_KEY_LIMIT == KEY_STRIDE
