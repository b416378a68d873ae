"""Keyed event-time session windows served per stream.

CPU, small sizes, through a real `SpuServer` socket and at the engine's
surface: a stream that carries a `dsl.SessionProgram` (NEXmark Q11, user
sessions) equals the plain per-record reference
`benchmark/references/nexmark_q11.py` byte for byte and by its offset
rule: one slice, several slices of one stream (sessions carried across
them), both swap patterns of `modes/drain_eventtime.py`; a hot session of
thousands of rows across the scan's blocks; the gap exactly (merges) and
one millisecond over (splits); the close at exactly the end; an
out-of-order row that bridges two sessions of the bank; a late row and a
key out of range (counted, dropped); two streams whose banks never mix;
a bank and emit columns grown from 64 entries; a discarded and a
rolled-back slice. The interpreter states the same rows and hands a
stream back; the reference equals a brute-force union of intervals.
"""

from __future__ import annotations

import asyncio
import json
import random
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
    (BENCH / "configs" / "fluvio-nexmark-q11-1p.json").read_text())
SOURCE = CONFIG["chain"][0]["adhoc"]
REF = manifest.load_plugin(BENCH, "references", "nexmark_q11")
GEN = manifest.load_plugin(BENCH, "corpora", "gen_nexmark_bids")

N = 4096 + 200            # 33 stored batches and a short one
PER_BATCH = 128
# 23 bids/s: 4,296 bids are 187 s of event time and about 300 sessions
# close; a stored batch is 5.6 s, so a swapped pair is over the 4 s delay
# at the configuration's lateness: the tests that must see no late bid
# run the query with 12 s of lateness (as the window stage's tests do)
RATE = 25
GAP = 10_000
PARAMS = {"gap_ms": GAP, "lateness_ms": 12_000, "window_ms": GAP,
          "slide_ms": GAP}
T0 = CONFIG["corpus"]["params"]["base_time_ms"]


def _source(lateness_ms=PARAMS["lateness_ms"]):
    assert "lateness_ms=4000" in SOURCE
    return SOURCE.replace("lateness_ms=4000", f"lateness_ms={lateness_ms}")


def _chain(lateness_ms=PARAMS["lateness_ms"]):
    (step,) = CONFIG["chain"]
    return invocations([step | {"adhoc": _source(lateness_ms)}])


def _params(lateness_ms):
    return PARAMS | {"lateness_ms": lateness_ms}


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    FAULTS.clear()
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


def _engine_chain(*sources, backend="tpu"):
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig

    b = SmartEngine(backend=backend).builder()
    for src in sources:
        b.add_smart_module(SmartModuleConfig(), src)
    return b.initialize()


def _flat(values):
    lens = np.fromiter(map(len, values), dtype=np.int64, count=len(values))
    off = np.zeros(len(values) + 1, dtype=np.int64)
    np.cumsum(lens, out=off[1:])
    return np.frombuffer(b"".join(values), dtype=np.uint8), off, list(values)


def _corpus(order=None, n=N):
    params = CONFIG["corpus"]["params"] | {"first_event_rate": RATE}
    values = to_values(*GEN.generate(n, [20261003, 0], **params))
    if order is not None:
        tail = len(order) * PER_BATCH
        values = [v for b in order
                  for v in values[b * PER_BATCH:(b + 1) * PER_BATCH]] + values[tail:]
    return _flat(values)


def _bid(bidder, t):
    return b'{"auction":1000,"bidder":%d,"price":5,"dateTime":%d,"extra":"x"}' % (
        bidder, t)


def _serve(tmp_path, corpus, body, per=PER_BATCH):
    flat, off, values = corpus

    async def run():
        broker = Broker(CONFIG, str(tmp_path / "log"))
        await broker.start()
        try:
            for b in encode_batches(flat, off, 0, len(values), per):
                await broker.write([b])
            return await body(broker)
        finally:
            await broker.stop()

    return asyncio.run(run())


def _two_batches(corpus):
    return int(corpus[1][2 * PER_BATCH]) + 20 * 2 * PER_BATCH


def _held_to_reference(stream, values, lo=0, lateness_ms=PARAMS["lateness_ms"]):
    """`spubench.check`'s own comparison of a whole stream, and of each
    response's count, against the plain reference over ``values[lo:]``."""
    ref = check.Reference(REF, values[lo:], lo, _params(lateness_ms))
    assert ref.offsets_rule == "nondecreasing"
    assert check.compare(ref, lo, lo + len(values[lo:]), stream.batches) == []
    for a, b, batches in stream.responses:
        assert sum(x.records_len() for x in batches) == ref.count(a, b)
        assert check.headers_in_order(batches, a, b)
    return ref


def _drain_one(tmp_path, corpus, max_bytes, lateness_ms=PARAMS["lateness_ms"],
               per=PER_BATCH):
    async def body(broker):
        s = await _Stream(broker, _chain(lateness_ms), max_bytes,
                          end=len(corpus[2])).open()
        return await s.drain(), broker.slice_counts()

    return _serve(tmp_path, corpus, body, per)


# -- through the socket ---------------------------------------------------------


def _swaps(pattern):
    pairs = N // PER_BATCH // 2
    return [b for p in range(pairs)
            for b in ((2 * p + 1, 2 * p) if pattern(p) else (2 * p, 2 * p + 1))]


@pytest.mark.parametrize("order,slices", [
    (None, "one"), (None, "several"),
    (_swaps(lambda p: p % 3 == 1), "several"),
    (_swaps(lambda p: True), "several"),
], ids=["one-slice", "in-order", "swap-some", "swap-all"])
def test_session_stream_equals_reference(tmp_path, order, slices):
    corpus = _corpus(order)
    mb = 64 << 20 if slices == "one" else _two_batches(corpus)
    stream, counts = _drain_one(tmp_path, corpus, mb)
    assert (len(stream.responses) == 1) == (slices == "one")
    assert counts["fastpath_slices"] == len(stream.responses)
    assert counts["fallback_slices"] == 0
    ref = _held_to_reference(stream, corpus[2])
    rows = [json.loads(v) for v in REF.fold(corpus[2], **PARAMS)[1]]
    assert len(rows) > 200
    # sessions span slices: many a session's bids lie in several responses
    assert max(r["endtime"] - GAP - r["starttime"] for r in rows) > 3 * 5_600
    # disorder under the delay: every seed's rows are the same rows
    assert ref.flat.tobytes() == check.Reference(
        REF, _corpus()[2], 0, PARAMS).flat.tobytes()
    assert TELEMETRY.path_records().get("interpreter", 0) == 0
    got = TELEMETRY.session_counts()
    assert got["closed"] == len(rows) and got["late"] == got["invalid"] == 0
    assert TELEMETRY.link_variant_counts()["enc-direct-rows"] >= 1


def test_reopened_stream_starts_from_an_empty_bank(tmp_path):
    corpus = _corpus()
    lo = 16 * PER_BATCH

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
    return [e.detail for e in TELEMETRY.events.recent() if e.kind == "session-grow"]


def test_bank_grows_from_64_entries_under_a_retried_slice(tmp_path, monkeypatch):
    """1,536 bidders of one bid each, in slices of 256 bids 1 ms apart:
    every session stays open, so a bank that starts at 64 entries
    outgrows itself in the stream's slices; the first slice's fetch is
    retried besides."""
    monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
    monkeypatch.setattr(window_stage, "WINDOW_CAPACITY_START", 64)
    n = 1536
    corpus = _flat([_bid(7 + i, T0 + i) for i in range(n)])

    async def body(broker):
        mb = _two_batches(corpus)
        FAULTS.inject("device", first=1)
        first = await (await _Stream(broker, _chain(), mb, end=n).open()).drain()
        grown = _grow_events()
        c0 = TELEMETRY.compile_totals()["compiles"]
        second = await (await _Stream(broker, _chain(), mb, end=n).open()).drain()
        (cached,) = broker.server.ctx.stream_chains.values()
        return (first, second, grown, cached.tpu_chain,
                TELEMETRY.compile_totals()["compiles"] - c0)

    first, second, grown, tpu, compiles = _serve(tmp_path, corpus, body)
    _held_to_reference(first, corpus[2])
    _held_to_reference(second, corpus[2])
    assert TELEMETRY.snapshot()["counters"]["retries"] == {"device": 1}
    assert len(first.responses) == 6
    assert [g.split(" emit")[0] for g in grown] == [
        "bank 64->256", "bank 256->512", "bank 512->1024", "bank 1024->2048"]
    # the learned capacity stayed with the compiled chain: the second
    # stream grew nothing and compiled nothing
    assert tpu._window.capacity == 2048 and _grow_events() == grown
    assert compiles == 0


def test_a_late_row_and_an_invalid_key_are_dropped_and_counted(tmp_path):
    """At the configuration's 4 s delay, in slices of one stored batch of
    30 bids: bidder 5 bids every second for 30 s; a bid 15 s behind the
    newest arrives in the second slice (its own window closed before
    it), and two bids name no bidder in range."""
    values = [_bid(5, T0 + 1000 * i) for i in range(30)]
    values += [_bid(6, T0 + 15_000), _bid(2**31, T0 + 29_000),
               _bid(-1, T0 + 29_500)]
    values += [_bid(5, T0 + 31_000 + 1000 * i) for i in range(30)]
    values += [_bid(9, T0 + 200_000)]                   # closes every session
    corpus = _flat(values)
    stream, counts = _drain_one(tmp_path, corpus, int(corpus[1][30]) + 600,
                                lateness_ms=4000, per=30)
    assert counts["fallback_slices"] == 0 and len(stream.responses) >= 2
    ref = _held_to_reference(stream, corpus[2], lateness_ms=4000)
    src, out, late, invalid = REF.fold(values, **_params(4000))
    assert (late, invalid) == (1, 2) and len(ref.lens) == 1
    assert json.loads(out[0]) == {"bidder": 5, "bid_count": 60,
                                  "starttime": T0, "endtime": T0 + 70_000}
    got = TELEMETRY.session_counts()
    assert (got["late"], got["invalid"], got["closed"]) == (1, 2, 1)
    drops = sorted(e.detail for e in TELEMETRY.events.recent()
                   if e.kind == "session-drop")
    assert drops == ["invalid:2", "late:1"]


# -- at the engine's surface ----------------------------------------------------


def _buffers(values, per):
    out = []
    for lo in range(0, len(values), per):
        records = [Record(value=v, offset_delta=i)
                   for i, v in enumerate(values[lo:lo + per])]
        out.append(RecordBuffer.from_records(records, base_offset=lo))
    return out


def _values_of(buf):
    return [r.value for r in buf.to_records()]


def _served(values, per, lateness_ms=4000):
    """(rows of each slice, the stream) through a fresh stream of the
    fused chain, the interpreter's rows besides."""
    stream = _engine_chain(_source(lateness_ms)).tpu_chain.open_stream()
    fused = [_values_of(stream.process_buffer(b)) for b in _buffers(values, per)]
    _chain_, interp = _interpret(values, per=per, lateness_ms=lateness_ms)
    assert interp == fused
    return fused, stream


def _interpret(values, backend="python", per=PER_BATCH,
               lateness_ms=PARAMS["lateness_ms"]):
    chain = _engine_chain(_source(lateness_ms), backend=backend)
    assert chain.backend_in_use == backend
    out = []
    for lo in range(0, len(values), per):
        records = [Record(value=v, offset_delta=i)
                   for i, v in enumerate(values[lo:lo + per])]
        got = chain.process(SmartModuleInput.from_records(records, lo, 1_000_000))
        assert got.error is None, got.error
        out.append([r.value for r in got.successes])
    return chain, out


def _rows(parts):
    return [json.loads(v) for part in parts for v in part]


@pytest.mark.parametrize("step,sessions", [(GAP, 1), (GAP + 1, 2)],
                         ids=["exactly-the-gap", "one-ms-over"])
def test_the_gap_exactly_merges_and_one_ms_over_splits(step, sessions):
    values = [_bid(3, T0), _bid(3, T0 + step), _bid(4, T0 + 100_000)]
    parts, _stream = _served(values, 8)
    assert parts[0] == REF.fold(values, **_params(4000))[1]
    assert len(_rows(parts)) == sessions
    assert _rows(parts)[-1]["endtime"] == T0 + step + GAP


def test_the_close_at_exactly_the_end():
    """A session of bidder 3 ends at t + 10 s; the watermark (newest
    event time less 4 s) reaches it one slice later, at exactly the end:
    it closes there, not a millisecond before."""
    end = T0 + GAP
    values = [_bid(3, T0), _bid(4, end + 4000 - 1), _bid(4, end + 4000)]
    parts, stream = _served(values, 2)
    assert parts == [[], [dsl.session_row_bytes(
        stream._window.program, 3, 1, T0, end)]]
    src, out, _late, _invalid = REF.fold(values, **_params(4000))
    assert src.tolist() == [2] and out == parts[1]


def test_a_bridging_row_merges_two_sessions_of_the_bank():
    """Bidder 5's bids at 0 and 15 s are two sessions of the bank after
    the first slice; a bid at 7.5 s (disorder under a 30 s delay)
    touches both and leaves one session of three bids."""
    values = [_bid(5, T0), _bid(5, T0 + 15_000), _bid(5, T0 + 7_500),
              _bid(8, T0 + 16_000), _bid(8, T0 + 100_000)]
    parts, stream = _served(values, 2, lateness_ms=30_000)
    rows = _rows(parts)
    assert rows == [json.loads(v) for v in REF.fold(values, **_params(30_000))[1]]
    assert rows[0] == {"bidder": 5, "bid_count": 3, "starttime": T0,
                       "endtime": T0 + 25_000}
    # the device counts the bank session that merged into an earlier
    # one of its own; the interpreter the session a record merged into
    # another: one each
    assert TELEMETRY.session_counts()["bridged"] == 2


def test_a_hot_session_crosses_the_scan_blocks():
    """3,000 bids of bidder 11 a millisecond apart in one slice, other
    bidders among them: one session of 3,000 rows, longer than the
    blocked scan's 1,024-row block, then closed by a bid far later."""
    rng = random.Random(43)
    values = []
    for i in range(3000):
        values.append(_bid(11, T0 + i))
        if i % 7 == 0:
            values.append(_bid(rng.randrange(20, 40), T0 + i))
    values.append(_bid(12, T0 + 100_000))
    parts, _stream = _served(values, 4096)
    rows = _rows(parts)
    assert {"bidder": 11, "bid_count": 3000, "starttime": T0,
            "endtime": T0 + 2999 + GAP} in rows
    assert parts[0] == REF.fold(values, **_params(4000))[1]


def test_python_backend_states_the_reference():
    values = _corpus(_swaps(lambda p: p % 2 == 0))[2]
    _chain_, out = _interpret(values)
    assert [v for part in out for v in part] == REF.fold(values, **PARAMS)[1]


def test_a_discarded_and_a_rolled_back_slice_leave_the_bank():
    values = _corpus()[2]
    src, want = REF.expect(values, **PARAMS)
    stream = _engine_chain(_source()).tpu_chain.open_stream()
    bufs = _buffers(values, 4 * PER_BATCH)
    second = [v for i, v in zip(src, want)
              if 4 * PER_BATCH <= i < 8 * PER_BATCH]
    got = _values_of(stream.process_buffer(bufs[0]))
    bank = stream._window_bank
    ids, sessions = bank.ids, bank.occupancy
    # dispatched ahead and dropped: the bank was never committed
    stream.discard_dispatch(stream.dispatch_buffer(bufs[1]))
    assert bank.ids is ids and bank.occupancy == sessions
    # fetched, then declined by its caller: back to where it started
    handle = stream.dispatch_buffer(bufs[1])
    declined = _values_of(stream.finish_buffer(bufs[1], handle))
    assert bank.ids is not ids
    stream.rollback_finished(handle)
    assert bank.ids is ids and bank.occupancy == sessions
    for buf in bufs[1:]:
        got += _values_of(stream.process_buffer(buf))
    assert got == want and declined == second and second


def test_interpreter_takes_over_mid_stream_and_hands_back():
    """A slice the fused path cannot finish is re-run by the
    interpreter from the device bank, and the next slice runs fused
    from what the interpreter left."""
    from fluvio_tpu.resilience.faults import InjectedFault

    values = _corpus()[2]
    want = REF.fold(values, **PARAMS)[1]
    chain = _engine_chain(_source())
    got = []
    for n, lo in enumerate(range(0, N, 4 * PER_BATCH)):
        if n == 4:
            FAULTS.inject("device", first=1,
                          exc=InjectedFault("device", transient=False))
        records = [Record(value=v) for v in values[lo:lo + 4 * PER_BATCH]]
        out = chain.process(SmartModuleInput.from_records(records, lo, 1_000_000))
        assert out.error is None, out.error
        got += [r.value for r in out.successes]
    assert got == want
    assert TELEMETRY.snapshot()["counters"]["spills"] == {"fused-error": 1}
    assert TELEMETRY.path_records()["interpreter"] == 4 * PER_BATCH


def test_session_chain_is_one_device_and_its_rows_the_chains_output():
    """`enable_sharded` refuses a banked chain; a filter may precede
    the session stage, a fan-out or another banked stage may not, and
    nothing follows."""
    from fluvio_tpu.smartengine.engine import EngineError

    tpu = _engine_chain(SOURCE).tpu_chain
    assert tpu.stateful and tpu._window is tpu.stages[-1]
    assert tpu._window.kind == "session"
    with pytest.raises(ValueError, match="cannot be sharded"):
        tpu.enable_sharded(2)
    explode = "smartmodule.array_map(dsl=dsl.ArrayMapProgram())(None)"
    with pytest.raises(EngineError, match="DSL program"):
        _engine_chain(explode, SOURCE)
    with pytest.raises(EngineError, match="DSL program"):
        _engine_chain(SOURCE, SOURCE)
    only5 = ("smartmodule.filter(dsl=dsl.FilterProgram(predicate=dsl.Contains("
             "arg=dsl.Value(), literal=b'\"bidder\":5,')))(None)")
    values = [_bid(5, T0), _bid(6, T0 + 5), _bid(5, T0 + 20_000),
              _bid(5, T0 + 60_000)]
    filtered = _engine_chain(only5, SOURCE)
    assert filtered.tpu_chain._window is not None
    records = [Record(value=v, offset_delta=i) for i, v in enumerate(values)]
    out = filtered.process(SmartModuleInput.from_records(records, 0, 1))
    assert [r.value for r in out.successes] == REF.fold(
        [values[0], values[2], values[3]], **_params(4000))[1]


def test_program_round_trips_and_renders_one_way():
    (program,) = [
        p for p in _engine_chain(SOURCE, backend="python").instances[0]
        ._dsl_programs.values()]
    assert isinstance(program, dsl.SessionProgram)
    assert dsl.Expr.from_json(json.loads(json.dumps(program.to_json()))) == program
    assert (program.gap_ms, program.lateness_ms) == (10_000, 4_000)
    assert dsl.session_row_bytes(program, 1001, 3, T0, T0 + 12_000) == (
        b'{"bidder":1001,"bid_count":3,"starttime":1436918400000,'
        b'"endtime":1436918412000}')


# -- the reference against a brute-force union of intervals --------------------


def _brute(bids, gap):
    """Sessions as connected unions of the closed intervals [t, t + gap]
    of each bidder, ordered by (end, bidder): what a stream emits once
    every session closed."""
    out = []
    for bidder in sorted({b for b, _ in bids}):
        times = sorted(t for b, t in bids if b == bidder)
        start = last = times[0]
        count = 0
        for t in times:
            if t > last + gap:
                out.append((last + gap, bidder, count, start))
                start, count = t, 0
            last, count = t, count + 1
        out.append((last + gap, bidder, count, start))
    return sorted(out)


@pytest.mark.parametrize("seed", range(6))
def test_reference_equals_a_union_of_intervals(seed):
    """Random bidders and times with disorder under the delay, then one
    bid far later that closes everything: the reference's rows are the
    brute-force sessions, in order, whatever order the bids came in."""
    rng = random.Random(seed)
    gap = rng.choice([50, 100, 10_000])
    lateness = 4 * gap
    bids, t = [], 0
    for _ in range(rng.randrange(200, 600)):
        t += rng.randrange(0, gap // 3 + 2)
        bids.append((rng.randrange(0, 12), t))
    # disorder: a bid moves back among its neighbours, by less than the delay
    for i in range(len(bids) - 1, 0, -1):
        j = max(0, i - rng.randrange(0, 4))
        if bids[i][1] - bids[j][1] < lateness // 2:
            bids[i], bids[j] = bids[j], bids[i]
    closer = (99, max(t for _, t in bids) + 2 * gap + lateness)
    values = [_bid(b, tt) for b, tt in bids + [closer]]
    params = {"gap_ms": gap, "lateness_ms": lateness, "window_ms": gap,
              "slide_ms": gap}
    src, out, late, invalid = REF.fold(values, **params)
    assert (late, invalid) == (0, 0)
    want = _brute(bids, gap)
    assert [json.loads(v) for v in out] == [
        {"bidder": b, "bid_count": n, "starttime": s, "endtime": e}
        for e, b, n, s in want]
    # and in the reference's source order every row is due at its record
    assert all(np.diff(src) >= 0)
