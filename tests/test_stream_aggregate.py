"""ISSUE-30: a stateful chain served per stream over one compiled program.

CPU, small sizes, through a real `SpuServer` socket: an aggregate
stream equals a plain reference byte for byte and offset for offset;
every stream starts from its own invocation's seed and carries it
across its slices, never across streams; a second stream of a chain is
a cache hit with no compile; `max_bytes` drops no processed batch; a
lookback chain is still built per stream.
"""

from __future__ import annotations

import asyncio
import json
import re
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
from spubench.broker import Broker, encode_batches  # noqa: E402
from spubench.ragged import to_values  # noqa: E402

from fluvio_tpu.schema.smartmodule import (  # noqa: E402
    SmartModuleInvocation,
    SmartModuleInvocationKind,
    SmartModuleInvocationWasm,
)
from fluvio_tpu.schema.spu import StreamFetchRequest  # noqa: E402
from fluvio_tpu.spu import smart_chain  # noqa: E402
from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

N = 2048
PER_BATCH = 512

AGGREGATE = '''
@smartmodule.aggregate(dsl=dsl.AggregateProgram(
    contribution=dsl.ParseInt(arg=dsl.JsonGet(arg=dsl.Value(), key="@param:field=n")),
    combine="@param:combine=add"))
def a(acc, record):
    n = dsl.parse_int_prefix(dsl.json_get_bytes(record.value, "n"))
    return str(dsl.parse_int_prefix(acc) + n).encode()
'''
FILTER = '''
@smartmodule.filter(dsl=dsl.FilterProgram(
    predicate=dsl.RegexMatch(arg=dsl.Value(), pattern="fluvio")))
def f(record):
    import re
    return re.search(b"fluvio", record.value) is not None
'''
LOOKBACK = '''
@smartmodule.filter(dsl=dsl.FilterProgram(
    predicate=dsl.RegexMatch(arg=dsl.Value(), pattern="fluvio")))
def f(record):
    return b"fluvio" in record.value

@smartmodule.look_back
def lb(record):
    pass
'''


def expect(values, field="n", initial=b"", keep=None):
    """The plain reference (the same few lines as
    `benchmark/references/aggregate_field.py`): Python `int` running
    sum from the seed, one output per (kept) input at the input's index."""
    acc = int(initial or b"0")
    src, out = [], []
    for i, v in enumerate(values):
        if keep is not None and not keep(v):
            continue
        acc += int(json.loads(v)[field])
        src.append(i)
        out.append(str(acc).encode())
    return src, out


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


@pytest.fixture(scope="module")
def corpus():
    gen = manifest.load_plugin(BENCH, "corpora", "gen_json")
    flat, off = gen.generate(N, [20260928, 0])
    return flat, off, to_values(flat, off)


def _invocation(kind, source, params=None, seed=b"", lookback_last=None):
    inv = SmartModuleInvocation(
        wasm=SmartModuleInvocationWasm.adhoc(source.encode()),
        kind=SmartModuleInvocationKind[kind],
        params=dict(params or {}),
        accumulator=seed,
    )
    if lookback_last is not None:
        inv.lookback_last = lookback_last
    return inv


def _agg(seed=b""):
    return _invocation("AGGREGATE", AGGREGATE,
                       {"field": "n", "combine": "add"}, seed)


class _Stream:
    """One stream fetch with its own invocations, acked response by
    response (`spubench.broker.ConsumerStream` takes the chain from the
    configuration; these tests vary the seed per stream)."""

    def __init__(self, broker, invocations, max_bytes):
        self.broker, self.invocations, self.max_bytes = (
            broker, invocations, max_bytes)
        self.cur = 0
        self.batches = []
        self.responses = 0

    async def open(self):
        self._stream = await self.broker.socket.create_stream(
            StreamFetchRequest(
                topic="bench", partition=0, fetch_offset=0,
                max_bytes=self.max_bytes, smartmodules=self.invocations,
            )
        )
        return self

    async def step(self):
        from fluvio_tpu.schema.spu import OffsetUpdate, UpdateOffsetsRequest

        response = await self._stream.next()
        part = response.partition
        assert part.error_code == 0, part.error_message
        self.batches += part.records.batches
        self.cur = part.next_filter_offset
        self.responses += 1
        await self.broker.socket.send_async(UpdateOffsetsRequest(offsets=[
            OffsetUpdate(offset=self.cur, session_id=response.stream_id)
        ]))

    async def drain(self):
        while self.cur < N:
            await self.step()
        await self._stream.close()
        return self

    def decoded(self):
        d = check.decode_batches(self.batches)
        ends = np.cumsum(d["lens"])
        flat = d["flat"].tobytes()
        values = [flat[a:b] for a, b in zip(ends - d["lens"], ends)]
        return d["offsets"].tolist(), values


def _serve(tmp_path, corpus, body):
    """Start an SPU over the corpus in four stored batches and run
    ``body(broker)`` against it."""
    flat, off, _values = corpus
    cfg = json.loads((BENCH / "configs" / "fluvio-northstar-1p.json").read_text())

    async def run():
        broker = Broker(cfg, str(tmp_path / "log"))
        await broker.start()
        try:
            for b in encode_batches(flat, off, 0, N, PER_BATCH):
                await broker.write([b])
            return await body(broker)
        finally:
            await broker.stop()

    return asyncio.run(run())


def _one_batch_bytes(corpus):
    return int(corpus[1][PER_BATCH]) + 64


@pytest.mark.parametrize("seed", [b"", b"1000"], ids=["no-seed", "seed-1000"])
def test_aggregate_stream_equals_reference(tmp_path, corpus, seed):
    async def body(broker):
        s = await _Stream(broker, [_agg(seed)], _one_batch_bytes(corpus)).open()
        return await s.drain(), broker.slice_counts()

    stream, counts = _serve(tmp_path, corpus, body)
    assert stream.responses >= 3
    assert counts["fastpath_slices"] == stream.responses
    assert counts["fallback_slices"] == 0
    offsets, values = stream.decoded()
    src, out = expect(corpus[2], initial=seed)
    assert offsets == src and values == out
    assert TELEMETRY.path_records().get("interpreter", 0) == 0


def test_second_stream_is_a_cache_hit_from_its_own_seed(tmp_path, corpus):
    async def body(broker):
        mb = _one_batch_bytes(corpus)
        first = await (await _Stream(broker, [_agg(b"7")], mb).open()).drain()
        m0 = broker.slice_counts()
        c0 = TELEMETRY.compile_totals()["compiles"]
        second = await (await _Stream(broker, [_agg(b"7")], mb).open()).drain()
        return (first, second, m0, broker.slice_counts(),
                TELEMETRY.compile_totals()["compiles"] - c0)

    first, second, m0, m1, compiles = _serve(tmp_path, corpus, body)
    want = expect(corpus[2], initial=b"7")
    assert first.decoded() == (want[0], want[1])
    assert second.decoded() == (want[0], want[1])   # from 7 again, not from the end
    assert m0["stream_chain_builds"] == 1 and m0["stream_chain_hits"] == 0
    assert m1["stream_chain_builds"] == 1 and m1["stream_chain_hits"] == 1
    assert compiles == 0


def test_interleaved_streams_keep_their_own_carry(tmp_path, corpus):
    async def body(broker):
        mb = _one_batch_bytes(corpus)
        a = await _Stream(broker, [_agg(b"")], mb).open()
        await a.step()      # a's chain is built and cached before b asks
        b = await _Stream(broker, [_agg(b"")], mb).open()      # same key: one program
        c = await _Stream(broker, [_agg(b"500000")], mb).open()
        while min(a.cur, b.cur, c.cur) < N:
            for s in (a, b, c):
                if s.cur < N:
                    await s.step()
        return a, b, c, broker.slice_counts()

    a, b, c, counts = _serve(tmp_path, corpus, body)
    assert min(a.responses, b.responses, c.responses) >= 3
    plain = expect(corpus[2])
    assert a.decoded() == (plain[0], plain[1])
    assert b.decoded() == (plain[0], plain[1])
    seeded = expect(corpus[2], initial=b"500000")
    assert c.decoded() == (seeded[0], seeded[1])
    # a and b share one compiled chain; c's seed is another key
    assert counts["stream_chain_builds"] == 2 and counts["stream_chain_hits"] == 1


def test_max_bytes_below_a_stored_batch_delivers_every_batch_once(
        tmp_path, corpus):
    async def body(broker):
        return await (await _Stream(broker, [_agg()], 1024).open()).drain()

    stream = _serve(tmp_path, corpus, body)
    # a read slice holds at least one stored batch; its output is far
    # over 1,024 bytes and none of it may be cut (the carry has advanced)
    assert stream.responses == N // PER_BATCH
    offsets, values = stream.decoded()
    src, out = expect(corpus[2])
    assert offsets == src and values == out


def test_filter_then_aggregate_equals_reference(tmp_path, corpus):
    async def body(broker):
        chain = [_invocation("FILTER", FILTER), _agg(b"3")]
        return await (
            await _Stream(broker, chain, _one_batch_bytes(corpus)).open()
        ).drain()

    stream = _serve(tmp_path, corpus, body)
    pat = re.compile(b"fluvio")
    src, out = expect(corpus[2], initial=b"3", keep=pat.search)
    assert 0 < len(src) < N
    offsets, values = stream.decoded()
    assert offsets == src and values == out


def test_lookback_chain_is_rebuilt_per_stream(tmp_path, corpus):
    async def body(broker):
        mb = _one_batch_bytes(corpus)
        chain = [_invocation("FILTER", LOOKBACK, lookback_last=1)]
        for _ in range(2):
            await (await _Stream(broker, chain, mb).open()).drain()
        return broker.slice_counts(), len(broker.server.ctx.stream_chains)

    counts, cached = _serve(tmp_path, corpus, body)
    assert counts["stream_chain_builds"] == 2 and counts["stream_chain_hits"] == 0
    assert cached == 0


def test_streams_of_a_cached_chain_share_programs_not_state(corpus):
    """Engine level: `open_stream` gives the compiled executor's own
    jits and caches by reference and a state of its own."""
    from fluvio_tpu.smartengine import SmartEngine
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
    from fluvio_tpu.protocol.record import Record

    b = SmartEngine(backend="tpu").builder()
    b.add_smart_module(_agg(b"10").to_config(), AGGREGATE)
    cached = b.initialize()
    s1, s2 = cached.open_stream(), cached.open_stream()
    t0, t1, t2 = cached.tpu_chain, s1.tpu_chain, s2.tpu_chain
    assert t1._jit_ragged is t0._jit_ragged and t2.stages is t0.stages
    assert t1.state is not t2.state and t1.state is not t0.state
    records = [Record(value=v) for v in corpus[2][:8]]
    for i, r in enumerate(records):
        r.offset_delta = i
    out = t1.process_buffer(RecordBuffer.from_records(records))
    t1._ensure_host_state()
    total = 10 + sum(json.loads(v)["n"] for v in corpus[2][:8])
    assert out.to_records()[-1].value == str(total).encode()
    assert t1.carries[0][0] == total
    assert s1.instances[0].accumulator == str(total).encode()
    # nothing of s1's state reached the cached chain or its sibling
    assert t0.carries[0][0] == 10 and t2.carries[0][0] == 10
    assert t0._device_carries is None and t2._device_carries is None
    assert cached.instances[0].accumulator == b"10"
    assert s2.instances[0].accumulator == b"10"
    # what is learned about the program is shared
    assert t0.h2d_bytes_total == t1.h2d_bytes_total > 0


def test_acquire_hands_out_streams_of_one_cached_chain(tmp_path, corpus):
    async def body(broker):
        ctx = broker.server.ctx
        one = smart_chain.acquire_stream_chain([_agg(b"5")], ctx)
        two = smart_chain.acquire_stream_chain([_agg(b"5")], ctx)
        other = smart_chain.acquire_stream_chain([_agg(b"6")], ctx)
        return one, two, other, list(ctx.stream_chains.values())

    one, two, other, cached = _serve(tmp_path, corpus, body)
    assert len(cached) == 2
    assert one is not two and one not in cached and two not in cached
    assert one.tpu_chain._compiled is two.tpu_chain._compiled
    assert one.tpu_chain._compiled is cached[0].tpu_chain
    assert other.tpu_chain._compiled is cached[1].tpu_chain
    assert one.tpu_chain.carries[0][0] == 5 and other.tpu_chain.carries[0][0] == 6
