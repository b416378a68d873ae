"""The consumer's side of the socket, on the flow tracing's clock.

CPU, counts and structure only (a time read here says nothing about the
chip). One in-process `SpuServer` drains a four-slice backlog twice, two
streams on one connection, through the client's own `MultiplexerSocket`:

- one `ConsumeFlow` per response, with ``consume_recv``,
  ``consume_decode`` and ``consume_ack`` in that order;
- every response's record joins its slice flow exactly, on
  (``stream_id``, ``next_offset``), and that key is unique across the
  passes;
- the consumer's phases lie inside the server's ``send`` .. ``ack_wait``
  of the slice that pushed the response, or between streams;
- the slice's ``frame`` and ``send`` are adjacent: together they are the
  interval ``send`` alone covered before the frame build left it;
- the Perfetto export draws each response on its own ``consume`` lane,
  with an arrow from the slice's ``send``.

The zero-cost arm (capture off) is in `test_telemetry_overhead.py`.
"""

from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from spubench import manifest  # noqa: E402
from spubench.broker import Broker, encode_batches  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY, render_trace  # noqa: E402
from fluvio_tpu.telemetry import memory as memory_mod  # noqa: E402
from fluvio_tpu.telemetry.flow import (  # noqa: E402
    CONSUME_PHASES,
    SLICE_PHASES,
)
from fluvio_tpu.telemetry.spans import PHASES  # noqa: E402

PER_BATCH = 2048
SLICES = 4
PASSES = 2


@pytest.fixture(autouse=True)
def _fresh_registry():
    memory_mod.reset_engine()
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()
    memory_mod.reset_engine()


def _drain(tmp_path, passes=PASSES):
    """Write ``SLICES`` stored batches and drain them ``passes`` times,
    one stream a pass, ``max_bytes`` holding one batch a slice."""
    cfg = json.loads((BENCH / "configs" / "fluvio-northstar-1p.json")
                     .read_text())
    gen = manifest.load_plugin(BENCH, "corpora", cfg["corpus"]["generator"])
    n = SLICES * PER_BATCH
    flat, off = gen.generate(n, [7, 0])
    one_batch = int(off[PER_BATCH])

    async def run():
        broker = Broker(cfg, str(tmp_path / "log"))
        await broker.start()
        try:
            for b in encode_batches(flat, off, 0, n, PER_BATCH):
                await broker.write([b])
            out = []
            for _ in range(passes):
                async with broker.stream(0, one_batch) as stream:
                    cur = 0
                    while cur < n:
                        r = await stream.next()
                        out.append(r)
                        cur = r.next_offset
            # the handler closes a flow after its ack wait
            for _ in range(200):
                if len([f for f in TELEMETRY.flows.recent()
                        if f.records]) >= len(out):
                    break
                await asyncio.sleep(0.01)
            return out
        finally:
            await broker.stop()

    return asyncio.run(run())


def _phase(rec, name):
    (p,) = [p for p in rec.phases if p[0] == name]
    return p[1], p[1] + p[2]


def test_every_response_has_a_record_joined_to_its_slice(tmp_path):
    responses = _drain(tmp_path)
    assert len(responses) == PASSES * SLICES
    recs = TELEMETRY.consumes.recent()
    flows = [f for f in TELEMETRY.flows.recent() if f.stream_id is not None]
    assert len(recs) == len(responses) == len(flows)
    for rec, resp in zip(recs, responses):
        assert [p[0] for p in rec.phases] == list(CONSUME_PHASES)
        assert rec.next_offset == resp.next_offset
        assert rec.records == resp.records_out > 0
        assert rec.frame_bytes > resp.bytes_out
        assert rec.t_end is not None and rec.t_end >= _phase(
            rec, "consume_ack")[1]
    # the key: unique across the passes, and the same on both sides
    keys = [(r.stream_id, r.next_offset) for r in recs]
    assert len(set(keys)) == len(keys)
    assert len({r.stream_id for r in recs}) == PASSES
    by_key = {(f.stream_id, f.next_offset): f for f in flows}
    assert set(by_key) == set(keys)
    for f in flows:
        d = f.to_dict()
        assert (d["stream_id"], d["next_offset"]) == (f.stream_id,
                                                      f.next_offset)
    for rec in recs:
        c = rec.to_dict()
        assert [p[0] for p in c["phases"]] == list(CONSUME_PHASES)
        assert set(c["phases_ms"]) == set(CONSUME_PHASES)
        assert by_key[(c["stream_id"], c["next_offset"])].records == c[
            "records"]


def test_consumer_phases_lie_inside_the_servers_waits(tmp_path):
    _drain(tmp_path)
    flows = [f for f in TELEMETRY.flows.recent() if f.stream_id is not None]
    by_key = {(f.stream_id, f.next_offset): f for f in flows}
    eps = 1e-6
    for rec in TELEMETRY.consumes.recent():
        f = by_key[(rec.stream_id, rec.next_offset)]
        send0, _ = _phase(f, "send")
        _, ack1 = _phase(f, "ack_wait")
        for name, start, secs in rec.phases:
            inside = send0 - eps <= start and start + secs <= ack1 + eps
            between = all(start + secs <= g.t0 or start >= g.t_end
                          for g in flows)
            assert inside or between, (name, rec.flow_id, f.flow_id)
        # the ack is written inside the wait that it ends
        a0, a1 = _phase(rec, "consume_ack")
        w0, w1 = _phase(f, "ack_wait")
        assert w0 - eps <= a0 and a1 <= w1 + eps


def test_frame_and_send_cover_what_send_covered(tmp_path):
    _drain(tmp_path)
    flows = [f for f in TELEMETRY.flows.recent() if f.stream_id is not None]
    assert flows
    for f in flows:
        names = [p[0] for p in f.phases]
        i = names.index("frame")
        assert names[i + 1] == "send" and names[i - 1] == "encode"
        _f0, f1 = _phase(f, "frame")
        s0, _s1 = _phase(f, "send")
        # one annotation exit and one entry between them, no work
        assert 0.0 <= s0 - f1 < 2e-3
    assert "frame" in SLICE_PHASES
    assert not set(CONSUME_PHASES) & (set(SLICE_PHASES) | set(PHASES))


def test_trace_draws_each_response_from_its_send(tmp_path):
    _drain(tmp_path, passes=1)
    recs = TELEMETRY.consumes.recent()
    doc = render_trace()
    ev = doc["traceEvents"]
    lanes = [e for e in ev if e.get("cat") == "consume-phase"]
    assert {e["name"] for e in lanes} == set(CONSUME_PHASES)
    names = {e["args"]["name"] for e in ev if e.get("ph") == "M"
             and e.get("name") == "thread_name"}
    assert any(n.startswith("consume lane") for n in names)
    flows = [f for f in TELEMETRY.flows.recent() if f.stream_id is not None]
    slice_tids = {e["tid"] for e in ev if e.get("cat") == "slice"}
    consume_tids = {e["tid"] for e in ev if e.get("cat") == "consume"}
    assert slice_tids and consume_tids and not slice_tids & consume_tids
    for rec in recs:
        arrow = [e for e in ev if e.get("cat") == "consume-flow"
                 and e["id"] == rec.flow_id]
        assert [e["ph"] for e in arrow] == ["s", "f"]
        s, f = arrow
        assert s["tid"] in slice_tids and f["tid"] in consume_tids
        assert len([g for g in flows if (g.stream_id, g.next_offset)
                    == (rec.stream_id, rec.next_offset)]) == 1
        assert s["ts"] <= f["ts"]
    # an arrow id is never a slice flow's
    assert not {r.flow_id for r in recs} & {f.flow_id for f in flows}


def test_only_stream_fetch_responses_keep_a_record():
    """A follower's sync is a stream response too, but no ack names it:
    it keeps no record, and the consume ring holds consumers only."""
    from fluvio_tpu.schema.internal_spu import SyncRecords
    from fluvio_tpu.schema.spu import StreamFetchResponse
    from fluvio_tpu.transport.multiplexing import MultiplexerSocket

    mux = MultiplexerSocket.__new__(MultiplexerSocket)
    mux._unacked = {}
    rec = TELEMETRY.begin_consume()
    assert mux._await_ack(rec, SyncRecords(topic="t", partition=3), 10) is None
    assert mux._unacked == {} and len(TELEMETRY.consumes) == 0
    resp = StreamFetchResponse(stream_id=7)
    resp.partition.next_filter_offset = 42
    assert mux._await_ack(rec, resp, 10) == 7
    assert mux._unacked == {7: rec} and rec.frame_bytes == 14
    # a second response of the stream, unacked before it: the first closes
    nxt = TELEMETRY.begin_consume()
    mux._await_ack(nxt, resp, 10)
    assert TELEMETRY.consumes.recent() == [rec] and mux._unacked == {7: nxt}
