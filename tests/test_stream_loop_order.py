"""ISSUE-31: one stream-loop order for every chain the chip serves.

CPU, small sizes, counting and ordering only (no duration is asserted):
through a real `SpuServer` socket, a stateless, a fan-out and a stateful
chain are served by ONE loop whose order is fetch slice k, dispatch slice
k+1, then split back and encode slice k:

- the consumer's byte stream equals what `process_batches` gives slice by
  slice, and the flows show the order (with the `interleaved` field set
  on all but a pass's last slice),
- a stateless `max_bytes` cut discards the dispatched next slice and the
  stream resumes at the cut,
- a fan-out overflow in `finish(k)` retries with nothing else in flight,
- a stateful slice that declines after its fetch restores the carry of
  the slice dispatched ahead and its own before the rerun,
- a consumer that disconnects with a slice in flight leaves no handle,
- the loop's passes run off the event loop.
"""

from __future__ import annotations

import asyncio
import json
import sys
import threading
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

from fluvio_tpu.schema.spu import Isolation  # noqa: E402
from fluvio_tpu.smartengine import native_backend  # noqa: E402
from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor  # noqa: E402
from fluvio_tpu.spu import public_service, smart_chain  # noqa: E402
from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

N = 2048
PER_BATCH = 512
STATELESS = "fluvio-northstar-1p"
FANOUT = "fluvio-array-explode-1p"
STATEFUL = "fluvio-aggregate-1p"


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _corpus(cfg, n=N):
    gen = manifest.load_plugin(BENCH, "corpora", cfg["corpus"]["generator"])
    return gen.generate(n, [20260928, 0])


def _ragged(values):
    lens = np.fromiter((len(v) for v in values), np.int64, len(values))
    off = np.concatenate([[0], np.cumsum(lens)])
    return np.frombuffer(b"".join(values), np.uint8), off


def _serve(tmp_path, cfg, flat, off, body, n=N):
    """Start an SPU over the corpus in stored batches of `PER_BATCH` and
    run ``body(broker)`` against it."""

    async def run():
        broker = Broker(cfg, str(tmp_path / "log"))
        await broker.start()
        try:
            for b in encode_batches(flat, off, 0, n, PER_BATCH):
                await broker.write([b])
            return await body(broker)
        finally:
            await broker.stop()

    return asyncio.run(run())


async def _drain(broker, max_bytes, n=N):
    out = []
    async with broker.stream(0, max_bytes) as stream:
        cur = 0
        while cur < n:
            r = await stream.next()
            out.append(r)
            cur = r.next_offset
    return out


async def _flows_landed(count):
    """The handler closes a flow after its ack wait: let the last one
    land before the server stops."""
    for _ in range(400):
        flows = [f for f in TELEMETRY.flows.recent() if f.records > 0]
        if len(flows) >= count:
            return flows
        await asyncio.sleep(0.01)
    raise AssertionError("flows did not land")


def _wire(batches):
    return [
        (b.base_offset, b.header.last_offset_delta, b.records_len(),
         bytes(b.raw_records))
        for b in batches
    ]


def _slice_by_slice(broker, cfg, max_bytes, n=N):
    """What `process_batches` gives for the same slices, one after the
    other, on a stream of its own of the same chain."""
    chain = smart_chain.acquire_stream_chain(
        invocations(cfg["chain"]), broker.server.ctx
    )
    out, offset = [], 0
    while offset < n:
        rslice = broker.leader.read_records(
            offset, max_bytes, Isolation.READ_UNCOMMITTED
        )
        result = smart_chain.process_batches(
            chain, rslice.decode_batches(parse_records=False), max_bytes,
            None, start_offset=offset,
        )
        out.append((result.next_offset, _wire(result.records.batches)))
        offset = result.next_offset
    return out


def _decoded(responses):
    d = check.decode_batches([b for r in responses for b in r.batches])
    ends = np.cumsum(d["lens"])
    flat = d["flat"].tobytes()
    return d["offsets"].tolist(), [
        flat[a:b] for a, b in zip(ends - d["lens"], ends)
    ]


# -- one order, three kinds of chain -----------------------------------------


@pytest.mark.parametrize("config_name,chunk_rows", [
    (STATELESS, None), (STATELESS, 128), (FANOUT, None), (STATEFUL, None),
], ids=["stateless", "stateless-chunked", "fanout", "stateful"])
def test_one_loop_serves_every_chain_in_the_same_order(
        tmp_path, monkeypatch, config_name, chunk_rows):
    if chunk_rows:
        monkeypatch.setattr(smart_chain, "_DISPATCH_CHUNK_ROWS", chunk_rows)
    cfg = _config(config_name)
    flat, off = _corpus(cfg)
    one_batch = int(off[PER_BATCH]) + 64

    async def body(broker):
        responses = await _drain(broker, one_batch)
        flows = await _flows_landed(len(responses))
        return (responses, flows, broker.slice_counts(),
                _slice_by_slice(broker, cfg, one_batch))

    responses, flows, counts, want = _serve(tmp_path, cfg, flat, off, body)
    assert len(responses) >= 3
    assert counts["fallback_slices"] == 0
    assert TELEMETRY.path_records().get("interpreter", 0) == 0
    # (a) byte for byte what the slices give one by one
    assert [(r.next_offset, _wire(r.batches)) for r in responses] == want
    # (b) the order: finish(k) | dispatch(k+1) | ... materialize(k) ends
    assert len(flows) == len(responses)
    if chunk_rows:
        spans = [s for s in TELEMETRY.spans.recent()
                 if s.flow_id == flows[0].flow_id]
        assert len(spans) == PER_BATCH // chunk_rows

    def phase(flow, name):
        (hit,) = [(s, s + d) for n, s, d in flow.phases if n == name]
        return hit

    for k, (this, nxt) in enumerate(zip(flows, flows[1:])):
        assert phase(this, "finish")[1] <= phase(nxt, "dispatch")[0], k
        assert phase(nxt, "dispatch")[1] <= phase(this, "materialize")[1], k
        assert phase(this, "materialize")[1] <= phase(this, "encode")[0], k
    assert [f.interleaved for f in flows] == [True] * (len(flows) - 1) + [False]
    assert [f["interleaved"] for f in TELEMETRY.flows_json()
            if f["records"] > 0] == [f.interleaved for f in flows]


# -- the stateless cut -------------------------------------------------------


def test_stateless_cut_discards_the_next_slice_and_resumes_at_the_cut(
        tmp_path, monkeypatch):
    cfg = _config(STATELESS)
    flat, off = _corpus(cfg)
    discarded = []
    discard = smart_chain.PendingSlice.discard

    def spy(self, tpu):
        if self.chunks and self.parts is None:
            discarded.append(self.read_from)
        return discard(self, tpu)

    monkeypatch.setattr(smart_chain.PendingSlice, "discard", spy)

    async def body(broker):
        whole = await _drain(broker, 1 << 24)
        before = len(discarded)
        cut = await _drain(broker, 1024)
        return whole, cut, before, TELEMETRY.gauge_value("inflight_queue_depth")

    whole, cut, before, depth = _serve(tmp_path, cfg, flat, off, body)
    # a stored batch's output is over 1,024 bytes: every response is cut
    assert before == 0 and len(cut) > N // PER_BATCH
    # each cut found a slice dispatched ahead of it, read from the end of
    # the batch the cut fell in, and discarded it
    cut_at = [r.next_offset for r in cut if r.next_offset % PER_BATCH]
    ahead = [o - o % PER_BATCH + PER_BATCH for o in cut_at]
    assert cut_at and discarded == [o for o in ahead if o < N]
    # and the stream resumed AT the cut: every output once, in order
    assert _decoded(cut) == _decoded(whole)
    assert [r.next_offset for r in cut] == sorted({r.next_offset for r in cut})
    assert depth == 0


# -- the fan-out overflow ----------------------------------------------------


def test_fanout_overflow_retries_with_nothing_else_in_flight(
        tmp_path, monkeypatch):
    cfg = _config(FANOUT)
    # two batches of 2 elements a record, then two of 8: the learned
    # capacity (at least 4 a row) overflows in the THIRD slice's finish,
    # when a fourth is read and staged
    values = [
        json.dumps([f"e{i}-{j}" for j in range(2 if i < 2 * PER_BATCH else 8)]
                   ).encode()
        for i in range(N)
    ]
    flat, off = _ragged(values)
    seen = []
    learn = TpuChainExecutor._learn_cap

    def spy(self, buf, total):
        seen.append((
            TELEMETRY.gauge_value("live_batch_handles"),
            TELEMETRY.gauge_value("inflight_queue_depth"),
            len([f for f in TELEMETRY.flows.recent() if f.records > 0]),
        ))
        return learn(self, buf, total)

    monkeypatch.setattr(TpuChainExecutor, "_learn_cap", spy)

    async def body(broker):
        responses = await _drain(broker, int(off[PER_BATCH]) + 64)
        return responses, broker.slice_counts()

    responses, counts = _serve(tmp_path, cfg, flat, off, body)
    assert len(responses) == N // PER_BATCH and counts["fallback_slices"] == 0
    # the overflow was met mid-stream (two slices served before it), by
    # the one live handle: its own
    assert seen and seen[0] == (1, 0, 2)
    offsets, got = _decoded(responses)
    # strings lose their quotes (`references/array_explode.py`)
    assert got == [e.encode() for v in values for e in json.loads(v)]
    assert offsets == sorted(offsets)


# -- a stateful slice that declines after its fetch --------------------------


def test_stateful_decline_after_fetch_restores_both_carries(
        tmp_path, monkeypatch):
    cfg = _config(STATEFUL)
    flat, off = _corpus(cfg)
    values = to_values(flat, off)
    events = []
    real_slab = native_backend.record_slab
    calls = {"n": 0, "armed": False}

    def slab(*a, **k):
        # the entry a served slice's encode opens (`tpu_materialize`)
        if calls["armed"]:
            calls["n"] += 1
            if calls["n"] == 2:     # the second served slice, once
                events.append("encode-refused")
                return None
        return real_slab(*a, **k)

    discard = smart_chain.PendingSlice.discard
    rollback = smart_chain.PendingSlice.rollback
    rerun = smart_chain._process_batches_per_record

    def spy_discard(self, tpu):
        if self.chunks and self.parts is None:
            events.append("discard-ahead")
        return discard(self, tpu)

    def spy_rollback(self, tpu):
        events.append("rollback")
        return rollback(self, tpu)

    def spy_rerun(*a, **k):
        events.append("rerun")
        return rerun(*a, **k)

    monkeypatch.setattr(native_backend, "record_slab", slab)
    monkeypatch.setattr(smart_chain.PendingSlice, "discard", spy_discard)
    monkeypatch.setattr(smart_chain.PendingSlice, "rollback", spy_rollback)
    monkeypatch.setattr(smart_chain, "_process_batches_per_record", spy_rerun)

    async def body(broker):
        calls["armed"] = True       # the log is written: count serves only
        responses = await _drain(broker, int(off[PER_BATCH]) + 64)
        return responses, broker.slice_counts()

    responses, counts = _serve(tmp_path, cfg, flat, off, body)
    assert events[:4] == ["encode-refused", "discard-ahead", "rollback", "rerun"]
    assert counts["fallback_reasons"].get("encode-failed") == 1
    # the running sum is the reference's at every offset: nothing was
    # counted twice, by the rerun or by the slice read again after it
    acc, want = 0, []
    for v in values:
        acc += int(json.loads(v)["n"])
        want.append(str(acc).encode())
    offsets, got = _decoded(responses)
    assert offsets == list(range(N)) and got == want
    assert TELEMETRY.gauge_value("inflight_queue_depth") == 0


# -- a consumer that goes away -----------------------------------------------


@pytest.mark.parametrize("config_name", [STATELESS, STATEFUL],
                         ids=["stateless", "stateful"])
def test_disconnect_with_a_slice_in_flight_leaves_no_handle(
        tmp_path, config_name):
    cfg = _config(config_name)
    flat, off = _corpus(cfg)

    async def body(broker):
        from fluvio_tpu.schema.spu import StreamFetchRequest

        stream = await broker.socket.create_stream(StreamFetchRequest(
            topic="bench", partition=0, fetch_offset=0,
            max_bytes=int(off[PER_BATCH]) + 64,
            smartmodules=invocations(cfg["chain"]),
        ))
        first = await stream.next()     # and no ack: slice 2 stays out
        depth = TELEMETRY.gauge_value("inflight_queue_depth")
        live = TELEMETRY.gauge_value("live_batch_handles")
        client, broker.client = broker.client, None
        await client.close()
        for _ in range(400):
            if not TELEMETRY.gauge_value("inflight_queue_depth"):
                break
            await asyncio.sleep(0.01)
        return (first, depth, live,
                TELEMETRY.gauge_value("inflight_queue_depth"),
                TELEMETRY.gauge_value("live_batch_handles"))

    first, depth, live, depth_after, live_after = _serve(
        tmp_path, cfg, flat, off, body)
    assert first.partition.error_code == 0
    # the second slice went out before the first was sent
    assert depth == 1 and live == 1
    assert depth_after == 0 and live_after == 0


# -- the event loop stays free -----------------------------------------------


def test_another_connection_is_answered_while_a_slice_materializes(
        tmp_path, monkeypatch):
    cfg = _config(FANOUT)
    flat, off = _corpus(cfg)
    entered, release = threading.Event(), threading.Event()
    where = []
    materialize = public_service.tpu_materialize

    def held(*a, **k):
        where.append(threading.current_thread())
        entered.set()
        release.wait(30)
        return materialize(*a, **k)

    monkeypatch.setattr(public_service, "tpu_materialize", held)

    async def body(broker):
        from fluvio_tpu.client import Fluvio

        loop_thread = threading.current_thread()
        serving = asyncio.ensure_future(_drain(broker, int(off[PER_BATCH]) + 64))
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, entered.wait, 30)
            assert entered.is_set() and not release.is_set()
            other = await Fluvio.connect(broker.server.public_addr)
            try:
                consumer = await other.partition_consumer("bench", 0)
                info = await asyncio.wait_for(consumer.fetch_offsets(), 20)
            finally:
                await other.close()
            answered_while_held = not release.is_set() and not serving.done()
        finally:
            release.set()
        responses = await serving
        return info, answered_while_held, loop_thread, responses

    info, answered_while_held, loop_thread, responses = _serve(
        tmp_path, cfg, flat, off, body)
    assert answered_while_held and info.leo == N
    assert where and all(t is not loop_thread for t in where)
    assert len(responses) == N // PER_BATCH


# -- ISSUE-34: a served slice's way out is one native pass ---------------------


def _per_record_slices(broker, cfg, max_bytes, n=N):
    """What the per-record path gives for the same slices, on a stream of
    its own: (next offset, [(base, last delta, count, record bytes)])."""
    chain = smart_chain.acquire_stream_chain(
        invocations(cfg["chain"]), broker.server.ctx
    )
    out, offset = [], 0
    while offset < n:
        rslice = broker.leader.read_records(
            offset, max_bytes, Isolation.READ_UNCOMMITTED
        )
        result = smart_chain.process_batches_per_record(
            chain, rslice.decode_batches(parse_records=False), max_bytes
        )
        out.append((result.next_offset, [
            (b.base_offset, b.header.last_offset_delta, b.records_len(),
             b._encode_record_section())
            for b in result.records.batches
        ]))
        offset = result.next_offset
    return out


@pytest.mark.parametrize("config_name,compact,form", [
    (STATELESS, "auto", "enc-direct-view"),
    (FANOUT, "auto", "enc-direct-view"),
    (STATEFUL, "auto", "enc-direct-int"),
    (STATELESS, "off", "enc-columns"),
], ids=["stateless", "fanout", "stateful", "stateless-dense"])
def test_served_slice_goes_out_in_one_native_pass(
        tmp_path, monkeypatch, config_name, compact, form):
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

    monkeypatch.setenv("FLUVIO_RESULT_COMPACT", compact)
    cfg = _config(config_name)
    flat, off = _corpus(cfg)
    one_batch = int(off[PER_BATCH]) + 64
    armed, hits = {"on": False}, []

    def tripwire(owner, name):
        real = getattr(owner, name)

        def wired(*a, **k):
            if armed["on"]:
                hits.append(name)
            return real(*a, **k)

        plain = isinstance(owner.__dict__[name], staticmethod)
        monkeypatch.setattr(owner, name, staticmethod(wired) if plain else wired)

    tripwire(RecordBuffer, "to_columns")
    tripwire(RecordBuffer, "dense_values")
    tripwire(TpuChainExecutor, "_ints_to_ascii_host")

    async def body(broker):
        lv0 = TELEMETRY.link_variant_counts()
        armed["on"] = True
        try:
            responses = await _drain(broker, one_batch)
        finally:
            armed["on"] = False
        lv = TELEMETRY.link_variant_counts()
        booked = {
            k: lv[k] - lv0.get(k, 0) for k in lv
            if k.startswith("enc-") and lv[k] > lv0.get(k, 0)
        }
        return (responses, booked, broker.slice_counts(),
                _per_record_slices(broker, cfg, one_batch))

    responses, booked, counts, want = _serve(tmp_path, cfg, flat, off, body)
    assert counts["fallback_slices"] == 0
    served = counts["fastpath_slices"]
    assert served == len(responses) == N // PER_BATCH
    # the consumer's bytes are the per-record path's, slice by slice
    assert [
        (r.next_offset, [
            (b.base_offset, b.header.last_offset_delta, b.records_len(),
             bytes(b.raw_records))
            for b in r.batches
        ])
        for r in responses
    ] == want
    # every served slice booked ONE encode form: the direct pass for the
    # forms the cells serve, the general form for a dense buffer
    assert booked == {form: served}
    if form == "enc-columns":
        assert "to_columns" in hits
    else:
        assert hits == []


# -- the view form is booked by view-mode chains and by no other ------------------


def _window_or_group_stream(tmp_path, module_name):
    """A stream of the window (NEXmark Q5) or group (Q17) cell's chain,
    served over several slices by that test file's own harness."""
    import importlib

    mod = importlib.import_module(module_name)
    corpus = mod._corpus()

    async def body(broker):
        s = await mod._Stream(
            broker, mod._chain(), mod._two_batches(corpus),
            end=len(corpus[2]),
        ).open()
        await s.drain()
        return len(s.responses), broker.slice_counts()

    return mod._serve(tmp_path, corpus, body)


@pytest.mark.parametrize("chain", [
    "northstar", "fanout", "aggregate", "window", "group",
])
def test_view_form_is_booked_by_view_chains_only(tmp_path, chain):
    if chain in ("window", "group"):
        served, counts = _window_or_group_stream(
            tmp_path, f"test_stream_{chain}")
    else:
        cfg = _config({"northstar": STATELESS, "fanout": FANOUT,
                       "aggregate": STATEFUL}[chain])
        flat, off = _corpus(cfg)

        async def body(broker):
            responses = await _drain(broker, int(off[PER_BATCH]) + 64)
            return len(responses), broker.slice_counts()

        served, counts = _serve(tmp_path, cfg, flat, off, body)
    assert counts["fallback_slices"] == 0
    assert counts["fastpath_slices"] == served > 1
    booked = TELEMETRY.link_variant_counts().get("enc-direct-view", 0)
    assert booked == (served if chain in ("northstar", "fanout") else 0)
