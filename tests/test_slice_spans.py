"""ISSUE-26: one span tree per served slice, and device scopes.

CPU, counts and structure only (a time read here says nothing about the
chip):

- a served two-slice stream through a real `SpuServer`, for a stateless
  and for a fan-out chain (one stream loop serves both since ISSUE-31),
  yields slice flows whose phases include the served path's nine names,
  in wall order and never overlapping (the loop's passes run on worker
  threads, one after the other), and together covering the stream's
  span; slice 2 is dispatched after slice 1's `finish` and before its
  `materialize` ends; every
  `BatchSpan` of the stream names a flow of the ring; a span's `wait`
  fits inside the span,
- the compiled text of each chain program (narrow, striped, sharded)
  carries every device scope the chain uses in its op metadata.
"""

from __future__ import annotations

import asyncio
import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from spubench import manifest  # noqa: E402
from spubench.broker import Broker, encode_batches  # noqa: E402

from fluvio_tpu.models import lookup  # noqa: E402
from fluvio_tpu.protocol.record import Record  # noqa: E402
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig  # noqa: E402
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer  # noqa: E402
from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402
from fluvio_tpu.telemetry import memory as memory_mod  # noqa: E402
from fluvio_tpu.telemetry import render_trace  # noqa: E402
from fluvio_tpu.telemetry.spans import (  # noqa: E402
    DEVICE_SCOPES,
    DEVICE_SCOPES_TAG,
    PHASES,
)

SERVED_PHASES = (
    "read", "wire_decode", "stage", "dispatch", "finish", "materialize",
    "encode", "send", "ack_wait",
)
PER_BATCH = 2048


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


# -- a served stream ---------------------------------------------------------


def _serve_two_slices(tmp_path, config_name: str):
    """Write two stored batches, read them back through a stream fetch
    whose ``max_bytes`` holds one batch a slice; returns the responses."""
    cfg = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    gen = manifest.load_plugin(BENCH, "corpora", cfg["corpus"]["generator"])
    flat, off = gen.generate(2 * PER_BATCH, [7, 0])
    one_batch = int(off[PER_BATCH])

    async def run():
        broker = Broker(cfg, str(tmp_path / "log"))
        await broker.start()
        try:
            for b in encode_batches(flat, off, 0, 2 * PER_BATCH, PER_BATCH):
                await broker.write([b])
            out = []
            async with broker.stream(0, one_batch) as stream:
                cur = 0
                while cur < 2 * PER_BATCH:
                    r = await stream.next()
                    out.append(r)
                    cur = r.next_offset
            # the handler closes a flow after its ack wait: let the last
            # one land before the server stops
            for _ in range(200):
                if len(TELEMETRY.flows) >= len(out):
                    break
                await asyncio.sleep(0.01)
            return out, broker.slice_counts()
        finally:
            await broker.stop()

    return asyncio.run(run())


@pytest.mark.parametrize("config_name", [
    "fluvio-northstar-1p", "fluvio-array-explode-1p",
], ids=["stateless-chain", "fanout-chain"])
def test_served_stream_yields_one_span_tree_per_slice(tmp_path, config_name):
    responses, counts = _serve_two_slices(tmp_path, config_name)
    assert len(responses) == 2
    assert counts["fastpath_slices"] >= 2 and counts["fallback_slices"] == 0

    flows = [f for f in TELEMETRY.flows.recent() if f.records > 0]
    assert len(flows) == 2
    every = []
    for f in flows:
        names = [name for name, _s, _d in f.phases]
        # all nine, each once, in the order the served path runs them
        assert [n for n in names if n in SERVED_PHASES] == list(SERVED_PHASES)
        starts = [s for _n, s, _d in f.phases]
        assert starts == sorted(starts)
        assert f.t0 <= starts[0] and f.t_end is not None
        assert f.phases[-1][0] == "ack_wait"
        # the flow closed after its ack wait, not at the push
        assert f.t_end >= f.phases[-1][1] + f.phases[-1][2]
        doc = f.to_dict()
        assert [p[0] for p in doc["phases"]] == names
        assert set(doc["phases_ms"]) == set(names)
        every += [(s, s + d, n, f.flow_id) for n, s, d in f.phases]
    # one pass at a time: no phase of any flow overlaps another
    every.sort()
    for (a0, a1, an, af), (b0, _b1, bn, bf) in zip(every, every[1:]):
        assert b0 >= a1 - 1e-6, ((an, af), (bn, bf))
    # and together they cover the stream's span on that task
    t0 = min(f.t0 for f in flows)
    t1 = max(f.t_end for f in flows)
    assert sum(e - s for s, e, _n, _f in every) >= 0.90 * (t1 - t0)
    # slice 2 went out after slice 1's blocking half and before its
    # host half was over: the device works under materialize and encode
    d2 = next((s, e) for s, e, n, fid in every
              if n == "dispatch" and fid == flows[1].flow_id)
    f1 = next(e for _s, e, n, fid in every
              if n == "finish" and fid == flows[0].flow_id)
    m1 = next(e for _s, e, n, fid in every
              if n == "materialize" and fid == flows[0].flow_id)
    assert f1 <= d2[0] and d2[1] <= m1
    assert [f.interleaved for f in flows] == [True, False]
    assert [f.to_dict()["interleaved"] for f in flows] == [True, False]

    # every chunk names its slice; `wait` is exclusive and inside the span
    ids = {f.flow_id for f in TELEMETRY.flows.recent()}
    spans = [s for s in TELEMETRY.spans.recent() if s.records == PER_BATCH]
    assert len(spans) >= 2
    assert {s.flow_id for s in spans} == {f.flow_id for f in flows} <= ids
    for s in spans:
        assert 0.0 < s.phase("wait") <= s.t_end - s.t0
        assert s.to_dict()["flow_id"] == s.flow_id
        assert "wait" in s.to_dict()["phases_ms"]
    assert PHASES[-1] == "wait"

    # the trace renders the phases on the slice lane and joins by id
    doc = render_trace()
    lanes = [e for e in doc["traceEvents"] if e.get("cat") == "slice-phase"]
    assert {e["name"] for e in lanes} >= set(SERVED_PHASES)
    for f in flows:
        steps = [e for e in doc["traceEvents"]
                 if e.get("cat") == "flow" and e["id"] == f.flow_id
                 and e["ph"] == "t"]
        assert len(steps) == sum(s.flow_id == f.flow_id
                                 for s in TELEMETRY.spans.recent())


def test_declined_slice_books_interpret(tmp_path):
    """A slice the fast path declines is one `interpret` phase, not a
    hole in its flow."""
    from fluvio_tpu.spu import smart_chain

    class _Chain:
        tpu_chain = None

        def process(self, inp, metrics):
            from fluvio_tpu.smartmodule.types import SmartModuleOutput

            return SmartModuleOutput(successes=list(inp.records()))

    flow = TELEMETRY.begin_flow("c@t/0")
    result = smart_chain.process_batches(_Chain(), [], 1 << 20, flow=flow)
    TELEMETRY.end_flow(flow, records=0)
    assert result.records.total_records() == 0
    assert [n for n, _s, _d in flow.phases] == ["interpret"]


# -- device scopes -----------------------------------------------------------


def _chain(*specs):
    b = SmartEngine(backend="tpu").builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    chain = b.initialize()
    assert chain.backend_in_use == "tpu"
    return chain.tpu_chain


def _buf(values):
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
    return RecordBuffer.from_records(records)


def _json(n, pad=0):
    return [
        f'{{"name":"{("fluvio", "kafka", "pulsar")[i % 3]}-{i & 63}",'
        f'"n":{i},"pad":"{"x" * pad}"}}'.encode()
        for i in range(n)
    ]


def _scopes_in(hlo: str) -> set:
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        for part in op_name.split("/"):
            if part in DEVICE_SCOPES or re.fullmatch(r"stage\d+\.\w+", part):
                found.add(part)
    return found


def _compiled_text(ex, attr: str, buf) -> str:
    """Run ``buf`` through the executor with a spy on its jit entry
    ``attr`` and return the compiled text (op metadata included) of the
    program it called."""
    seen = {}
    wrapped = getattr(ex, attr)

    def spy(*args, **kwargs):
        seen["call"] = (args, kwargs)
        return wrapped(*args, **kwargs)

    setattr(ex, attr, spy)
    try:
        out = ex.process_buffer(buf)
    finally:
        setattr(ex, attr, wrapped)
    assert out.count > 0 and "call" in seen
    args, kwargs = seen["call"]
    return wrapped.__wrapped__.lower(*args, **kwargs).compile().as_text()


@pytest.fixture
def both_links(monkeypatch):
    """Raw flat up and result encode down, as on the chip."""
    monkeypatch.setenv("FLUVIO_RESULT_COMPRESS", "on")
    monkeypatch.setenv("FLUVIO_RESULT_COMPACT", "on")


def test_north_star_program_carries_every_scope(both_links):
    ex = _chain(("regex-filter", {"regex": "fluvio"}),
                ("json-map", {"field": "name"}))
    hlo = _compiled_text(ex, "_jit_ragged", _buf(_json(4096)))
    found = _scopes_in(hlo)
    assert found >= {"repad", "stage0.filter", "stage1.map",
                     "compact", "pack", "link_encode"}, found
    assert "link_decode" not in found
    # the program's NAME carries the vocabulary's version: the compile
    # cache keys on it, never on a scope (debug info is stripped there)
    assert f"_chain_fn_ragged_{DEVICE_SCOPES_TAG}" in hlo.splitlines()[0]


def test_fanout_program_carries_every_scope(both_links):
    ex = _chain(("array-map-json", None))
    values = [f'["a{i & 255}","b{i}",{i},"x","y"]'.encode() for i in range(4096)]
    found = _scopes_in(_compiled_text(ex, "_jit_ragged", _buf(values)))
    assert found >= {"repad", "stage0.array_map", "compact",
                     "pack", "link_encode"}, found


def test_byte_mode_program_packs_its_payload(both_links):
    """A chain whose outputs are new bytes (a running sum rendered in
    ASCII, then filtered) ships the packed payload: `pack` is
    `_packed_payload` here, not the descriptor stream."""
    ex = _chain(("aggregate-sum", None), ("regex-filter", {"regex": "1"}))
    assert not ex._viewable and not ex._int_output
    values = [str(100 + i).encode() for i in range(1024)]
    found = _scopes_in(_compiled_text(ex, "_jit_ragged", _buf(values)))
    assert found >= {"repad", "stage0.aggregate", "stage1.filter", "compact",
                     "pack", "link_encode"}, found


@pytest.mark.parametrize("config_name", [
    "fluvio-northstar-1p", "fluvio-array-explode-1p",
], ids=["pipelined-loop", "serial-loop"])
def test_served_slice_ships_its_flat_raw(tmp_path, monkeypatch, config_name):
    """The program a served slice runs takes its flat raw: the staged
    i32 words are its first operand, no `link_decode` scope is in it,
    and the up-link books no link variant (it has one form)."""
    from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor

    calls = []
    init = TpuChainExecutor.__init__

    def spying_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        jit = self._jit_ragged

        def spy(*a, **k):
            calls.append((jit, a, k))
            return jit(*a, **k)

        self._jit_ragged = spy

    monkeypatch.setattr(TpuChainExecutor, "__init__", spying_init)
    lv0 = TELEMETRY.link_variant_counts()
    responses, counts = _serve_two_slices(tmp_path, config_name)
    assert len(responses) == 2
    assert counts["fastpath_slices"] >= 2 and counts["fallback_slices"] == 0
    lv = TELEMETRY.link_variant_counts()
    # the family counts the down-link's forms and the encode's: no up-link
    grown = {k for k in lv if lv[k] > lv0.get(k, 0)}
    assert grown and all(
        k.startswith(("down-", "agg-", "enc-")) for k in grown
    ), lv

    served = [c for c in calls if c[1][1].shape[0] >= PER_BATCH]
    assert served
    jit, args, kwargs = served[-1]
    assert args[0].dtype == np.int32 and args[0].ndim == 1
    assert len(args) == 9 and set(kwargs) == {
        "width", "kwidth", "has_keys", "has_offsets", "ts_mode",
        "fanout_cap", "enc", "pack",
    }
    found = _scopes_in(jit.__wrapped__.lower(*args, **kwargs).compile().as_text())
    assert "repad" in found and "link_decode" not in found, found


def test_striped_program_carries_its_scopes(monkeypatch):
    for k, v in (("THRESHOLD", "64"), ("WIDTH", "64"), ("OVERLAP", "16")):
        monkeypatch.setenv(f"FLUVIO_STRIPE_{k}", v)
    ex = _chain(("regex-filter", {"regex": "flu[vV]io"}))
    buf = _buf(_json(256, pad=200))
    assert ex._striped_chain() is not None and ex._needs_stripes(buf)
    found = _scopes_in(_compiled_text(ex, "_jit_striped", buf))
    assert found >= {"repad", "stage0.filter", "compact"}, found


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_program_carries_its_scopes():
    ex = _chain(("regex-filter", {"regex": "fluvio"}),
                ("json-map", {"field": "name"}))
    ex.enable_sharded(8)
    sh = ex._sharded
    seen = {}
    jitted = sh._jitted

    def spy(uploads, cfg):
        fn = jitted(uploads, cfg)

        def call(*args, **kwargs):
            seen["call"] = (fn, args, kwargs)
            return fn(*args, **kwargs)

        return call

    sh._jitted = spy
    out = ex.process_buffer(_buf(_json(1024)))
    assert out.count > 0
    fn, args, kwargs = seen["call"]
    hlo = fn.__wrapped__.lower(*args, **kwargs).compile().as_text()
    found = _scopes_in(hlo)
    assert found >= {"repad", "stage0.filter", "stage1.map", "compact"}, found


def test_scope_vocabulary_is_fixed():
    assert DEVICE_SCOPES == (
        "link_decode", "repad", "stage", "compact", "pack", "link_encode",
    )
    assert np.all([" " not in s and "/" not in s for s in DEVICE_SCOPES])
