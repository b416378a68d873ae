"""Device-side result compaction + the glz ENCODE ladder (ISSUE-12).

Four surfaces:

- differential fuzz of the device compressor (both rungs) against the
  host decoders across corpora x chunk sizes, plus wire-format legality
  (the encoder must emit streams the host decoders accept: chunk-local
  non-overlapping matches, u8 run lengths, bounded depth),
- the encode demotion ladder from BOTH seams (sync dispatch, async
  fetch) including sharded, carry-lineage-exact through heal epochs,
- donation safety (fresh staging per dispatch: heal/retry re-dispatches
  never read a donated buffer),
- fetch/compute overlap correctness under injected fetch faults with
  exactly-once carry accounting.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.resilience import faults
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu import glz
from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor
from fluvio_tpu.smartmodule import SmartModuleInput
from fluvio_tpu.telemetry import TELEMETRY


def _pad8(data) -> np.ndarray:
    raw = np.frombuffer(data, np.uint8) if isinstance(data, bytes) else data
    out = np.zeros((len(raw) + 7) & ~7, np.uint8)
    out[: len(raw)] = raw
    return out


def _corpora():
    rng = np.random.default_rng(0)
    return {
        "json": _pad8(b'{"name":"fluvio-7","n":123,"pad":"xyz"}' * 700),
        "periodic5": _pad8(bytes(range(5)) * 4000),
        "const": _pad8(b"x" * 30000),
        "zeros_tail": np.concatenate(
            [rng.integers(0, 256, 1024).astype(np.uint8),
             np.zeros(31744, np.uint8)]
        ),
        "random": rng.integers(0, 256, 16384).astype(np.uint8),
        "tiny": _pad8(b"abcdefgh"),
        "vocab": _pad8(
            np.tile(np.array([1, 0, 7, 0, 6, 0, 250, 199], np.uint8), 3000)
        ),
        # the byte patterns only the deleted up-link compressor was
        # tested on (PR 33): the encoder and both host decoders take them
        "zeros": np.zeros(64 * 1024, np.uint8),
        "run": _pad8(b"ab" * 40000),
        "period28": _pad8(b'{"name":"fluvio-1","n":123}\n' * 3000),
        "mixed": _pad8(np.concatenate([
            np.frombuffer(b'{"name":"kafka-3","n":77}' * 1000, np.uint8),
            rng.integers(0, 256, 8192).astype(np.uint8),
            np.frombuffer(b'{"name":"fluvio-9","n":5}' * 1000, np.uint8),
        ])),
        # wide-record shape: few records of ~30 KB (long runs + a
        # repeated header)
        "wide": _pad8(b"".join(
            (b'{"name":"fluvio-%d","body":"' % (i & 7)) + b"x" * 30000 + b'"}'
            for i in range(8)
        )),
    }


def _encode(raw, chunk):
    f = jax.jit(lambda r: glz.encode_result(r, chunk))
    ll, ml, srcs, lits, n_seq, n_lit, depth = [
        np.asarray(x) for x in f(jnp.asarray(raw))
    ]
    return ll, ml, srcs, lits, int(n_seq), int(n_lit), int(depth)


_CORPUS_NAMES = (
    "json", "periodic5", "const", "zeros_tail", "random", "tiny", "vocab",
    "zeros", "run", "period28", "mixed", "wide",
)


@pytest.mark.parametrize("name", _CORPUS_NAMES)
@pytest.mark.parametrize("chunk", [4096, 16384])
def test_encode_roundtrip_differential(name, chunk):
    """Device compressor vs host decode vs raw, per corpus: the native
    reference decoder AND the numpy device-mirror must both reproduce
    the raw bytes from the device encoder's tokens."""
    raw = _corpora()[name]
    ll, ml, srcs, lits, n_seq, n_lit, depth = _encode(raw, chunk)
    got = glz.decode_result_host(
        ll, ml, srcs, lits, n_seq, n_lit, len(raw), depth
    )
    assert np.array_equal(got, raw), (chunk, name, "host")
    comp = glz.Compressed(
        ll[:n_seq], ml[:n_seq], srcs[:n_seq], lits[:n_lit],
        depth, len(raw),
    )
    got2 = glz.decompress_numpy(comp)
    assert np.array_equal(got2, raw), (chunk, name, "numpy")


def _stream(lit_lens, match_lens, srcs, lits, out_len):
    return glz.Compressed(
        lit_lens=np.array(lit_lens, np.uint8),
        match_lens=np.array(match_lens, np.uint8),
        srcs=np.array(srcs, np.int32),
        lits=np.arange(lits, dtype=np.uint8), depth=1, out_len=out_len,
    )


@pytest.mark.skipif(not glz.available(), reason="native glz library unavailable")
@pytest.mark.parametrize("rc,comp", [
    (1, _stream([12, 8], [0, 0], [0, 0], 20, out_len=16)),
    (2, _stream([12, 8], [0, 0], [0, 0], 16, out_len=20)),
    (3, _stream([12], [8], [8], 12, out_len=20)),
    (4, _stream([12], [4], [0], 12, out_len=20)),
    (5, _stream([12, 0, 0], [0, 0, 8], [-1, 99, 4], 12, out_len=20)),
], ids=["output-overrun", "literal-overrun", "overlapping-source",
        "length-mismatch", "zero-total-sequence"])
def test_native_decoder_fails_closed(rc, comp):
    """What the device sent is validated, not trusted: every return
    code of `glz_decompress` surfaces as a ValueError (interior (0,0)
    sequences are invalid glz: the gather decode's labeling cannot
    represent them)."""
    with pytest.raises(ValueError, match=rf"corrupt glz stream \(rc={rc}\)"):
        glz.decompress_host(comp)


def test_encode_wire_legality():
    """Stream invariants the decoders rely on: sequence lengths fit the
    u8 fields, every match's source region lies strictly before its own
    output AND inside its own chunk, and the reported depth bounds the
    real chain depth (<= MAX_DEPTH)."""
    chunk = 4096
    for name, raw in _corpora().items():
        ll, ml, srcs, lits, n_seq, n_lit, depth = _encode(raw, chunk)
        assert depth <= glz.MAX_DEPTH
        ll, ml, srcs = ll[:n_seq], ml[:n_seq], srcs[:n_seq]
        assert int(ll.astype(np.int64).sum()) == n_lit, name
        assert int((ll.astype(np.int64) + ml).sum()) == len(raw), name
        dst = np.cumsum(ll.astype(np.int64) + ml) - ml
        m = ml > 0
        # matches start at dst (after the literals), read [src, src+ml)
        assert (srcs[m] + ml[m] <= dst[m]).all(), name
        assert (srcs[m] // chunk == dst[m] // chunk).all(), (
            name, "match source crossed its chunk",
        )


def test_encode_compile_size_smoke_gate():
    """CI gate: the encode kernel's jit at the headline shape must
    trace+compile+run in bounded time on the CPU backend (<60 s) — the
    compile-size smoke the decode ladder pins, mirrored."""
    raw = _pad8(b'{"name":"fluvio-1","n":1}' * 40000)  # ~1 MB headline flat
    t0 = time.time()
    ll, ml, srcs, lits, n_seq, n_lit, depth = _encode(raw, glz.GLZ_CHUNK)
    elapsed = time.time() - t0
    assert elapsed < 60, f"encode jit took {elapsed:.1f}s"
    got = glz.decode_result_host(
        ll, ml, srcs, lits, n_seq, n_lit, len(raw), depth
    )
    assert np.array_equal(got, raw)


def test_desc_stream_split_inverse():
    """`_desc_stream` (traced) and `_desc_split` (host) are inverses at
    every field-width tier."""
    for width in (200, 60000, 1 << 20):
        n = 64
        rng = np.random.default_rng(width)
        st = rng.integers(0, width, n).astype(np.int32)
        ln = rng.integers(0, width + 1, n).astype(np.int32)
        desc = np.asarray(
            TpuChainExecutor._desc_stream(
                jnp.asarray(st), jnp.asarray(ln), width
            )
        )
        assert len(desc) % 8 == 0
        st2, ln2 = TpuChainExecutor._desc_split(desc, n, width)
        assert (st2 == st).all() and (ln2 == ln).all(), width


# -- executor integration -----------------------------------------------------


def _chain(backend, *specs, mesh=0):
    b = SmartEngine(backend=backend, mesh_devices=mesh).builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize()


def _records(values, ts=False):
    out = []
    for i, v in enumerate(values):
        r = Record(value=v)
        r.offset_delta = i
        if ts:
            r.timestamp_delta = i * 7
        out.append(r)
    return out


def _run_both(mods, values, mesh=0):
    tc = _chain("tpu", *mods, mesh=mesh)
    pc = _chain("python", *mods)
    assert tc.tpu_chain is not None
    t = tc.process(SmartModuleInput.from_records(_records(values), 0, 100))
    p = pc.process(SmartModuleInput.from_records(_records(values), 0, 100))
    tv = [(r.value, r.key, r.offset_delta) for r in t.successes]
    pv = [(r.value, r.key, r.offset_delta) for r in p.successes]
    assert tv == pv
    return tc, tv


SPAN_MODS = [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})]
FAN_MODS = [("array-map-json", None)]
# aggregate NOT last -> byte-mode output columns (the packed-payload path)
BYTE_MODS = [
    ("aggregate-field", {"field": "n", "combine": "add"}),
    ("regex-filter", {"regex": "[0-9]"}),
]


def _span_corpus(n=4000):
    return [f'{{"name":"fluvio-{i & 511}","n":{i}}}'.encode() for i in range(n)]


def _fan_corpus(n=3000):
    return [f'["a{i & 255}",{i},{i * 3},"x"]'.encode() for i in range(n)]


@pytest.fixture()
def enc_on(monkeypatch):
    monkeypatch.setenv("FLUVIO_RESULT_COMPRESS", "on")


def test_span_chain_ships_tokens(enc_on):
    lv0 = TELEMETRY.link_variant_counts()
    tc, tv = _run_both(SPAN_MODS, _span_corpus())
    assert len(tv) == 4000
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("down-glz-xla", 0) > lv0.get("down-glz-xla", 0)


def test_fanout_chain_ships_tokens(enc_on):
    lv0 = TELEMETRY.link_variant_counts()
    tc, tv = _run_both(FAN_MODS, _fan_corpus())
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("down-glz-xla", 0) > lv0.get("down-glz-xla", 0)


def test_byte_mode_packed_payload_differential(enc_on):
    """Byte-mode chains (aggregate mid-chain) ship ONE packed payload;
    outputs stay byte-equal to the interpreter and the result buffer is
    flat-backed (padded output matrix never built)."""
    vals = _span_corpus(2000)
    tc = _chain("tpu", *BYTE_MODS)
    pc = _chain("python", *BYTE_MODS)
    t = tc.process(SmartModuleInput.from_records(_records(vals), 0, 100))
    p = pc.process(SmartModuleInput.from_records(_records(vals), 0, 100))
    assert [(r.value, r.key) for r in t.successes] == [
        (r.value, r.key) for r in p.successes
    ]


def test_byte_mode_flat_backed_output(enc_on):
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

    vals = _span_corpus(2000)
    tc = _chain("tpu", *BYTE_MODS)
    ex = tc.tpu_chain
    buf = RecordBuffer.from_records(_records(vals), 0, 100)
    out = ex.process_buffer(buf)
    assert out.values is None, "compacted byte-mode output must be flat-backed"
    # to_columns consumes the flat directly and matches the dense form
    cols = out.to_columns()
    dense = out.dense_values()
    n = out.count
    mask = (
        np.arange(dense.shape[1], dtype=np.int32)[None, :]
        < out.lengths[:n, None]
    )
    assert np.array_equal(cols["val_flat"], dense[:n][mask])


def test_result_compact_off_parity(monkeypatch):
    """FLUVIO_RESULT_COMPACT=off restores the dense paths bit-for-bit."""
    monkeypatch.setenv("FLUVIO_RESULT_COMPACT", "off")
    tc, tv = _run_both(SPAN_MODS, _span_corpus(1000))
    assert tc.tpu_chain._result_compact is False
    assert tc.tpu_chain._enc_variant == "off"  # compress requires compact


# -- demotion ladder ----------------------------------------------------------


def test_dispatch_seam_runtime_fault_latches_off(enc_on, monkeypatch):
    """A sync RUNTIME failure of the encoder latches encode off; the
    same staged arrays re-dispatch and outputs stay exact."""
    calls = {"n": 0}

    def bomb(*a, **k):
        calls["n"] += 1
        raise RuntimeError("simulated device encode runtime failure")

    monkeypatch.setattr(glz, "enc_match_xla", bomb)
    heals0 = TELEMETRY.heals
    tc, tv = _run_both(SPAN_MODS, _span_corpus(1000))
    assert calls["n"] >= 1
    assert tc.tpu_chain._enc_variant == "off"
    assert TELEMETRY.heals > heals0


@pytest.mark.parametrize("mesh", [0, 4], ids=["single", "sharded"])
@pytest.mark.parametrize(
    "exc",
    [
        NotImplementedError("Unimplemented primitive in lowering"),
        TypeError("lowering rejected the operand type"),
    ],
    ids=["notimplemented", "typeerror"],
)
def test_dispatch_seam_lowering_error_propagates(enc_on, monkeypatch, mesh, exc):
    """A LOWERING error of the encoder is a program fault (ISSUE 22):
    it raises through `process()` under backend="tpu" — no heal, no
    rung demoted, no interpreter re-run — single-device and sharded."""
    if mesh and len(jax.devices()) < mesh:
        pytest.skip(f"needs {mesh} devices")

    def refuse(*a, **k):
        raise exc

    monkeypatch.setattr(glz, "enc_match_xla", refuse)
    tc = _chain("tpu", *SPAN_MODS, mesh=mesh)
    assert (tc.tpu_chain._sharded is not None) == bool(mesh)
    heals0 = TELEMETRY.heals
    spills0 = dict(TELEMETRY.snapshot()["counters"]["spills"])
    with pytest.raises(type(exc)):
        tc.process(
            SmartModuleInput.from_records(_records(_span_corpus(1000)), 0, 100)
        )
    assert tc.tpu_chain._enc_variant == "xla", "a program fault demotes nothing"
    assert TELEMETRY.heals == heals0
    assert TELEMETRY.snapshot()["counters"]["spills"] == spills0


def test_dispatch_seam_injected_fault_demotes(enc_on, monkeypatch):
    """The armed glz_encode fault point takes the sync demotion path a
    real trace failure would (deterministic-class)."""
    monkeypatch.setenv(
        "FLUVIO_FAULTS", "glz_encode:first=1,exc=deterministic"
    )
    faults._load_from_env()
    try:
        heals0 = TELEMETRY.heals
        tc, tv = _run_both(SPAN_MODS, _span_corpus(1000))
        assert TELEMETRY.heals > heals0
        assert tc.tpu_chain._enc_variant == "off"  # xla rung demoted off
    finally:
        faults.FAULTS.clear()


def test_fetch_seam_host_decode_failure_falls_back_raw(enc_on, monkeypatch):
    """A corrupt token stream surfaces at the HOST decode: one rung
    down, the raw descriptor columns (still in packed) ship instead —
    no re-dispatch, outputs exact."""
    real = glz.decode_result_host
    state = {"bombed": 0}

    def bomb(*a, **k):
        state["bombed"] += 1
        raise ValueError("corrupt glz stream (rc=2)")

    monkeypatch.setattr(glz, "decode_result_host", bomb)
    heals0 = TELEMETRY.heals
    tc, tv = _run_both(SPAN_MODS, _span_corpus(1000))
    assert state["bombed"] == 1
    assert TELEMETRY.heals > heals0
    assert tc.tpu_chain._enc_variant == "off"
    monkeypatch.setattr(glz, "decode_result_host", real)


def test_fetch_seam_runtime_failure_heals_with_carry_lineage(
    enc_on, monkeypatch
):
    """Async (device runtime) failures of encode-armed AGGREGATE batches
    heal through the shared re-dispatch: carries roll back to the
    handle snapshot, results never double-count."""
    real_fetch = TpuChainExecutor._fetch
    state = {"bombed": False}

    def fetch_bomb(self, buf, header, packed, spec=None, defer=False):
        if spec and spec.get("enc_used") and not state["bombed"]:
            state["bombed"] = True
            raise RuntimeError("simulated device runtime failure")
        return real_fetch(self, buf, header, packed, spec, defer)

    monkeypatch.setattr(TpuChainExecutor, "_fetch", fetch_bomb)
    # byte-mode chain with an aggregate carry: encode armed AND carries
    tc = _chain("tpu", *BYTE_MODS)
    pc = _chain("python", *BYTE_MODS)
    for lo in (0, 1000):
        vals = _span_corpus(2000)[lo : lo + 1000]
        t = tc.process(SmartModuleInput.from_records(_records(vals), 0, 100))
        p = pc.process(SmartModuleInput.from_records(_records(vals), 0, 100))
        assert [(r.value, r.key) for r in t.successes] == [
            (r.value, r.key) for r in p.successes
        ]
    assert state["bombed"], "the fetch bomb should have fired"


def test_sharded_encode_and_fetch_demotion(enc_on, monkeypatch):
    """Sharded: per-shard tokens engage under shard_map; a sharded host
    decode failure demotes one rung and the batch still materializes
    exactly (the raw columns re-fetch)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    lv0 = TELEMETRY.link_variant_counts()
    tc, tv = _run_both(SPAN_MODS, _span_corpus(8000), mesh=8)
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("down-glz-xla", 0) > lv0.get("down-glz-xla", 0)

    real = glz.decode_result_host
    state = {"bombed": 0}

    def bomb(*a, **k):
        state["bombed"] += 1
        raise ValueError("corrupt glz stream (rc=2)")

    monkeypatch.setattr(glz, "decode_result_host", bomb)
    heals0 = TELEMETRY.heals
    tc2, tv2 = _run_both(SPAN_MODS, _span_corpus(8000), mesh=8)
    assert state["bombed"] == 1
    assert TELEMETRY.heals > heals0
    monkeypatch.setattr(glz, "decode_result_host", real)


# -- donation -----------------------------------------------------------------


def test_donation_safety_with_heal_redispatch(monkeypatch):
    """FLUVIO_DONATE=on: every dispatch stages fresh device arrays, so
    the encode heal's re-dispatch after a fetch-time failure cannot read
    a donated buffer (no use-after-donate), and outputs stay exact."""
    monkeypatch.setenv("FLUVIO_DONATE", "on")
    monkeypatch.setenv("FLUVIO_RESULT_COMPRESS", "on")
    real_fetch = TpuChainExecutor._fetch
    state = {"bombed": False}

    def fetch_bomb(self, buf, header, packed, spec=None, defer=False):
        if spec and spec.get("enc_used") and not state["bombed"]:
            state["bombed"] = True
            raise RuntimeError("simulated device runtime failure")
        return real_fetch(self, buf, header, packed, spec, defer)

    monkeypatch.setattr(TpuChainExecutor, "_fetch", fetch_bomb)
    heals0 = TELEMETRY.heals
    tc, tv = _run_both(SPAN_MODS, _span_corpus(6000))
    assert state["bombed"]
    assert TELEMETRY.heals > heals0
    assert len(tv) == 6000


def test_donation_stream_reuses_buffer_safely(monkeypatch):
    """The bench/stream pattern re-dispatches ONE RecordBuffer many
    times; with donation on, each dispatch's fresh `jnp.asarray` staging
    keeps that sound."""
    monkeypatch.setenv("FLUVIO_DONATE", "on")
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

    tc = _chain("tpu", *SPAN_MODS)
    ex = tc.tpu_chain
    buf = RecordBuffer.from_records(_records(_span_corpus(512)), 0, 100)
    outs = list(ex.process_stream(iter([buf] * 4)))
    assert len(outs) == 4
    first = [r.value for r in outs[0].to_records()]
    for o in outs[1:]:
        assert [r.value for r in o.to_records()] == first


# -- fetch/compute overlap ----------------------------------------------------


def test_overlap_stream_order_and_equality(monkeypatch):
    """FLUVIO_FETCH_OVERLAP=on: the pipelined stream yields the same
    buffers in the same order as the serialized path."""
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

    vals = _span_corpus(3000)
    bufs = [
        RecordBuffer.from_records(_records(vals[lo : lo + 750]), 0, 100)
        for lo in range(0, 3000, 750)
    ]
    monkeypatch.setenv("FLUVIO_FETCH_OVERLAP", "on")
    tc = _chain("tpu", *SPAN_MODS)
    got = [
        [r.value for r in o.to_records()]
        for o in tc.tpu_chain.process_stream(iter(bufs))
    ]
    monkeypatch.setenv("FLUVIO_FETCH_OVERLAP", "off")
    tc2 = _chain("tpu", *SPAN_MODS)
    want = [
        [r.value for r in o.to_records()]
        for o in tc2.tpu_chain.process_stream(iter(bufs))
    ]
    assert got == want


def test_overlap_fetch_fault_stateless_exactly_once(monkeypatch):
    """Overlapped stateless stream under an injected transient fetch
    fault: the bounded retry re-runs the batch inside its finish and
    every batch still yields exactly once with exact bytes."""
    monkeypatch.setenv("FLUVIO_FETCH_OVERLAP", "on")
    monkeypatch.setenv("FLUVIO_FAULTS", "fetch:first=1")
    faults._load_from_env()
    try:
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

        vals = _span_corpus(3000)
        bufs = [
            RecordBuffer.from_records(_records(vals[lo : lo + 750]), 0, 100)
            for lo in range(0, 3000, 750)
        ]
        tc = _chain("tpu", *SPAN_MODS)
        outs = list(tc.tpu_chain.process_stream(iter(bufs)))
        assert [o.count for o in outs] == [750] * 4
        pc = _chain("python", *SPAN_MODS)
        p = pc.process(
            SmartModuleInput.from_records(_records(vals[:750]), 0, 100)
        )
        assert [r.value for r in outs[0].to_records()] == [
            r.value for r in p.successes
        ]
    finally:
        faults.FAULTS.clear()


def test_overlap_fetch_fault_aggregate_exactly_once(monkeypatch):
    """Overlapped AGGREGATE stream under a transient fetch fault: the
    retried batch's heal bumps the carry-lineage epoch, so the already-
    in-flight next batch spills (`heal-lineage`) — and the device
    accumulator must then hold EXACTLY the retried batch's contribution
    (counted once, with the invalidated in-flight dispatch rolled back
    to the healed tip)."""
    monkeypatch.setenv("FLUVIO_FETCH_OVERLAP", "on")
    monkeypatch.setenv("FLUVIO_FAULTS", "fetch:first=1")
    faults._load_from_env()
    try:
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
        from fluvio_tpu.smartengine.tpu.executor import TpuSpill

        vals = [str(100 + i).encode() for i in range(4000)]
        bufs = [
            RecordBuffer.from_records(_records(vals[lo : lo + 1000]), 0, 100)
            for lo in range(0, 4000, 1000)
        ]
        tc = _chain("tpu", ("aggregate-sum", None))
        ex = tc.tpu_chain
        spilled = False
        try:
            for _ in ex.process_stream(iter(bufs)):
                pass
        except TpuSpill as e:
            spilled = True
            assert e.reason == "heal-lineage"
        ex._ensure_host_state()
        s1 = sum(100 + i for i in range(1000))
        if spilled:
            # exactly-once: batch 1 (faulted, retried, healed) counted
            # ONCE; the invalidated in-flight batch contributed nothing
            assert ex.carries[0][0] == s1
        else:  # timing let every batch finish: the full sum, once each
            assert ex.carries[0][0] == sum(100 + i for i in range(4000))
    finally:
        faults.FAULTS.clear()


def test_overlap_off_is_zero_cost(monkeypatch):
    """With overlap off, the fetch worker pool must never be touched."""
    monkeypatch.setenv("FLUVIO_FETCH_OVERLAP", "off")
    from fluvio_tpu.smartengine.tpu import executor as ex_mod
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

    def tripwire(*a, **k):
        raise AssertionError("fetch pool touched with overlap off")

    monkeypatch.setattr(ex_mod, "_fetch_mat_pool", tripwire)
    tc = _chain("tpu", *SPAN_MODS)
    buf = RecordBuffer.from_records(_records(_span_corpus(256)), 0, 100)
    outs = list(tc.tpu_chain.process_stream(iter([buf] * 2)))
    assert len(outs) == 2
