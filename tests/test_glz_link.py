"""glz link compression: device decode + compressed staging (ISSUE-8).

Differential contract: THREE decoders must agree byte-for-byte on every
corpus — the native sequential oracle (glz.cpp), the numpy mirror of
the gather rounds, and the traced gather-round device decode —
including chunked streams, padded token arrays, striped wide records,
sharded staging, and the heal/retry interleavings that latch
compression off mid-stream. Runtime faults heal; lowering/compile
errors are program faults and propagate (ISSUE 22).
"""

import os
import time

import numpy as np
import pytest

from fluvio_tpu.smartengine.tpu import glz

pytestmark = pytest.mark.skipif(
    not glz.available(), reason="native glz library unavailable"
)


def _json_corpus(n, seed=2024):
    rng = np.random.default_rng(seed)
    names = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "redpanda", "flink"]
    vals = [
        f'{{"name":"{names[rng.integers(0, 6)]}-{i & 255}",'
        f'"n":{rng.integers(0, 100000)}}}'.encode()
        for i in range(n)
    ]
    return np.frombuffer(b"".join(vals), dtype=np.uint8).copy()


CORPORA = {
    "json": lambda: _json_corpus(6000),
    "zeros": lambda: np.zeros(96 * 1024, np.uint8),
    "period28": lambda: np.frombuffer(
        b'{"name":"fluvio-1","n":123}\n' * 5000, np.uint8
    ).copy(),
    "mixed": lambda: np.concatenate(
        [
            _json_corpus(2000),
            np.random.default_rng(3).integers(0, 256, 8192).astype(np.uint8),
            _json_corpus(2000, seed=5),
        ]
    ),
    # wide-record shape: few records, each ~30 KB (the striped regime's
    # byte layout — long runs + a repeated header)
    "wide": lambda: np.frombuffer(
        b"".join(
            (b'{"name":"fluvio-%d","body":"' % (i & 7))
            + b"x" * 30000
            + b'"}'
            for i in range(8)
        ),
        np.uint8,
    ).copy(),
}


def _gather_decode(comp, seq_extra=0, lit_extra=0):
    """Decode via the traced gather rounds, optionally with zero-padded
    token arrays (the executor's bucketed staging form)."""
    import jax.numpy as jnp

    ns = len(comp.lit_lens)
    ll = np.zeros(ns + seq_extra, np.uint8)
    ll[:ns] = comp.lit_lens
    ml = np.zeros(ns + seq_extra, np.uint8)
    ml[:ns] = comp.match_lens
    srcs = np.zeros(ns + seq_extra, np.int32)
    srcs[:ns] = comp.srcs
    lits = np.zeros(comp.lits.size + lit_extra, np.uint8)
    lits[: comp.lits.size] = comp.lits
    return np.asarray(
        glz.decompress_device(
            jnp.asarray(ll), jnp.asarray(ml), jnp.asarray(srcs),
            jnp.asarray(lits), jnp.int32(comp.depth), comp.out_len,
        )
    )


@pytest.mark.parametrize("name", sorted(CORPORA))
@pytest.mark.parametrize("chunk", [16 * 1024, 64 * 1024])
def test_three_decoder_differential(name, chunk):
    raw = CORPORA[name]()
    comp, reason = glz.compress_link(raw, max_ratio=1.0, chunk=chunk)
    assert comp is not None, f"{name}: {reason}"
    assert comp.depth <= glz.MAX_DEPTH
    assert comp.chunk_bytes == chunk
    assert np.array_equal(glz.decompress_host(comp), raw), "host oracle"
    assert np.array_equal(glz.decompress_numpy(comp), raw), "numpy mirror"
    assert np.array_equal(_gather_decode(comp), raw), "gather rounds"
    # the executor's padded-token staging form must decode identically
    assert np.array_equal(
        _gather_decode(comp, seq_extra=37, lit_extra=11), raw
    ), "gather w/ padded tokens"


def test_chunk_locality_invariant():
    """Every match source stays inside its own chunk — the wire
    format's chunk-locality invariant."""
    raw = CORPORA["json"]()
    comp, _ = glz.compress_link(raw, max_ratio=1.0, chunk=16 * 1024)
    cs = comp.chunk_seqs
    assert cs is not None and cs[-1] == len(comp.lit_lens)
    for c in range(len(cs) - 1):
        lo, hi = int(cs[c]), int(cs[c + 1])
        live = comp.match_lens[lo:hi] > 0
        assert (comp.srcs[lo:hi][live] >= c * comp.chunk_bytes).all(), c
        assert (
            comp.srcs[lo:hi][live] < (c + 1) * comp.chunk_bytes
        ).all(), c


def test_deep_match_chains_at_max_depth():
    """A corpus whose greedy parse chains matches to the depth cap —
    the pathological case the gather rounds must still cover."""
    raw = _json_corpus(9000)
    comp, _ = glz.compress_link(raw, max_ratio=1.0, chunk=64 * 1024)
    assert comp.depth == glz.MAX_DEPTH, comp.depth
    assert np.array_equal(_gather_decode(comp), raw)


def test_compress_link_decline_reasons():
    assert glz.compress_link(np.zeros(64, np.uint8)) == (
        None, glz.DECLINE_BELOW_MIN
    )
    rng = np.random.default_rng(11)
    noise = rng.integers(0, 256, 128 * 1024).astype(np.uint8)
    comp, reason = glz.compress_link(noise)
    assert comp is None and reason == glz.DECLINE_RATIO
    comp, reason = glz.compress_link(_json_corpus(4000))
    assert comp is not None and reason is None


def test_merged_stream_valid_for_legacy_decoders():
    """A chunked stream is a plain glz stream (absolute sources): the
    whole-buffer decoders need no sidecar."""
    raw = CORPORA["period28"]()
    comp, _ = glz.compress_link(raw, max_ratio=1.0, chunk=16 * 1024)
    legacy = glz.Compressed(
        lit_lens=comp.lit_lens, match_lens=comp.match_lens,
        srcs=comp.srcs, lits=comp.lits, depth=comp.depth,
        out_len=comp.out_len,
    )
    assert np.array_equal(glz.decompress_host(legacy), raw)
    assert np.array_equal(glz.decompress_numpy(legacy), raw)


# ---------------------------------------------------------------------------
# Executor-level: compressed staging
# ---------------------------------------------------------------------------


def _build(backend, specs, mesh=None):
    from fluvio_tpu.models import lookup
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig

    eng = (
        SmartEngine(backend=backend, mesh_devices=mesh)
        if mesh
        else SmartEngine(backend=backend)
    )
    b = eng.builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize()


def _run_chain(chain, vals, ts=None):
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    records = [Record(value=v) for v in vals]
    for i, r in enumerate(records):
        r.offset_delta = i
        if ts is not None:
            r.timestamp_delta = int(ts[i])
    out = chain.process(SmartModuleInput.from_records(records, 0, 1_000_000))
    assert out.error is None, out.error
    return [(r.value, r.key, r.offset_delta) for r in out.successes]


def _json_vals(n=6000, seed=7):
    rng = np.random.default_rng(seed)
    names = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "redpanda", "flink"]
    return [
        f'{{"name":"{names[rng.integers(0, 6)]}-{i & 255}",'
        f'"n":{rng.integers(0, 100000)}}}'.encode()
        for i in range(n)
    ]


@pytest.fixture
def glz_env(monkeypatch):
    monkeypatch.setenv("FLUVIO_LINK_COMPRESS", "on")


@pytest.mark.parametrize(
    "specs",
    [
        [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})],
        [("aggregate-field", {"field": "n", "combine": "add"})],
        [("array-map-json", None)],
    ],
    ids=["filter_map", "aggregate", "array_map"],
)
def test_executor_compressed_staging_parity(glz_env, specs):
    from fluvio_tpu.telemetry import TELEMETRY

    if specs[0][0] == "array-map-json":
        vals = [
            f'["a{i & 31}","b{i % 997}",{i},"x"]'.encode() for i in range(6000)
        ]
    else:
        vals = _json_vals()
    lv0 = TELEMETRY.link_variant_counts()
    chain = _build("tpu", specs)
    got = _run_chain(chain, vals)
    ex = chain.tpu_chain
    assert ex._link_compress
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("glz-gather", 0) > lv0.get("glz-gather", 0), (
        "the compressed form should have shipped this batch"
    )
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


def test_striped_wide_records_ship_compressed(glz_env, monkeypatch):
    """The wide-record (striped) layout crosses the link compressed and
    re-stripes entirely on device — the wide300/fat70k class."""
    monkeypatch.setenv("FLUVIO_STRIPE_THRESHOLD", "16384")
    body = "x" * 30000
    vals = [
        f'{{"name":"fluvio-{i & 7}","body":"{body}"}}'.encode()
        for i in range(48)
    ]
    specs = [("regex-filter", {"regex": "fluvio"})]
    chain = _build("tpu", specs)
    got = _run_chain(chain, vals)
    ex = chain.tpu_chain
    raw_bytes = sum(len(v) for v in vals)
    assert ex.h2d_bytes_total < raw_bytes / 4, (
        f"striped upload should be compressed: {ex.h2d_bytes_total} "
        f"vs {raw_bytes} raw"
    )
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


def test_sharded_staging_ships_compressed(glz_env):
    """Sharded dispatch: per-shard glz streams decode inside the shard
    body."""
    from fluvio_tpu.telemetry import TELEMETRY

    vals = _json_vals(8000)
    specs = [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})]
    lv0 = TELEMETRY.link_variant_counts()
    chain = _build("tpu", specs, mesh=4)
    got = _run_chain(chain, vals)
    ex = chain.tpu_chain
    raw_bytes = sum(len(v) for v in vals)
    assert ex.h2d_bytes_total < raw_bytes, "sharded upload should undercut raw"
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("glz-gather", 0) > lv0.get("glz-gather", 0)
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


def test_sharded_aggregate_carries_exact_across_stream(glz_env):
    vals_a = [f"{(i * 3) & 63:06d}".encode() for i in range(6000)]
    vals_b = [f"{(i * 5) & 63:06d}".encode() for i in range(6000)]
    specs = [("aggregate-sum", None)]
    chain = _build("tpu", specs, mesh=4)
    got_a = _run_chain(chain, vals_a)
    got_b = _run_chain(chain, vals_b)
    py = _build("python", specs)
    ref_a = _run_chain(py, vals_a)
    ref_b = _run_chain(py, vals_b)
    assert got_a == ref_a and got_b == ref_b


def test_sharded_striped_declines_wide(glz_env, monkeypatch):
    """The one wide-path exclusion left: sharded STRIPED batches ship
    raw, with the per-batch `glz-wide-unsupported` decline counted."""
    from fluvio_tpu.telemetry import TELEMETRY

    monkeypatch.setenv("FLUVIO_STRIPE_THRESHOLD", "16384")
    body = "y" * 30000
    vals = [
        f'{{"name":"fluvio-{i & 7}","body":"{body}"}}'.encode()
        for i in range(32)
    ]
    specs = [("regex-filter", {"regex": "fluvio"})]
    d0 = dict(TELEMETRY.declines)
    lv0 = TELEMETRY.link_variant_counts()
    chain = _build("tpu", specs, mesh=4)
    got = _run_chain(chain, vals)
    assert (
        TELEMETRY.declines.get(glz.DECLINE_WIDE, 0)
        > d0.get(glz.DECLINE_WIDE, 0)
    )
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("raw", 0) > lv0.get("raw", 0)
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


def test_decline_reason_counted_per_batch(glz_env):
    """An incompressible corpus ships raw with `glz-ratio` on the
    decline counter — once per dispatched batch, from the cached
    compression verdict."""
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput
    from fluvio_tpu.telemetry import TELEMETRY

    rng = np.random.default_rng(13)
    vals = [
        bytes(rng.integers(33, 127, 40).astype(np.uint8)) + b"fluvio"
        for _ in range(4000)
    ]
    records = [Record(value=v) for v in vals]
    for i, r in enumerate(records):
        r.offset_delta = i
    buf = RecordBuffer.from_smartmodule_input(
        SmartModuleInput.from_records(records)
    )
    chain = _build("tpu", [("regex-filter", {"regex": "fluvio"})])
    ex = chain.tpu_chain
    d0 = dict(TELEMETRY.declines)
    lv0 = TELEMETRY.link_variant_counts()
    outs = list(ex.process_stream(iter([buf, buf, buf])))
    assert len(outs) == 3
    assert (
        TELEMETRY.declines.get(glz.DECLINE_RATIO, 0)
        - d0.get(glz.DECLINE_RATIO, 0)
    ) == 3, "one glz-ratio decline per dispatched batch"
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("raw", 0) - lv0.get("raw", 0) == 3


# ---------------------------------------------------------------------------
# Heal: a RUNTIME failure of a compressed batch latches raw staging; a
# LOWERING/compile error is a program fault and propagates (ISSUE 22)
# ---------------------------------------------------------------------------


def _heals():
    from fluvio_tpu.telemetry import TELEMETRY

    return TELEMETRY.snapshot()["counters"]["heals"]


def test_dispatch_runtime_fault_latches_raw(glz_env):
    """The injected decode seam (a runtime-class fault, deterministic so
    no retry masks it): the SAME batch re-stages raw, compression
    latches off for this executor, outputs stay exact."""
    from fluvio_tpu.resilience import faults

    vals = _json_vals()
    specs = [("regex-filter", {"regex": "fluvio"})]
    chain = _build("tpu", specs)
    h0 = _heals()
    faults.FAULTS.inject("glz_decode", first=1, exc="deterministic")
    try:
        got = _run_chain(chain, vals)
    finally:
        faults.FAULTS.clear()
    ex = chain.tpu_chain
    assert not ex._link_compress, "a decode runtime fault latches raw"
    assert _heals() == h0 + 1
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


def test_dispatch_runtime_error_latches_raw(glz_env, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("device decode failed at run time")

    monkeypatch.setattr(glz, "decompress_device", boom)
    vals = _json_vals()
    specs = [("regex-filter", {"regex": "fluvio"})]
    chain = _build("tpu", specs)
    got = _run_chain(chain, vals)
    ex = chain.tpu_chain
    assert not ex._link_compress, "a runtime failure latches raw"
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


@pytest.mark.parametrize("mesh", [None, 4], ids=["single", "sharded"])
@pytest.mark.parametrize(
    "exc",
    [
        NotImplementedError("Only 2D gather is supported"),
        TypeError("lowering rejected the operand type"),
    ],
    ids=["notimplemented", "typeerror"],
)
def test_lowering_error_propagates(glz_env, monkeypatch, mesh, exc):
    """What only lowering raises is a program fault: under
    backend="tpu" it raises through `process()` with NO heal counted,
    no rung demoted and no interpreter re-run."""
    from fluvio_tpu.telemetry import TELEMETRY

    def refuse(*a, **k):
        raise exc

    monkeypatch.setattr(glz, "decompress_device", refuse)
    vals = _json_vals(8000)
    specs = [("regex-filter", {"regex": "fluvio"})]
    chain = _build("tpu", specs, mesh=mesh)
    h0 = _heals()
    sp0 = dict(TELEMETRY.snapshot()["counters"]["spills"])
    with pytest.raises(type(exc)):
        _run_chain(chain, vals)
    ex = chain.tpu_chain
    assert ex._link_compress, "a program fault must not demote anything"
    assert _heals() == h0
    assert TELEMETRY.snapshot()["counters"]["spills"] == sp0


def test_sharded_dispatch_runtime_error_latches_raw(glz_env, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("device decode failed under shard_map")

    monkeypatch.setattr(glz, "decompress_device", boom)
    vals = _json_vals(8000)
    specs = [("regex-filter", {"regex": "fluvio"})]
    chain = _build("tpu", specs, mesh=4)
    got = _run_chain(chain, vals)
    ex = chain.tpu_chain
    assert not ex._link_compress
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


def test_sharded_transient_fetch_fault_keeps_compression(glz_env):
    """A TRANSIENT finish-side fault on a compressed sharded batch must
    ride the bounded retry with the ladder untouched: the retry re-ships
    the same compressed form (from the per-buffer cache), and a
    recoverable hiccup never costs the executor its link compression."""
    from fluvio_tpu.resilience import faults
    from fluvio_tpu.telemetry import TELEMETRY

    faults.FAULTS.inject("device", first=1)  # transient-class
    try:
        vals = _json_vals(8000)
        specs = [("regex-filter", {"regex": "fluvio"})]
        chain = _build("tpu", specs, mesh=4)
        lv0 = dict(TELEMETRY.link_variant_counts())
        got = _run_chain(chain, vals)
    finally:
        faults.FAULTS.clear()
    ex = chain.tpu_chain
    assert ex._link_compress, "transient fault must not latch glz off"
    lv = {
        k: v - lv0.get(k, 0)
        for k, v in TELEMETRY.link_variant_counts().items()
        if v - lv0.get(k, 0)
    }
    # the H2D family only: the down-* keys are the result side's own
    # variant family (PR-12) and move independently
    assert {k for k in lv if not k.startswith("down-")} == {"glz-gather"}, lv
    assert got == _run_chain(_build("python", specs), vals)


def test_sharded_deterministic_finish_failure_latches_raw(glz_env):
    """A DETERMINISTIC finish-side runtime failure of a compressed
    sharded batch makes the decode the prime suspect: compression
    latches off and the same batch re-dispatches raw."""
    from fluvio_tpu.resilience import faults

    faults.FAULTS.inject("device", first=1, exc="deterministic")
    try:
        vals = _json_vals(8000)
        specs = [("regex-filter", {"regex": "fluvio"})]
        chain = _build("tpu", specs, mesh=4)
        got = _run_chain(chain, vals)
    finally:
        faults.FAULTS.clear()
    ex = chain.tpu_chain
    assert not ex._link_compress
    assert got == _run_chain(_build("python", specs), vals)


def _int_bufs(n_bufs, n=6000):
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    bufs, val_lists = [], []
    for b in range(n_bufs):
        vals = [f"{(i * (b + 1)) & 63:06d}".encode() for i in range(n)]
        records = [Record(value=v) for v in vals]
        for i, r in enumerate(records):
            r.offset_delta = i
        bufs.append(
            RecordBuffer.from_smartmodule_input(
                SmartModuleInput.from_records(records)
            )
        )
        val_lists.append(vals)
    return bufs, val_lists


def test_fetch_heal_latches_raw_and_preserves_carry_lineage(
    glz_env, monkeypatch
):
    """The async heal: batch k's decode runtime failure surfaces at
    fetch while k+1 (already dispatched compressed, carries chained) is
    in flight. The heal latches compression off and the carry-lineage
    epoch machinery must still re-dispatch k+1 from the healed tip,
    bit-exact vs the interpreter."""
    from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor

    real_fetch = TpuChainExecutor._fetch
    state = {"bombed": False}

    def fetch_bomb(self, buf, header, packed, spec=None, defer=False):
        if spec and spec.get("glz_used") and not state["bombed"]:
            state["bombed"] = True
            raise RuntimeError("simulated decode runtime failure")
        return real_fetch(self, buf, header, packed, spec, defer)

    monkeypatch.setattr(TpuChainExecutor, "_fetch", fetch_bomb)
    chain = _build("tpu", [("aggregate-sum", None)])
    ex = chain.tpu_chain
    bufs, val_lists = _int_bufs(2)
    outs = list(ex.process_stream(iter(bufs)))
    assert state["bombed"]
    assert not ex._link_compress, "fetch heal latches compression off"
    assert len(outs) == 2

    py = _build("python", [("aggregate-sum", None)])
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    for out, vals in zip(outs, val_lists):
        records = [Record(value=v) for v in vals]
        for i, r in enumerate(records):
            r.offset_delta = i
        ref = py.process(SmartModuleInput.from_records(records))
        assert [r.value for r in out.to_records()] == [
            r.value for r in ref.successes
        ]
    ex._ensure_host_state()
    assert ex.carries[0][0] == int(py.instances[0].accumulator)


# ---------------------------------------------------------------------------
# CI gates: compile-size smoke + zero-cost chooser
# ---------------------------------------------------------------------------


def test_gather_decode_compile_size_gate():
    """jit of the gather-round decode at a bench-shaped bucket must stay
    well-bounded (the PR-4 DFA gate's methodology): a pathological
    lowering would blow up trace/compile time long before it blew up
    the chip."""
    import jax
    import jax.numpy as jnp

    out_len = 1 << 20  # 1 MiB bucket, 4 chunks at the 256 KiB default
    seq = np.zeros(4096, np.uint8)
    srcs = np.zeros(4096, np.int32)
    lits = np.zeros(1 << 19, np.uint8)

    fn = jax.jit(
        lambda a, b, c, d: glz.decompress_device(
            a, b, c, d, jnp.int32(glz.MAX_DEPTH), out_len
        )
    )
    t0 = time.perf_counter()
    fn(
        jnp.asarray(seq), jnp.asarray(seq), jnp.asarray(srcs),
        jnp.asarray(lits),
    ).block_until_ready()
    wall = time.perf_counter() - t0
    assert wall < 60.0, f"glz gather decode compile took {wall:.1f}s"


def _trip_glz(monkeypatch):
    """Every glz entry point the staging could reach raises."""

    def tripwire(*a, **k):
        raise AssertionError("glz touched with link compression off")

    for name in ("compress_link", "compress", "decompress_device"):
        monkeypatch.setattr(glz, name, tripwire)


def test_variant_chooser_zero_cost_when_disabled(monkeypatch):
    """With link compression off, the staging-variant chooser must cost
    NOTHING per dispatch: no compressor calls, no glz module work at
    all (the overhead-gate companion to the perf
    arms in test_telemetry_overhead.py)."""
    monkeypatch.delenv("FLUVIO_LINK_COMPRESS", raising=False)  # auto -> off
    _trip_glz(monkeypatch)
    vals = _json_vals(2000)
    specs = [("regex-filter", {"regex": "fluvio"})]
    chain = _build("tpu", specs)
    ex = chain.tpu_chain
    assert not ex._link_compress
    got = _run_chain(chain, vals)
    ref = _run_chain(_build("python", specs), vals)
    assert got == ref


# ---------------------------------------------------------------------------
# Preflight differential: predicted link variant == telemetry truth
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "specs",
    [
        [("regex-filter", {"regex": "fluvio"})],
        [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})],
    ],
    ids=["filter", "filter_map"],
)
def test_preflight_link_variant_matches_telemetry(monkeypatch, specs):
    from fluvio_tpu.analysis import preflight_for_specs
    from fluvio_tpu.telemetry import TELEMETRY

    monkeypatch.setenv("FLUVIO_LINK_COMPRESS", "on")
    vals = _json_vals(4000)
    pred = preflight_for_specs(specs, max(len(v) for v in vals))
    assert pred["link_variant"] == "glz-gather"
    lv0 = TELEMETRY.link_variant_counts()
    chain = _build("tpu", specs)
    _run_chain(chain, vals)
    lv = TELEMETRY.link_variant_counts()
    moved = [
        k for k, v in lv.items()
        if v > lv0.get(k, 0) and not k.startswith("down-")
    ]
    assert moved == [pred["link_variant"]], (
        f"predicted {pred['link_variant']}, telemetry observed {moved}"
    )


def test_preflight_predicts_raw_when_disabled(monkeypatch):
    from fluvio_tpu.analysis import preflight_for_specs

    monkeypatch.setenv("FLUVIO_LINK_COMPRESS", "off")
    pred = preflight_for_specs([("regex-filter", {"regex": "fluvio"})], 64)
    assert pred["link_variant"] == "raw"


# ---------------------------------------------------------------------------
# Policy (ISSUE 27): `auto` ships the staged flat raw on an attached
# chip too; `on` is the only way onto the compressed link
# ---------------------------------------------------------------------------


def _compressible_buf():
    """A 64 KiB ragged flat of the north star's JSON shape (about half
    its bytes are matches for the compressor)."""
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
    from fluvio_tpu.smartmodule import SmartModuleInput

    vals, size = [], 0
    for v in _json_vals(4000):
        if size + len(v) > 64 * 1024:
            break
        vals.append(v)
        size += len(v)
    records = [Record(value=v) for v in vals]
    for i, r in enumerate(records):
        r.offset_delta = i
    buf = RecordBuffer.from_smartmodule_input(
        SmartModuleInput.from_records(records)
    )
    return vals, buf


@pytest.fixture
def attached_chip(monkeypatch):
    """`jax.default_backend()` says "tpu" to the `auto` policies. The
    other autos that read the same call are pinned to their CPU side:
    the program still runs on the CPU (donation warns there, and a
    Pallas kernel outside the interpreter does not lower)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("FLUVIO_DONATE", "off")
    monkeypatch.setenv("FLUVIO_RESULT_COMPRESS", "off")
    monkeypatch.setenv("FLUVIO_TPU_PALLAS", "0")


@pytest.mark.parametrize("flag", [None, "auto", "off"])
def test_auto_ships_raw_on_an_attached_chip(monkeypatch, attached_chip, flag):
    from fluvio_tpu.smartengine.tpu.executor import effective_link_compress
    from fluvio_tpu.telemetry import TELEMETRY

    if flag is None:
        monkeypatch.delenv("FLUVIO_LINK_COMPRESS", raising=False)
    else:
        monkeypatch.setenv("FLUVIO_LINK_COMPRESS", flag)
    assert not effective_link_compress()
    _trip_glz(monkeypatch)
    specs = [("regex-filter", {"regex": "fluvio"})]
    vals, buf = _compressible_buf()
    ex = _build("tpu", specs).tpu_chain
    assert not ex._link_compress
    # no compress-ahead job for the stream loops to schedule
    assert ex._precompress_fn(buf) is None
    lv0 = TELEMETRY.link_variant_counts()
    outs = list(ex.process_stream(iter([buf])))
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("raw", 0) - lv0.get("raw", 0) == 1
    assert lv.get("glz-gather", 0) == lv0.get("glz-gather", 0)
    assert getattr(buf, "_glz_cache", None) is None
    assert outs[0].count == sum(b"fluvio" in v for v in vals)


def test_flag_on_still_ships_glz_gather(monkeypatch, attached_chip):
    """The path the next `simplicity` issue deletes is honest until
    then: pinned on, the same buffer crosses compressed, inflates on the
    device and comes back byte-equal."""
    from fluvio_tpu.smartengine.tpu.executor import effective_link_compress
    from fluvio_tpu.telemetry import TELEMETRY

    monkeypatch.setenv("FLUVIO_LINK_COMPRESS", "on")
    assert effective_link_compress()
    specs = [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})]
    vals, buf = _compressible_buf()
    chain = _build("tpu", specs)
    ex = chain.tpu_chain
    assert ex._link_compress and ex._precompress_fn(buf) is not None
    lv0 = TELEMETRY.link_variant_counts()
    got = _run_chain(chain, vals)
    lv = TELEMETRY.link_variant_counts()
    assert lv.get("glz-gather", 0) - lv0.get("glz-gather", 0) == 1
    assert lv.get("raw", 0) == lv0.get("raw", 0)
    assert got == _run_chain(_build("python", specs), vals)
