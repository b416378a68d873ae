"""Described-v5e compiles of the main path's kernels and programs.

The TPU's compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (`on-chip-measurement` guide §2, rehearsal 3):
what the chip's compiler would refuse — a Mosaic lowering gap, a slice
not aligned to the tiling, a program that does not fit 16 GB — is
refused here, at no chip time. Every Pallas kernel left in
`pallas_kernels.py` and every program the `auto` policies select on a
TPU is compiled at real widths (1,048,576 records; 70 KiB stripes).

Rules this file keeps (they are why it is ONE file):
- the topology is described inside a module-scoped fixture that skips
  when it cannot be described — never at import, in a skipif, in
  parametrize arguments or in conftest.py (only one process may load
  libtpu; xdist workers all import every test file);
- everything compiles in the test's own process, compilation cache off;
- `jax.default_backend` is steered by monkeypatch IN THE TEST so the
  `auto` policies take their TPU branch — not by a program option.

A compile that passes is not a chip run and is never reported as one.
"""

from __future__ import annotations

import functools
import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # compiler logs stay out of /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

ROWS = 1 << 20          # 1,048,576 records: the north-star batch, padded
JSON_FLAT = 44 * ROWS   # 4-aligned ragged bytes of 1M gen_json records
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A described-chip executable is written to the persistent cache
    but cannot be read back without a chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the repo's `auto` policies onto their TPU branch: Pallas
    kernels for real (no interpreter), the glz result encoder on the way
    down, donation, the associative DFA, the fast JSON kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for flag in (
        "FLUVIO_TPU_PALLAS", "FLUVIO_RESULT_COMPRESS",
        "FLUVIO_DONATE", "FLUVIO_DFA_ASSOC", "FLUVIO_TPU_FAST_JSON",
    ):
        monkeypatch.delenv(flag, raising=False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert total < HBM_BYTES, f"program needs {total / 1e9:.1f} GB of 16 GB HBM"
    return compiled.as_text()


# ---------------------------------------------------------------------------
# Pallas kernels (the three the chip's compiler takes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [64, 128])
def test_pallas_json_get_span(one_chip, as_tpu, width):
    from fluvio_tpu.smartengine.tpu import pallas_kernels as pk

    hlo = _compile(
        jax.jit(lambda v, l: pk.json_get_span_pallas(v, l, "name")),
        _sds((ROWS, width), jnp.uint8, one_chip),
        _sds((ROWS,), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("width", [64, 128])
def test_pallas_extract(one_chip, as_tpu, width):
    from fluvio_tpu.smartengine.tpu import pallas_kernels as pk

    hlo = _compile(
        jax.jit(pk.extract_pallas),
        _sds((ROWS, width), jnp.uint8, one_chip),
        _sds((ROWS,), jnp.int32, one_chip),
        _sds((ROWS,), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "pattern,width",
    [
        ("fluvio", 64),
        ("fluvio", 128),
        ("^(fluvio|kafka|pulsar)-[0-3]$", 64),
    ],
    ids=["literal-64", "literal-128", "alternation22-64"],
)
def test_pallas_dfa_match(one_chip, as_tpu, pattern, width):
    from fluvio_tpu.ops.regex_dfa import compile_regex
    from fluvio_tpu.smartengine.tpu import pallas_kernels as pk

    dfa = compile_regex(pattern)
    assert pk.dfa_supported(dfa)
    hlo = _compile(
        jax.jit(lambda v, l: pk.dfa_match_pallas(v, l, dfa)),
        _sds((ROWS, width), jnp.uint8, one_chip),
        _sds((ROWS,), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# Chain programs at 1M records / 70 KiB stripes
# ---------------------------------------------------------------------------


def _chain(specs):
    from fluvio_tpu.models import lookup
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig

    b = SmartEngine(backend="tpu").builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize().tpu_chain


def _program_args(ex, probe, rows, flat_bytes, sharding):
    """The jit's argument list as `_dispatch` stages it, as shapes: the
    static axes come from a tiny probe buffer of the same record shape,
    the array extents from the real batch (rows, ragged flat bytes)."""
    from fluvio_tpu.smartengine.tpu.executor import (
        TpuChainExecutor,
        stage_link_columns,
    )

    lengths_up, has_keys, has_offsets, ts_mode, _ = stage_link_columns(probe)
    assert not has_keys and not has_offsets
    bucket = TpuChainExecutor._bucket_bytes(flat_bytes)
    i64 = lambda: _sds((), jnp.int64, sharding)  # noqa: E731
    carries = tuple(
        (i64(), i64(), _sds((), jnp.bool_, sharding)) for _ in ex.carries
    )
    if ex._window is not None:   # a window chain's carry is its bank
        col = _sds((ex._window.capacity,), jnp.int64, sharding)
        carries = (col, col, col, i64())
    args = (
        _sds((bucket // 4,), jnp.int32, sharding),
        _sds((rows,), lengths_up.dtype, sharding),
        None, None, None,
        None,
        _sds((), jnp.int32, sharding),
        i64(),
        carries,
    )
    kwargs = dict(
        kwidth=probe.keys.shape[1],
        has_keys=False,
        has_offsets=False,
        ts_mode=ts_mode,
    )
    return args, kwargs


def _json_probe():
    import chip_smoke

    values, _ = chip_smoke.gen_json(8, 1)
    return chip_smoke.pack(values)


def _compile_ragged(ex, probe, sharding):
    args, kwargs = _program_args(ex, probe, ROWS, JSON_FLAT, sharding)
    enc, pack = ex._down_axes(False)
    return _compile(
        ex._jit_ragged.__wrapped__, *args,
        width=probe.width, fanout_cap=None, enc=enc, pack=pack, **kwargs,
    )


NORTH_STAR = [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})]


# `test_repad_addresses_blocks` reads the two chain programs' compiled
# text again: cached, so each is paid for once, whichever test asks first
@functools.cache
def _north_star_hlo(one_chip):
    ex = _chain(NORTH_STAR)
    assert ex._enc_variant == "xla"
    return _compile_ragged(ex, _json_probe(), one_chip)


def test_ragged_north_star(one_chip, as_tpu):
    """2_filter_map at 1M records: Pallas DFA + Pallas JSON span inside
    the fused chain, the XLA result encoder on the way down, and the
    raw flat on the way up."""
    assert "tpu_custom_call" in _north_star_hlo(one_chip)


def test_ragged_filter(one_chip, as_tpu):
    """1_filter: a literal pattern lowers to the XLA window compare (no
    Pallas kernel expected), mask-only downlink."""
    ex = _chain([("regex-filter", {"regex": "fluvio"})])
    _compile_ragged(ex, _json_probe(), one_chip)


def test_ragged_aggregate(one_chip, as_tpu):
    ex = _chain([("aggregate-field", {"field": "n", "combine": "add"})])
    hlo = _compile_ragged(ex, _json_probe(), one_chip)
    assert "tpu_custom_call" in hlo  # the Pallas JSON span feeds the sum


Q5_ROWS = 1 << 18


@functools.cache
def _q5_hlo(one_chip):
    import json
    from pathlib import Path

    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
    from fluvio_tpu.protocol.record import Record

    cfg = json.loads((Path(__file__).resolve().parents[1] / "benchmark" /
                      "configs" / "fluvio-nexmark-q5-1p.json").read_text())
    b = SmartEngine(backend="tpu").builder()
    b.add_smart_module(SmartModuleConfig(), cfg["chain"][0]["adhoc"])
    ex = b.initialize().tpu_chain
    ex._window.capacity = ex._window.emit = 1 << 16
    records = [Record(value=b"x" * 120, offset_delta=i) for i in range(8)]
    probe = RecordBuffer.from_records(records)
    args, kwargs = _program_args(ex, probe, Q5_ROWS, 15_000_000, one_chip)
    enc, pack = ex._down_axes(False)
    assert (enc, pack) == ("off", False) and probe.width == 128
    return _compile(
        ex._jit_ragged.__wrapped__, *args, width=probe.width,
        fanout_cap=ex._fanout_cap(probe), enc=enc, pack=pack, **kwargs,
    )


def test_ragged_window_q5(one_chip, as_tpu):
    """`q5-drain`'s slice: 147,456 bids of at most 120 B (262,144 rows x
    128), five window phases a bid sort-merged into a bank grown to
    65,536 entries, the per-window maximum over 65,536 emit rows."""
    assert "sort" in _q5_hlo(one_chip)


@pytest.mark.parametrize(
    "program,rows,wwidth",
    [(_north_star_hlo, ROWS, 16), (_q5_hlo, Q5_ROWS, 32)],
    ids=["north_star", "q5"],
)
def test_repad_addresses_blocks(one_chip, as_tpu, program, rows, wwidth):
    """The re-pad hands the compiler a ROW gather of aligned 128-word
    blocks (`kernels.rows_from_word_starts`), never one index a word
    (rows x wwidth of them ran at 155 M words/s and were the largest
    device operation of three cells: PERF.md section 6, PR 38), and every
    operation of the rebuild keeps the `repad` scope, by which
    `device_link_ms_per_mrec` and `device_named_share` book it."""
    from fluvio_tpu.smartengine.tpu.kernels import ROW_BLOCK_WORDS as B

    hlo = program(one_chip)
    per_row = (wwidth + B - 2) // B + 1
    lines = hlo.splitlines()
    gathers = [ln for ln in lines if " gather(" in ln and "/repad/" in ln]
    assert gathers, "the rebuild's block fetch is a gather under `repad`"
    for ln in gathers:
        out = [int(d) for d in re.search(r"= s32\[([\d,]+)\]", ln).group(1).split(",")]
        sizes = [int(d) for d in re.search(r"slice_sizes=\{([\d,]+)\}", ln).group(1).split(",")]
        indices = int(np.prod(out)) // int(np.prod(sizes))
        assert int(np.prod(sizes)) == B and indices <= rows * per_row, ln[:300]
    # the shifter's windows are shapes nothing else in the program has:
    # s32[rows, wwidth + 2**b - 1] and the fetched s32[rows, per_row * 128]
    widths = {wwidth + (1 << b) - 1 for b in range(1, B.bit_length() - 1)}
    widths.add(per_row * B)
    shaped = [
        ln for ln in lines
        if "op_name=" in ln and any(f"= s32[{rows},{w}]" in ln for w in widths)
    ]
    assert len(shaped) >= len(widths)
    for ln in shaped:
        assert "/repad/" in ln, ln[:300]


def _scoped_compaction_hlo(which: str, sharding):
    from fluvio_tpu.smartengine.tpu.kernels import fanout_scatter
    from fluvio_tpu.windows.kernels import compact_front, prefix_sum

    if which == "compact_front":
        def fn(mask, x):
            with jax.named_scope("stage0.window_merge"):
                n, (packed,) = compact_front(mask, 4096, prefix_sum(x))
                return n, packed

        return "stage0.window_merge", _compile(
            jax.jit(fn),
            _sds((1 << 16,), jnp.bool_, sharding),
            _sds((1 << 16,), jnp.int64, sharding),
        )

    def fan(flag, start, length, fflag, fstart, flen, contributing):
        with jax.named_scope("stage0.array_map"):
            return fanout_scatter(
                flag, start, length, fflag, fstart, flen, contributing, 20_000
            )

    n, w = 4096, 5
    b, i = jnp.bool_, jnp.int32
    return "stage0.array_map", _compile(
        jax.jit(fan),
        _sds((n, w), b, sharding), _sds((n, w), i, sharding),
        _sds((n, w), i, sharding), _sds((n,), b, sharding),
        _sds((n,), i, sharding), _sds((n,), i, sharding),
        _sds((n,), b, sharding),
    )


@pytest.mark.parametrize("which", ["compact_front", "fanout_scatter"])
def test_window_scans_and_gathers_keep_their_scope(one_chip, as_tpu, which):
    """`device_named_share` books an operation by the scope in its
    `op_name`. `jnp.cumsum` (on a TPU) and `jnp.take` (fill mode) lower
    through functions that drop the name stack, and the compiler's
    rewrite of one long reduce-window keeps no metadata at all: the
    merge's prefix sums were 2.2 % of `q5-drain`'s device time under no
    scope (PR 35). `prefix_sum` and `compact_front` keep theirs; the
    window compaction's ONE int32 scatter (`compaction_sources`)
    and the gathers through it keep the caller's scope, with no search
    `while` left. The fan-out places with no scatter, gather or sort at
    all; its one prefix sum, over the records, keeps the scope."""
    scope, hlo = _scoped_compaction_hlo(which, one_chip)
    lines = hlo.splitlines()
    scatters = [ln for ln in lines if " scatter(" in ln]
    gathers = [ln for ln in lines if " gather(" in ln]
    windows = [ln for ln in lines if " reduce-window(" in ln]
    assert not [ln for ln in lines if " while(" in ln]
    if which == "compact_front":
        assert len(scatters) == 1 and "unique_indices=true" in scatters[0]
        ops = scatters + gathers + windows
        assert len(ops) >= 3
    else:
        assert not scatters and not gathers
        assert not [ln for ln in lines if " sort(" in ln]
        ops = windows
        assert ops
    for ln in ops:
        assert scope in ln, ln[:200]


# `explode-drain`'s slice: 524,288 padded records of width 64 (six
# elements each), a learned capacity of 3,145,728 output rows and a
# learned bound of 8 elements a record
EXPLODE_ROWS, EXPLODE_WIDTH, EXPLODE_CAP, EXPLODE_BOUND = 1 << 19, 64, 3 << 20, 8


def test_fanout_place_at_the_explode_cell_shape(one_chip, as_tpu):
    """The placement flattened the grid to 524,288 x 65 = 34,078,720
    byte slots and sorted, scattered and gathered over all of them, nine
    in ten empty: the largest device operations of `explode-drain`
    (PERF.md section 5). At the cell's own shape no sort, scatter,
    gather or reduce-window of that size is left, and each that is left
    keeps `stage0.array_map`, by which `device_chain_ms_per_mrec` books
    it."""
    from fluvio_tpu.smartengine.tpu.kernels import fanout_place

    n, w = EXPLODE_ROWS, EXPLODE_WIDTH

    def fan(flag, start, length, fflag, fstart, flen, contributing, view, src):
        with jax.named_scope("stage0.array_map"):
            return fanout_place(
                flag, start, length, fflag, fstart, flen, contributing,
                EXPLODE_CAP, EXPLODE_BOUND,
                (view, src, jnp.arange(n, dtype=jnp.int32)),
            )

    b, i = jnp.bool_, jnp.int32
    hlo = _compile(
        jax.jit(fan),
        _sds((n, w), b, one_chip), _sds((n, w), i, one_chip),
        _sds((n, w), i, one_chip), _sds((n,), b, one_chip),
        _sds((n,), i, one_chip), _sds((n,), i, one_chip),
        _sds((n,), b, one_chip), _sds((n,), i, one_chip),
        _sds((n,), i, one_chip),
    )
    heavy = [
        ln for ln in hlo.splitlines()
        if re.search(r" (sort|scatter|gather|reduce-window)\(", ln)
    ]
    assert heavy  # the records' prefix sum
    for ln in heavy:
        assert "stage0.array_map" in ln, ln[:200]
        for dims in re.findall(r"[a-z]\d*\[([\d,]+)\]", ln.split(" = ", 1)[1][:200]):
            size = int(np.prod([int(d) for d in dims.split(",")]))
            assert size != n * (w + 1), ln[:200]


def _compile_striped(ex, n_records, sharding):
    import chip_smoke

    probe = chip_smoke.pack(chip_smoke.gen_fat(2))
    assert ex._striped_chain() is not None and ex._needs_stripes(probe)
    rec = int(probe.lengths[0])
    flat_bytes = n_records * ((rec + 3) // 4 * 4)
    rows = 1024  # pack() pads 976 records to the next pow2
    args, kwargs = _program_args(ex, probe, rows, flat_bytes, sharding)
    shape = type(
        "B", (), {"rows": rows, "count": n_records, "width": probe.width,
                  "lengths": np.full(rows, rec, np.int32)},
    )
    enc, pack = ex._down_axes(True)
    return _compile(
        ex._jit_striped.__wrapped__, *args,
        srows=ex._stripe_rows(shape), kmax=ex._stripe_kmax(shape),
        fanout_cap=None, enc=enc, pack=pack, **kwargs,
    )


def test_striped_regex_json_fat(one_chip, as_tpu):
    """10_regex_json_fat: 70 KiB records striped over device rows; the
    JsonGet regex runs the associative DFA at 22 states x 15 classes."""
    specs = [("json-regex-filter",
              {"key": "name", "regex": "^(fluvio|kafka|pulsar)-[0-3]$"})]
    _compile_striped(_chain(specs), 976, one_chip)


def test_windows_step(one_chip, as_tpu):
    """`fluvio_tpu/windows/kernels.py` update step at bench.py's
    16,384-record window batch."""
    from fluvio_tpu.windows import WindowSpec
    from fluvio_tpu.windows.kernels import WindowJits
    from fluvio_tpu.windows.spec import KIND_TO_OP

    spec = WindowSpec(
        window_ms=1000, slide_ms=0, op=KIND_TO_OP["sum_int"], keyed=False,
        emit_capacity=0, delta_only=True,
    )
    k, rows = spec.capacity, 16_384
    _compile(
        WindowJits(spec).update_values.__wrapped__,
        _sds((k,), jnp.int64, one_chip),
        _sds((k,), jnp.int64, one_chip),
        _sds((k,), jnp.int64, one_chip),
        _sds((), jnp.int64, one_chip),
        _sds((rows, 8), jnp.uint8, one_chip),
        _sds((rows,), jnp.int32, one_chip),
        _sds((rows,), jnp.int64, one_chip),
        _sds((rows,), jnp.bool_, one_chip),
    )
