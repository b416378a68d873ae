"""Described-v5e compile of `q17-drain`'s slice program (ISSUE 39).

A file of its own, beside `test_chip_compile.py` (whose rules it keeps:
the topology is described inside a module-scoped fixture, everything
compiles in the test's own process with the compilation cache off, the
`auto` policies are steered by monkeypatch): the driver runs `--dist
loadfile`, so a file is one worker's, and that file already carries
Q5's compile of minutes. A compile that passes is not a chip run.
"""

from __future__ import annotations

import json
import os
import re
from pathlib import Path

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

HBM_BYTES = 16 * 1024**3
ROWS = 1 << 18            # 147,456 bids pad to 262,144 rows of 128 B
CAPACITY = 1 << 17        # about 66,000 (auction, day) keys


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for flag in ("FLUVIO_TPU_PALLAS", "FLUVIO_RESULT_COMPRESS",
                 "FLUVIO_DONATE", "FLUVIO_DFA_ASSOC", "FLUVIO_TPU_FAST_JSON"):
        monkeypatch.delenv(flag, raising=False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _q17_hlo(one_chip):
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
    from fluvio_tpu.smartengine.tpu.executor import (
        TpuChainExecutor, stage_link_columns,
    )

    cfg = json.loads((Path(__file__).resolve().parents[1] / "benchmark" /
                      "configs" / "fluvio-nexmark-q17-1p.json").read_text())
    b = SmartEngine(backend="tpu").builder()
    b.add_smart_module(SmartModuleConfig(), cfg["chain"][0]["adhoc"])
    ex = b.initialize().tpu_chain
    stage = ex._window
    stage.capacity = CAPACITY
    probe = RecordBuffer.from_records(
        [Record(value=b"x" * 120, offset_delta=i) for i in range(8)])
    lengths_up, has_keys, has_offsets, ts_mode, _ = stage_link_columns(probe)
    assert not has_keys and not has_offsets and probe.width == 128
    i64 = _sds((), jnp.int64, one_chip)
    col = _sds((CAPACITY,), jnp.int64, one_chip)
    lanes = _sds((len(stage.ops), CAPACITY), jnp.int64, one_chip)
    carries = (col, lanes, None, i64)
    enc, pack = ex._down_axes(False)
    assert (enc, pack) == ("off", False)
    compiled = ex._jit_ragged.__wrapped__.lower(
        _sds((TpuChainExecutor._bucket_bytes(15_000_000) // 4,), jnp.int32,
             one_chip),
        _sds((ROWS,), lengths_up.dtype, one_chip),
        None, None, None, None,
        _sds((), jnp.int32, one_chip), i64, carries,
        width=probe.width, kwidth=probe.keys.shape[1], has_keys=False,
        has_offsets=False, ts_mode=ts_mode, fanout_cap=ex._fanout_cap(probe),
        enc=enc, pack=pack,
    ).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"program needs {total / 1e9:.1f} GB of 16 GB HBM"
    return compiled.as_text()


def _lines(hlo, opcode):
    return [ln for ln in hlo.splitlines() if f" {opcode}(" in ln]


def test_ragged_group_q17(one_chip, as_tpu):
    """`q17-drain`'s slice: 147,456 bids of at most 120 B (262,144 rows
    x 128) merged with a 131,072-entry table of seven lanes. ONE sort,
    of (id, position); no scatter of int64 rows: the one scatter is of
    int32 positions (an int64 one is a pair of u32 operands on the
    chip); the Pallas JSON span feeds auction, dateTime and price once
    each, not once a column; every sort, scatter, gather and
    reduce-window of the stage under a `group*` scope, and no sort,
    scatter or gather of the program under no scope at all (the
    compiler's rewrite of the re-pad's `jnp.cumsum` keeps no metadata:
    PERF.md section 7)."""
    hlo = _q17_hlo(one_chip)
    (sort,) = _lines(hlo, "sort")
    assert "stage0.group/stage0.group_merge/sort" in sort
    assert sort.count("[393216]") >= 3 and "s64" not in sort.split(" sort(")[0]
    (scatter,) = _lines(hlo, "scatter")
    assert re.search(r"= s32\[393216\]\S* scatter\(", scatter), scatter[:200]
    assert "stage0.group/stage0.group_emit" in scatter
    assert 3 <= hlo.count('custom_call_target="tpu_custom_call"') <= 6
    named = 0
    for opcode in ("sort", "scatter", "gather", "reduce-window"):
        for ln in _lines(hlo, opcode):
            (op_name,) = re.findall(r'op_name="([^"]*)"', ln) or [""]
            if opcode != "reduce-window":
                assert "/stage0.group" in op_name or "/repad/" in op_name, ln[:300]
            if "/stage0." in op_name:
                assert re.search(
                    r"/stage0\.group/stage0\.group_(merge|emit)/", op_name
                ), op_name
                named += 1
    assert named >= 10
