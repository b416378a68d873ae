#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the system's main path once, through the entry points a user
calls, on ONE TPU chip in ONE process:

  broker   an in-process `SpuServer` (``backend="tpu"``) on 127.0.0.1:0,
           RECORDS JSON records written into one topic/partition, then
           consumed TWICE through `Fluvio.connect` ->
           `partition_consumer.stream_batches` with the north-star chain
           (regex-filter ``fluvio`` + json-map ``name``); every emitted
           (offset, value) is compared with a host reference that does
           not import the engine (`re` + `json`).
  chains   engine-level (`SmartEngine(backend="tpu")`): one full-size
           batch each of 1_filter / 3_aggregate / 4_array_map /
           5_windowed (classic chain + the device-resident window
           runtime) and one striped 70 KiB-record batch
           (10_regex_json_fat), each against the `python` backend on a
           prefix slice and against host truth by count/sum on the
           whole batch.
  truth    asserted from TELEMETRY: zero heals, zero stripe fallbacks,
           no `fused-error` spill, breakers closed, zero interpreter
           records, link/encode variants used == variants resolved.
  drill    one injected transient device fault: the healed re-dispatch
           must not read a donated buffer (runs AFTER truth, so its
           retry does not blur the zero-heal assertion).

``--chips 4`` runs ONLY the multi-chip path and what it is compared
with: the record-sharded executor (``mesh_devices=4``) on the
north-star chain and 4 partitions over 4 device groups, each against
the one-device output of the same batch.

Exit code is non-zero (and the result line is never printed) when jax
finds no TPU, when fewer chips than asked are attached, or when any
phase fails. Timings printed here are SMOKE timings (wall clock of one
cold and one warm pass), not a benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import re
import sys
import tempfile
import time

import numpy as np

# what `jax.devices()[0].platform` must say; the CPU rehearsal in
# tests/test_chip_smoke.py steers these by monkeypatch — the program
# itself has no option that lets it pass without the chip
REQUIRED_PLATFORM = "tpu"
RECORDS = 1_000_000       # north-star batch (BASELINE.json: 1M-record batches)
FAT_RECORDS = 976         # 70 KiB records: bench.py's 1M // 1024 divisor
WINDOW_BATCH = 16_384     # window-runtime batch (bench.py's batch_records)
WINDOW_BATCHES = 8
SLICE = 2_048             # prefix compared against the `python` backend
FAT_SLICE = 32
WIRE_BATCH = 16_384       # records per stored batch (as bench.py writes them)
STREAM_TIMEOUT_S = 900.0  # a consume pass that hangs is a failure, not a wait

_T0 = time.time()


def say(msg: str) -> None:
    print(f"[{time.time() - _T0:7.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# corpora (seeded; the `gen_json` shape of bench.py, re-stated here so the
# script needs nothing but the engine package)
# ---------------------------------------------------------------------------

NAMES = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "redpanda", "flink"]


def gen_json(n: int, seed: int):
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(NAMES), size=n)
    nums = rng.integers(0, 100000, size=n)
    return [
        f'{{"name":"{NAMES[picks[i]]}-{i & 1023}","n":{nums[i]}}}'.encode()
        for i in range(n)
    ], nums


def gen_arrays(n: int, seed: int):
    rng = np.random.default_rng(seed + 1)
    nums = rng.integers(0, 10000, size=(n, 3))
    return [
        f'["a{i & 255}","b{nums[i][0]}",{nums[i][1]},{nums[i][2]},"x","y"]'.encode()
        for i in range(n)
    ]


def gen_ints(n: int, seed: int):
    rng = np.random.default_rng(seed + 2)
    nums = rng.integers(0, 1000, size=n)
    return [str(nums[i]).encode() for i in range(n)], nums


def gen_fat(n: int):
    body = "x" * (70 * 1024)
    return [
        f'{{"name":"fluvio-{i & 7}","body":"{body}"}}'.encode()
        for i in range(n)
    ]


def pack(values, ts=None):
    """values -> RecordBuffer via one vectorized ragged copy."""
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer, bucket_width

    n = len(values)
    width = bucket_width(max(len(v) for v in values))
    rows = 8
    while rows < n:
        rows *= 2
    arr = np.zeros((rows, width), dtype=np.uint8)
    lengths = np.zeros(rows, dtype=np.int32)
    flat = np.frombuffer(b"".join(values), dtype=np.uint8)
    lens = np.array([len(v) for v in values], dtype=np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    arr[np.repeat(np.arange(n), lens),
        np.arange(flat.size) - np.repeat(starts, lens)] = flat
    lengths[:n] = lens
    buf = RecordBuffer.from_arrays(arr, lengths, count=n)
    buf.offset_deltas = np.arange(rows, dtype=np.int32)
    if ts is not None:
        tcol = np.zeros(rows, dtype=np.int64)
        tcol[:n] = ts
        buf.timestamp_deltas = tcol
        buf.base_timestamp = 1_000_000
    return buf


def build_chain(backend: str, specs, mesh: int = 0):
    from fluvio_tpu.models import lookup
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig

    b = SmartEngine(backend=backend, mesh_devices=mesh).builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    chain = b.initialize()
    assert chain.backend_in_use == backend, (backend, chain.backend_in_use)
    return chain


def out_columns(out):
    """(val_flat, val_off, off_delta) of an output buffer, computed once
    per buffer (the ragged gather over 1M rows is not free)."""
    cols = getattr(out, "_smoke_columns", None)
    if cols is None:
        c = out.to_columns()
        cols = out._smoke_columns = (c["val_flat"], c["val_off"], c["off_delta"])
    return cols


def out_values(out, lo: int, hi: int):
    flat, off, _ = out_columns(out)
    return [bytes(flat[off[i]:off[i + 1]]) for i in range(lo, hi)]


# records the `python` reference backend itself ran (it books under the
# `interpreter` path): subtracted before the truth phase asserts that
# the chains under test interpreted nothing
_REFERENCE_RECORDS = [0]


def python_reference(specs, values, ts, k: int):
    """The `python` backend over the first ``k`` inputs."""
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    k = min(k, len(values))
    _REFERENCE_RECORDS[0] += k
    chain = build_chain("python", specs)
    records = [Record(value=v) for v in values[:k]]
    for i, r in enumerate(records):
        r.offset_delta = i
        if ts is not None:
            r.timestamp_delta = int(ts[i])
    out = chain.process(SmartModuleInput.from_records(records, 0, 1_000_000))
    assert out.error is None, out.error
    return [(r.value, r.offset_delta) for r in out.successes]


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def require_device(chips: int):
    """Open the backend once and refuse anything but the asked chips."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != REQUIRED_PLATFORM:
        raise SystemExit(
            f"chip_smoke: jax found platform {d0.platform!r} "
            f"({d0.device_kind}), not {REQUIRED_PLATFORM!r} — this script "
            "proves the chip path and does not run without the chip"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} asked, jax sees {len(devs)} device(s)"
        )
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def resolved_modes() -> dict:
    """What every `auto` policy resolved to on this backend."""
    from fluvio_tpu.analysis.spec import resolve_gates
    from fluvio_tpu.smartengine.tpu import XLA_CACHE_DIR, executor, pallas_kernels

    g = resolve_gates()
    return {
        "backend": g["backend"],
        "pallas": pallas_kernels.pallas_active(),
        "pallas_interpret": pallas_kernels.interpret_mode(),
        "result_compact": g["result_compact"],
        "result_compress_down": g["result_compress"],
        "down_variant": "xla" if g["result_compress"] else "off",
        "donation": executor.effective_donation(),
        "dfa_assoc": g["dfa_assoc"],
        "fast_json": g["fast_json"],
        "cache_dir": XLA_CACHE_DIR,
    }


def compile_note(ct0: dict) -> str:
    from fluvio_tpu.telemetry import TELEMETRY

    ct = TELEMETRY.compile_totals()
    return (
        f"compiles={ct['compiles'] - ct0['compiles']} "
        f"compile_s={ct['seconds'] - ct0['seconds']:.1f}"
    )


# ---------------------------------------------------------------------------
# phase: broker
# ---------------------------------------------------------------------------

NORTH_STAR_FILTER_SM = b"""
@smartmodule.filter(dsl=dsl.FilterProgram(
    predicate=dsl.RegexMatch(arg=dsl.Value(), pattern="fluvio")))
def f(record):
    import re
    return re.search(b"fluvio", record.value) is not None
"""

NORTH_STAR_MAP_SM = b"""
@smartmodule.map(dsl=dsl.MapProgram(
    value=dsl.Upper(arg=dsl.JsonGet(arg=dsl.Value(), key="@param:field=name"))))
def m(record):
    return dsl.ascii_upper(dsl.json_get_bytes(record.value, "name"))
"""

NORTH_STAR_SPECS = [
    ("regex-filter", {"regex": "fluvio"}),
    ("json-map", {"field": "name"}),
]


def north_star_host_reference(values):
    """(offset, value) the chain must emit — `re` + `json` only."""
    pat = re.compile(rb"fluvio")
    return [
        (i, json.loads(v)["name"].upper().encode())
        for i, v in enumerate(values)
        if pat.search(v)
    ]


def wire_batches(values):
    """Stored batches, WIRE_BATCH records each, natively encoded — the
    form `bench.py` writes (the client producer's per-record Python path
    does not fit 1M records inside the smoke's time limit)."""
    from fluvio_tpu.protocol.record import Batch
    from fluvio_tpu.smartengine import native_backend

    out = []
    for lo in range(0, len(values), WIRE_BATCH):
        chunk = values[lo:lo + WIRE_BATCH]
        m = len(chunk)
        flat = np.frombuffer(b"".join(chunk), dtype=np.uint8)
        val_off = np.zeros(m + 1, dtype=np.int64)
        np.cumsum([len(v) for v in chunk], out=val_off[1:])
        raw = native_backend.encode_record_columns(
            flat, val_off,
            np.zeros(1, np.uint8), np.zeros(m + 1, np.int64),
            np.zeros(m, np.uint8),
            np.arange(m, dtype=np.int64), np.zeros(m, np.int64),
        )
        b = Batch(base_offset=0, raw_records=raw, raw_record_count=m)
        b.header.first_timestamp = 1_000_000
        b.header.max_time_stamp = 1_000_000
        b.header.last_offset_delta = m - 1
        out.append(b)
    return out


async def _broker(values, expect) -> None:
    from fluvio_tpu.client import ConsumerConfig, Fluvio, Offset
    from fluvio_tpu.protocol.record import RecordSet
    from fluvio_tpu.schema.smartmodule import (
        SmartModuleInvocation,
        SmartModuleInvocationKind,
        SmartModuleInvocationWasm,
    )
    from fluvio_tpu.spu import SpuConfig, SpuServer
    from fluvio_tpu.storage.config import ReplicaConfig
    from fluvio_tpu.telemetry import TELEMETRY

    n = len(values)
    slabs = wire_batches(values)
    tmp = tempfile.mkdtemp(prefix="fluvio-smoke-")
    config = SpuConfig(
        id=9001,
        public_addr="127.0.0.1:0",
        log_base_dir=tmp,
        replication=ReplicaConfig(base_dir=tmp),
    )
    config.smart_engine.backend = "tpu"
    server = SpuServer(config)
    await server.start()
    try:
        server.ctx.create_replica("smoke", 0)
        leader = server.ctx.leader_for("smoke", 0)
        t0 = time.time()
        for b in slabs:
            rs = RecordSet()
            rs.add(b)
            await leader.write_record_set(rs)
        say(f"broker: wrote {n} records in {len(slabs)} stored batches "
            f"via leader.write_record_set ({time.time() - t0:.2f}s)")
        cfg = ConsumerConfig(
            disable_continuous=True,
            max_bytes=16 << 20,
            smartmodules=[
                SmartModuleInvocation(
                    wasm=SmartModuleInvocationWasm.adhoc(NORTH_STAR_FILTER_SM),
                    kind=SmartModuleInvocationKind.FILTER,
                ),
                SmartModuleInvocation(
                    wasm=SmartModuleInvocationWasm.adhoc(NORTH_STAR_MAP_SM),
                    kind=SmartModuleInvocationKind.MAP,
                    params={"field": "name"},
                ),
            ],
        )
        client = await Fluvio.connect(server.public_addr)
        try:
            consumer = await client.partition_consumer("smoke", 0)

            async def consume_once():
                got = []
                t0 = time.time()
                async for batch in consumer.stream_batches(
                    Offset.beginning(), cfg
                ):
                    base = batch.base_offset
                    got.extend(
                        (base + r.offset_delta, r.value)
                        for r in batch.memory_records()
                    )
                return got, time.time() - t0

            for label in ("cold", "warm"):
                ct0 = TELEMETRY.compile_totals()
                got, dt = await asyncio.wait_for(
                    consume_once(), STREAM_TIMEOUT_S
                )
                assert len(got) == len(expect), (
                    f"broker {label} pass: {len(got)} records out, host "
                    f"reference says {len(expect)}"
                )
                assert got == expect, (
                    f"broker {label} pass: emitted (offset, value) stream "
                    "differs from the host reference"
                )
                say(f"broker: {label} pass records_in={n} "
                    f"records_out={len(got)} wall_s={dt:.2f} "
                    f"(smoke timing, not a benchmark) {compile_note(ct0)}")
        finally:
            await client.close()
    finally:
        await server.stop()
    m = server.ctx.metrics.smartmodule.to_dict()
    say(f"broker: fastpath_slices={m['fastpath_slices']} "
        f"fallback_slices={m['fallback_slices']} "
        f"fallback_reasons={m['fallback_reasons']}")
    assert m["fastpath_slices"] > 0, "broker fast path never engaged"
    assert m["fallback_slices"] == 0, (
        f"north-star slices fell back: {m['fallback_reasons']}"
    )


def phase_broker(seed: int) -> None:
    values, _ = gen_json(RECORDS, seed)
    expect = north_star_host_reference(values)
    say(f"broker: {len(values)} records generated (seed {seed}), host "
        f"reference expects {len(expect)} out")
    asyncio.run(_broker(values, expect))


# ---------------------------------------------------------------------------
# phase: chains (engine-level)
# ---------------------------------------------------------------------------


def run_tpu(specs, values, ts=None):
    from fluvio_tpu.telemetry import TELEMETRY

    chain = build_chain("tpu", specs)
    buf = pack(values, ts)
    ct0 = TELEMETRY.compile_totals()
    t0 = time.time()
    out = chain.tpu_chain.process_buffer(buf)
    return chain, out, time.time() - t0, compile_note(ct0)


def check_prefix(name, specs, values, ts, out, k):
    """Outputs stemming from the first k inputs == the python backend's."""
    ref = python_reference(specs, values, ts, k)
    flat, off, od = out_columns(out)
    got = [
        (bytes(flat[off[i]:off[i + 1]]), int(od[i])) for i in range(len(ref))
    ]
    assert got == ref, f"{name}: prefix of {k} inputs differs from python backend"
    return len(ref)


def phase_chains(seed: int) -> None:
    n = RECORDS
    values, nums = gen_json(n, seed)
    pat = re.compile(rb"fluvio")

    # 1_filter — outputs are the matching input records themselves
    specs = [("regex-filter", {"regex": "fluvio"})]
    _, out, dt, cn = run_tpu(specs, values)
    want = np.array([i for i, v in enumerate(values) if pat.search(v)])
    _, off, od = out_columns(out)
    assert out.count == len(want), ("1_filter count", out.count, len(want))
    assert np.array_equal(od, want), "1_filter: surviving offsets differ"
    assert int(off[-1]) == sum(len(values[i]) for i in want), "1_filter bytes"
    k = check_prefix("1_filter", specs, values, None, out, SLICE)
    say(f"chains: 1_filter in={n} out={out.count} prefix_ok={k} "
        f"wall_s={dt:.2f} {cn}")

    # 3_aggregate — running sum of field n, one output per record
    specs = [("aggregate-field", {"field": "n", "combine": "add"})]
    chain, out, dt, cn = run_tpu(specs, values)
    run = np.cumsum(nums.astype(np.int64))
    assert out.count == n, ("3_aggregate count", out.count)
    assert int(out_values(out, n - 1, n)[0]) == int(run[-1]), "3_aggregate sum"
    tail = [int(v) for v in out_values(out, n - SLICE, n)]
    assert tail == [int(x) for x in run[n - SLICE:]], "3_aggregate tail"
    chain.tpu_chain._ensure_host_state()
    assert chain.tpu_chain.carries[0][0] == int(run[-1]), "3_aggregate carry"
    k = check_prefix("3_aggregate", specs, values, None, out, SLICE)
    say(f"chains: 3_aggregate in={n} out={out.count} sum={int(run[-1])} "
        f"prefix_ok={k} wall_s={dt:.2f} {cn}")
    del values, out

    # 4_array_map — six elements per record
    arrays = gen_arrays(n, seed)
    specs = [("array-map-json", None)]
    _, out, dt, cn = run_tpu(specs, arrays)
    assert out.count == 6 * n, ("4_array_map count", out.count)
    _, off, _ = out_columns(out)
    want_bytes = sum(
        sum(len(e) if isinstance(e, str) else len(str(e)) for e in json.loads(v))
        for v in arrays
    )
    assert int(off[-1]) == want_bytes, ("4_array_map bytes", int(off[-1]), want_bytes)
    k = check_prefix("4_array_map", specs, arrays, None, out, SLICE)
    say(f"chains: 4_array_map in={n} out={out.count} bytes={want_bytes} "
        f"prefix_ok={k} wall_s={dt:.2f} {cn}")
    del arrays, out

    # 5_windowed — classic tumbling-sum chain, then the device-resident
    # window runtime against the repo's HostWindowReference
    ints, inums = gen_ints(n, seed)
    ts = np.arange(n, dtype=np.int64) * 4  # 250 records per 1000 ms window
    specs = [("windowed-sum", {"kind": "sum_int", "window_ms": "1000"})]
    _, out, dt, cn = run_tpu(specs, ints, ts)
    win = ts // 1000
    run = np.cumsum(inums.astype(np.int64))
    first = np.searchsorted(win, win, side="left")
    want_run = run - np.where(first > 0, run[first - 1], 0)
    assert out.count == n, ("5_windowed count", out.count)
    tail = [int(v) for v in out_values(out, n - SLICE, n)]
    assert tail == [int(x) for x in want_run[n - SLICE:]], "5_windowed tail"
    k = check_prefix("5_windowed", specs, ints, ts, out, SLICE)
    say(f"chains: 5_windowed(classic) in={n} out={out.count} prefix_ok={k} "
        f"wall_s={dt:.2f} {cn}")
    phase_window_runtime(ints, ts)
    del ints, out

    # 10_regex_json_fat — striped 70 KiB records, 22-state JsonGet regex
    fat = gen_fat(FAT_RECORDS)
    specs = [("json-regex-filter",
              {"key": "name", "regex": "^(fluvio|kafka|pulsar)-[0-3]$"})]
    chain, out, dt, cn = run_tpu(specs, fat)
    assert chain.tpu_chain._striped_chain() is not None, "fat chain not stripeable"
    want = np.array([i for i in range(len(fat)) if (i & 7) <= 3])
    _, off, od = out_columns(out)
    assert np.array_equal(od, want), "10_regex_json_fat: survivors differ"
    assert int(off[-1]) == sum(len(fat[i]) for i in want), "10_regex_json_fat bytes"
    k = check_prefix("10_regex_json_fat", specs, fat, None, out, FAT_SLICE)
    say(f"chains: 10_regex_json_fat in={len(fat)} out={out.count} "
        f"prefix_ok={k} wall_s={dt:.2f} {cn}")


def phase_window_runtime(ints, ts) -> None:
    from fluvio_tpu.telemetry import TELEMETRY
    from fluvio_tpu.windows import (
        HostWindowReference,
        MaterializedView,
        WindowSpec,
        WindowedRuntime,
    )
    from fluvio_tpu.windows.spec import KIND_TO_OP, delta_enabled

    spec = WindowSpec(
        window_ms=1000, slide_ms=0, op=KIND_TO_OP["sum_int"], keyed=False,
        emit_capacity=0, delta_only=delta_enabled(),
    )
    ref, view, rt = HostWindowReference(spec), MaterializedView(spec), WindowedRuntime(spec)
    ct0 = TELEMETRY.compile_totals()
    t0 = time.time()
    total = min(len(ints), WINDOW_BATCH * WINDOW_BATCHES)
    for a in range(0, total, WINDOW_BATCH):
        b = min(a + WINDOW_BATCH, total)
        view.apply_delta(rt.process_buffer(pack(ints[a:b], ts[a:b])))
        ref.process_batch([
            (0, int(r), int(t) + 1_000_000) for r, t in zip(ints[a:b], ts[a:b])
        ])
        assert rt.bank.snapshot() == ref.bank_entries(), (
            f"window runtime: device carry diverged from host at record {b}"
        )
    assert view.table() == ref.table(), "window runtime: view != host reference"
    say(f"chains: 5_windowed(runtime) {spec.describe()} in={total} "
        f"windows={len(ref.table())} exact=True wall_s={time.time() - t0:.2f} "
        f"{compile_note(ct0)}")


# ---------------------------------------------------------------------------
# phase: device truth
# ---------------------------------------------------------------------------


def phase_truth(modes: dict) -> None:
    import jax

    from fluvio_tpu.telemetry import TELEMETRY

    assert TELEMETRY.enabled, "telemetry must be on for the truth phase"
    snap = TELEMETRY.snapshot()
    c = snap["counters"]
    comp = snap["compile"]
    paths = TELEMETRY.path_records()
    down = {k: v for k, v in c["link_variants"].items() if k.startswith("down-")}
    say(f"truth: modes={json.dumps(modes, sort_keys=True)}")
    say(f"truth: heals={c['heals']} stripe_fallbacks={c['stripe_fallbacks']} "
        f"spills={c['spills']} retries={c['retries']} "
        f"breaker={c['breaker']['states']} declines={c['declines']}")
    interpreted = paths.get("interpreter", 0) - _REFERENCE_RECORDS[0]
    say(f"truth: path_records={paths} (of which python-reference "
        f"{_REFERENCE_RECORDS[0]}) link_down={down}")
    say(f"truth: compiles={sum(comp['by_kind'].values())} by_kind={comp['by_kind']} "
        f"compile_s={sum(comp['seconds_by_kind'].values()):.1f} "
        f"persistent_cache_hits={comp['persistent_cache_hits']} "
        f"persistent_cache_misses={comp['persistent_cache_misses']} "
        f"cache_dir={modes['cache_dir']}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"truth: peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")

    assert c["heals"] == 0, f"heals={c['heals']}: a rung was demoted"
    assert c["stripe_fallbacks"] == 0, c["stripe_fallbacks"]
    assert "fused-error" not in c["spills"], c["spills"]
    assert not c["spills"], f"unexpected spills on clean corpora: {c['spills']}"
    assert all(s == "closed" for s in c["breaker"]["states"].values()), c["breaker"]
    assert c["breaker"]["short_circuits"] == 0, c["breaker"]
    assert interpreted == 0, f"{interpreted} records ran interpreted: {paths}"
    assert paths.get("fused", 0) > 0 and paths.get("striped", 0) > 0, paths
    # variants USED == variants RESOLVED at build time: encoded
    # results shipped only under the resolved encoder
    if modes["result_compress_down"]:
        assert set(down) <= {"down-glz-xla", "down-packed"}, down
    else:
        assert not any(k.startswith("down-glz") for k in down), down


def phase_drill(seed: int) -> None:
    """One injected transient device fault (finish side, after the
    dispatch donated its staged buffers): the bounded retry re-stages
    fresh arrays and the outputs stay exact."""
    from fluvio_tpu.resilience import faults
    from fluvio_tpu.telemetry import TELEMETRY

    n = min(RECORDS, 65_536)
    values, _ = gen_json(n, seed)
    specs = NORTH_STAR_SPECS
    chain = build_chain("tpu", specs)
    buf = pack(values)
    clean = out_values(chain.tpu_chain.process_buffer(buf), 0, SLICE)
    def recoveries():
        c = TELEMETRY.snapshot()["counters"]
        return sum(c["retries"].values()), c["heals"]

    r0, h0 = recoveries()
    faults.FAULTS.inject("device", first=1)
    try:
        out = chain.tpu_chain.process_buffer(buf)
    finally:
        faults.FAULTS.clear()
    r1, h1 = recoveries()
    want = [v for _, v in north_star_host_reference(values)]
    assert out_values(out, 0, out.count) == want, "drill: healed output differs"
    assert clean == want[:SLICE]
    # an encode-armed batch answers a fetch-side fault by latching
    # that stage off and re-dispatching (a heal); a plain batch retries
    assert (r1 - r0) + (h1 - h0) == 1, (
        f"drill: expected one recovery, saw retries={r1 - r0} heals={h1 - h0}"
    )
    say(f"drill: injected transient device fault recovered "
        f"(retries={r1 - r0} heals={h1 - h0}), out={out.count} exact=True")


# ---------------------------------------------------------------------------
# --chips 4: the multi-chip path and what it is compared with
# ---------------------------------------------------------------------------


def _new_live_devices(before_ids):
    import jax

    fresh = [a for a in jax.live_arrays() if id(a) not in before_ids]
    widest = max((len(a.sharding.device_set) for a in fresh), default=0)
    devices = set()
    for a in fresh:
        devices |= set(a.sharding.device_set)
    return widest, devices


def phase_multichip(seed: int, chips: int) -> None:
    import jax

    from fluvio_tpu.partition.placement import (
        parse_placement_rules,
        partition_key,
        plan_placement,
    )
    from fluvio_tpu.partition.runtime import PartitionRuntime

    n = RECORDS
    values, nums = gen_json(n, seed)
    buf = pack(values)

    # record-sharded executor vs the one-device executor, same batch
    one = build_chain("tpu", NORTH_STAR_SPECS)
    t0 = time.time()
    ref_out = one.tpu_chain.process_buffer(buf)
    say(f"multichip: one-device north-star out={ref_out.count} "
        f"wall_s={time.time() - t0:.2f}")
    ref_flat, ref_off, ref_od = out_columns(ref_out)
    want = north_star_host_reference(values)
    assert [int(o) for o in ref_od] == [o for o, _ in want]
    before = {id(a) for a in jax.live_arrays()}
    sharded = build_chain("tpu", NORTH_STAR_SPECS, mesh=chips)
    ex = sharded.tpu_chain
    assert ex._sharded is not None and ex._sharded.n == chips, "not sharded"
    t0 = time.time()
    handle = ex.dispatch_buffer(buf)
    widest, devices = _new_live_devices(before)
    assert widest == chips and len(devices) == chips, (
        f"sharded dispatch: arrays span {widest} devices, {len(devices)} "
        f"distinct — the mesh folded"
    )
    out = ex.finish_buffer(buf, handle)
    flat, off, od = out_columns(out)
    assert np.array_equal(od, ref_od) and np.array_equal(off, ref_off)
    assert np.array_equal(flat, ref_flat), "sharded output != one-device output"
    say(f"multichip: record-sharded north-star over {chips} devices "
        f"out={out.count} == one-device, wall_s={time.time() - t0:.2f}")
    del handle, out, ref_out

    # 9_partitioned: `chips` partitions spread over `chips` device groups
    specs = [
        ("regex-filter", {"regex": "fluvio"}),
        ("aggregate-field", {"field": "n", "combine": "add"}),
    ]
    parts = chips
    per_vals = [values[p::parts] for p in range(parts)]
    bufs = [pack(v) for v in per_vals]
    pat = re.compile(rb"fluvio")
    # the one-device comparison: each partition's slice through ONE
    # single-device chain (one compile); its running-sum carry advances
    # by exactly that partition's sum
    c1 = build_chain("tpu", specs)
    single_sums, single_counts, prev = [], [], 0
    for p in range(parts):
        o1 = c1.tpu_chain.process_buffer(bufs[p])
        c1.tpu_chain._ensure_host_state()
        single_sums.append(c1.tpu_chain.carries[0][0] - prev)
        prev = c1.tpu_chain.carries[0][0]
        single_counts.append(o1.count)
    chain = build_chain("tpu", specs)
    plan = plan_placement(
        parse_placement_rules(".*=spread"),
        [partition_key("smoke", p) for p in range(parts)],
        chips,
    )
    before = {id(a) for a in jax.live_arrays()}
    rt = PartitionRuntime(chain.tpu_chain, plan, chain=chain)
    grid = np.asarray(rt.mesh.devices)
    assert grid.shape[0] == chips, f"partition mesh folded to {grid.shape}"
    t0 = time.time()
    counts = {}
    for topic, p, _b, o in rt.process_interleaved(
        [("smoke", p, bufs[p]) for p in range(parts)]
    ):
        counts[p] = o.count
    _, devices = _new_live_devices(before)
    assert len(devices) == chips, (
        f"partition carries sit on {len(devices)} devices, want {chips}"
    )
    for p in range(parts):
        got = rt.carry_snapshot("smoke", p)[0][0]
        host = sum(
            int(nums[i]) for i in range(p, n, parts) if pat.search(values[i])
        )
        assert got == single_sums[p] == host, (p, got, single_sums[p], host)
        assert counts[p] == single_counts[p], (p, counts[p], single_counts[p])
    say(f"multichip: 9_partitioned {parts} partitions over {grid.shape[0]} "
        f"device groups: per-partition sums == one-device == host, "
        f"wall_s={time.time() - t0:.2f}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2024)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    device = require_device(args.chips)
    modes = resolved_modes()
    say(f"modes: {json.dumps(modes, sort_keys=True)}")
    if args.chips > 1:
        phase_multichip(args.seed, args.chips)
    else:
        phase_broker(args.seed)
        phase_chains(args.seed)
        phase_truth(modes)
        phase_drill(args.seed)
    say(f"done in {time.time() - _T0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
