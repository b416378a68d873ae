"""North-star benchmark: SmartModule chain records/sec on the real chip.

Runs ALL FIVE BASELINE.json configs over 1M-record batches:

  1. regex-filter                      (filter only)
  2. regex-filter + json-map           (THE headline north-star chain)
  3. aggregate (general form: sum over a JSON field via the monoid path)
  4. array_map JSON-array explode
  5. stateful windowed aggregate

and prints ONE JSON line ``{"metric", "value", "unit", "vs_baseline",
"configs"}`` where value/vs_baseline are the headline config #2 numbers
and ``configs`` carries every config's records/sec + ratio.

``vs_baseline`` is measured against this repo's native (C++) per-record
engine executing the same chain on the host CPU from the wire-encoded
slab — the reference's own wasmtime engine cannot run here (no Rust
toolchain in the image; see HOST_BASELINE.md), and the compiled per-record
loop is its execution model. Environment knobs: ``BENCH_SMOKE=1``
shrinks shapes for a fast correctness pass; ``BENCH_RECORDS=<n>``
overrides the batch size; ``BENCH_CONFIGS=2,4`` restricts the configs.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
import traceback

import numpy as np


_T0 = time.time()


def log(msg: str) -> None:
    print(f"[{time.time()-_T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def build_chain(backend: str, specs, mesh: int = 0):
    from fluvio_tpu.models import lookup
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig

    b = SmartEngine(backend=backend, mesh_devices=mesh or 0).builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize()


def _pack(values, ts=None):
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
    dst_rows = np.repeat(np.arange(n), lens)
    dst_cols = np.arange(flat.size) - np.repeat(starts, lens)
    arr[dst_rows, dst_cols] = flat
    lengths[:n] = lens
    buf = RecordBuffer.from_arrays(arr, lengths, count=n)
    buf.offset_deltas = np.arange(rows, dtype=np.int32)
    if ts is not None:
        tcol = np.zeros(rows, dtype=np.int64)
        tcol[:n] = ts
        buf.timestamp_deltas = tcol
        buf.base_timestamp = 1_000_000
    return buf


def gen_json(n: int):
    """JSON corpus: ~half the names match the regex (configs 1/2/3)."""
    rng = np.random.default_rng(2024)
    names = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "redpanda", "flink"]
    picks = rng.integers(0, len(names), size=n)
    nums = rng.integers(0, 100000, size=n)
    return [
        f'{{"name":"{names[picks[i]]}-{i & 1023}","n":{nums[i]}}}'.encode()
        for i in range(n)
    ]


def gen_arrays(n: int):
    """JSON-array corpus, ~6 elements per record (config #4)."""
    rng = np.random.default_rng(7)
    nums = rng.integers(0, 10000, size=(n, 3))
    return [
        f'["a{i & 255}","b{nums[i][0]}",{nums[i][1]},{nums[i][2]},"x","y"]'.encode()
        for i in range(n)
    ]


def gen_ints(n: int):
    rng = np.random.default_rng(11)
    nums = rng.integers(0, 1000, size=n)
    return [str(nums[i]).encode() for i in range(n)]


def gen_keyed_ints(n: int):
    """``"<key> <value>"`` two-int records for the keyed windowed
    family (config #12): 64 keys, values 0..999."""
    rng = np.random.default_rng(13)
    keys = rng.integers(0, 64, size=n)
    vals = rng.integers(0, 1000, size=n)
    return [f"{keys[i]} {vals[i]}".encode() for i in range(n)]


def _ts_event_time(n: int):
    """Monotonic event-time ms for the windowed family: 4 ms spacing
    -> 250 records per 1000 ms window. The seed corpus's cyclic
    ``% 60_000`` timestamps wrap every minute, which a watermark
    engine correctly reads as ~100% late data — useless for windows."""
    return np.arange(n, dtype=np.int64) * 4


def gen_json_300b(n: int):
    """~300-byte records: spans exceed 255 so the D2H descriptors ride
    the uint16 narrowing tier instead of uint8."""
    rng = np.random.default_rng(2025)
    names = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "redpanda", "flink"]
    picks = rng.integers(0, len(names), size=n)
    pad = "p" * 240
    return [
        f'{{"name":"{names[picks[i]]}-{i & 1023}","pad":"{pad}","n":{i}}}'.encode()
        for i in range(n)
    ]


def gen_fat_70k(n: int):
    """>64 KiB records: wider than the narrow device layout, so batches
    stage as STRIPED segments (smartengine/tpu/stripes.py) — one record
    across K fixed-width device rows sharing a segment id, filter
    verdicts reduced per segment. This config measures the striped fused
    path that replaced the record-too-wide interpreter spill."""
    body = "x" * (70 * 1024)
    return [
        f'{{"name":"fluvio-{i & 7}","body":"{body}"}}'.encode()
        for i in range(n)
    ]


CONFIGS = {
    "1_filter": {
        "specs": [("regex-filter", {"regex": "fluvio"})],
        "corpus": gen_json,
    },
    "2_filter_map": {
        "specs": [
            ("regex-filter", {"regex": "fluvio"}),
            ("json-map", {"field": "name"}),
        ],
        "corpus": gen_json,
    },
    "3_aggregate": {
        "specs": [("aggregate-field", {"field": "n", "combine": "add"})],
        "corpus": gen_json,
    },
    "4_array_map": {
        "specs": [("array-map-json", None)],
        "corpus": gen_arrays,
    },
    # windowed family (ISSUE-19): device-resident window state with
    # delta-only emission. #5 keeps the classic windowed-sum chain as
    # its A arm (the d2h-wall baseline the delta engine must cut).
    "5_windowed": {
        "specs": [("windowed-sum", {"kind": "sum_int", "window_ms": "1000"})],
        "corpus": gen_ints,
        "ts": _ts_event_time,
        "windowed": {"kind": "sum_int", "window_ms": 1000, "classic": True},
    },
    # narrowing-tier sweep (review round 3 weak #8): 300 B records push span
    # descriptors onto the uint16 tier; 70 KiB records exceed the narrow
    # layout and measure the STRIPED fused path (formerly the
    # record-too-wide interpreter fallback). ``divisor`` scales the
    # record count so the corpus stays a sane number of bytes.
    "6_wide300": {
        "specs": [
            ("regex-filter", {"regex": "fluvio"}),
            ("json-map", {"field": "name"}),
        ],
        "corpus": gen_json_300b,
        "divisor": 4,
    },
    "7_fat70k": {
        "specs": [("regex-filter", {"regex": "fluvio"})],
        "corpus": gen_fat_70k,
        "divisor": 1024,
    },
    # sharded striped: wide batches under shard_map; skips cleanly
    # when the backend has fewer devices than the mesh.
    "8_sharded_fat": {
        "specs": [("regex-filter", {"regex": "fluvio"})],
        "corpus": gen_fat_70k,
        "divisor": 1024,
        "mesh": 8,
    },
    # partitioned-topic execution (ISSUE-13): ≥2 partitions run
    # concurrently over the (partitions × records) device-group mesh
    # through the partition runtime — per-partition HBM-resident
    # aggregate carries and consumer offsets, one mid-run group
    # failure + rebalance, and a per-partition-sum exactness pin
    # against the host. Compact line carries `part:{n,rebal}`.
    "9_partitioned": {
        "specs": [
            ("regex-filter", {"regex": "fluvio"}),
            ("aggregate-field", {"field": "n", "combine": "add"}),
        ],
        "corpus": gen_json,
        "divisor": 2,
        "partitions": 4,
        "groups": 2,
    },
    # JsonGet-sourced NON-literal regex over fat records (ISSUE-16):
    # formerly the interpreter spill family, now the striped in-span
    # DFA path. The 22-state pattern crosses the legacy 16-state
    # associative gate, so this config only stays striped under the
    # class-packed 64-state default — it is the bench's live pin that
    # the raised gate + class packing actually moved a spill family.
    "10_regex_json_fat": {
        "specs": [
            ("json-regex-filter",
             {"key": "name", "regex": "^(fluvio|kafka|pulsar)-[0-3]$"}),
        ],
        "corpus": gen_fat_70k,
        "divisor": 1024,
    },
    # windowed family, engine-only members (ISSUE-19): sliding (#11,
    # fanout 4) and per-key segmented state over "k v" records (#12).
    # No classic chain can express their semantics, so their d2h
    # evidence is the hardware-independent delta-vs-full byte ratio;
    # both pin bit-equality against the host reference at EVERY batch
    # boundary. `emit`/`batch_records` size the bounded emit slice so
    # a batch's event-time span never overflows it (overflow degrades
    # to a resync, which the exactness pin would reject).
    "11_windowed_sliding": {
        "specs": [("windowed-sum", {"kind": "sum_int", "window_ms": "1000"})],
        "corpus": gen_ints,
        "ts": _ts_event_time,
        "divisor": 2,
        "windowed": {"kind": "sum_int", "window_ms": 1000, "slide_ms": 250},
    },
    "12_windowed_keyed": {
        "specs": [("windowed-sum", {"kind": "sum_int", "window_ms": "1000"})],
        "corpus": gen_keyed_ints,
        "ts": _ts_event_time,
        "divisor": 2,
        "windowed": {"kind": "sum_int", "window_ms": 1000, "keyed": True,
                     "emit": 4096, "batch_records": 8192},
    },
}


def _compile_delta(a: dict, b: dict) -> dict:
    """Diff two TELEMETRY.compile_totals() snapshots into the bench's
    per-config compile record (counts, wall seconds, trace-cache hits,
    persistent-cache hit/miss attribution)."""
    by_kind = {
        k: v - a["by_kind"].get(k, 0)
        for k, v in b["by_kind"].items()
        if v - a["by_kind"].get(k, 0)
    }
    return {
        "compiles": b["compiles"] - a["compiles"],
        "compile_s": round(b["seconds"] - a["seconds"], 2),
        "by_kind": by_kind,
        "persistent_hits": b["persistent_hits"] - a["persistent_hits"],
        "persistent_misses": b["persistent_misses"] - a["persistent_misses"],
        "cache_hits": b["jit_cache_hits"] - a["jit_cache_hits"],
    }


def _link_deltas(lv0: dict, dc0: dict) -> tuple:
    """(D2H ``down-*`` variant deltas, glz-decline deltas) since the
    captured baselines — the bench's per-config link attribution
    (which form the results crossed DOWN in, and WHY batches shipped
    unencoded)."""
    from fluvio_tpu.telemetry import TELEMETRY

    moved = {
        k: v - lv0.get(k, 0)
        for k, v in TELEMETRY.link_variant_counts().items()
        if v - lv0.get(k, 0) > 0
    }
    dn = {k: v for k, v in moved.items() if k.startswith("down-")}
    dc = {
        k: v - dc0.get(k, 0)
        for k, v in dict(TELEMETRY.declines).items()
        if k.startswith("glz-") and v - dc0.get(k, 0) > 0
    }
    return dn, dc


def bench_tpu(chain, buf, runs: int, passes: int, deadline=None) -> tuple:
    import jax

    from fluvio_tpu.telemetry import TELEMETRY

    executor = chain.tpu_chain
    # path honesty: diff the telemetry per-path record counters around
    # the run so each config reports the path it ACTUALLY executed
    # (fused / striped / interpreter) instead of a static label
    pr0 = TELEMETRY.path_records()
    # link attribution: which down-link variant each fetch used and
    # which glz decline reasons fired (feeds the per-config `link`
    # record in BENCH_DETAIL.json)
    lv0 = TELEMETRY.link_variant_counts()
    dc0 = dict(TELEMETRY.declines)
    # compile attribution: the instrumented jit entry points record
    # every trace-cache miss, so the first call splits into
    # compile-vs-execute instead of one opaque number
    ct0 = TELEMETRY.compile_totals()
    t0 = time.time()
    out = executor.process_buffer(buf)
    first_call = time.time() - t0
    ct_first = TELEMETRY.compile_totals()
    log(f"  first call (compile): {first_call:.2f}s; {out.count} records out")
    # split: dispatch covers H2D + device compute; a full call adds the
    # descriptor D2H + host materialization, attributed separately.
    t0 = time.time()
    header, packed = executor._dispatch(buf, fanout_cap=executor._fanout_cap(buf))
    jax.block_until_ready((header, packed))
    dispatch = time.time() - t0
    h0, d0 = executor.h2d_bytes_total, executor.d2h_bytes_total
    # phase attribution rides the SERIAL pass: phases are sequential
    # there, so their sum must track the measured wall time (the
    # pipelined passes below overlap device with host by design)
    pt0 = TELEMETRY.phase_totals()
    t0 = time.time()
    out = executor.process_buffer(buf)
    single = time.time() - t0
    pt1 = TELEMETRY.phase_totals()
    phase_ms = {
        k: round((pt1[k][1] - pt0[k][1]) * 1000, 2)
        for k in pt1
        if pt1[k][1] > pt0[k][1]
    }
    link_mb = (
        (executor.h2d_bytes_total - h0) / 1e6,
        (executor.d2h_bytes_total - d0) / 1e6,
    )
    log(
        f"  single-batch {single*1000:.0f}ms "
        f"(dispatch H2D+compute {dispatch*1000:.0f}ms, "
        f"fetch D2H+materialize {max(single-dispatch,0)*1000:.0f}ms; "
        f"link bytes up {link_mb[0]:.1f}MB down {link_mb[1]:.2f}MB)"
    )
    # sustained pipelined throughput over several passes: host-clock
    # times wander, so report every pass and take the median across
    # passes rather than trusting one number
    times = []
    # e2e latency baselines for EVERY path family: a striped (or
    # spilled) config records into its own histogram, and reading only
    # "fused" would silently drop its p50/p99 from the breakdown
    e2e_paths = ("fused", "striped", "interpreter")
    hist0 = {p: TELEMETRY.batch_hist_copy(p) for p in e2e_paths}
    for p in range(passes):
        if times and deadline and time.time() > deadline:
            # once one pass has landed, stop burning an exhausted
            # budget on repetitions
            log(f"  pass {p}+ skipped: budget deadline passed")
            break
        t0 = time.time()
        for out in executor.process_stream(iter([buf] * runs)):
            pass
        times.append((time.time() - t0) / runs)
        log(f"  pass {p}: pipelined {times[-1]*1000:.0f}ms/batch")
    e2e_hist = None
    for p in e2e_paths:
        d = TELEMETRY.batch_hist_copy(p).diff(hist0[p])
        e2e_hist = d if e2e_hist is None else e2e_hist.merge(d)
    phases = _phase_breakdown(
        single, phase_ms, e2e_hist,
        pipelined_s=statistics.median(times) if times else 0.0,
    )
    deltas = {
        k: v - pr0.get(k, 0)
        for k, v in TELEMETRY.path_records().items()
        if v - pr0.get(k, 0) > 0
    }
    # no counter movement (FLUVIO_TELEMETRY=0) must stay "unknown", not
    # masquerade as fused — that would be the static label all over again
    path_info = {
        "path": max(deltas, key=deltas.get) if deltas else "unknown",
        "records": deltas,
    }
    # whole-run compile record + the first call's compile-vs-execute
    # split (the execute half is everything the first call did that was
    # not a recorded compile: staging, transfer, device, fetch)
    compile_info = _compile_delta(ct0, TELEMETRY.compile_totals())
    fc_compile = _compile_delta(ct0, ct_first)["compile_s"]
    compile_info["first_call_compile_s"] = fc_compile
    compile_info["first_call_execute_s"] = round(
        max(first_call - fc_compile, 0.0), 2
    )
    log(
        f"  compiles: {compile_info['compiles']} "
        f"({compile_info['compile_s']}s; first call "
        f"{fc_compile}s compile + "
        f"{compile_info['first_call_execute_s']}s execute; "
        f"pc {compile_info['persistent_hits']}h/"
        f"{compile_info['persistent_misses']}m)"
    )
    down_variants, glz_declines = _link_deltas(lv0, dc0)
    link_info = {
        "up_mb": round(link_mb[0], 2),
        "down_mb": round(link_mb[1], 2),
        # D2H (result) side: which form the outputs crossed down in —
        # the ISSUE-12 compaction/encode ladder's per-config evidence
        # (majority engaged variant; mixed runs keep the histogram)
        "down_variant": (
            max(down_variants, key=down_variants.get)
            if down_variants
            else "off"
        ),
        "down_variants": down_variants,
    }
    if glz_declines:
        link_info["declines"] = glz_declines
    log(f"  link: {link_info}")
    return (out, times, first_call, link_mb, phases, path_info,
            compile_info, link_info)


def _phase_breakdown(
    single_s: float, phase_ms: dict, e2e_hist, pipelined_s: float = 0.0
) -> dict:
    """Compact per-phase record for BENCH_DETAIL.json: serial-pass wall
    + per-phase ms (their sum must track the wall within ~10%), p50/p99
    end-to-end batch latency across the pipelined passes, the top-3
    phase shares of attributed time, and the fetch-overlap ratio —
    what fraction of the serial pass's d2h+fetch time the pipelined
    loop hid behind other batches' phases (1.0 = the result side is
    fully off the critical path; 0 = it serializes)."""
    total = sum(phase_ms.values())
    top = sorted(phase_ms.items(), key=lambda kv: -kv[1])[:3]
    out = {
        "wall_ms": round(single_s * 1000, 2),
        "phase_sum_ms": round(total, 2),
        "phase_ms": phase_ms,
        "top": [
            [name, round(ms / total, 2) if total else 0.0] for name, ms in top
        ],
    }
    fetch_side = phase_ms.get("fetch", 0.0) + phase_ms.get("d2h", 0.0)
    if pipelined_s and fetch_side > 0:
        hidden = single_s * 1000 - pipelined_s * 1000
        out["fetch_overlap"] = round(
            max(0.0, min(1.0, hidden / fetch_side)), 2
        )
    if e2e_hist.count:
        out["e2e_p50_ms"] = round(e2e_hist.percentile(50) * 1000, 2)
        out["e2e_p99_ms"] = round(e2e_hist.percentile(99) * 1000, 2)
    return out


def bench_host_baseline(specs, values, ts, base_n: int, backend: str) -> float:
    """Per-record engine on a subset; returns records/sec.

    ``native`` is the honest wasmtime proxy (compiled C++ per-record
    loops from the wire-encoded slab, the reference engine's execution
    model); ``python`` is the interpreted floor. Timestamps ride along
    so windowed aggregates do the same window-reset work as the TPU run.
    """
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    from fluvio_tpu.smartengine.engine import EngineError

    try:
        chain = build_chain(backend, specs)
    except EngineError:
        return 0.0
    if backend == "native" and chain.backend_in_use != "native":
        return 0.0
    base_ts = 1_000_000 if ts is not None else -1
    records = [Record(value=v) for v in values[:base_n]]
    for i, r in enumerate(records):
        r.offset_delta = i
        if ts is not None:
            r.timestamp_delta = int(ts[i])
    if backend == "native":
        from fluvio_tpu.protocol.codec import ByteWriter

        w = ByteWriter()
        for r in records:
            r.encode(w)
        inp = SmartModuleInput.from_raw(
            w.bytes(), base_n, base_timestamp=base_ts
        )
    else:
        inp = SmartModuleInput.from_records(records, base_timestamp=base_ts)
    t0 = time.time()
    out = chain.process(inp)
    dt = time.time() - t0
    assert out.error is None
    return base_n / dt


def verify_outputs(specs, values, ts, check_n: int) -> None:
    """Fresh-chain spot-check: TPU outputs equal the reference engine's
    (fresh chains on both sides so stateful accumulators start equal)."""
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartmodule import SmartModuleInput

    def run(backend):
        chain = build_chain(backend, specs)
        records = [Record(value=v) for v in values[:check_n]]
        for i, r in enumerate(records):
            r.offset_delta = i
            if ts is not None:
                r.timestamp_delta = int(ts[i])
        out = chain.process(
            SmartModuleInput.from_records(records, 0, 1_000_000)
        )
        assert out.error is None
        return [(r.value, r.key, r.offset_delta) for r in out.successes]

    got, ref = run("tpu"), run("python")
    assert got == ref, "TPU output diverged from reference engine"
    log(f"  verified {len(ref)} outputs byte-equal to reference")


def _run_partitioned_config(
    name: str, cfg: dict, n: int, smoke: bool, deadline=None
) -> dict:
    """Partitioned-topic measurement (ISSUE-13): P partition streams
    interleave through one PartitionRuntime over the (partitions ×
    records) device-group mesh — per-partition HBM-resident carries +
    consumer offsets, one injected group failure + rebalance between
    measured passes, and an exactness pin: the per-partition aggregate
    sums must reproduce the host-computed per-partition truth."""
    from fluvio_tpu.partition.placement import (
        parse_placement_rules,
        partition_key,
        plan_placement,
    )
    from fluvio_tpu.partition.runtime import PartitionRuntime
    from fluvio_tpu.telemetry import TELEMETRY

    parts = int(cfg["partitions"])
    groups = int(cfg.get("groups", 2))
    divisor = cfg.get("divisor", 1)
    if divisor > 1:
        n = max(n // divisor, 1024)
    runs = 2 if smoke else 3
    log(f"[{name}] generating {n} records over {parts} partitions ...")
    values = cfg["corpus"](n)
    # preflight: the partitioned path executes the same predicted
    # ladder per partition; predicted-vs-actual lands below
    preflight = None
    try:
        from fluvio_tpu.analysis import preflight_for_specs

        preflight = preflight_for_specs(
            cfg["specs"], max(len(v) for v in values)
        )
        log(f"  preflight: predicted path {preflight['path']}")
    except Exception as e:  # noqa: BLE001 — analysis must never cost a run
        log(f"  preflight analysis failed: {type(e).__name__}: {e}")
    # round-robin split: partition p owns values[p::parts]
    per_part = [values[p::parts] for p in range(parts)]
    bufs = [_pack(v) for v in per_part]
    chain = build_chain("tpu", cfg["specs"])
    assert chain.backend_in_use == "tpu", name
    # spread, not hash: the measurement wants BOTH groups owning
    # partitions so the injected group failure really moves some
    plan = plan_placement(
        parse_placement_rules(".*=spread"),
        [partition_key("bench", p) for p in range(parts)],
        groups,
    )
    runtime = PartitionRuntime(chain.tpu_chain, plan, chain=chain)
    # streaming-lag evidence (ISSUE-15): each partition gets a stand-in
    # leader whose LEO advances as the pass "appends" its slice, so the
    # lag engine's committed-vs-HW join and the record-age histogram
    # (append stamp -> served) produce real numbers for the lag block
    from fluvio_tpu.telemetry import lag as lag_mod

    class _BenchLeader:
        def __init__(self):
            self._leo = 0

        def leo(self):
            return self._leo

        def hw(self):
            return self._leo

    leaders = {}
    for p in range(parts):
        key = partition_key("bench", p)
        leaders[key] = _BenchLeader()
        runtime.offsets.attach_leader(key, leaders[key])
    pr0 = TELEMETRY.path_records()
    stream = [("bench", p, bufs[p]) for p in range(parts)]
    t0 = time.time()
    for _ in runtime.process_interleaved(list(stream)):
        pass
    first_call = time.time() - t0
    # elastic-rebalancer evidence (ISSUE-18): an injected lag skew pins
    # partition 0 hot on its device group and the armed daemon MOVES it
    # onto the colder group before the measured passes — the timings
    # below therefore include a voluntary live migration (lazy carry
    # re-placement at next dispatch) on top of the injected group
    # failure, and the exactness pin must close across BOTH
    reb_block = None
    try:
        from fluvio_tpu.partition.rebalancer import (
            PartitionRebalancer,
            RebalanceConfig,
            rebalance_enabled,
        )

        if groups > 1 and rebalance_enabled():
            clock = [0.0]
            hot_key = partition_key("bench", 0)
            lags = {hot_key: float(bufs[0].count)}

            def _mover(key, group, reason):
                topic, _, pstr = key.rpartition("/")
                return runtime.move_partition(topic, int(pstr), group)

            reb = PartitionRebalancer(
                lambda: runtime.plan,
                _mover,
                config=RebalanceConfig(
                    interval_s=0.0, burn=1.0, cooldown_s=0.0,
                    max_moves=1, hysteresis=4.0,
                ),
                clock=lambda: clock[0],
                lag_reader=lambda: dict(lags),
            )
            src = runtime.plan.assignments.get(hot_key)
            reb.tick()  # first sighting seeds the burn baseline
            clock[0] += 1.0
            reb.tick()  # stalled backlog -> hot -> voluntary move
            reb_block = {
                "moves": reb.moves_total,
                "rollbacks": reb.rollbacks,
                "from": src,
                "to": runtime.plan.assignments.get(hot_key),
                "drain_s": None,  # the first measured pass below
            }
            log(
                f"  rebalance: {reb.moves_total} voluntary move(s) "
                f"g{src} -> g{reb_block['to']}"
            )
    except Exception as e:  # noqa: BLE001 — evidence must not cost a run
        log(f"  rebalance evidence unavailable: {type(e).__name__}: {e}")
    times = []
    rebal_done = False
    for r in range(runs):
        if r == 1 and groups > 1 and not rebal_done:
            # injected group failure between passes: the survivors take
            # over (carries migrate at next dispatch) — the timing of
            # later passes INCLUDES the rebalanced layout
            runtime.fail_group(0)
            rebal_done = True
        t_append = time.time()
        for p in range(parts):
            leaders[partition_key("bench", p)]._leo += bufs[p].count
        t0 = time.time()
        for topic, p, buf, out in runtime.process_interleaved(list(stream)):
            key = partition_key(topic, p)
            runtime.offsets.advance(
                key, runtime.offsets.committed(key) + buf.count
            )
            lag_mod.note_serve(
                key, int(buf.count), max(time.time() - t_append, 0.0)
            )
        times.append(time.time() - t0)
        if deadline is not None and time.time() > deadline:
            break
    t_med = statistics.median(times)
    tpu_rps = n / t_med
    log(
        f"  partitioned tpu: {[f'{t*1000:.0f}ms' for t in times]} -> "
        f"{tpu_rps:,.0f} records/s across {parts} partitions"
    )
    # exactness pin: each partition's final aggregate carry must equal
    # the host-computed sum over ITS slice of the corpus, across
    # 1 + runs passes and the mid-run rebalance
    exact = True
    try:
        import json as _json
        import re as _re

        field = cfg["specs"][-1][1]["field"]
        pat = _re.compile(cfg["specs"][0][1]["regex"].encode())
        for p in range(parts):
            # host truth mirrors the chain: only records surviving the
            # regex filter reach the aggregate
            want = sum(
                _json.loads(v).get(field, 0)
                for v in per_part[p]
                if pat.search(v)
            ) * (1 + len(times))
            got = runtime.carry_snapshot("bench", p)[0][0]
            if got != want:
                exact = False
                log(f"  EXACTNESS FAIL p{p}: device {got} != host {want}")
    except Exception as e:  # noqa: BLE001 — the pin must not kill the run
        log(f"  exactness pin unavailable: {type(e).__name__}: {e}")
        exact = None
    deltas = {
        k: v - pr0.get(k, 0)
        for k, v in TELEMETRY.path_records().items()
        if v - pr0.get(k, 0) > 0
    }
    path = max(deltas, key=deltas.get) if deltas else "unknown"
    base_rps = bench_host_baseline(
        cfg["specs"], values, None, min(n, 2000 if smoke else 20000), "native"
    ) or bench_host_baseline(
        cfg["specs"], values, None, min(n, 2000), "python"
    )
    result = {
        "records_per_sec": round(tpu_rps),
        "payload_mb_per_sec": round(
            sum(len(v) for v in values) / t_med / 1e6, 1
        ),
        "baseline_records_per_sec": round(base_rps),
        "vs_baseline": round(tpu_rps / base_rps, 2) if base_rps else None,
        "pass_ms": [round(t * 1000) for t in times],
        "first_call_s": round(first_call, 2),
        "path": path,
        "path_records": deltas,
        # the partition evidence block (compact line: part:{n,rebal})
        "part": {
            "n": parts,
            "groups": groups,
            "rebal": runtime.rebalances,
            "moves": runtime.moves,
            "exact": exact,
            "offsets": runtime.offsets.snapshot(),
            "plan": runtime.plan.to_dict()["assignments"],
        },
    }
    # the rebalance evidence block (compact line: rebal:{moves,drain_s})
    if reb_block is not None and times:
        reb_block["drain_s"] = round(times[0], 3)
        result["rebalance"] = reb_block
    # per-config streaming-lag block (ISSUE-15): max residual consumer
    # lag across partitions after the run + worst record-age p99. The
    # compact line carries one tiny suite-wide lag:{max,age_p99} key;
    # full per-partition detail stays in BENCH_DETAIL.json
    try:
        lag_mod.engine().sample()
        per_part_lag = lag_mod.engine().snapshot()
        if per_part_lag:
            result["lag"] = {
                "max": max(
                    int(e.get("lag", 0)) for e in per_part_lag.values()
                ),
                "age_p99_ms": max(
                    float(e.get("age_p99_ms", 0.0))
                    for e in per_part_lag.values()
                ),
                "per_partition": per_part_lag,
            }
            log(
                f"  lag: max {result['lag']['max']} records, "
                f"age_p99 {result['lag']['age_p99_ms']:.0f}ms"
            )
    except Exception as e:  # noqa: BLE001 — lag evidence must not cost a run
        log(f"  lag evidence unavailable: {type(e).__name__}: {e}")
    if preflight is not None:
        preflight["actual"] = path
        preflight["agree"] = (
            preflight["path"] == path if path != "unknown" else None
        )
        result["preflight"] = preflight
    return result


def _run_windowed_config(
    name: str, cfg: dict, n: int, smoke: bool, deadline=None
) -> dict:
    """Windowed-family driver (ISSUE-19): the delta-only windowed-state
    engine measured against host truth at every batch boundary.

    Two arms. The **classic arm** (``windowed.classic``, config #5
    only) runs the pre-existing ship-every-record windowed-sum chain
    through `_run_config` — its serial-pass ``phases.phase_ms.d2h`` is
    the downlink wall the delta engine must cut >=3x. The **delta arm**
    streams the same corpus through `WindowedRuntime` in batches: the
    window bank never leaves the device, only closed windows + changed
    accumulators cross down (`WindowDelta`), folded into a
    `MaterializedView` and pinned bit-equal against
    `HostWindowReference` — table AND device carry — after EVERY batch.
    Engine-only members (sliding/keyed) have no classic chain for their
    semantics; their d2h evidence is the hardware-independent
    delta-vs-full byte ratio."""
    from fluvio_tpu.telemetry import TELEMETRY
    from fluvio_tpu.windows import (
        HostWindowReference,
        MaterializedView,
        WindowSpec,
        WindowedRuntime,
    )
    from fluvio_tpu.windows.spec import KIND_TO_OP, delta_enabled

    w = cfg["windowed"]
    spec = WindowSpec(
        window_ms=int(w["window_ms"]),
        slide_ms=int(w.get("slide_ms", 0)),
        op=KIND_TO_OP[str(w.get("kind", "sum_int"))],
        keyed=bool(w.get("keyed", False)),
        emit_capacity=int(w.get("emit", 0)),
        delta_only=delta_enabled(),
    )

    result = None
    if w.get("classic"):
        result = _run_config(name, cfg, n, smoke, deadline, headline=False)
    divisor = cfg.get("divisor", 1)
    if divisor > 1:
        n = max(n // divisor, 1024)

    log(f"[{name}] delta arm: {spec.describe()} over {n} records")
    values = cfg["corpus"](n)
    ts = cfg["ts"](n)

    preflight = result.get("preflight") if result else None
    if preflight is None:
        try:
            from fluvio_tpu.analysis import preflight_for_specs

            preflight = preflight_for_specs(
                cfg["specs"], max(len(v) for v in values)
            )
            log(
                "  preflight: predicted window variant "
                f"{preflight.get('window_variant', 'off')}"
            )
        except Exception as e:  # noqa: BLE001 — analysis must never cost a run
            log(f"  preflight analysis failed: {type(e).__name__}: {e}")

    per = int(w.get("batch_records", 16384))
    if smoke:
        # smoke still wants several inter-batch carry boundaries
        per = min(per, max(n // 6, 512))
    # even split: a runt tail batch would land in a smaller padded-rows
    # shape bucket and pay a full fresh compile for 2 records
    n_batches = max(1, -(-n // per))
    per = -(-n // n_batches)
    slices = [(a, min(a + per, n)) for a in range(0, n, per)]

    ref = HostWindowReference(spec)
    view = MaterializedView(spec)
    rt = WindowedRuntime(spec)
    ct0 = TELEMETRY.compile_totals()
    pt0 = TELEMETRY.phase_totals()
    wc0 = TELEMETRY.window_counts()
    bt = []  # per-batch device-arm seconds
    ref_wall = 0.0  # host-truth fold seconds (the python baseline)
    rows_kind = 0  # deltas that shipped as delta rows (vs resync)
    pt_warm = None  # phase totals AFTER the compile-paying first batch
    for a, b in slices:
        buf = _pack(values[a:b], ts[a:b])
        t0 = time.time()
        delta = rt.process_buffer(buf)
        bt.append(time.time() - t0)
        if pt_warm is None:
            pt_warm = TELEMETRY.phase_totals()
        view.apply_delta(delta)
        rows_kind += delta.kind == "rows"
        # host truth over the same records at the same absolute event
        # time (_pack stamps base_timestamp=1_000_000). The corpora are
        # pure ASCII ints, so int() matches the kernel's leading-int
        # parse exactly.
        t0 = time.time()
        if spec.keyed:
            recs = []
            for r, t in zip(values[a:b], ts[a:b]):
                k, v = r.split(b" ", 1)
                recs.append((int(k), int(v), int(t) + 1_000_000))
        else:
            recs = [
                (0, int(r), int(t) + 1_000_000)
                for r, t in zip(values[a:b], ts[a:b])
            ]
        ref.process_batch(recs)
        ref_wall += time.time() - t0
        # the exactness pins: device carry bit-equal after EVERY batch;
        # the materialized view's full table under delta-only emission
        assert rt.bank.snapshot() == ref.bank_entries(), (
            f"{name}: device carry diverged from host at record {b}"
        )
    # full-table pin holds on BOTH emission variants: resync deltas
    # carry the batch's closes, so FLUVIO_WINDOW_DELTA=0 converges too
    assert view.table() == ref.table(), (
        f"{name}: materialized view diverged from host reference"
    )

    wc1 = TELEMETRY.window_counts()
    kinds = {
        k: v - wc0[1].get(k, 0)
        for k, v in wc1[1].items()
        if v - wc0[1].get(k, 0)
    }
    delta_bytes = wc1[2] - wc0[2]
    full_bytes = wc1[3] - wc0[3]
    pt1 = TELEMETRY.phase_totals()

    def _d2h_ms(since):
        return round(
            (pt1.get("d2h", (0, 0.0))[1] - since.get("d2h", (0, 0.0))[1])
            * 1000,
            2,
        )

    d2h_ms = _d2h_ms(pt0)
    # warm d2h: the classic arm's phase split comes from a warm serial
    # pass, so the apples-to-apples delta-arm number excludes the first
    # batch's one-time slice-bucket compile
    warm_records = n - (slices[0][1] - slices[0][0])
    d2h_warm_ms = _d2h_ms(pt_warm) if len(slices) > 1 else d2h_ms
    # first batch pays the window-kernel compiles (attributed below);
    # steady-state throughput is the warm batches' median
    warm = bt[1:] or bt
    rps = per / statistics.median(warm)
    base_rps = n / ref_wall if ref_wall else 0.0
    log(
        f"  delta arm: {rps:,.0f} records/s warm "
        f"({len(slices)} batches, first {bt[0]*1000:.0f}ms), "
        f"delta {delta_bytes/1e6:.3f}MB vs full {full_bytes/1e6:.3f}MB"
    )

    win = {
        "mode": spec.mode,
        "keys": len({k for (k, _s) in ref.table()}),
        "batches": len(slices),
        "closed": wc1[0] - wc0[0],
        "late": kinds.get("late", 0),
        "deltas": {k: v for k, v in kinds.items() if k != "late"},
        "delta_mb": round(delta_bytes / 1e6, 3),
        "full_mb": round(full_bytes / 1e6, 3),
        # the hardware-independent acceptance signal: what fraction of
        # the classic per-record emission's bytes the deltas shipped
        "delta_ratio": (
            round(delta_bytes / full_bytes, 4) if full_bytes else None
        ),
        "d2h_ms_delta": d2h_ms,
        "d2h_ms_delta_warm": d2h_warm_ms,
        "rps_delta": round(rps),
        "state_bytes": rt.bank.state_bytes(),
        "exact": True,  # the asserts above did not fire
    }
    observed = "win-delta" if rows_kind >= len(slices) / 2 else "win-full"
    if result is not None:
        classic_d2h = (result.get("phases") or {}).get("phase_ms", {}).get(
            "d2h"
        )
        if classic_d2h:
            # warm-for-warm: the classic phases ride a warm serial pass
            # over n records; scale it to the delta arm's warm record
            # count before comparing
            classic_warm = classic_d2h * warm_records / n
            win["d2h_ms_classic"] = classic_d2h
            win["d2h_cut"] = round(
                classic_warm / max(d2h_warm_ms, 0.01), 1
            )
            log(
                f"  d2h: classic {classic_d2h}ms -> delta warm "
                f"{d2h_warm_ms}ms ({win['d2h_cut']}x)"
            )
    else:
        result = {
            "records_per_sec": round(rps),
            "pass_ms": [round(t * 1000) for t in bt],
            "first_call_s": round(bt[0], 2),
            "baseline_records_per_sec": round(base_rps),
            "vs_baseline": round(rps / base_rps, 2) if base_rps else None,
            "compile": _compile_delta(ct0, TELEMETRY.compile_totals()),
            "path": "windowed",
            "path_records": {"windowed": n},
        }
    result["win"] = win
    if preflight is not None:
        # windowed agreement: predicted emission variant vs the one the
        # deltas actually shipped under; a classic arm's path agreement
        # (when judgeable) must hold too
        path_agree = preflight.get("agree")
        win_agree = preflight.get("window_variant", "off") == observed
        preflight["window_actual"] = observed
        preflight["agree"] = (
            win_agree if path_agree is None else (path_agree and win_agree)
        )
        preflight.setdefault("actual", observed)
        result["preflight"] = preflight
    return result


def run_config(name: str, cfg: dict, n: int, smoke: bool, deadline=None) -> dict:
    # per-config device-memory attribution: restart the ledger's
    # config watermark so the mem block charges peak bytes to THIS
    # config, then attach the block to whatever the run produced
    _mem_reset_peak()
    result = _dispatch_config(name, cfg, n, smoke, deadline)
    _attach_memory_block(result)
    return result


def _mem_reset_peak() -> None:
    try:
        from fluvio_tpu.telemetry import memory as memory_mod

        eng = memory_mod.peek()
        if eng is not None:
            eng.reset_peak()
    except Exception:  # noqa: BLE001 — accounting must never cost a run
        pass


def _attach_memory_block(result) -> None:
    """Per-config ``memory`` block for BENCH_DETAIL.json (the compact
    line's tiny ``mem`` key summarizes across configs)."""
    try:
        from fluvio_tpu.telemetry import memory as memory_mod

        blk = memory_mod.bench_block()
        if blk and isinstance(result, dict) and "skipped" not in result:
            result["memory"] = blk
    except Exception:  # noqa: BLE001 — accounting must never cost a run
        pass


def _dispatch_config(
    name: str, cfg: dict, n: int, smoke: bool, deadline=None
) -> dict:
    if cfg.get("partitions"):
        return _run_partitioned_config(name, cfg, n, smoke, deadline)
    if cfg.get("windowed"):
        return _run_windowed_config(name, cfg, n, smoke, deadline)
    headline = name == "2_filter_map"
    return _run_config(name, cfg, n, smoke, deadline, headline)


def _run_config(
    name: str,
    cfg: dict,
    n: int,
    smoke: bool,
    deadline,
    headline: bool,
) -> dict:
    runs = (3 if smoke else 5) if headline else (2 if smoke else 3)
    passes = 3 if headline else 2
    divisor = cfg.get("divisor", 1)
    if divisor > 1:
        n = max(n // divisor, 1024)
    base_n = min(n, 2000 if smoke else 20000)

    mesh = int(cfg.get("mesh", 0))
    if mesh:
        import jax

        n_dev = len(jax.devices())
        if n_dev < mesh:
            log(f"[{name}] skipped: mesh={mesh} but {n_dev} device(s)")
            return {"skipped": f"needs {mesh} devices (have {n_dev})"}

    log(f"[{name}] generating {n} records ...")
    values = cfg["corpus"](n)
    ts = cfg["ts"](n) if "ts" in cfg else None

    # preflight static analysis (fluvio_tpu/analysis/): predict the
    # executed path for THIS corpus's width before dispatching anything;
    # after the run the telemetry-observed path lands next to it so
    # BENCH_DETAIL.json shows predicted-vs-actual per config
    preflight = None
    try:
        from fluvio_tpu.analysis import preflight_for_specs

        preflight = preflight_for_specs(
            cfg["specs"], max(len(v) for v in values), sharded=bool(mesh)
        )
        log(f"  preflight: predicted path {preflight['path']}")
    except Exception as e:  # noqa: BLE001 — analysis must never cost a run
        log(f"  preflight analysis failed: {type(e).__name__}: {e}")

    if name in ("7_fat70k", "10_regex_json_fat"):
        # sanity: the striped layout must engage (no record-too-wide or
        # JsonGet-regex spill left in the matrix) — a chain that
        # silently fell back would report interpreter numbers under a
        # fused label
        probe = build_chain("tpu", cfg["specs"])
        assert probe.backend_in_use == "tpu", name
        assert probe.tpu_chain._striped_chain() is not None, (
            f"{name} chain must lower striped"
        )
    buf = _pack(values, ts)

    # SLO satellite: run-scoped verdict block — a private time-series
    # force-ticked around the measurement, so the windowed rules read
    # exactly this config's observations (not the whole suite's)
    slo_eng = None
    try:
        from fluvio_tpu.telemetry import slo as slo_mod
        from fluvio_tpu.telemetry.timeseries import TimeSeries

        slo_eng = slo_mod.SloEngine(timeseries=TimeSeries(
            window_s=3600.0, capacity=2
        ))
    except Exception as e:  # noqa: BLE001 — SLO must never cost a run
        log(f"  slo engine unavailable: {type(e).__name__}: {e}")

    verify_outputs(cfg["specs"], values, ts, min(n, 512))
    chain = build_chain("tpu", cfg["specs"], mesh=mesh)
    assert chain.backend_in_use == "tpu", name

    # admission satellite: with the AOT warmup gate armed, precompile
    # this corpus's shape bucket BEFORE the measurement — the bench's
    # per-config `compile` delta then reads ZERO serve-time compiles
    # (the acceptance signal) and the `admission` block records what
    # the warmup paid
    adm_warm = None
    adm0 = None
    try:
        from fluvio_tpu.admission import warmup as adm_warmup
        from fluvio_tpu.telemetry import TELEMETRY as _TEL

        adm0 = dict(_TEL.admission)
        if adm_warmup.warmup_enabled() and not chain.tpu_chain._fanout:
            # exact-coverage warmup: dispatch the corpus buffer's shape
            # TWIN (same rows/width/flat buckets, synthetic bytes), so
            # the measured passes below compile NOTHING — the per-config
            # `compile` delta is the zero-serve-compiles acceptance pin.
            # Fan-out chains skip it: the twin's element density would
            # perturb the learned capacity ratio the real corpus needs
            rep = adm_warmup.warm_buffer(chain.tpu_chain, buf)
            adm_warm = {
                "buckets": len(rep.buckets),
                "compiles": rep.compiles,
                "compile_s": round(rep.compile_s, 2),
            }
            log(f"  admission warmup: {adm_warm}")
    except Exception as e:  # noqa: BLE001 — admission must never cost a run
        log(f"  admission warmup failed: {type(e).__name__}: {e}")
    if slo_eng is not None:
        # the verdict window opens HERE, after verify/build/warmup: the
        # counters the time-series samples are suite-cumulative, so a
        # tick taken before those steps let their compiles land in
        # every config's window and flagged configs that compiled
        # nothing themselves
        slo_eng.timeseries.force_tick()
    try:
        (out, times, first_call, link_mb, phases, path_info, compile_info,
         link_info) = bench_tpu(chain, buf, runs, passes, deadline)
    except Exception as e:
        # hardening vs the round-5 parsed:null class: a config that
        # dies mid-measurement still contributes its link evidence to
        # the emitted line (run_suite merges `bench_partial` into the
        # error entry)
        e.bench_partial = {
            "link": {
                "up_mb": round(chain.tpu_chain.h2d_bytes_total / 1e6, 2),
            }
        }
        raise
    t_med = statistics.median(times)
    tpu_rps = n / t_med
    # payload throughput: the per-byte view is what makes record-width
    # configs comparable (wide records cost more per record by design)
    corpus_bytes = sum(len(v) for v in values)
    tpu_mbps = corpus_bytes / t_med / 1e6
    log(
        f"  tpu: {[f'{t*1000:.0f}ms' for t in times]} -> "
        f"{tpu_rps:,.0f} records/s ({tpu_mbps:.1f} MB/s payload)"
    )

    native_rps = bench_host_baseline(
        cfg["specs"], values, ts, min(n, base_n * 10), "native"
    )
    py_rps = 0.0
    if not native_rps:
        py_rps = bench_host_baseline(cfg["specs"], values, ts, base_n, "python")
    base_rps = native_rps or py_rps
    log(
        f"  {'native C++' if native_rps else 'python'} baseline: "
        f"{base_rps:,.0f} records/s"
    )
    result = {
        "records_per_sec": round(tpu_rps),
        "payload_mb_per_sec": round(tpu_mbps, 1),
        "baseline_records_per_sec": round(base_rps),
        "vs_baseline": round(tpu_rps / base_rps, 2) if base_rps else None,
        "pass_ms": [round(t * 1000) for t in times],
        # compile-cache amortization evidence (review round 4 weak #7): a warm
        # persistent XLA cache makes this <2s; cold compiles are 20-40s
        "first_call_s": round(first_call, 2),
        # per-config compile breakdown (telemetry jit instrumentation):
        # counts + wall seconds by entry-point kind, trace-cache hits,
        # persistent-.xla_cache hit/miss, and the first call split into
        # compile-vs-execute — replaces reading the crude suite-level
        # cache-direntry diff as the only compile evidence
        "compile": compile_info,
        "link_mb": [round(m, 2) for m in link_mb],
        # per-config link breakdown (ISSUE-8): link MB both ways, the
        # down-link variant the results shipped under (telemetry
        # link_variants deltas) and which glz decline reasons fired
        "link": link_info,
        # per-phase breakdown (telemetry subsystem): serial-pass wall +
        # phase attribution + pipelined p50/p99 end-to-end
        "phases": phases,
        # the ACTUALLY executed path (from telemetry counters, not a
        # static label): fused / striped / interpreter, plus the raw
        # per-path record deltas for mixed runs
        "path": path_info["path"],
        "path_records": path_info["records"],
    }
    if adm0 is not None:
        # admission evidence: shed decisions during the measurement +
        # the warmed-bucket count (compact line carries a tiny
        # adm:{shed,warm} key; this block is the detail-file record)
        try:
            from fluvio_tpu.admission.types import SHED_REASONS
            from fluvio_tpu.telemetry import TELEMETRY as _TEL2

            shed = sum(
                v - adm0.get(k, 0)
                for k, v in dict(_TEL2.admission).items()
                if k in SHED_REASONS
            )
            if adm_warm is not None or shed:
                result["admission"] = {
                    "shed": shed,
                    "warm": (adm_warm or {}).get("buckets", 0),
                }
                if adm_warm is not None:
                    result["admission"]["warmup"] = adm_warm
        except Exception:  # noqa: BLE001 — admission must never cost a run
            pass
    if slo_eng is not None:
        # per-config SLO verdict (targets, observed windows, verdict):
        # full block in BENCH_DETAIL.json; the compact line carries one
        # worst-of-suite slo key
        try:
            slo_eng.timeseries.force_tick()
            result["slo"] = slo_mod.summarize(slo_eng.evaluate(tick=False))
            log(f"  slo: {result['slo'].get('verdict')}")
        except Exception as e:  # noqa: BLE001 — SLO must never cost a run
            log(f"  slo evaluation failed: {type(e).__name__}: {e}")
    if preflight is not None:
        # predicted-vs-actual agreement: "unknown" actual (telemetry
        # off) is unjudgeable, not a disagreement
        preflight["actual"] = path_info["path"]
        preflight["agree"] = (
            preflight["path"] == path_info["path"]
            if path_info["path"] != "unknown"
            else None
        )
        result["preflight"] = preflight
    # DFA table-shape evidence (ISSUE-16 class packing): per-pattern
    # packed state/class counts + table bytes for every regex param
    # this config compiled; the compact line carries one tiny
    # dfa:{classes,states} key from the suite's largest table
    dfa_detail = _dfa_detail(cfg["specs"])
    if dfa_detail:
        result["dfa"] = dfa_detail
    if _LINK.get("h2d_mb_s") and _LINK.get("d2h_mb_s"):
        # what this batch's transfers alone cost on the measured link:
        # pass_ms at (or under) this floor means the pipeline is
        # link-bound — the engine is saturating the link, not the chip
        floor_ms = (
            link_mb[0] / _LINK["h2d_mb_s"] + link_mb[1] / _LINK["d2h_mb_s"]
        ) * 1000
        result["link_floor_ms"] = round(floor_ms)
        result["link_saturation"] = round(floor_ms / (t_med * 1000), 2)
    return result


def _dfa_detail(specs) -> list:
    """Per-pattern DFA table shapes for a config's regex params — the
    BENCH_DETAIL.json record behind the compact line's tiny
    ``dfa:{classes,states}`` key (ISSUE-16 byte-class packing
    evidence: class count, state count, packed table bytes)."""
    out = []
    try:
        from fluvio_tpu.ops.regex_dfa import compile_regex_cached

        for _sm_name, params in specs:
            pattern = (params or {}).get("regex")
            if not pattern:
                continue
            dfa = compile_regex_cached(pattern)
            out.append({
                "pattern_len": len(pattern),
                "states": int(dfa.n_states),
                "classes": int(dfa.n_classes),
                "table_bytes": int(dfa.table_bytes),
                "packed": bool(dfa.packed),
            })
    except Exception:  # noqa: BLE001 — evidence must never cost a run
        return []
    return out


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


def run_broker_e2e(n: int, smoke: bool, engine_rps: float) -> dict:
    """Config #2 through a REAL SPU over a real socket (review round 2 #6).

    Writes the corpus into a replica as native-encoded batches, then
    consumes through the chain with the batch-level client surface,
    measuring sustained records/sec across the produce->store->read->
    chain->encode->socket->ack loop. Target: within ~1.2x of the
    engine-only number.
    """
    import asyncio
    import tempfile

    from fluvio_tpu.client import ConsumerConfig, Fluvio, Offset
    from fluvio_tpu.protocol.record import Batch, RecordSet
    from fluvio_tpu.schema.smartmodule import (
        SmartModuleInvocation,
        SmartModuleInvocationKind,
        SmartModuleInvocationWasm,
    )
    from fluvio_tpu.smartengine import native_backend
    from fluvio_tpu.spu import SpuConfig, SpuServer
    from fluvio_tpu.storage.config import ReplicaConfig

    values = gen_json(n)
    batch_records = 16384
    log("[broker_e2e] building wire batches ...")
    slabs = []
    for lo in range(0, n, batch_records):
        chunk = values[lo : lo + batch_records]
        m = len(chunk)
        flat = np.frombuffer(b"".join(chunk), dtype=np.uint8)
        lens = np.array([len(v) for v in chunk], dtype=np.int64)
        val_off = np.zeros(m + 1, dtype=np.int64)
        np.cumsum(lens, out=val_off[1:])
        raw = native_backend.encode_record_columns(
            flat,
            val_off,
            np.zeros(1, np.uint8),
            np.zeros(m + 1, np.int64),
            np.zeros(m, np.uint8),
            np.arange(m, dtype=np.int64),
            np.zeros(m, np.int64),
        )
        b = Batch(base_offset=0, raw_records=raw, raw_record_count=m)
        b.header.first_timestamp = 1_000_000
        b.header.max_time_stamp = 1_000_000
        b.header.last_offset_delta = m - 1
        slabs.append(b)

    async def run() -> dict:
        tmp = tempfile.mkdtemp(prefix="fluvio-bench-")
        config = SpuConfig(
            id=9001,
            public_addr="127.0.0.1:0",
            log_base_dir=tmp,
            replication=ReplicaConfig(base_dir=tmp),
        )
        config.smart_engine.backend = "tpu"
        server = SpuServer(config)
        await server.start()
        server.ctx.create_replica("bench", 0)
        leader = server.ctx.leader_for("bench", 0)
        t0 = time.time()
        for b in slabs:
            rs = RecordSet()
            rs.add(b)
            await leader.write_record_set(rs)
        log(f"[broker_e2e] wrote {n} records in {time.time()-t0:.2f}s")

        cfg = ConsumerConfig(
            disable_continuous=True,
            # big read slices: each slice is ONE coalesced device dispatch,
            # so slice size sets the compute/transfer amortization
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
        consumer = await client.partition_consumer("bench", 0)

        async def consume_once() -> tuple:
            got = 0
            t0 = time.time()
            async for batch in consumer.stream_batches(Offset.beginning(), cfg):
                got += batch.records_len()
            return got, time.time() - t0

        got, dt0 = await consume_once()  # warm pass (pays the compiles)
        log(f"[broker_e2e] warm pass: {got} records in {dt0:.2f}s")
        got, dt = await consume_once()  # measured pass
        await client.close()
        await server.stop()
        rps = n / dt
        m = server.ctx.metrics.smartmodule.to_dict()
        log(
            f"[broker_e2e] consumed {got} records out of {n} in {dt:.2f}s "
            f"-> {rps:,.0f} records/s; fastpath={m['fastpath_slices']} "
            f"fallback={m['fallback_slices']} ({m['fallback_reasons']})"
        )
        assert got > 0
        assert m["fastpath_slices"] > 0, "broker fast path never engaged"
        return {
            "records_per_sec": round(rps),
            "vs_engine_only": round(rps / engine_rps, 2) if engine_rps else None,
            "fastpath_slices": m["fastpath_slices"],
            "fallback_slices": m["fallback_slices"],
        }

    return asyncio.run(run())


# which backend the suite targets. Set once in main() before the suite:
#   "tpu" — the default: a chip-targeting run. It FAILS (non-zero exit,
#           no result line) when jax finds no TPU — it never re-runs
#           itself on the host CPU.
#   "cpu" — BENCH_CPU=1, the ONE explicit CPU mode (hermetic smoke:
#           counts, bytes and exactness; its rates are not chip rates)
_BACKEND_MODE = "tpu"


def _force_cpu() -> None:
    # BENCH_CPU=1 only: pin the platform before any backend initializes
    import jax

    jax.config.update("jax_platforms", "cpu")


def _device_block() -> dict:
    """The device every result names, as jax reports it."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def _require_tpu() -> None:
    """A chip-targeting run that finds no TPU stops here: non-zero exit
    and no result line (the open of the backend raises on its own when
    the chip cannot be opened at all)."""
    dev = _device_block()
    if dev["platform"] != "tpu":
        log(f"no TPU: jax found {dev} — a chip-targeting bench does not "
            "run on another backend (BENCH_CPU=1 is the explicit CPU mode)")
        sys.exit(3)
    log(f"device: {dev}")


def _truth_counters() -> tuple:
    from fluvio_tpu.telemetry import TELEMETRY

    c = TELEMETRY.snapshot()["counters"]
    return int(c["heals"]), int(c["spills"].get("fused-error", 0))


_TRUTH_AT_START = (0, 0)  # captured in main() before the suite


def _device_truth() -> dict:
    """The smoke's zero-heal / zero-`fused-error` assertion, carried by
    every result: a run that healed down a ladder or re-ran fused
    batches on the interpreter measured a path nobody meant to."""
    heals, fused = (
        now - start for now, start in zip(_truth_counters(), _TRUTH_AT_START)
    )
    return {"heals": heals, "fused_error": fused, "ok": not heals and not fused}


def _xla_cache_dir() -> str:
    # the engine owns the resolution (it is what configures jax with it)
    from fluvio_tpu.smartengine.tpu import XLA_CACHE_DIR

    return XLA_CACHE_DIR


def _xla_cache_entries() -> int:
    d = _xla_cache_dir()
    if not d:
        return 0
    try:
        return sum(1 for f in os.listdir(d) if not f.startswith("."))
    except OSError:
        return 0


_CACHE_ENTRIES_AT_START = None  # captured in main() before the suite


def _cache_stats() -> dict:
    """Suite-level compile evidence for the JSON line. The per-config
    `compile` breakdowns (from the telemetry jit instrumentation) carry
    the real attribution now; this section keeps the persistent-cache
    dir + entries_written (the warm-cache proof: a warm run writes 0)
    plus the suite's compile totals."""
    stats = {"dir": _xla_cache_dir() or "off"}
    if _CACHE_ENTRIES_AT_START is not None:
        stats["entries_written"] = (
            _xla_cache_entries() - _CACHE_ENTRIES_AT_START
        )
    try:
        from fluvio_tpu.telemetry import TELEMETRY

        ct = TELEMETRY.compile_totals()
        stats["compiles"] = ct["compiles"]
        stats["compile_s"] = round(ct["seconds"], 2)
        stats["persistent_hits"] = ct["persistent_hits"]
        stats["persistent_misses"] = ct["persistent_misses"]
    except Exception:  # noqa: BLE001 — evidence, never a crash
        pass
    return stats


def _build_output(results: dict, extra_error: str = "") -> tuple:
    """One builder for the output JSON — the healthy emit in main() and
    the watchdog's degraded emit both come through here so the shapes
    cannot drift apart. Returns (out_dict, exit_code); out is None only
    for an intentionally-restricted run that matched no config."""
    good = {
        k: v
        for k, v in results.items()
        if "records_per_sec" in v  # excludes aux sections like "codecs"
        and "error" not in v
        and "skipped" not in v
    }
    degraded = bool(extra_error) or any("error" in v for v in results.values())
    # the exit code reflects suite-level failure only (watchdog error or
    # no measurable headline); a single errored config keeps its
    # `degraded` marker on the entry but must not fail the emit — the
    # round-5 lesson is that partial evidence beats a dead run
    exit_degraded = bool(extra_error)
    if good:
        headline_name = (
            "2_filter_map" if "2_filter_map" in good else next(iter(good))
        )
        headline = good[headline_name]
        inner = {
            "metric": "smartmodule_chain_records_per_sec",
            "value": headline["records_per_sec"],
            "unit": "records/s",
            "vs_baseline": headline["vs_baseline"],
            "configs": dict(results),
        }
        if headline_name != "2_filter_map":
            # never let a substitute config masquerade as the headline; a
            # BENCH_CONFIGS-restricted run is intentional, a failed
            # headline config is degraded
            inner["headline_config"] = headline_name
    elif not extra_error:
        return None, 2
    else:
        degraded = True
        exit_degraded = True
        inner = {
            "metric": "smartmodule_chain_records_per_sec",
            "value": 0,
            "unit": "records/s",
            "vs_baseline": 0,
            "configs": dict(results),
        }
    if degraded:
        inner["degraded"] = True
    if extra_error:
        inner["error"] = extra_error
    inner["xla_cache"] = _cache_stats()
    inner["concurrency"] = _concurrency_verdict()
    if _LINK:
        inner["link"] = dict(_LINK)
    inner["backend"] = "cpu" if _BACKEND_MODE == "cpu" else "tpu"
    inner["device"] = _device_block()
    truth = inner["device_truth"] = _device_truth()
    if not truth["ok"]:
        inner["degraded"] = True
        exit_degraded = True
    return inner, (1 if exit_degraded else 0)


# the driver captures only the TAIL of stdout (~2000 chars) and parses
# the last JSON line; round 5's line outgrew the window and came back
# ``parsed: null``. The emit contract is therefore two-layer: full
# detail to BENCH_DETAIL.json (+ stderr log), and ONE compact summary
# line, capped well under the window, as the last stdout line.
COMPACT_LINE_LIMIT = 1500


def _compact_configs(configs: dict) -> dict:
    out = {}
    for name, c in configs.items():
        if not isinstance(c, dict):
            continue
        if name == "codecs":
            # aux section: whole-block detail (including its error form)
            # stays in BENCH_DETAIL.json — round 5's line overgrew the
            # driver window carrying it
            continue
        if "records_per_sec" in c:
            e = {"rps": c["records_per_sec"]}
            if c.get("vs_baseline") is not None:
                e["x"] = c["vs_baseline"]
            if "vs_engine_only" in c:
                e["x_engine"] = c["vs_engine_only"]
            if c.get("path") and c["path"] != "fused":
                # the executed-path tag (from telemetry counters); fused
                # is the default and stays implicit to keep the line lean
                e["path"] = c["path"]
            out[name] = e
        elif "error" in c:
            out[name] = {"error": str(c["error"])[:80]}
            if isinstance(c.get("link"), dict) and "up_mb" in c["link"]:
                # the errored config's partial byte evidence (from
                # `bench_partial`) still rides the line
                out[name]["up_mb"] = c["link"]["up_mb"]
        elif "skipped" in c:
            out[name] = {"skipped": c["skipped"]}
    return out


def _concurrency_verdict():
    """Whole-package lock-discipline verdict for BENCH_DETAIL.json ONLY
    — the compact driver line never grows a key for it (`_compact_line`
    is allowlist-based). A bench run that ships with a lock-order cycle
    or an unguarded shared write should say so next to its numbers."""
    try:
        from fluvio_tpu.analysis import analyze_concurrency

        report = analyze_concurrency()
        return {
            "errors": len(report.errors()),
            "warnings": len(report.warnings()),
            "locks": len(report.locks),
            "order_edges": len(report.edges),
            "cycles": len(report.cycles),
        }
    except Exception as e:  # noqa: BLE001 — analysis must never cost a run
        return {"error": f"{type(e).__name__}: {e}"[:120]}


def _preflight_counts(configs: dict):
    """Predicted-vs-actual path agreement across a results dict: the
    compact line's tiny ``preflight`` key ({"agree": n, "of": m}); full
    per-config hazard reports stay in BENCH_DETAIL.json."""
    judged = [
        c["preflight"].get("agree")
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("preflight"), dict)
        and c["preflight"].get("agree") is not None
    ]
    if not judged:
        return None
    return {"agree": sum(1 for a in judged if a), "of": len(judged)}


def _partition_counts(configs: dict):
    """Partitioned-config evidence for the compact line's tiny ``part``
    key: partition count + rebalances survived. None when no config ran
    partitioned. Full plan/offsets/exactness detail stays in
    BENCH_DETAIL.json only (the ≤1500-char contract)."""
    blocks = [
        c["part"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("part"), dict)
    ]
    if not blocks:
        return None
    return {
        "n": sum(b.get("n", 0) for b in blocks),
        "rebal": sum(b.get("rebal", 0) for b in blocks),
    }


def _rebalance_counts(configs: dict):
    """Elastic-rebalancer evidence for the compact line's tiny ``rebal``
    key: voluntary moves landed + the post-move drain pass duration
    (worst across configs). None when no config armed the daemon. Full
    move records (src/dst groups, rollbacks) stay in BENCH_DETAIL.json
    only (the ≤1500-char contract)."""
    blocks = [
        c["rebalance"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("rebalance"), dict)
    ]
    if not blocks:
        return None
    return {
        "moves": sum(int(b.get("moves", 0)) for b in blocks),
        "drain_s": max(float(b.get("drain_s") or 0.0) for b in blocks),
    }


def _lag_counts(configs: dict):
    """Suite-wide streaming-lag evidence for the compact line's tiny
    ``lag`` key: worst residual consumer lag + worst record-age p99
    (ms) across every config that carried a lag block. None when no
    config tracked lag. Full per-partition joins stay in
    BENCH_DETAIL.json only (the ≤1500-char contract)."""
    blocks = [
        c["lag"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("lag"), dict)
    ]
    if not blocks:
        return None
    return {
        "max": max(int(b.get("max", 0)) for b in blocks),
        "age_p99": round(
            max(float(b.get("age_p99_ms", 0.0)) for b in blocks), 1
        ),
    }


def _soak_counts(configs: dict):
    """Soak-family evidence for the compact line's tiny ``soak`` key:
    the nominal scenario's steady-state p99 record age (ms) + shed
    ratio. None when the soak family didn't run. Full per-scenario
    verdict documents stay in BENCH_DETAIL.json only (the ≤1500-char
    contract)."""
    blocks = [
        c["soak"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("soak"), dict)
    ]
    if not blocks:
        return None
    b = blocks[0]
    return {"p99_age": b.get("p99_age"), "shed_ratio": b.get("shed_ratio")}


def _admission_counts(configs: dict):
    """Suite-wide admission evidence for the compact line's tiny
    ``adm`` key: total shed decisions + total warmed buckets. None when
    no config carried an admission block (controller unarmed)."""
    blocks = [
        c["admission"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("admission"), dict)
    ]
    if not blocks:
        return None
    return {
        "shed": sum(int(b.get("shed", 0)) for b in blocks),
        "warm": sum(int(b.get("warm", 0)) for b in blocks),
    }


def _dfa_counts(configs: dict):
    """Largest compiled DFA table across the suite — the compact
    line's tiny ``dfa`` key ({"classes": c, "states": s}: the packing
    evidence at a glance). None when no config carried a dfa block.
    Per-pattern shapes (table bytes, packed flag) stay in
    BENCH_DETAIL.json only (the ≤1500-char contract)."""
    rows = [
        d
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("dfa"), list)
        for d in c["dfa"]
        if isinstance(d, dict)
    ]
    if not rows:
        return None
    top = max(rows, key=lambda d: int(d.get("table_bytes", 0)))
    return {"classes": top.get("classes"), "states": top.get("states")}


def _win_counts(configs: dict):
    """Windowed-family evidence for the compact line's tiny ``win``
    key: worst (largest) delta-vs-full downlink ratio + most distinct
    keys across the family. None when no windowed config ran. Full
    per-config blocks (d2h A/B, per-kind delta rows, exactness,
    state bytes) stay in BENCH_DETAIL.json only (the ≤1500-char
    contract)."""
    blocks = [
        c["win"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("win"), dict)
    ]
    if not blocks:
        return None
    ratios = [
        b["delta_ratio"]
        for b in blocks
        if isinstance(b.get("delta_ratio"), (int, float))
    ]
    return {
        "delta_ratio": max(ratios) if ratios else None,
        "keys": max(int(b.get("keys", 0)) for b in blocks),
    }


def _mem_counts(configs: dict):
    """Device-memory evidence for the compact line's tiny ``mem`` key:
    worst per-config ledger peak + the owner classes that ever held
    bytes across the family (plus the leak count when non-zero). Full
    per-config blocks (per-owner bytes, reconcile doc) stay in
    BENCH_DETAIL.json only (the ≤1500-char contract)."""
    blocks = [
        c["memory"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("memory"), dict)
    ]
    if not blocks:
        return None
    peaks = [
        b["peak_mb"]
        for b in blocks
        if isinstance(b.get("peak_mb"), (int, float))
    ]
    owners = sorted({
        o for b in blocks for o in (b.get("owners") or {})
    })
    out = {
        "peak_mb": max(peaks) if peaks else None,
        "owners": owners,
    }
    leaks = sum(int(b.get("leaks", 0) or 0) for b in blocks)
    if leaks:
        out["leaks"] = leaks
    return out


def _slo_verdict(configs: dict):
    """Worst per-config SLO verdict across the suite — the compact
    line's tiny ``slo`` key; full per-config blocks (targets, observed
    windows) stay in BENCH_DETAIL.json."""
    order = {"ok": 0, "warn": 1, "breach": 2}
    verds = [
        c["slo"]["verdict"]
        for c in configs.values()
        if isinstance(c, dict) and isinstance(c.get("slo"), dict)
        and c["slo"].get("verdict") in order
    ]
    if not verds:
        return None
    return max(verds, key=lambda v: order[v])


def _compact_line(out: dict, limit: int = COMPACT_LINE_LIMIT) -> dict:
    """Compress the full output object into the driver-facing summary
    line: headline numbers, per-config rps/ratio pairs, link weather,
    cache-writes count — everything else lives in the detail file. A
    final guard drops whole sections until the serialized line fits."""
    compact = {
        "metric": out.get("metric"),
        "value": out.get("value"),
        "unit": out.get("unit"),
        "vs_baseline": out.get("vs_baseline"),
    }
    for k in ("backend", "device", "device_truth", "degraded",
              "headline_config"):
        if k in out:
            compact[k] = out[k]
    if "error" in out:
        compact["error"] = str(out["error"])[:160]
    if "link" in out:
        compact["link"] = dict(out["link"])  # copy: up_mb is added below
    if isinstance(out.get("xla_cache"), dict) and "entries_written" in out["xla_cache"]:
        compact["xla_cache"] = {
            "entries_written": out["xla_cache"]["entries_written"]
        }
    # ONE compact phases key: the headline config's breakdown (p50/p99
    # end-to-end + top-3 phase shares); full per-config phase tables
    # live in BENCH_DETAIL.json
    headline_cfg = (out.get("configs") or {}).get(
        out.get("headline_config", "2_filter_map")
    )
    # the tiny link:{up_mb} key (ISSUE-8 hardening): the headline's
    # measured upload MB rides the line even when other configs
    # errored — byte evidence survives a degraded run
    if isinstance(headline_cfg, dict) and isinstance(
        headline_cfg.get("link"), dict
    ):
        hl = headline_cfg["link"]
        compact.setdefault("link", {})
        if "up_mb" in hl:
            compact["link"]["up_mb"] = hl["up_mb"]
    # the tiny down:{mb,variant} key (ISSUE-12): the headline's result-
    # side bytes + engaged down-link variant — the compaction/encode
    # acceptance evidence rides the line like up_mb does
    if isinstance(headline_cfg, dict) and isinstance(
        headline_cfg.get("link"), dict
    ):
        hl = headline_cfg["link"]
        if "down_mb" in hl:
            compact["down"] = {
                "mb": hl["down_mb"],
                "variant": hl.get("down_variant", "off"),
            }
    if isinstance(headline_cfg, dict) and isinstance(
        headline_cfg.get("phases"), dict
    ):
        ph = headline_cfg["phases"]
        compact["phases"] = {
            k: ph[k] for k in ("e2e_p50_ms", "e2e_p99_ms", "top") if k in ph
        }
    # tiny compile key: the headline's compile count/seconds +
    # persistent-cache [hits, misses]; full per-config breakdowns stay
    # in BENCH_DETAIL.json
    if isinstance(headline_cfg, dict) and isinstance(
        headline_cfg.get("compile"), dict
    ):
        comp = headline_cfg["compile"]
        compact["compile"] = {
            "n": comp.get("compiles"),
            "s": comp.get("compile_s"),
            "pc": [
                comp.get("persistent_hits", 0),
                comp.get("persistent_misses", 0),
            ],
        }
    if "configs" in out:
        compact["configs"] = _compact_configs(out["configs"])
        # preflight satellite: ONE compact predicted-vs-actual agreement
        # count (analyzer honesty at a glance; detail stays in the file)
        pf = _preflight_counts(out["configs"])
        if pf:
            compact["preflight"] = pf
        sv = _slo_verdict(out["configs"])
        if sv:
            compact["slo"] = sv
        adm = _admission_counts(out["configs"])
        if adm:
            compact["adm"] = adm
        lg = _lag_counts(out["configs"])
        if lg:
            compact["lag"] = lg
        sk = _soak_counts(out["configs"])
        if sk:
            compact["soak"] = sk
        pt = _partition_counts(out["configs"])
        if pt:
            compact["part"] = pt
        rb = _rebalance_counts(out["configs"])
        if rb:
            compact["rebal"] = rb
        df = _dfa_counts(out["configs"])
        if df:
            compact["dfa"] = df
        wn = _win_counts(out["configs"])
        if wn:
            compact["win"] = wn
        mm = _mem_counts(out["configs"])
        if mm:
            compact["mem"] = mm
    compact["detail"] = "BENCH_DETAIL.json"
    # "link" drops LAST (the link calibration is what makes a low
    # headline interpretable) — the bulky sections go first
    for drop in (
        "configs", "dfa", "win", "mem", "soak", "lag",
        "rebal", "part", "adm", "slo", "preflight", "down", "compile",
        "phases", "error", "xla_cache", "link",
    ):
        if len(json.dumps(compact)) <= limit:
            break
        compact.pop(drop, None)
    if len(json.dumps(compact)) > limit:
        # last resort (round-5 hardening): some irreducible field still
        # blew the window — the driver MUST get a parseable line, so
        # collapse to the bare headline core
        core = {
            k: compact[k]
            for k in ("metric", "value", "unit", "vs_baseline",
                      "backend", "device", "degraded")
            if k in compact
        }
        core["detail"] = "BENCH_DETAIL.json"
        compact = core
    return compact


def _emit(out: dict) -> None:
    """Publish a result object under the two-layer contract (healthy
    exit AND the watchdog's degraded emit both come through here)."""
    detail = json.dumps(out, indent=1)
    try:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
        )
        with open(path, "w") as f:
            f.write(detail + "\n")
    except OSError as e:  # the compact line must still go out
        log(f"BENCH_DETAIL.json write failed: {e}")
    log("full result detail:\n" + detail)
    print(json.dumps(_compact_line(out)), flush=True)


_BSTART = _T0  # budget clock; reset once the backend is resolved


def _arm_watchdog(results: dict, budget: float) -> dict:
    """Hard-deadline guard for a device that stalls MID-RUN.

    The budget checks between configs/passes cannot interrupt a device
    call that is already blocked; this daemon thread
    waits past any plausible healthy runtime, then prints the
    best-so-far JSON line and hard-exits so the driver always gets a
    parseable result. ``state["done"]`` disarms it on normal completion.
    """
    import threading

    deadline = _BSTART + budget * 1.6 + 300
    state = {"done": False}

    def watch() -> None:
        while True:
            time.sleep(10)
            if state["done"]:
                return
            if time.time() > deadline:
                # a concurrent main-thread write can race the snapshot;
                # the guard must never die silently, so retry on anything
                try:
                    out, _ = _build_output(
                        dict(results),
                        extra_error="watchdog: hard deadline exceeded "
                        "(device stalled mid-run)",
                    )
                    _emit(out)
                except Exception:  # noqa: BLE001 — retry next tick
                    continue
                os._exit(1)

    threading.Thread(target=watch, daemon=True).start()
    return state


_LINK: dict = {}


def _calibrate_link() -> None:
    """Measure the host link's round-trip latency and H2D/D2H bandwidth.

    The host<->device link, not the chip, is the first ceiling a
    byte-bound chain meets at bench shapes. Recording the link
    alongside every run turns a low headline into an interpretable
    number: compare each config's pass_ms against its link_floor_ms."""
    import jax

    try:
        dev = jax.devices()[0]
        tiny = np.zeros(8, np.uint8)
        np.asarray(jax.device_put(tiny, dev))  # warm the path
        rtts = []
        for _ in range(3):
            t0 = time.time()
            np.asarray(jax.device_put(tiny, dev))
            rtts.append(time.time() - t0)
        big = np.random.default_rng(7).integers(
            0, 255, 16 * 1024 * 1024, np.uint8
        )
        jax.device_put(big, dev).block_until_ready()  # warm
        t0 = time.time()
        up = jax.device_put(big, dev)
        up.block_until_ready()
        # decimal MB/s: the consumers (link_mb, link_floor_ms) divide
        # byte counters by 1e6, so the bandwidths must match that unit
        h2d = big.nbytes / 1e6 / max(time.time() - t0, 1e-9)
        # D2H: fetch a directly-uploaded buffer — a sliced view would put
        # an XLA slice compile inside the timed window and understate the
        # bandwidth by 10-50x on a healthy link
        down = jax.device_put(big[: 4 * 1024 * 1024], dev)
        down.block_until_ready()
        t0 = time.time()
        np.asarray(down)
        d2h = 4 * 1024 * 1024 / 1e6 / max(time.time() - t0, 1e-9)
        _LINK.update(
            rtt_ms=round(statistics.median(rtts) * 1000, 1),
            h2d_mb_s=round(h2d, 1),
            d2h_mb_s=round(d2h, 1),
        )
        log(
            f"link: rtt {_LINK['rtt_ms']}ms, "
            f"H2D {h2d:.0f} MB/s, D2H {d2h:.0f} MB/s"
        )
    except Exception as e:  # noqa: BLE001 — calibration must never kill a run
        log(f"link calibration failed: {type(e).__name__}: {e}")


def run_suite(results: dict, n: int, smoke: bool, budget: float, only) -> None:
    """Run every selected config (headline first) plus broker e2e,
    filling ``results`` in place (the watchdog snapshots it mid-run)."""
    wanted = set(only.split(",")) if only else None
    order = sorted(CONFIGS, key=lambda k: k != "2_filter_map")
    for name in order:
        if wanted and name.split("_")[0] not in wanted and name not in wanted:
            continue
        have_good = any(
            "error" not in v and "skipped" not in v for v in results.values()
        )
        if have_good and time.time() - _BSTART > budget:
            # skip only once ONE config has a real number: a driver run
            # must always carry at least one measurement, however slow
            # the run (and a failed headline must not skip the rest)
            log(f"[{name}] skipped: BENCH_BUDGET={budget:.0f}s exhausted")
            results[name] = {"skipped": "budget"}
            continue
        try:
            results[name] = run_config(
                name, CONFIGS[name], n, smoke, deadline=_BSTART + budget
            )
        except Exception as e:  # noqa: BLE001 — one config must not lose the run
            traceback.print_exc(file=sys.stderr)
            entry = {"error": f"{type(e).__name__}: {e}"}
            partial = getattr(e, "bench_partial", None)
            if isinstance(partial, dict):
                # a mid-measurement death still reports what crossed
                # the link (the compact line's per-config link key)
                entry.update(partial)
            results[name] = entry
    # re-order in PLACE: the watchdog holds a reference to this dict and
    # must keep seeing every later write (broker_e2e below)
    ordered = {k: results[k] for k in CONFIGS if k in results}
    results.clear()
    results.update(ordered)

    good = {k: v for k, v in results.items() if "error" not in v and "skipped" not in v}
    if os.environ.get("BENCH_BROKER", "1") == "1" and "2_filter_map" in good:
        if time.time() - _BSTART > budget * 1.2:
            log(f"[broker_e2e] skipped: BENCH_BUDGET={budget:.0f}s exhausted")
            results["broker_e2e"] = {"skipped": "budget"}
        else:
            try:
                results["broker_e2e"] = run_broker_e2e(
                    n, smoke, good["2_filter_map"]["records_per_sec"]
                )
            except Exception as e:  # noqa: BLE001
                traceback.print_exc(file=sys.stderr)
                results["broker_e2e"] = {"error": f"{type(e).__name__}: {e}"}

    if os.environ.get("BENCH_CODECS", "1") == "1":
        try:
            results["codecs"] = run_codec_bench()
        except Exception as e:  # noqa: BLE001
            traceback.print_exc(file=sys.stderr)
            results["codecs"] = {"error": f"{type(e).__name__}: {e}"}

    # LAST: soak scenarios reset the telemetry registry per run, so
    # they must not precede any block that reads it mid-measurement
    if os.environ.get("BENCH_SOAK", "1") == "1":
        try:
            results["soak"] = run_soak_bench()
        except Exception as e:  # noqa: BLE001
            traceback.print_exc(file=sys.stderr)
            results["soak"] = {"error": f"{type(e).__name__}: {e}"}


def run_codec_bench() -> dict:
    """Per-codec MB/s on a 1 MB json-ish corpus (review round 4 weak #6).

    Quantifies the pure-Python lz4/snappy cliff vs the native library
    built from fluvio_tpu/native/codecs.cpp, and names which implementation the
    broker would actually use (`impl` mirrors compression.py's pick)."""
    import gzip

    from fluvio_tpu.protocol import compression as comp

    rec = b'{"name":"fluvio-%d","n":%d,"pad":"' + b"x" * 60 + b'"}'
    data = b"".join((rec % (i, i * 7)) for i in range(10000))

    def rate(fn, arg):
        t0 = time.time()
        out = fn(arg)
        return out, len(data) / max(time.time() - t0, 1e-9) / 1e6

    report = {}
    lz4_mod, lz4_impl = comp.lz4_codec()
    snappy_mod, snappy_impl = comp.snappy_codec()
    entries = [
        ("gzip", gzip, "stdlib"),
        ("lz4", lz4_mod, lz4_impl),
        ("snappy", snappy_mod, snappy_impl),
    ]
    try:
        from fluvio_tpu.protocol import lz4_py, snappy_py

        if lz4_impl != "python":  # quantify the cliff the fallback WOULD be
            entries.append(("lz4_py_fallback", lz4_py, "python"))
        if snappy_impl != "python":
            entries.append(("snappy_py_fallback", snappy_py, "python"))
    except ImportError:  # pragma: no cover
        pass
    for name, mod, impl in entries:
        c, c_mbs = rate(mod.compress, data)
        out, d_mbs = rate(mod.decompress, c)
        assert out == data, name
        report[name] = {
            "impl": impl,
            "compress_mb_s": round(c_mbs, 1),
            "decompress_mb_s": round(d_mbs, 1),
            "ratio": round(len(c) / len(data), 3),
        }
        log(
            f"[codecs] {name} ({impl}): {c_mbs:.0f} MB/s c, "
            f"{d_mbs:.0f} MB/s d, ratio {len(c)/len(data):.2f}"
        )
    return report


def run_soak_bench() -> dict:
    """Multi-tenant soak smoke family (ISSUE-17): the three tier-1
    scenarios through the real serving paths, scored against the
    observability surfaces. The expected exit codes are pinned —
    ``nominal`` and ``fairness`` must pass, ``overload`` must be
    detected as queueing collapse — so a bench run catches a scoring
    regression, not just a perf one. The compact line carries the
    nominal scenario's steady-state health as ``soak:{p99_age,
    shed_ratio}``; full per-scenario verdicts stay in
    BENCH_DETAIL.json (the ≤1500-char contract)."""
    from fluvio_tpu.soak import build_verdict, parse_scenario, run_scenario
    from fluvio_tpu.telemetry import TELEMETRY

    if not TELEMETRY.enabled:
        return {"skipped": "telemetry capture off"}
    expected = {"nominal": 0, "overload": 1, "fairness": 0}
    report = {"scenarios": {}}
    for name, want_rc in expected.items():
        sc = parse_scenario(name)
        doc = build_verdict(sc, run_scenario(sc))
        report["scenarios"][name] = {
            "verdict": doc["verdict"],
            "rc": doc["rc"],
            "expected_rc": want_rc,
            "p99_age_ms": doc["p99_age_ms"],
            "shed_ratio": doc["shed_ratio"],
            "fairness": doc["fairness"],
            "checks": {c["name"]: c["ok"] for c in doc["checks"]},
        }
        log(
            f"[soak] {name}: verdict={doc['verdict']} rc={doc['rc']} "
            f"(want {want_rc}) p99_age={doc['p99_age_ms']}ms "
            f"shed={doc['shed_ratio']} fairness={doc['fairness']}"
        )
    nominal = report["scenarios"]["nominal"]
    report["soak"] = {
        "p99_age": round(float(nominal["p99_age_ms"]), 1),
        "shed_ratio": nominal["shed_ratio"],
        "ok": sum(
            1
            for s in report["scenarios"].values()
            if s["rc"] == s["expected_rc"]
        ),
        "of": len(report["scenarios"]),
    }
    return report


def main() -> None:
    global _BSTART, _BACKEND_MODE, _CACHE_ENTRIES_AT_START, _TRUTH_AT_START
    if os.environ.get("BENCH_CPU") == "1":
        # the explicit hermetic CPU mode — never touches the chip
        _BACKEND_MODE = "cpu"
        _force_cpu()
    else:
        # one process, no probe child: this process opens the chip
        # itself, and a run that finds no TPU exits without a result
        _require_tpu()
    _BSTART = time.time()
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    default_n = "20000" if smoke else "1000000"
    n = int(os.environ.get("BENCH_RECORDS", default_n))
    only = os.environ.get("BENCH_CONFIGS")
    # the resolved down-link modes ride the link block (no mode is
    # armed here: every backend runs the program its `auto` resolves)
    from fluvio_tpu.smartengine.tpu.executor import (
        effective_result_compact, effective_result_compress,
    )

    _LINK["down_compact"] = "on" if effective_result_compact() else "off"
    _LINK["down_glz"] = "on" if effective_result_compress() else "off"
    log(
        f"result compaction: {_LINK['down_compact']}, "
        f"down-link glz: {_LINK['down_glz']}"
    )

    # bound the whole run so the driver always gets a JSON line. The
    # headline config runs first so it is never the one a tight budget
    # skips.
    budget = float(os.environ.get("BENCH_BUDGET", "2100"))
    _CACHE_ENTRIES_AT_START = _xla_cache_entries()
    _TRUTH_AT_START = _truth_counters()
    results = {}
    watchdog = _arm_watchdog(results, budget)
    if _BACKEND_MODE == "tpu":
        _calibrate_link()  # under the watchdog, like the suite itself
    run_suite(results, n, smoke, budget, only)

    watchdog["done"] = True
    out, rc = _build_output(results)
    if out is None:
        log(f"no configs succeeded (BENCH_CONFIGS={only!r}; known: {list(CONFIGS)})")
        sys.exit(rc)
    _emit(out)
    # regression tripwires (a failed headline config or a broker e2e
    # assertion like 'fast path never engaged') surface in the exit code
    # while the compact line above still carries every number that DID
    # run (full detail in BENCH_DETAIL.json)
    sys.exit(rc)


if __name__ == "__main__":
    main()
