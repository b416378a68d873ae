"""Contract tests for bench.py's output JSON builder.

The result line is the artifact the driver reads; these pin its shapes:
backend + device (platform, kind, count) on every emit, the zero-heal /
zero-`fused-error` device truth, no result at all from a chip-targeting
run that finds no TPU (there is no CPU fallback), aux sections (codecs)
never becoming the headline, and degraded/headline_config markers.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import pytest

_BENCH_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py"
)


def _bench():
    """Import bench.py as a module without running main()."""
    if "bench" in sys.modules:
        return sys.modules["bench"]
    spec = importlib.util.spec_from_file_location("bench", _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    return mod


GOOD = {
    "records_per_sec": 1000,
    "baseline_records_per_sec": 500,
    "vs_baseline": 2.0,
    "first_call_s": 0.3,
}


@pytest.fixture(autouse=True)
def _restore_backend_mode(monkeypatch):
    """Each test sets bench._BACKEND_MODE explicitly; restore the
    module default afterwards so the cached sys.modules entry cannot
    leak state into later-importing tests."""
    b = _bench()
    monkeypatch.setattr(b, "_BACKEND_MODE", b._BACKEND_MODE)
    # the device truth is a delta from the suite's start: earlier test
    # files in this worker may have healed on purpose
    monkeypatch.setattr(b, "_TRUTH_AT_START", b._truth_counters())
    yield


def test_healthy_tpu_emit_carries_backend_and_cache():
    b = _bench()
    b._BACKEND_MODE = "tpu"
    out, rc = b._build_output({"2_filter_map": dict(GOOD)})
    assert rc == 0
    assert out["value"] == 1000 and out["vs_baseline"] == 2.0
    assert out["backend"] == "tpu"
    assert "xla_cache" in out
    assert "degraded" not in out


def test_every_result_names_its_device():
    """platform / device_kind / device count ride the detail object AND
    the compact line (a number without its device is not a result)."""
    import json

    import jax

    b = _bench()
    b._BACKEND_MODE = "cpu"
    out, rc = b._build_output({"2_filter_map": dict(GOOD)})
    want = {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    assert rc == 0 and out["device"] == want
    parsed = json.loads(json.dumps(b._compact_line(out)))
    assert parsed["device"] == want and parsed["backend"] == "cpu"
    assert parsed["device_truth"] == {"heals": 0, "fused_error": 0, "ok": True}


@pytest.mark.parametrize("seam", ["heal", "fused-error"])
def test_device_truth_fails_a_run_that_healed(seam):
    """The smoke's assertion, carried by every result: a heal or a
    `fused-error` interpreter re-run inside the suite marks the emit
    degraded and the exit non-zero."""
    from fluvio_tpu.telemetry import TELEMETRY

    b = _bench()
    b._BACKEND_MODE = "tpu"
    if seam == "heal":
        TELEMETRY.add_heal()
    else:
        TELEMETRY.add_spill("fused-error")
    out, rc = b._build_output({"2_filter_map": dict(GOOD)})
    assert rc == 1 and out["degraded"] is True
    assert out["device_truth"]["ok"] is False
    assert out["value"] == 1000  # the numbers still ride, labeled


def test_chip_targeting_run_without_tpu_prints_no_result():
    """`python bench.py` on a machine with no TPU exits non-zero with
    NO result line — there is no probe child and no CPU re-run."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_SMOKE="1")
    env.pop("BENCH_CPU", None)
    proc = subprocess.run(
        [sys.executable, _BENCH_PATH], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no TPU" in proc.stderr


def test_aux_sections_never_become_headline():
    b = _bench()
    b._BACKEND_MODE = "cpu"
    results = {
        "codecs": {"lz4": {"impl": "native"}},
        "1_filter": dict(GOOD),
    }
    out, rc = b._build_output(results)
    assert out["value"] == 1000
    assert out["headline_config"] == "1_filter"  # substitute is labeled


def test_watchdog_error_marks_degraded():
    b = _bench()
    b._BACKEND_MODE = "tpu"
    out, rc = b._build_output(
        {"2_filter_map": dict(GOOD)}, extra_error="watchdog: stalled"
    )
    assert rc == 1 and out["degraded"] is True
    assert out["error"] == "watchdog: stalled"
    assert out["value"] == 1000  # best-so-far numbers still ride along


def test_restricted_run_with_no_match_returns_none():
    b = _bench()
    b._BACKEND_MODE = "tpu"
    out, rc = b._build_output({})
    assert out is None and rc == 2


def test_link_calibration_rides_every_emit():
    """A live run records the host link (rtt + bandwidth both
    ways) so a low headline is interpretable: the reader compares each
    config's pass_ms with its link_floor_ms instead of guessing whether
    the chip or the link set the ceiling."""
    b = _bench()
    b._BACKEND_MODE = "tpu"
    b._LINK.update(rtt_ms=65.0, h2d_mb_s=49.0, d2h_mb_s=37.0)
    try:
        out, rc = b._build_output({"2_filter_map": dict(GOOD)})
        assert out["link"] == {"rtt_ms": 65.0, "h2d_mb_s": 49.0, "d2h_mb_s": 37.0}
    finally:
        b._LINK.clear()


def _full_config(rps: int, x: float, path: str = "fused") -> dict:
    """A config entry with every field a real healthy run carries."""
    return {
        "records_per_sec": rps,
        "payload_mb_per_sec": round(rps / 31000, 1),
        "baseline_records_per_sec": int(rps / x) if x else 0,
        "vs_baseline": x,
        "pass_ms": [1681, 1552, 1520],
        "first_call_s": 21.68,
        "link_mb": [34.62, 4.33],
        "link_floor_ms": 777,
        "link_saturation": 0.45,
        # ISSUE-8: per-config link breakdown (link MB both ways + glz
        # decline attribution from the telemetry counters)
        "link": {
            "up_mb": 34.62,
            "down_mb": 4.33,
            # ISSUE-12: the result-side (D2H) variant family — which
            # form the outputs crossed down in
            "down_variant": "down-glz-pallas",
            "down_variants": {"down-glz-pallas": 7},
            "declines": {},
        },
        "path": path,
        "path_records": {path: rps * 7},
        # ISSUE-5: per-config compile breakdown from the telemetry jit
        # instrumentation (replaces the crude suite-level direntry diff
        # as the per-config compile evidence)
        "compile": {
            "compiles": 3,
            "compile_s": 19.42,
            "by_kind": {"ragged": 2, "dfa_table": 1},
            "persistent_hits": 1,
            "persistent_misses": 2,
            "cache_hits": 41,
            "first_call_compile_s": 19.42,
            "first_call_execute_s": 2.26,
        },
        "phases": {
            "wall_ms": 1693.4,
            "phase_sum_ms": 1650.2,
            "phase_ms": {
                "stage": 201.5, "h2d": 144.2, "dispatch": 55.1,
                "device": 901.2, "fetch": 240.8, "d2h": 107.4,
            },
            "top": [["device", 0.55], ["fetch", 0.15], ["stage", 0.12]],
            # ISSUE-12: fraction of the serial pass's d2h+fetch the
            # pipelined loop hid behind other batches' phases
            "fetch_overlap": 0.64,
            "e2e_p50_ms": 1554.0,
            "e2e_p99_ms": 1698.0,
        },
        # ISSUE-6: per-config preflight record (predicted-vs-actual
        # executed path from the static analyzer, full detail file-only)
        "preflight": {
            "path": path, "actual": path, "agree": True,
            "down_variant": "down-glz-pallas",
        },
        # SLO-PR satellite: per-config verdict block (targets, observed
        # windows, verdict) — full detail file-only; the compact line
        # carries one worst-of-suite slo key
        "slo": {
            "verdict": "ok",
            "rules": {
                "e2e_p99": {
                    "observed": 1.698, "target": 2.0, "verdict": "ok",
                    "chain": "filter+map",
                },
                "spill_ratio": {
                    "observed": 0.0, "target": 0.05, "verdict": "ok",
                    "chain": "_engine",
                },
            },
        },
    }


def _full_results() -> dict:
    """Results shaped like round 5's real capture — the size class that
    overgrew the driver's tail window and came back ``parsed: null``."""
    results = {
        name: _full_config(rps, x, path)
        for name, rps, x, path in [
            ("1_filter", 552722, 0.41, "fused"),
            ("2_filter_map", 577711, 1.12, "fused"),
            ("3_aggregate", 820770, 3.48, "fused"),
            ("4_array_map", 160755, 2.73, "fused"),
            ("5_windowed", 599025, 3.63, "fused"),
            ("6_wide300", 218726, 0.32, "fused"),
            ("7_fat70k", 190253, 19.94, "striped"),
        ]
    }
    results["broker_e2e"] = {
        "records_per_sec": 300392,
        "vs_engine_only": 0.52,
        "fastpath_slices": 6,
        "fallback_slices": 0,
    }
    results["codecs"] = {
        name: {
            "impl": impl,
            "compress_mb_s": 744.2,
            "decompress_mb_s": 1297.6,
            "ratio": 0.098,
        }
        for name, impl in [
            ("gzip", "stdlib"), ("lz4", "native"), ("snappy", "native"),
            ("lz4_py_fallback", "python"), ("snappy_py_fallback", "python"),
        ]
    }
    return results


def test_compact_line_fits_driver_window():
    """The driver captures ~2000 trailing chars of stdout; the summary
    line must stay under 1500 for a FULL seven-config run with broker,
    codecs, link calibration, and cache stats attached."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    b._LINK.update(
        rtt_ms=65.0, h2d_mb_s=49.0, d2h_mb_s=37.0
    )
    try:
        out, rc = b._build_output(_full_results())
        line = json.dumps(b._compact_line(out))
    finally:
        b._LINK.clear()
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["value"] == 577711 and parsed["vs_baseline"] == 1.12
    assert parsed["backend"] == "tpu"
    assert parsed["configs"]["6_wide300"] == {"rps": 218726, "x": 0.32}
    assert parsed["configs"]["broker_e2e"]["x_engine"] == 0.52
    assert "codecs" not in parsed["configs"]  # aux detail stays in the file
    # executed-path honesty: the telemetry-derived path tag rides the
    # line for non-fused configs only (fused stays implicit)
    assert parsed["configs"]["7_fat70k"]["path"] == "striped"
    assert "path" not in parsed["configs"]["1_filter"]
    assert "fallback" not in parsed["configs"]["7_fat70k"]  # static label is gone
    # ISSUE-8: the tiny link key carries the headline's measured upload
    # MB next to the link calibration
    assert parsed["link"]["up_mb"] == 34.62
    assert parsed["link"]["h2d_mb_s"] == 49.0
    assert parsed["detail"] == "BENCH_DETAIL.json"
    # telemetry satellite: ONE compact phases key (the headline's p50/p99
    # + top-3 phase shares); the per-config phase tables stay in the file
    assert parsed["phases"]["e2e_p50_ms"] == 1554.0
    assert parsed["phases"]["top"][0][0] == "device"
    assert "phase_ms" not in parsed["phases"]  # full table is detail-only
    # ISSUE-5 satellite: a tiny headline compile key (count/seconds +
    # persistent-cache [hits, misses]); full per-config breakdowns stay
    # in BENCH_DETAIL.json
    assert parsed["compile"] == {"n": 3, "s": 19.42, "pc": [1, 2]}
    assert "compile" not in parsed["configs"]["2_filter_map"]
    # ISSUE-6 satellite: ONE compact preflight key — predicted-vs-actual
    # path agreement across the matrix; per-config hazard detail stays
    # in BENCH_DETAIL.json
    assert parsed["preflight"] == {"agree": 7, "of": 7}
    assert "preflight" not in parsed["configs"]["2_filter_map"]
    # SLO satellite: ONE tiny worst-of-suite verdict key on the line;
    # the per-config blocks (targets, observed windows) stay in
    # BENCH_DETAIL.json
    assert parsed["slo"] == "ok"
    assert "slo" not in parsed["configs"]["2_filter_map"]


def test_compact_line_trims_pathological_blowup_keeps_link():
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    b._LINK.update(rtt_ms=65.0, h2d_mb_s=49.0, d2h_mb_s=37.0)
    results = {
        f"cfg_{i:02d}": {"error": "boom " * 100} for i in range(40)
    }
    results["2_filter_map"] = dict(GOOD)
    try:
        out, _ = b._build_output(results, extra_error="x" * 5000)
        line = json.dumps(b._compact_line(out))
    finally:
        b._LINK.clear()
    assert len(line) <= 1500
    parsed = json.loads(line)
    assert parsed["value"] == 1000
    # the link calibration survives trimming: `link` drops last
    assert parsed["link"]["h2d_mb_s"] == 49.0


def test_compact_line_fits_with_codecs_and_device_blocks():
    """The compact line must stay under 1500 chars with the codecs
    block present in the results — trimmed from stdout, kept in
    BENCH_DETAIL.json — and still name its device."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    out, rc = b._build_output(_full_results())
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["configs"]["2_filter_map"]["rps"] == 577711
    assert "codecs" not in parsed["configs"]
    assert set(parsed["device"]) == {"platform", "kind", "count"}
    # the detail object still carries the full codecs block
    assert "codecs" in out["configs"]


def test_no_hidden_cpu_path_left_in_entry_points():
    """grep gate (ISSUE 22): no `cpu_fallback`, no device-probe child,
    and no `jax_platforms` switch outside the explicit BENCH_CPU=1 mode
    and `dryrun_multichip`'s virtual-device child."""
    root = os.path.dirname(_BENCH_PATH)
    bench_src = open(_BENCH_PATH).read()
    graft_src = open(os.path.join(root, "__graft_entry__.py")).read()
    for src in (bench_src, graft_src):
        assert "cpu_fallback" not in src
        assert "_probe_device" not in src and "probe-ok" not in src
    # bench: the one switch lives in _force_cpu (BENCH_CPU=1)
    assert bench_src.count('"jax_platforms"') == 1
    assert "import subprocess" not in bench_src
    # graft entry: only dryrun_multichip's child may pin the platform
    entry_body = graft_src[graft_src.index("def entry("):]
    entry_body = entry_body[: entry_body.index("\ndef ", 10)]
    assert "jax_platforms" not in entry_body
    assert "subprocess" not in entry_body


def test_errored_config_keeps_link_evidence_on_the_line():
    """ISSUE-8 hardening vs the round-5 ``parsed: null`` class: a
    config that died mid-measurement still reports its partial link
    bytes (run_suite merges `bench_partial` into the error entry), and
    the compact line carries them."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    b._LINK.update(rtt_ms=65.0, h2d_mb_s=49.0, d2h_mb_s=37.0)
    results = {
        "2_filter_map": dict(GOOD),
        "6_wide300": {
            "error": "RuntimeError: device stalled mid-pass",
            "link": {"up_mb": 12.4},
        },
    }
    try:
        out, rc = b._build_output(results)
        line = json.loads(json.dumps(b._compact_line(out)))
    finally:
        b._LINK.clear()
    assert rc == 0  # per-config errors degrade the entry, not the emit
    assert out["configs"]["6_wide300"]["link"]["up_mb"] == 12.4
    assert line["configs"]["6_wide300"]["up_mb"] == 12.4
    assert "error" in line["configs"]["6_wide300"]


def test_compact_line_hard_trim_always_parseable():
    """Even a pathological object whose irreducible fields exceed the
    window must collapse to a parseable headline core."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    out, _ = b._build_output(
        {"2_filter_map": dict(GOOD)}, extra_error="x" * 5000
    )
    # sabotage: force an un-droppable giant value into the compact core
    out["headline_config"] = "2_filter_map" + "y" * 5000
    line = json.dumps(b._compact_line(out))
    assert len(line) <= b.COMPACT_LINE_LIMIT
    parsed = json.loads(line)
    assert parsed["value"] == 1000
    assert parsed["detail"] == "BENCH_DETAIL.json"


def test_link_floor_fields_survive_the_emit():
    # the per-config link floor (what the batch's transfers alone cost
    # on the calibrated link) must ride through _build_output untouched
    # (the judge reads it to tell a link-bound pass from a chip-bound one)
    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg = dict(GOOD)
    cfg["link_floor_ms"] = 777
    cfg["link_saturation"] = 0.45
    out, rc = b._build_output({"2_filter_map": cfg})
    assert rc == 0
    got = out["configs"]["2_filter_map"]
    assert got["link_floor_ms"] == 777
    assert got["link_saturation"] == 0.45



def test_slo_line_key_is_worst_of_suite():
    """A single breached config colors the whole line's slo key, and
    the per-config block still rides BENCH_DETAIL.json untouched."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg_ok = dict(GOOD)
    cfg_ok["slo"] = {"verdict": "ok", "rules": {}}
    cfg_bad = dict(GOOD)
    cfg_bad["slo"] = {
        "verdict": "breach",
        "rules": {
            "e2e_p99": {"observed": 9.1, "target": 2.0,
                        "verdict": "breach", "chain": "filter+map"},
        },
        "breached_chains": ["filter+map"],
    }
    out, rc = b._build_output(
        {"2_filter_map": cfg_ok, "5_windowed": cfg_bad}
    )
    assert rc == 0
    assert out["configs"]["5_windowed"]["slo"]["verdict"] == "breach"
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["slo"] == "breach"
    # configs without any slo block leave the key off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "slo" not in json.loads(json.dumps(b._compact_line(out2)))


def test_adm_line_key_aggregates_shed_and_warm():
    """ISSUE-11: a tiny ``adm:{shed,warm}`` key rides the compact line
    when any config carried an admission block; full warmup/shed detail
    stays in BENCH_DETAIL.json, and the ≤1500-char contract holds with
    the key present."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg1 = dict(GOOD)
    cfg1["admission"] = {
        "shed": 3, "warm": 2,
        "warmup": {"buckets": 2, "compiles": 4, "compile_s": 11.2},
    }
    cfg2 = dict(GOOD)
    cfg2["admission"] = {"shed": 1, "warm": 1}
    out, rc = b._build_output({"2_filter_map": cfg1, "1_filter": cfg2})
    assert rc == 0
    # detail block rides BENCH_DETAIL.json untouched
    assert out["configs"]["2_filter_map"]["admission"]["warmup"][
        "compiles"
    ] == 4
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["adm"] == {"shed": 4, "warm": 3}
    assert "admission" not in line["configs"]["2_filter_map"]
    # configs without admission blocks leave the key off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "adm" not in json.loads(json.dumps(b._compact_line(out2)))


def test_adm_key_fits_contract_and_trims_before_link():
    """The full seven-config line with the adm key stays ≤1500 chars,
    and the blowup trim drops ``adm`` before ``link`` (the link calibration drops
    last)."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    b._LINK.update(
        rtt_ms=65.0, h2d_mb_s=49.0, d2h_mb_s=37.0
    )
    results = _full_results()
    for cfg in results.values():
        if isinstance(cfg, dict) and "records_per_sec" in cfg:
            cfg["admission"] = {"shed": 2, "warm": 1}
    try:
        out, _ = b._build_output(results)
        line = json.dumps(b._compact_line(out))
    finally:
        b._LINK.clear()
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    n_blocks = sum(
        1
        for cfg in results.values()
        if isinstance(cfg, dict) and "admission" in cfg
    )
    assert parsed["adm"] == {"shed": 2 * n_blocks, "warm": n_blocks}
    # trim ladder order: adm drops before link (the contract field)
    import re

    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src).group(1)
    assert ladder.index('"adm"') < ladder.index('"link"')


def test_down_key_rides_compact_line_and_trims_before_link():
    """ISSUE-12: the headline's result-side evidence rides the line as
    the tiny ``down:{mb,variant}`` key, stays inside the 1500-char
    contract for a full run, and the blowup trim drops ``down`` BEFORE
    ``link`` (the link calibration drops last)."""
    import json
    import re

    bench = _bench()
    out, rc = bench._build_output(_full_results())
    line = json.dumps(bench._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["down"] == {"mb": 4.33, "variant": "down-glz-pallas"}
    src = open(bench.__file__).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"down"') < ladder.index('"link"')
    assert ladder.index('"down"') < ladder.index('"compile"')


def test_fetch_overlap_ratio_in_detail_not_line():
    """The per-config fetch_overlap ratio is detail-file evidence; the
    compact line's phases key carries only p50/p99/top."""
    import json

    bench = _bench()
    out, rc = bench._build_output(_full_results())
    cfg = out["configs"]["2_filter_map"]
    assert cfg["phases"]["fetch_overlap"] == 0.64
    compact = bench._compact_line(out)
    assert "fetch_overlap" not in json.dumps(compact.get("phases", {}))


def test_phase_breakdown_computes_overlap_ratio():
    bench = _bench()
    phases = bench._phase_breakdown(
        1.0,  # serial single pass: 1000 ms
        {"device": 500.0, "fetch": 300.0, "d2h": 100.0, "h2d": 100.0},
        _EmptyHist(),
        pipelined_s=0.7,  # pipelined hid 300 ms of the 400 ms fetch side
    )
    assert phases["fetch_overlap"] == 0.75
    # no pipelined number -> no ratio key (degraded runs stay honest)
    phases2 = bench._phase_breakdown(
        1.0, {"device": 500.0, "fetch": 300.0}, _EmptyHist()
    )
    assert "fetch_overlap" not in phases2


class _EmptyHist:
    count = 0


def test_sharded_config_skip_entry_rides_configs():
    """The 8_sharded_fat config skips cleanly on device-poor backends;
    the skip marker must survive the compact line."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    out, rc = b._build_output(
        {
            "2_filter_map": dict(GOOD),
            "8_sharded_fat": {"skipped": "needs 8 devices (have 1)"},
        }
    )
    assert rc == 0
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["configs"]["8_sharded_fat"]["skipped"].startswith("needs 8")


def test_preflight_counts_disagreement_and_unjudged():
    """The compact preflight key counts only judgeable configs: an
    ``agree: None`` (telemetry off -> actual unknown) is excluded, a
    real disagreement counts against the analyzer."""
    b = _bench()
    configs = {
        "a": {"preflight": {"path": "fused", "actual": "fused",
                            "agree": True}},
        "b": {"preflight": {"path": "fused", "actual": "interpreter",
                            "agree": False}},
        "c": {"preflight": {"path": "fused", "actual": "unknown",
                            "agree": None}},
        "d": {"records_per_sec": 1},  # no preflight at all
    }
    assert b._preflight_counts(configs) == {"agree": 1, "of": 2}
    assert b._preflight_counts({"d": {"records_per_sec": 1}}) is None


def test_preflight_survives_emit_and_line_trim_order():
    """The per-config preflight record rides BENCH_DETAIL.json through
    _build_output untouched, and the compact key drops BEFORE link in
    the blowup trim ladder (the link calibration drops last)."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg = dict(GOOD)
    cfg["preflight"] = {"path": "fused", "actual": "fused", "agree": True}
    out, rc = b._build_output({"2_filter_map": cfg})
    assert rc == 0
    assert out["configs"]["2_filter_map"]["preflight"]["agree"] is True
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["preflight"] == {"agree": 1, "of": 1}


def test_part_line_key_rides_compact_line():
    """ISSUE-13: a tiny ``part:{n,rebal}`` key rides the compact line
    when any config ran partitioned; the full plan/offsets/exactness
    block stays in BENCH_DETAIL.json only."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg = dict(GOOD)
    cfg["part"] = {
        "n": 4, "groups": 2, "rebal": 1, "exact": True,
        "offsets": {"bench/0": 4999, "bench/1": 4999},
        "plan": {"bench/0": 0, "bench/1": 1},
    }
    out, rc = b._build_output({"9_partitioned": cfg})
    assert rc == 0
    assert out["configs"]["9_partitioned"]["part"]["exact"] is True
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["part"] == {"n": 4, "rebal": 1}
    # the bulky detail never reaches the line
    assert "part" not in line["configs"].get("9_partitioned", {})
    # without a partitioned config the key stays off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "part" not in json.loads(json.dumps(b._compact_line(out2)))


def test_part_key_fits_contract_and_trims_before_link():
    """The full-matrix line with the part key stays ≤1500 chars and the
    blowup trim ladder drops ``part`` before ``link`` (the unconditional
    contract field) and before ``compile``."""
    import json
    import re

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = _full_results()
    results["9_partitioned"] = dict(GOOD)
    results["9_partitioned"]["part"] = {
        "n": 4, "groups": 2, "rebal": 1, "exact": True,
        "offsets": {f"bench/{i}": 4999 for i in range(4)},
        "plan": {f"bench/{i}": i % 2 for i in range(4)},
    }
    out, _ = b._build_output(results)
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    assert json.loads(line)["part"] == {"n": 4, "rebal": 1}
    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"part"') < ladder.index('"link"')
    assert ladder.index('"part"') < ladder.index('"compile"')


def test_lag_line_key_rides_compact_line():
    """ISSUE-15: a tiny ``lag:{max,age_p99}`` key rides the compact
    line when any config carried a streaming-lag block; the full
    per-partition join stays in BENCH_DETAIL.json only."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg = dict(GOOD)
    cfg["lag"] = {
        "max": 12,
        "age_p99_ms": 84.5,
        "per_partition": {
            "bench/0": {"committed": 4999, "hw": 5011, "lag": 12,
                        "age_p99_ms": 84.5},
            "bench/1": {"committed": 4999, "hw": 4999, "lag": 0,
                        "age_p99_ms": 60.0},
        },
    }
    out, rc = b._build_output({"9_partitioned": cfg})
    assert rc == 0
    assert out["configs"]["9_partitioned"]["lag"]["max"] == 12
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["lag"] == {"max": 12, "age_p99": 84.5}
    # the bulky per-partition join never reaches the line
    assert "lag" not in line["configs"].get("9_partitioned", {})
    # without a lag block the key stays off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "lag" not in json.loads(json.dumps(b._compact_line(out2)))


def test_lag_key_fits_contract_and_trims_before_part():
    """The full-matrix line with the lag key stays ≤1500 chars and the
    blowup trim ladder drops ``lag`` BEFORE ``part`` (and therefore
    before ``link``, the unconditional contract field)."""
    import json
    import re

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = _full_results()
    results["9_partitioned"] = dict(GOOD)
    results["9_partitioned"]["part"] = {
        "n": 4, "groups": 2, "rebal": 1, "exact": True,
        "offsets": {f"bench/{i}": 4999 for i in range(4)},
        "plan": {f"bench/{i}": i % 2 for i in range(4)},
    }
    results["9_partitioned"]["lag"] = {
        "max": 3, "age_p99_ms": 42.0,
        "per_partition": {
            f"bench/{i}": {"lag": i, "age_p99_ms": 42.0} for i in range(4)
        },
    }
    out, _ = b._build_output(results)
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["lag"] == {"max": 3, "age_p99": 42.0}
    assert parsed["part"] == {"n": 4, "rebal": 1}
    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"lag"') < ladder.index('"part"')
    assert ladder.index('"lag"') < ladder.index('"link"')

def test_dfa_line_key_rides_compact_line():
    """ISSUE-16: a tiny ``dfa:{classes,states}`` key rides the compact
    line when any config carried a DFA table block, read from the
    suite's LARGEST table; per-pattern shapes (table bytes, packed
    flag) stay in BENCH_DETAIL.json only."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg = dict(GOOD)
    cfg["dfa"] = [
        {"pattern_len": 6, "states": 8, "classes": 7,
         "table_bytes": 112, "packed": True},
        {"pattern_len": 29, "states": 22, "classes": 15,
         "table_bytes": 660, "packed": True},
    ]
    out, rc = b._build_output({"10_regex_json_fat": cfg})
    assert rc == 0
    assert out["configs"]["10_regex_json_fat"]["dfa"][1]["table_bytes"] == 660
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["dfa"] == {"classes": 15, "states": 22}
    # the per-pattern detail never reaches the line
    assert "dfa" not in line["configs"].get("10_regex_json_fat", {})
    # without a dfa block the key stays off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "dfa" not in json.loads(json.dumps(b._compact_line(out2)))


def test_soak_line_key_rides_compact_line():
    """ISSUE-17: a tiny ``soak:{p99_age,shed_ratio}`` key rides the
    compact line when the soak family ran (the nominal scenario's
    steady-state health); full per-scenario verdict documents stay in
    BENCH_DETAIL.json only."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = {"2_filter_map": dict(GOOD)}
    results["soak"] = {
        "scenarios": {
            "nominal": {"verdict": "pass", "rc": 0, "expected_rc": 0,
                        "p99_age_ms": 3.2, "shed_ratio": 0.0,
                        "fairness": 1.0,
                        "checks": {"exactly_once_accounting": True}},
            "overload": {"verdict": "collapse", "rc": 1, "expected_rc": 1,
                         "p99_age_ms": 0.0, "shed_ratio": 0.6,
                         "fairness": 1.0,
                         "checks": {"no_queueing_collapse": False}},
        },
        "soak": {"p99_age": 3.2, "shed_ratio": 0.0, "ok": 2, "of": 2},
    }
    out, rc = b._build_output(results)
    assert rc == 0
    # the aux section never becomes the headline
    assert out["value"] == 1000
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["soak"] == {"p99_age": 3.2, "shed_ratio": 0.0}
    # the bulky per-scenario verdicts never reach the line
    assert "scenarios" not in json.dumps(line)
    # without a soak block the key stays off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "soak" not in json.loads(json.dumps(b._compact_line(out2)))


def test_soak_key_fits_contract_and_trims_before_lag():
    """The full-matrix line with the soak key stays ≤1500 chars and the
    blowup trim ladder drops ``soak`` BEFORE ``lag`` (and therefore
    before ``part``/``link``, the unconditional contract field)."""
    import json
    import re

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = _full_results()
    results["soak"] = {
        "scenarios": {
            name: {"verdict": "pass", "rc": 0, "expected_rc": 0,
                   "p99_age_ms": 4.1, "shed_ratio": 0.02, "fairness": 0.97,
                   "checks": {"exactly_once_accounting": True,
                              "no_queueing_collapse": True,
                              "fairness": True, "no_starvation": True}}
            for name in ("nominal", "overload", "fairness")
        },
        "soak": {"p99_age": 4.1, "shed_ratio": 0.02, "ok": 3, "of": 3},
    }
    out, _ = b._build_output(results)
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["soak"] == {"p99_age": 4.1, "shed_ratio": 0.02}
    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"soak"') < ladder.index('"lag"')
    assert ladder.index('"soak"') < ladder.index('"part"')
    assert ladder.index('"soak"') < ladder.index('"link"')


def test_dfa_key_fits_contract_and_trims_before_link():
    """The full-matrix line with the dfa key stays ≤1500 chars and the
    blowup trim ladder drops ``dfa`` BEFORE ``lag``/``part``/``link``
    (the link calibration drops last)."""
    import json
    import re

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = _full_results()
    results["10_regex_json_fat"] = _full_config(41210, 8.3, "striped")
    results["10_regex_json_fat"]["dfa"] = [
        {"pattern_len": 29, "states": 22, "classes": 15,
         "table_bytes": 660, "packed": True},
    ]
    out, _ = b._build_output(results)
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["dfa"] == {"classes": 15, "states": 22}
    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"dfa"') < ladder.index('"lag"')
    assert ladder.index('"dfa"') < ladder.index('"link"')


def test_rebal_line_key_rides_compact_line():
    """ISSUE-18: a tiny ``rebal:{moves,drain_s}`` key rides the compact
    line when any config armed the rebalancer daemon; the full move
    records (src/dst groups, rollbacks) stay in BENCH_DETAIL.json."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    cfg = dict(GOOD)
    cfg["rebalance"] = {
        "moves": 1, "rollbacks": 0, "from": 0, "to": 1, "drain_s": 0.421,
    }
    out, rc = b._build_output({"9_partitioned": cfg})
    assert rc == 0
    assert out["configs"]["9_partitioned"]["rebalance"]["from"] == 0
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["rebal"] == {"moves": 1, "drain_s": 0.421}
    # the bulky detail never reaches the line
    assert "rebalance" not in line["configs"].get("9_partitioned", {})
    # without a daemon-armed config the key stays off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "rebal" not in json.loads(json.dumps(b._compact_line(out2)))


def test_rebal_key_fits_contract_and_trims_before_part():
    """The full-matrix line with the rebal key stays ≤1500 chars and
    the blowup trim ladder drops ``rebal`` BEFORE ``part`` (and
    therefore before ``link``, the unconditional contract field)."""
    import json
    import re

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = _full_results()
    results["9_partitioned"] = dict(GOOD)
    results["9_partitioned"]["part"] = {
        "n": 4, "groups": 2, "rebal": 1, "moves": 1, "exact": True,
        "offsets": {f"bench/{i}": 4999 for i in range(4)},
        "plan": {f"bench/{i}": i % 2 for i in range(4)},
    }
    results["9_partitioned"]["rebalance"] = {
        "moves": 1, "rollbacks": 0, "from": 0, "to": 1, "drain_s": 0.421,
    }
    out, _ = b._build_output(results)
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["rebal"] == {"moves": 1, "drain_s": 0.421}
    assert parsed["part"] == {"n": 4, "rebal": 1}
    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"rebal"') < ladder.index('"part"')
    assert ladder.index('"rebal"') < ladder.index('"link"')


def test_win_line_key_rides_compact_line():
    """ISSUE-19: a tiny ``win:{delta_ratio,keys}`` key rides the compact
    line when any windowed config ran — the WORST (largest) delta-vs-full
    downlink ratio and the widest key space across the family; the full
    per-config block (d2h A/B, per-kind delta rows, exactness, state
    bytes) stays in BENCH_DETAIL.json only."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = {}
    for name, ratio, keys in (
        ("5_windowed", 0.0111, 1), ("12_windowed_keyed", 0.31, 64),
    ):
        cfg = dict(GOOD)
        cfg["win"] = {
            "mode": "tumbling", "keys": keys, "batches": 6, "closed": 74,
            "late": 0, "deltas": {"close": 74, "upsert": 12},
            "delta_mb": 0.004, "full_mb": 0.35, "delta_ratio": ratio,
            "d2h_ms_delta": 3.4, "d2h_ms_delta_warm": 3.4,
            "rps_delta": 812000, "state_bytes": 56, "exact": True,
        }
        results[name] = cfg
    out, rc = b._build_output(results)
    assert rc == 0
    assert out["configs"]["5_windowed"]["win"]["exact"] is True
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["win"] == {"delta_ratio": 0.31, "keys": 64}
    # the bulky per-config block never reaches the line
    assert "win" not in line["configs"].get("5_windowed", {})
    # without a windowed config the key stays off entirely
    out2, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "win" not in json.loads(json.dumps(b._compact_line(out2)))


def test_win_key_fits_contract_and_trims_after_dfa_before_soak():
    """The full-matrix line with the win key stays ≤1500 chars and the
    blowup trim ladder drops ``win`` AFTER ``dfa`` but BEFORE ``soak``
    (and therefore before ``lag``/``part``/``link``, the unconditional
    contract field)."""
    import json
    import re

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = _full_results()
    results["5_windowed"] = _full_config(512000, 2.1, "windowed")
    results["5_windowed"]["win"] = {
        "mode": "sliding+keyed", "keys": 64, "batches": 6, "closed": 260,
        "late": 3, "deltas": {"close": 260, "upsert": 1800, "resync": 0},
        "delta_mb": 0.061, "full_mb": 0.35, "delta_ratio": 0.1741,
        "d2h_ms_delta": 4.9, "d2h_ms_delta_warm": 4.2, "rps_delta": 488000,
        "state_bytes": 1544, "exact": True, "d2h_cut": 6.0,
    }
    out, _ = b._build_output(results)
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["win"] == {"delta_ratio": 0.1741, "keys": 64}
    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"dfa"') < ladder.index('"win"')
    assert ladder.index('"win"') < ladder.index('"soak"')
    assert ladder.index('"win"') < ladder.index('"link"')


def test_mem_line_key_rides_compact_line():
    """ISSUE-20: a tiny ``mem:{peak_mb,owners}`` key rides the compact
    line when any config booked device memory — the WORST per-config
    ledger peak and the owner classes that held bytes across the
    family (plus ``leaks`` when non-zero); the full per-config block
    (per-owner bytes, reconcile doc) stays in BENCH_DETAIL.json."""
    import json

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = {}
    for name, peak, owners in (
        ("2_filter_map", 0.131, {"staged_batch": 98304}),
        ("5_windowed", 1.204, {"window_bank": 2888, "emit_buffer": 448}),
    ):
        cfg = dict(GOOD)
        cfg["memory"] = {"peak_mb": peak, "owners": owners}
        results[name] = cfg
    out, rc = b._build_output(results)
    assert rc == 0
    assert out["configs"]["5_windowed"]["memory"]["peak_mb"] == 1.204
    line = json.loads(json.dumps(b._compact_line(out)))
    assert line["mem"] == {
        "peak_mb": 1.204,
        "owners": ["emit_buffer", "staged_batch", "window_bank"],
    }
    # the bulky per-config block never reaches the line
    assert "memory" not in line["configs"].get("5_windowed", {})
    # a leaking run carries the count on the line
    results["5_windowed"]["memory"]["leaks"] = 2
    out2, _ = b._build_output(results)
    assert json.loads(
        json.dumps(b._compact_line(out2))
    )["mem"]["leaks"] == 2
    # without any booked config the key stays off entirely
    out3, _ = b._build_output({"2_filter_map": dict(GOOD)})
    assert "mem" not in json.loads(json.dumps(b._compact_line(out3)))


def test_mem_key_fits_contract_and_trims_after_win_before_soak():
    """The full-matrix line with the mem key stays ≤1500 chars and the
    blowup trim ladder drops ``mem`` AFTER ``win`` but BEFORE ``soak``
    (and therefore before ``lag``/``part``/``link``, the unconditional
    contract field)."""
    import json
    import re

    b = _bench()
    b._BACKEND_MODE = "tpu"
    results = _full_results()
    for name, cfg in results.items():
        cfg["memory"] = {
            "peak_mb": 0.262,
            "owners": {"staged_batch": 131072, "carry_bank": 4096},
        }
    out, _ = b._build_output(results)
    line = json.dumps(b._compact_line(out))
    assert len(line) <= 1500, f"compact line is {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["mem"] == {
        "peak_mb": 0.262, "owners": ["carry_bank", "staged_batch"],
    }
    src = open(_BENCH_PATH).read()
    ladder = re.search(r"for drop in \(([^)]*)\)", src, re.S).group(1)
    assert ladder.index('"win"') < ladder.index('"mem"')
    assert ladder.index('"mem"') < ladder.index('"soak"')
    assert ladder.index('"mem"') < ladder.index('"link"')
