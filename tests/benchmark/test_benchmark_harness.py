"""The benchmark's own tests; none needs the chip.

The loops are rehearsed on the CPU at a tiny size through a TEST-SIDE
switch (`spubench.device.REQUIRED_PLATFORM` is monkeypatched); the command
itself still refuses to run without a TPU (`test_command_refuses_without_tpu`).
A rate or a time read here is never a device number: the tests assert
counts, correctness and the shape of the result line only.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402
from spubench import check, device, manifest, shapes, trace_reduce  # noqa: E402
from spubench.ragged import to_values  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and not re.search(r"[\t\n]", s)


# -- the manifest ------------------------------------------------------------


def _check_keys_and_limits(m, root):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(m["command"]) <= 32 and all(_line(c) for c in m["command"])
    assert 1 <= len(m["paths"]) <= 16 and all(PATH.match(p) for p in m["paths"])
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert (root / "BENCHMARK.json").stat().st_size <= 64 * 1024
    # the full check with 24 cells must fit the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128


def _check_configs(m, root):
    names = [c["name"] for c in m["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        body = json.loads((root / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert body["guarantees"] and body["assumed"]


def _check_workloads(m, root):
    cells = [w["name"] for w in m["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(cells)) == len(cells)
    configs = {c["name"] for c in m["configs"]}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and _line(w["why"])
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(cells) // 2)


def _check_metrics(m, root):
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def cells_of(x):
        return set(x.get("workloads", cells))

    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.25
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["source"] in SOURCES and _line(x["layer"])
        # the metric it should move is reported in every cell where it is
        assert x["moves"] in e2e and cells_of(x) <= cells_of(e2e[x["moves"]])
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert cells_of(x) <= set(cells) and cells_of(x)
        # a list names a cell once
        assert len(x.get("workloads", ())) == len(set(x.get("workloads", ())))
    for cell in cells:
        assert any(cell in cells_of(x) and x["name"] != "setup_s"
                   for x in m["end_to_end"])
        assert any(cell in cells_of(x) for x in m["per_layer"])


def _check_readers(m, root):
    """Every metric entry names a reader file that loads and has `read`;
    every file under `layer_metrics/` is named by an entry, or is a paced
    cell's (`*.paced.py`: kept for `ns-paced`, PERF.md Open questions).
    So no reader sits in the tree that no manifest entry reads."""
    bench = root / m["paths"][0]
    for kind, entries in (("end_to_end", m["end_to_end"]),
                          ("layer_metrics", m["per_layer"])):
        for x in entries:       # no such file: `ManifestError`
            reader = manifest.load_plugin(bench, kind, x["name"])
            assert callable(getattr(reader, "read", None)), x["name"]
    named = {x["name"] for x in m["per_layer"]}
    unread = [p.name for p in sorted((bench / "layer_metrics").glob("*.py"))
              if p.stem not in named and not p.name.endswith(".paced.py")]
    assert unread == []


# every check takes (manifest, the root it lies in): the accepted manifest
# here, a copy with a cell added in `test_added_cell_joins_every_list`
MANIFEST_CHECKS = (_check_keys_and_limits, _check_configs, _check_workloads,
                   _check_metrics, _check_readers)


def test_manifest_keys_and_limits():
    _check_keys_and_limits(MANIFEST, REPO)


def test_manifest_configs():
    _check_configs(MANIFEST, REPO)


def test_manifest_workloads():
    _check_workloads(MANIFEST, REPO)


def test_manifest_metrics():
    _check_metrics(MANIFEST, REPO)


def test_manifest_readers():
    _check_readers(MANIFEST, REPO)


def _unread_file(bench, m):
    (bench / "layer_metrics" / "orphan_ms.py").write_text(
        "def read(obs):\n    return 1.0\n")


def _entry_without_file(bench, m):
    template = next(iter(m["per_layer"]))       # any entry; only the name matters
    m["per_layer"].append(dict(template, name="no_such_reader"))


def _file_without_read(bench, m):
    _entry_without_file(bench, m)
    (bench / "layer_metrics" / "no_such_reader.py").write_text("VALUE = 1.0\n")


@pytest.mark.parametrize("fault", [_unread_file, _entry_without_file,
                                   _file_without_read])
def test_check_readers_refuses(tmp_path, fault):
    """What let PR 31's two readers sit unread for five PRs: a reader file
    no entry names; and its mirror images."""
    shutil.copytree(BENCH / "layer_metrics", tmp_path / "benchmark" / "layer_metrics")
    shutil.copytree(BENCH / "end_to_end", tmp_path / "benchmark" / "end_to_end")
    m = json.loads(json.dumps(MANIFEST))
    _check_readers(m, tmp_path)
    fault(tmp_path / "benchmark", m)
    with pytest.raises((AssertionError, manifest.ManifestError)):
        _check_readers(m, tmp_path)


def test_files_under_paths_are_legally_named():
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard", "--"]
        + MANIFEST["paths"], cwd=REPO, capture_output=True, text=True,
    )
    # not a git checkout, or one unpacked inside an ignored directory of
    # another (git then lists nothing): walk the directories
    if out.returncode != 0 or not out.stdout.split():
        files = [str(p.relative_to(REPO)) for d in MANIFEST["paths"]
                 for p in (REPO / d).rglob("*")
                 if p.is_file() and "__pycache__" not in p.parts]
    else:
        files = out.stdout.split()
    assert files
    for f in files:
        assert PATH.match(f), f


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_resolves_by_name(cell):
    c = manifest.load_cell(cell)
    assert manifest.load_plugin(c.bench_dir, "modes", c.traffic["mode"]).run
    assert manifest.load_plugin(
        c.bench_dir, "corpora", c.config["corpus"]["generator"]).generate
    ref = manifest.load_plugin(
        c.bench_dir, "references", c.config["reference"]["name"])
    assert ref.expect and ref.OFFSETS in ("exact", "nondecreasing")
    assert bench_run.ROOT == REPO
    for kind, entries in (("end_to_end", c.end_to_end),
                          ("layer_metrics", c.per_layer)):
        assert entries
        for m in entries:
            assert callable(manifest.load_plugin(c.bench_dir, kind, m["name"]).read)


def test_benchmark_imports_neither_script():
    for p in list(BENCH.rglob("*.py")):
        text = p.read_text()
        assert not re.search(r"^\s*(import|from)\s+(bench|chip_smoke)\b", text, re.M), p


# -- corpora and references --------------------------------------------------


def test_corpora_equal_their_per_record_form():
    gj = manifest.load_plugin(BENCH, "corpora", "gen_json")
    ga = manifest.load_plugin(BENCH, "corpora", "gen_arrays")
    n, seed = 3000, [2**31 + 7, 0]
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(gj.NAMES), size=n)
    nums = rng.integers(0, 100000, size=n)
    want = [f'{{"name":"{gj.NAMES[picks[i]]}-{i & 1023}","n":{nums[i]}}}'.encode()
            for i in range(n)]
    assert to_values(*gj.generate(n, seed)) == want
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 10000, size=(n, 3))
    want = [f'["a{i & 255}","b{a[i][0]}",{a[i][1]},{a[i][2]},"x","y"]'.encode()
            for i in range(n)]
    assert to_values(*ga.generate(n, seed)) == want
    # the same seed gives the same inputs; another seed gives others
    assert to_values(*gj.generate(50, 5)) == to_values(*gj.generate(50, 5))
    assert to_values(*gj.generate(50, 5)) != to_values(*gj.generate(50, 6))


def _python_backend(specs, values):
    from fluvio_tpu.models import lookup
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
    from fluvio_tpu.smartmodule import SmartModuleInput

    b = SmartEngine(backend="python").builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    chain = b.initialize()
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
    out = chain.process(SmartModuleInput.from_records(records, 0, 1_000_000))
    assert out.error is None, out.error
    return [(r.offset_delta, r.value) for r in out.successes]


@pytest.mark.parametrize("config,specs", [
    ("fluvio-northstar-1p",
     [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})]),
    ("fluvio-array-explode-1p", [("array-map-json", None)]),
])
def test_reference_agrees_with_python_backend(config, specs):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    gen = manifest.load_plugin(BENCH, "corpora", cfg["corpus"]["generator"])
    refmod = manifest.load_plugin(BENCH, "references", cfg["reference"]["name"])
    values = to_values(*gen.generate(3000, 11))
    ref = check.Reference(refmod, values, 0, cfg["reference"]["params"])
    got = _python_backend(specs, values)
    assert len(got) == len(ref.lens) == ref.count(0, len(values))
    assert [len(v) for _, v in got] == ref.lens.tolist()
    assert b"".join(v for _, v in got) == ref.flat.tobytes()
    if ref.offsets_rule == "exact":
        assert [o for o, _ in got] == ref.src.tolist()


# -- the trace reduction -----------------------------------------------------


def test_trace_reduction_on_recorded_trace():
    import jax

    data = jax.profiler.ProfileData.from_text_proto(
        (BENCH / "testdata" / "trace_small.textproto").read_text()
    )
    # host clock: the open marker was written at perf_counter 100.0 s
    spans = [("device", 100.0040, 100.0070), ("fetch", 100.0081, 100.0094),
             ("stage", 100.0001, 100.0004)]
    r = trace_reduce.reduce_profile(data, (100.0, 100.010), spans)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.0045)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.003)
    assert ops["copy.2"] == pytest.approx(0.002)
    assert ops["fusion.3"] == pytest.approx(0.0005)
    assert "fusion.9" not in ops and "jit_chain" not in ops
    gaps = dict(r["idle_gaps"])
    # 5.0-8.0 ms is covered by the `device` span (4.0-7.0 of the window),
    # 9.0-10.5 by `fetch` (8.1-9.4 of the window); 1.0-2.0 by nothing
    assert gaps["device"] == pytest.approx(0.003)
    assert gaps["fetch"] == pytest.approx(0.0015)
    assert gaps["outside-executor-spans"] == pytest.approx(0.001)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_operation_names_are_cut_to_name_shape_opcode():
    hlo = ("%fusion.136 = u8[2621440]{0:T(1024)(128)(4,1)S(1)} fusion(u8[2621440]"
           "{0:T(1024)(128)(4,1)S(1)} %get-tuple-element.134), kind=kCustom, "
           "calls=%fused_computation.1.clone.clone")
    assert trace_reduce.short_name(hlo) == (
        "%fusion.136 u8[2621440] fusion(u8[2621440] %get-tuple-element.134), "
        "kind=kCustom")
    assert trace_reduce.short_name("copy.2") == "copy.2"
    assert len(trace_reduce.short_name("x" * 500)) == trace_reduce.NAME_CHARS
    tup = ("%while.1 = (s32[]{:T(128)}, u8[2621440]{0:T(1024)S(1)}) "
           "while((s32[]{:T(128)}) %tuple.72), condition=%c, body=%b")
    assert trace_reduce.short_name(tup).startswith(
        "%while.1 (s32[], u8[2621440]) while((s32[]) %tuple.72)")


def test_trace_without_device_operations_reads_nothing():
    import jax

    data = jax.profiler.ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }'
    )
    assert trace_reduce.reduce_profile(data) is None


def test_peaks_and_shapes():
    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks_for("TPU v99")
    with pytest.raises(KeyError):
        device.peaks_for("_source")
    from fluvio_tpu.smartengine.tpu import buffer

    for n in (1, 31, 33, 64, 100, 129, 300, 70 * 1024):
        assert shapes.bucket_width(n) == buffer.bucket_width(n)
    shape = {"max_in_len": 41, "max_out_len": 14, "fanout": 1}
    assert shapes.span_bytes(65536, shape) == 65536 * (64 + 4) + 65536 * (32 + 4)


# -- the loops, rehearsed on the CPU through the test-side switch ------------


REHEARSAL_BATCH = 512     # records to a stored batch in a rehearsal, at most
REHEARSAL_WARM_MAX = 4    # batches in a paced rehearsal's largest warm-up burst


def _tiny_config(cfg: dict, backlog: int) -> dict:
    """A configuration cut to a rehearsal's size. It keeps its own stored
    batch where that is smaller than `REHEARSAL_BATCH` (a deployment of
    wide records states a few to a batch: 512 of them would be tens of MB
    a batch), and then holds four such batches and whatever short tail
    ``backlog`` asks for beyond whole rehearsal batches."""
    per = min(REHEARSAL_BATCH, int(cfg["stored_batch_records"]))
    cfg["stored_batch_records"] = per
    cfg["backlog_records"] = min(backlog, 4 * per + backlog % REHEARSAL_BATCH)
    return cfg


def _tiny_root(tmp_path, backlog=2048, extra=None) -> Path:
    """A checkout-shaped directory with the benchmark's files, every
    configuration cut to a tiny backlog, and optional extra entries."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads(json.dumps(MANIFEST))
    for c in m["configs"]:
        f = root / c["file"]
        f.write_text(json.dumps(_tiny_config(json.loads(f.read_text()), backlog)))
    t = root / "benchmark" / "traffic" / "paced-16k.json"
    if t.exists():
        tr = json.loads(t.read_text())
        # a slow pace and warm-up bursts up to `REHEARSAL_WARM_MAX`: the
        # event loop has to stall for most of a second (a loaded CPU, the
        # profiler's start) before a window's slice coalesces more
        # batches than a burst had, which is a shape that compiles
        tr |= {"rate_batches_per_s": 5, "warm_single_batches": 2,
               "warm_max_batches": REHEARSAL_WARM_MAX, "grace_s": 30}
        t.write_text(json.dumps(tr))
    if extra:
        extra(root, m)
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


PACED_E2E = ("age_p50_ms", "age_p95_ms")
PACED_LAYER = ("slice_records_mean.paced", "exec_slice_ms_p50.paced",
               "compiles_in_window.paced", "gen_late_p95_ms.paced")


def _add_paced_cell(root, m):
    """`ns-paced`, the cell this benchmark keeps for later (PERF.md Open
    questions): its mode, mix and readers are in the tree, so manifest
    entries alone add it."""
    m["workloads"].append({
        "name": "ns-paced", "config": "fluvio-northstar-1p",
        "traffic": "paced-16k", "chips": 1, "why": "test"})
    for name in PACED_E2E:
        m["end_to_end"].append({
            "name": name, "unit": "ms", "better": "lower", "bound": 0.25,
            "source": "host_clock", "workloads": ["ns-paced"]})
    for name in PACED_LAYER:
        m["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "executor",
            "moves": "age_p95_ms", "workloads": ["ns-paced"]})


def _rehearse(monkeypatch, root, cell, trace=False, seconds=1.0):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    return bench_run.run_cell(cell, 2**31 + 11, seconds, trace, root=root,
                              t_process_start=time.perf_counter())


@pytest.mark.parametrize("cell", CELLS + ["ns-paced"])
def test_cell_rehearsal_on_cpu(monkeypatch, tmp_path, cell):
    root = _tiny_root(tmp_path, extra=_add_paced_cell)
    r = _rehearse(monkeypatch, root, cell)
    assert r["faults"] == [] and r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"   # named for what it ran on
    c = manifest.load_cell(cell, root)
    assert set(r["metrics"]) == {m["name"] for m in c.end_to_end}
    assert r["counts"]["fastpath_slices"] > 0
    assert r["counts"]["fallback_slices"] == 0
    assert r["counts"]["records_in"] > 0
    json.dumps(r)   # the result line is plain JSON
    # ... and ends with each number compared beside its limit; all hold
    assert list(r)[-1] == "compared" and len(r["compared"]) == 11
    assert all((c["value"] >= c["limit"]) if c.get("at_least")
               else (c["value"] <= c["limit"]) for c in r["compared"].values())


def test_paced_layer_metrics_on_cpu(monkeypatch, tmp_path):
    """Counts, not times: 20 batches at 5 a second are written, each gives
    one sample however late it comes (`grace_s` 30), and a compile in the
    window is held against the run only where every slice was a shape the
    warm-up had: a stall that coalesces more is this CPU's, not a fault."""
    root = _tiny_root(tmp_path, extra=_add_paced_cell)
    r = _rehearse(monkeypatch, root, "ns-paced", trace=True, seconds=4.0)
    assert r["correct"] is True and r["counts"]["samples"] == r["attempted"] == 20
    assert set(r["metrics"]) == set(PACED_LAYER)
    compiles = r["metrics"]["compiles_in_window.paced"]["value"]
    assert compiles == r["counts"]["compiles"]      # the window's own delta
    if r["counts"]["max_slice_batches"] <= REHEARSAL_WARM_MAX:
        assert compiles == 0.0
    assert 1 <= r["metrics"]["slice_records_mean.paced"]["value"] <= 20 * 400


def test_seed_orders_the_same_batches(tmp_path):
    """Every seed serves the same stored batches in another order."""
    from spubench.session import Session

    root = _tiny_root(tmp_path, backlog=2048 + 100)
    cell = manifest.load_cell("ns-drain", root)

    def batches(seed):
        s = Session(cell, seed, 1.0, False, 0.0)
        try:
            v = to_values(*s.generate(2048 + 100))
        finally:
            s.cleanup()
        return [tuple(v[i:i + 512]) for i in range(0, len(v), 512)]

    a, b, a2 = batches(2**31 + 5), batches(7), batches(2**31 + 5)
    assert a == a2 and a != b
    assert sorted(a[:4]) == sorted(b[:4]) and a[4] == b[4] and len(a[4]) == 100


def test_traced_rehearsal_reports_no_device_number(monkeypatch, tmp_path):
    """A CPU trace has no device plane: the device readers return nothing
    and `main` would refuse the line (no busy time)."""
    root = _tiny_root(tmp_path)
    r = _rehearse(monkeypatch, root, "ns-drain", trace=True, seconds=2.0)
    assert r["correct"] is True
    assert "busy_s" not in r["device"] and "breakdown" not in r
    for name in ("device_busy_ms_per_mrec", "device_idle_share",
                 "hbm_roofline_share"):
        assert name not in r["metrics"]
    for name in ("wire_out_mb_per_s", "fastpath_share", "spill_records",
                 "exec_up_ms_per_mrec", "exec_wait_ms_per_mrec"):
        assert name in r["metrics"]
    assert r["metrics"]["fastpath_share"]["value"] == 100.0
    assert r["metrics"]["spill_records"]["value"] == 0.0


def test_wrong_output_is_not_correct(monkeypatch, tmp_path):
    """`correct` is decided against the reference: a reference that states
    other values flips it."""
    def extra(root, m):
        p = root / "benchmark" / "references" / "northstar.py"
        p.write_text(p.read_text().replace(".upper()", ".lower()"))

    root = _tiny_root(tmp_path, extra=extra)
    r = _rehearse(monkeypatch, root, "ns-drain", seconds=0.2)
    assert r["correct"] is False
    assert any("bytes differ" in f for f in r["faults"])
    assert r["compared"]["reference_faults"]["value"] >= 1


def _add_dummy_cell(root, m):
    """A configuration, a traffic mix, a cell and a per-layer metric of
    its own, by new files plus manifest entries; no file that is there
    changes."""
    b = root / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    cfg = json.loads((b / "configs" / "fluvio-northstar-1p.json").read_text())
    cfg["name"] = "dummy-config"
    cfg["backlog_records"] = 1024
    (b / "configs" / "dummy-config.json").write_text(json.dumps(cfg))
    (b / "traffic" / "dummy-drain.json").write_text(
        json.dumps({"mode": "drain", "max_bytes": 20000}))
    (b / "layer_metrics" / "dummy_responses.py").write_text(
        "def read(obs):\n    return obs['responses']\n")
    m["configs"].append({
        "name": "dummy-config", "source": cfg["source"],
        "file": "benchmark/configs/dummy-config.json", "reduced": [],
        "why": "test"})
    m["workloads"].append({
        "name": "dummy-cell", "config": "dummy-config",
        "traffic": "dummy-drain", "chips": 1, "why": "test"})
    m["per_layer"].append({
        "name": "dummy_responses", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "client / socket",
        "moves": "records_in_per_s", "workloads": ["dummy-cell"]})
    for e in m["end_to_end"]:
        if e["name"] == "records_in_per_s":
            e["workloads"].append("dummy-cell")
    assert all(p.read_bytes() == data for p, data in before.items())


def test_dummy_cell_is_added_by_files_alone(monkeypatch, tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric are
    added by new files plus manifest entries; no existing file is edited."""
    root = _tiny_root(tmp_path, extra=_add_dummy_cell)
    r = _rehearse(monkeypatch, root, "dummy-cell", trace=True, seconds=0.5)
    assert r["correct"] is True
    # 20 kB slices hold one 512-record stored batch: two responses to a pass
    assert r["metrics"]["dummy_responses"]["value"] == r["counts"]["responses"]
    assert r["counts"]["responses"] > r["attempted"]
    assert set(r["metrics"]) == {"dummy_responses"}


def test_added_cell_joins_every_list(monkeypatch, tmp_path):
    """A later PR appends its cell to the `workloads` of every per-layer
    metric whose reader reads on it. Over a manifest copy whose new cell
    is in EVERY such list, the manifest checks of both test files hold
    (none pins a list, the number of cells or of configurations to what
    is accepted today), and the readers that are here read the new cell."""
    import test_tracing_readers as readers

    def extra(root, m):
        _add_dummy_cell(root, m)
        for e in m["per_layer"]:
            if "dummy-cell" not in e.get("workloads", ["dummy-cell"]):
                e["workloads"].append("dummy-cell")

    root = _tiny_root(tmp_path, extra=extra)
    m = json.loads((root / "BENCHMARK.json").read_text())
    assert len(m["workloads"]) == len(MANIFEST["workloads"]) + 1
    for check_manifest in MANIFEST_CHECKS:
        check_manifest(m, root)
    for name in readers.NEW_HOST + readers.NEW_DEVICE:
        readers.check_new_entry(m, name)
    r = _rehearse(monkeypatch, root, "dummy-cell", trace=True, seconds=0.5)
    assert r["correct"] is True
    # the host readers of the slice path read the new cell as they stand;
    # a CPU trace has no device plane, so the device readers stay silent
    assert set(readers.NEW_HOST) | {"dummy_responses", "fastpath_share",
                                    "exec_up_ms_per_mrec"} <= set(r["metrics"])
    assert not set(readers.NEW_DEVICE) & set(r["metrics"])


def _add_model_config(root, m):
    """What a `model_config` PR brings, by new files and by APPENDING to
    the manifest's lists alone: a configuration, a mix and a cell
    (`_add_dummy_cell`), the cell's name at the end of `records_in_per_s`
    and of every GENERAL reader's `workloads` (the readers that list every
    accepted cell; a reader of one chain's scopes stays that chain's), and
    two readers of its own at the end of `per_layer`."""
    accepted = {w["name"] for w in m["workloads"]}
    _add_dummy_cell(root, m)
    for e in m["per_layer"]:
        if accepted <= set(e.get("workloads", ())):
            e["workloads"].append("dummy-cell")
    (root / "benchmark" / "layer_metrics" / "dummy_slices.py").write_text(
        "def read(obs):\n    return obs['delta']['fastpath_slices']\n")
    m["per_layer"].append({
        "name": "dummy_slices", "unit": "slices", "better": "higher",
        "source": "program_counter", "layer": "slice path",
        "moves": "records_in_per_s", "workloads": ["dummy-cell"]})


def test_a_model_config_prs_additions_are_taken_by_appending(monkeypatch, tmp_path):
    """Every manifest-level assertion of `tests/benchmark` holds on a copy
    to which a `model_config` PR's entries were appended, and the new
    cell's rehearsal reports the readers it brought. An assertion that
    pins an entry's place in a list (PR 30's `per_layer[-4:]`, refused
    PR 35's wall) fails here, before the PR that meets it."""
    import test_aggregate_cell as aggregate
    import test_loop_order_readers as loop_readers
    import test_tracing_readers as readers

    root = _tiny_root(tmp_path, extra=_add_model_config)
    m = json.loads((root / "BENCHMARK.json").read_text())
    own = [e["name"] for e in m["per_layer"] if e["workloads"] == ["dummy-cell"]]
    assert own == ["dummy_responses", "dummy_slices"]
    assert len(m["per_layer"]) == len(MANIFEST["per_layer"]) + 2
    for check_manifest in MANIFEST_CHECKS:
        check_manifest(m, root)
    for name in readers.NEW_HOST + readers.NEW_DEVICE:
        readers.check_new_entry(m, name)
    aggregate.check_aggregate_entries(m)
    for name in loop_readers.LOOP_READERS:
        loop_readers.check_loop_reader_entry(m, name)
    # the accepted cells report what they reported
    for cell in CELLS:
        assert ([e["name"] for e in manifest.load_cell(cell, root).per_layer]
                == [e["name"] for e in manifest.load_cell(cell).per_layer])
    r = _rehearse(monkeypatch, root, "dummy-cell", trace=True, seconds=0.5)
    assert r["correct"] is True
    assert r["metrics"]["dummy_responses"]["value"] == r["counts"]["responses"]
    assert r["metrics"]["dummy_slices"]["value"] == r["counts"]["fastpath_slices"] > 0
    # ... beside the general readers it joined, the loop's three among
    # them; a reader of the aggregate chain alone is not asked
    assert set(loop_readers.LOOP_READERS) | set(readers.NEW_HOST) <= set(r["metrics"])
    assert not set(aggregate.NEW_READERS) & set(r["metrics"])


@pytest.mark.parametrize("own,backlog,want", [
    (8, 2048, (8, 32)),               # wide records: a few to a stored batch
    (8, 2048 + 100, (8, 132)),
    (100, 2048, (100, 400)),
    (512, 2048, (512, 2048)),
    (16384, 2048, (512, 2048)),       # the accepted two: as before
    (16384, 2048 + 100, (512, 2148)),
])
def test_rehearsal_size_keeps_a_smaller_stored_batch(own, backlog, want):
    cfg = _tiny_config({"stored_batch_records": own,
                        "backlog_records": 1_000_000, "name": "x"}, backlog)
    assert (cfg["stored_batch_records"], cfg["backlog_records"]) == want
    assert cfg["name"] == "x"


def test_configuration_of_eight_is_rehearsed_with_eight(monkeypatch, tmp_path):
    """A copied configuration that states 8 records to a stored batch is
    rehearsed with 8; the accepted configurations at 2,048 in 512s."""
    def extra(root, m):
        b = root / "benchmark"
        cfg = json.loads((b / "configs" / "fluvio-northstar-1p.json").read_text())
        assert (cfg["backlog_records"], cfg["stored_batch_records"]) == (2048, 512)
        cfg |= {"name": "narrow-config", "backlog_records": 976,
                "stored_batch_records": 8}
        (b / "configs" / "narrow-config.json").write_text(
            json.dumps(_tiny_config(cfg, 2048)))
        # one 8-record stored batch of 36 B records fits 500 B, two do not
        (b / "traffic" / "narrow-drain.json").write_text(
            json.dumps({"mode": "drain", "max_bytes": 500}))
        m["configs"].append({
            "name": "narrow-config", "source": cfg["source"],
            "file": "benchmark/configs/narrow-config.json", "reduced": [],
            "why": "test"})
        m["workloads"].append({
            "name": "narrow-cell", "config": "narrow-config",
            "traffic": "narrow-drain", "chips": 1, "why": "test"})
        for e in m["end_to_end"] + m["per_layer"]:
            if e["name"] in ("records_in_per_s", "fastpath_share"):
                e["workloads"].append("narrow-cell")

    root = _tiny_root(tmp_path, extra=extra)
    for c in MANIFEST["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        assert (cfg["backlog_records"], cfg["stored_batch_records"]) == (2048, 512)
    cell = manifest.load_cell("narrow-cell", root)
    assert cell.config["stored_batch_records"] == 8
    assert cell.config["backlog_records"] == 32
    r = _rehearse(monkeypatch, root, "narrow-cell", seconds=0.2)
    assert r["correct"] is True
    # every response carries one stored batch of 8
    assert r["counts"]["records_in"] == 8 * r["counts"]["responses"]


def test_result_line_ends_with_the_numbers_compared(monkeypatch, capsys):
    """`main` prints each number compared beside its limit as the last
    lines of standard error, and the result line carries them last."""
    compared = {"reference_faults": {"value": 2, "limit": 0},
                "fastpath_slices": {"value": 7, "limit": 1, "at_least": True}}
    monkeypatch.setattr(bench_run, "run_cell", lambda *a, **k: {
        "correct": False, "faults": ["warm-up pass: bytes differ"],
        "device": {"busy_s": 1.0}, "metrics": {}, "compared": compared})
    assert bench_run.main(["--workload", "ns-drain", "--seed", str(2**31 + 3),
                           "--seconds", "1", "--trace", "1"]) == 0
    out, err = capsys.readouterr()
    assert list(json.loads(out.strip().splitlines()[-1]))[-1] == "compared"
    assert err.strip().splitlines()[-3:] == [
        "benchmark: NOT CORRECT: warm-up pass: bytes differ",
        "benchmark: compared reference_faults 2 limit 0",
        "benchmark: compared fastpath_slices 7 limit >= 1"]


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no-such-cell")
    with pytest.raises(manifest.ManifestError):
        manifest.load_plugin(BENCH, "modes", "no-such-mode")


def test_command_refuses_without_tpu():
    """The real command, unsteered: no TPU -> non-zero exit, no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "nothing is measured without the chip" in out.stderr
