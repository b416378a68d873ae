"""ISSUE-30: what `agg-drain` adds to the benchmark; none needs the chip.

The CPU rehearsal of `test_benchmark_harness.py` picks the cell up by
itself. Here: the plain reference against `numpy.cumsum` and against the
program's interpreter, the four new readers on a fixture and on a
rehearsal (each returns None, never 0, where what it reads is absent),
and the manifest entries.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH), str(Path(__file__).resolve().parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_benchmark_harness as harness  # noqa: E402
from spubench import agg_bytes, check, manifest, shapes  # noqa: E402
from spubench import xplane_scopes as xs  # noqa: E402
from spubench.ragged import to_values  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

CELL, CONFIG = "agg-drain", "fluvio-aggregate-1p"
NEW_READERS = ("chain_acquire_ms_per_stream", "stream_chain_builds",
               "device_agg_ms_per_mrec", "agg_scan_hbm_share")


def _reader(name):
    return manifest.load_plugin(BENCH, "layer_metrics", name).read


def _config():
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    xs._CACHE.clear()
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


# -- the reference -----------------------------------------------------------


def _values(n=3000, seed=11):
    gen = manifest.load_plugin(BENCH, "corpora", "gen_json")
    return to_values(*gen.generate(n, seed))


def test_reference_agrees_with_cumsum_of_the_parsed_field():
    refmod = manifest.load_plugin(BENCH, "references", "aggregate_field")
    values = _values()
    n = np.array([int(v.rsplit(b":", 1)[1][:-1]) for v in values], dtype=np.int64)
    src, out = refmod.expect(values, **_config()["reference"]["params"])
    assert refmod.OFFSETS == "exact" and src.tolist() == list(range(len(values)))
    assert [int(v) for v in out] == np.cumsum(n).tolist()
    assert out[-1] == str(int(n.sum())).encode()
    _, seeded = refmod.expect(values, initial=b"1000")
    assert [int(v) for v in seeded] == (np.cumsum(n) + 1000).tolist()
    _, top = refmod.expect(values, combine="max")
    assert [int(v) for v in top] == np.maximum.accumulate(n).tolist()
    # it imports nothing of the program
    text = (BENCH / "references" / "aggregate_field.py").read_text()
    assert "fluvio_tpu" not in text and "spubench" not in text


@pytest.mark.parametrize("seed", [b"", b"1000"])
def test_reference_agrees_with_python_backend(seed):
    """The program's own interpreter, given the configuration's ad-hoc
    source, states what the reference states (as
    `test_benchmark_harness.test_reference_agrees_with_python_backend`
    holds the two accepted configurations)."""
    from fluvio_tpu.protocol.record import Record
    from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
    from fluvio_tpu.smartmodule import SmartModuleInput

    cfg = _config()
    (step,) = cfg["chain"]
    values = _values()
    b = SmartEngine(backend="python").builder()
    b.add_smart_module(
        SmartModuleConfig(params=step["params"], initial_data=seed), step["adhoc"])
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
    out = b.initialize().process(SmartModuleInput.from_records(records, 0, 1_000_000))
    assert out.error is None, out.error
    refmod = manifest.load_plugin(BENCH, "references", cfg["reference"]["name"])
    ref = check.Reference(
        refmod, values, 0, cfg["reference"]["params"] | {"initial": seed.decode()})
    assert [r.offset_delta for r in out.successes] == ref.src.tolist()
    assert b"".join(r.value for r in out.successes) == ref.flat.tobytes()
    assert ref.count(0, len(values)) == len(values)


# -- the manifest ------------------------------------------------------------


def check_aggregate_entries(m):
    """PR 30's four entries in the manifest ``m``: each is there once, for
    the aggregate cell alone. Where in the list they stand is nobody's to
    assert: a later PR appends its own after them."""
    for name in NEW_READERS:
        (entry,) = [e for e in m["per_layer"] if e["name"] == name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "records_in_per_s"


def test_cell_and_configuration_are_as_the_issue_names_them():
    cell = manifest.load_cell(CELL, REPO)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "drain-16m", 1)
    cfg, ns = cell.config, json.loads(
        (BENCH / "configs" / "fluvio-northstar-1p.json").read_text())
    assert cfg["reduced"] == [] and cfg["backlog_records"] == 1_000_000
    assert cfg["stored_batch_records"] == 16384
    assert cfg["corpus"] == ns["corpus"]                  # the north star's bytes
    for key in ("spus", "partitions", "replication", "in_sync_replica",
                "engine_backend", "chips"):
        assert cfg["deployment"][key] == ns["deployment"][key]
    assert cfg["guarantees"][:3] == ns["guarantees"] and len(cfg["guarantees"]) == 5
    assert [s["kind"] for s in cfg["chain"]] == ["AGGREGATE"]
    assert {m["name"] for m in cell.end_to_end} == {"records_in_per_s", "setup_s"}
    check_aggregate_entries(harness.MANIFEST)


# -- the device readers on a recorded shape ----------------------------------


def _agg_fixture_bytes() -> bytes:
    import jax

    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        (BENCH / "testdata" / "trace_agg_small.textproto").read_text())


def _device_obs(tmp_path, raw, busy_s):
    p = tmp_path / "host.xplane.pb"
    p.write_bytes(raw)
    # a 20 s window whose traced span is the fixture's 10 ms; 2M records,
    # two dispatches of 393,216 and 213,000 records in the span
    return {
        "trace": {"busy_s": busy_s, "window_s": 0.010, "path": str(p)},
        "window_s": 20.0, "records_in": 2_000_000,
        "trace_spans": [{"records": 393_216}, {"records": 213_000}],
        "shape": {"max_in_len": 41, "max_out_len": 14, "fanout": 1},
        "device_kind": "TPU v5 lite",
    }


def test_aggregate_device_readers_on_fixture(tmp_path):
    obs = _device_obs(tmp_path, _agg_fixture_bytes(), 0.00425)
    r = xs.reduce_run(obs)
    assert r["scope_s"] == pytest.approx({
        "repad": 0.001, "stage0.aggregate": 0.0005,
        "stage0.aggregate_scan": 0.00225, "compact": 0.00025})
    assert agg_bytes.agg_scope_seconds(r) == pytest.approx(0.00275)
    agg = _reader("device_agg_ms_per_mrec")(obs)
    assert agg == pytest.approx(0.00275 / 0.010 * 20.0 * 1e3 / 2.0)
    # the accepted chain reader counts the same operations, and compact
    chain = _reader("device_chain_ms_per_mrec")(obs)
    assert chain == pytest.approx(0.003 / 0.010 * 20.0 * 1e3 / 2.0)
    moved = (524_288 + 262_144) * (64 + 4 + 8)
    assert agg_bytes.agg_stage_bytes(393_216, obs["shape"]) == 524_288 * 76
    share = _reader("agg_scan_hbm_share")(obs)
    assert share == pytest.approx(100 * (moved / 819e9) / 0.00275)
    assert 0 < share < 100
    assert shapes.bucket_width(41) == 64


@pytest.mark.parametrize("name", ["device_agg_ms_per_mrec", "agg_scan_hbm_share"])
def test_aggregate_device_readers_stay_silent(tmp_path, monkeypatch, name):
    import test_tracing_readers as readers

    read = _reader(name)
    assert read(_device_obs(tmp_path, _agg_fixture_bytes(), 0.00425)) is not None
    assert read({"records_in": 5, "trace": None}) is None      # no traced run
    # a trace whose chain has no aggregate stage: None, not 0
    xs._CACHE.clear()
    assert read(_device_obs(tmp_path, readers._fixture_bytes(), 0.00775)) is None
    xs._CACHE.clear()
    assert read(_device_obs(tmp_path, b"", 0.00425)) is None   # no device plane
    # a program without the scopes (a parent commit): nothing, no raise
    monkeypatch.setattr(xs, "_vocabulary", lambda: None)
    assert read(_device_obs(tmp_path, _agg_fixture_bytes(), 0.00425)) is None


# -- the host readers --------------------------------------------------------


def test_chain_acquire_reader_is_the_mean_over_stream_opens():
    from test_tracing_readers import _seed_flow as _flow

    read = _reader("chain_acquire_ms_per_stream")
    obs = {"t_open": 100.0, "t_close": 110.0, "records_in": 4000}
    assert read(obs) is None                                   # no flow at all
    _flow(99.0, [("chain_acquire", 0.5), ("read", 0.1)])       # before the window
    _flow(101.0, [("read", 0.1), ("finish", 0.2)])             # a later slice
    assert read(obs) is None                  # no open in the window: None, not 0
    _flow(102.0, [("chain_acquire", 0.004), ("read", 0.1)])
    _flow(104.0, [("chain_acquire", 0.002), ("read", 0.1)])
    assert read(obs) == pytest.approx(3.0)


def test_stream_chain_builds_reader_counts_the_windows_builds():
    read = _reader("stream_chain_builds")
    slices = {"stream_chain_builds": 3, "stream_chain_hits": 9}
    obs = {"t_open": 100.0, "t_close": 110.0, "c_close": {"slices": slices}}
    # a program without the counter (a parent commit): None
    assert read(obs | {"c_close": {"slices": {"fastpath_slices": 4}}}) is None
    assert read(obs) == 0                     # the counter is there: 0 is a count
    for t in (99.0, 101.0, 105.5, 111.0):
        TELEMETRY.add_chain_build("aggregate")
        TELEMETRY.events.recent()[-1].t = t
    TELEMETRY.add_heal()
    assert read(obs) == 2
    # a ring that overwrote part of the window says nothing
    for _ in range(TELEMETRY.events.capacity):
        TELEMETRY.add_chain_build("aggregate")
        TELEMETRY.events.recent()[-1].t = 109.0
    assert read(obs) is None


def test_rehearsal_reads_the_host_readers_and_books_no_build(monkeypatch, tmp_path):
    root = harness._tiny_root(tmp_path)
    r = harness._rehearse(monkeypatch, root, CELL, trace=True, seconds=1.0)
    assert r["correct"] is True and r["attempted"] >= 2     # stream re-opens
    assert r["metrics"]["stream_chain_builds"]["value"] == 0.0
    assert r["metrics"]["chain_acquire_ms_per_stream"]["value"] > 0.0
    assert r["counts"]["compiles"] == 0
    assert r["metrics"]["fastpath_share"]["value"] == 100.0
    # a CPU trace has no device plane: the device readers stay silent
    assert "device_agg_ms_per_mrec" not in r["metrics"]
    assert "agg_scan_hbm_share" not in r["metrics"]
    # one response a pass covers the rehearsal's whole backlog, every
    # output an input's: the count the reference states
    assert r["counts"]["records_out"] == r["counts"]["records_in"]
