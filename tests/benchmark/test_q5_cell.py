"""ISSUE-37: what `q5-drain` adds to the benchmark; none needs the chip.

The CPU rehearsal of `test_benchmark_harness.py` picks the cell up by
itself (at the configuration's own event rate no window closes in a
rehearsal's 2,048 bids: the reference states 0 rows and the rehearsal
is `correct`; one here lowers the rate so that windows do close). Here:
the plain reference on a case small enough to check by hand, the
manifest entries by membership and floors (`check_window_entries`,
never an index), and the four window readers on a recorded fixture, on
seeded events and through a traced rehearsal, each None, never 0,
where what it reads is absent.
"""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH), str(Path(__file__).resolve().parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_benchmark_harness as harness  # noqa: E402
from spubench import manifest, window_bytes  # noqa: E402
from spubench import xplane_scopes as xs  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

CELL, CONFIG, MIX = "q5-drain", "fluvio-nexmark-q5-1p", "drain-evt-16m"
WINDOW_READERS = ("device_window_ms_per_mrec", "window_merge_hbm_share",
                  "window_late_records", "window_bank_growths")


def _reader(name):
    return manifest.load_plugin(BENCH, "layer_metrics", name).read


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    xs._CACHE.clear()
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


# -- the reference -------------------------------------------------------------


def _bid(auction, t):
    return b'{"auction":%d,"bidder":1,"price":100,"dateTime":%d,"extra":""}' % (
        auction, t)


def test_reference_on_a_case_checked_by_hand():
    """Windows of 10 sliding by 5, watermark 3 behind: bids of auction 7
    at 1, 2 and 6, of auction 9 at 2 and 7, then time jumps."""
    ref = manifest.load_plugin(BENCH, "references", "nexmark_q5")
    values = [_bid(7, 1), _bid(9, 2), _bid(7, 2), _bid(7, 6), _bid(9, 7),
              _bid(3, 12),     # watermark 9: nothing ends by then
              _bid(3, 13),     # watermark 10: [0,10) closes
              _bid(7, 4),      # 9 behind the newest bid
              _bid(3, 19)]     # watermark 16: [5,15) closes
    src, out, late = ref.fold(values, window_ms=10, slide_ms=5, lateness_ms=3)
    # [0,10): 7 x3 (at 1, 2, 6), 9 x2 -> auction 7; closed by record 6.
    # the bid of 7 at 4 arrives after it: late for [0,10) (k=0), and its
    # other window starts at -5 (k < 0: no such window) -> 1 late.
    # [5,15): 7 at 6, 9 at 7, 3 at 12 and 13 -> auction 3 with 2; closed
    # by record 8 (watermark 16 >= 15). [10,20) stays open.
    assert out == [b'{"window_end":10,"auction":7,"num":3}',
                   b'{"window_end":15,"auction":3,"num":2}']
    assert src.tolist() == [6, 8] and late == 1
    assert ref.OFFSETS == "nondecreasing"
    assert ref.expect(values, window_ms=10, slide_ms=5, lateness_ms=3)[1] == out
    # a tie emits every auction at the maximum, in ascending id
    _s, tie, _l = ref.fold([_bid(9, 1), _bid(7, 2), _bid(1, 30)],
                           window_ms=10, slide_ms=10, lateness_ms=0)
    assert tie == [b'{"window_end":10,"auction":7,"num":1}',
                   b'{"window_end":10,"auction":9,"num":1}']
    # it imports nothing of the program
    text = (BENCH / "references" / "nexmark_q5.py").read_text()
    assert "import fluvio_tpu" not in text and "from fluvio_tpu" not in text
    assert "spubench" not in text


# -- the manifest: by membership, never by position ----------------------------

WINDOW_ENTRIES = {
    "device_window_ms_per_mrec": ("ms/Mrec", "lower", "device_trace", "kernels"),
    "window_merge_hbm_share": ("%", "higher", "device_trace", "kernels"),
    "window_late_records": ("records", "lower", "program_counter", "engine"),
    "window_bank_growths": ("growths", "lower", "program_counter", "slice path"),
}
# PR 30's four: `test_aggregate_cell.py:check_aggregate_entries` holds
# each of their lists EQUAL to the aggregate cell's (PERF.md section 7)
AGGREGATE_ONLY = ("chain_acquire_ms_per_stream", "stream_chain_builds",
                  "device_agg_ms_per_mrec", "agg_scan_hbm_share")


def check_window_entries(m):
    """This PR's additions in the manifest ``m``: the cell, its
    configuration, the four window entries (each there once, moving
    `records_in_per_s`, listing the cell: a floor, a later windowed
    cell may join) and the cell once in the list of `records_in_per_s`
    and of every general reader. Where in a list anything stands is
    nobody's to assert: a later PR appends its own after these."""
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] and "q5.sql" in entry["source"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cells = {w["name"] for w in m["workloads"]}
    for name, (unit, better, source, layer) in WINDOW_ENTRIES.items():
        (e,) = [e for e in m["per_layer"] if e["name"] == name]
        assert (e["unit"], e["better"], e["source"], e["layer"]) == (
            unit, better, source, layer), name
        assert e["moves"] == "records_in_per_s"
        assert CELL in e["workloads"] and set(e["workloads"]) <= cells
    (rate,) = [e for e in m["end_to_end"] if e["name"] == "records_in_per_s"]
    assert rate["workloads"].count(CELL) == 1
    for e in m["per_layer"]:
        if {"ns-drain", "explode-drain", "agg-drain"} <= set(e["workloads"]):
            assert e["workloads"].count(CELL) == 1, e["name"]
        if e["name"] in AGGREGATE_ONLY:
            assert CELL not in e["workloads"], e["name"]


def _two_more_entries(m):
    """A later PR's additions: a windowed cell and two readers appended
    after this PR's four, its cell joining two of their lists."""
    m = copy.deepcopy(m)
    m["workloads"].append({
        "name": "q7-drain", "config": CONFIG, "traffic": "drain-16m",
        "chips": 1, "why": "a later windowed cell"})
    for name in ("window_merge_hbm_share", "window_late_records"):
        (e,) = [e for e in m["per_layer"] if e["name"] == name]
        e["workloads"].append("q7-drain")
    for name in ("later_reader_a", "later_reader_b"):
        m["per_layer"].append({
            "name": name, "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "kernels",
            "moves": "records_in_per_s", "workloads": [CELL, "q7-drain"]})
    return m


@pytest.mark.parametrize("later", [False, True], ids=["as-is", "appended-to"])
def test_window_entries_by_membership_and_floors(later):
    m = _two_more_entries(harness.MANIFEST) if later else harness.MANIFEST
    check_window_entries(m)
    # and the check does hold something: a cell taken off a list fails it
    broken = copy.deepcopy(m)
    (e,) = [e for e in broken["per_layer"] if e["name"] == "window_bank_growths"]
    e["workloads"].remove(CELL)
    with pytest.raises(AssertionError):
        check_window_entries(broken)


def test_cell_and_configuration_are_as_the_issue_names_them():
    cell = manifest.load_cell(CELL, REPO)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (CONFIG, MIX, 1)
    assert cell.traffic == {"mode": "drain_eventtime", "max_bytes": 16 * 2**20}
    cfg, ns = cell.config, json.loads(
        (BENCH / "configs" / "fluvio-northstar-1p.json").read_text())
    assert cfg["reduced"] == [] and cfg["backlog_records"] == 1_000_000
    assert cfg["stored_batch_records"] == 16384 and cfg["warm_passes"] == 2
    for key in ("spus", "partitions", "replication", "in_sync_replica",
                "engine_backend", "chips"):
        assert cfg["deployment"][key] == ns["deployment"][key]
    assert cfg["guarantees"][:3] == ns["guarantees"] and len(cfg["guarantees"]) == 6
    assert [s["kind"] for s in cfg["chain"]] == ["AGGREGATE"]
    assert "dsl.WindowProgram" in cfg["chain"][0]["adhoc"]
    # the source's shapes: window, slide, watermark delay, generator ratios
    assert cfg["reference"]["params"] == {
        "window_ms": 10000, "slide_ms": 2000, "lateness_ms": 4000}
    for text in ("window_ms=10000", "slide_ms=2000", "lateness_ms=4000"):
        assert text in cfg["chain"][0]["adhoc"]
    assert cfg["corpus"]["params"] | {"base_time_ms": 0} == {
        "first_event_rate": 10000, "hot_auction_ratio": 2,
        "hot_bidders_ratio": 4, "num_in_flight_auctions": 100,
        "avg_bid_byte_size": 100, "base_time_ms": 0}
    for key in ("bid_topic", "rendering", "extra", "base_time_ms", "output_row",
                "empty_bank", "seed_order", "consumer_max_bytes", "writes"):
        assert cfg["assumed"][key]
    (entry,) = [c for c in harness.MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert {e["name"] for e in cell.end_to_end} == {"records_in_per_s", "setup_s"}
    assert set(WINDOW_READERS) <= {e["name"] for e in cell.per_layer}
    # the window stage's own readers are files the harness can load
    for name in WINDOW_READERS:
        assert callable(_reader(name))


# -- the device readers on a recorded shape ------------------------------------


def _fixture_bytes(name="trace_window_small.textproto") -> bytes:
    import jax

    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        (BENCH / "testdata" / name).read_text())


def _device_obs(tmp_path, raw, busy_s):
    p = tmp_path / "host.xplane.pb"
    p.write_bytes(raw)
    # a 20 s window whose traced span is the fixture's 10 ms; 2M records,
    # two dispatches of 147,456 and 115,264 records in the span
    return {
        "trace": {"busy_s": busy_s, "window_s": 0.010, "path": str(p)},
        "window_s": 20.0, "records_in": 2_000_000,
        "trace_spans": [{"records": 147_456}, {"records": 115_264}],
        "shape": {"max_in_len": 120, "max_out_len": 53, "fanout": 1},
        "device_kind": "TPU v5 lite", "window_replicas": 5,
    }


def test_window_device_readers_on_fixture(tmp_path):
    obs = _device_obs(tmp_path, _fixture_bytes(), 0.0055)
    r = xs.reduce_run(obs)
    assert r["scope_s"] == pytest.approx({
        "repad": 0.001, "stage0.window": 0.0005,
        "stage0.window_merge": 0.0035, "stage0.window_top": 0.00025})
    assert window_bytes.window_scope_seconds(r) == pytest.approx(0.00425)
    assert window_bytes.merge_scope_seconds(r) == pytest.approx(0.0035)
    got = _reader("device_window_ms_per_mrec")(obs)
    assert got == pytest.approx(0.00425 / 0.010 * 20.0 * 1e3 / 2.0)
    # the accepted chain reader counts the same operations
    chain = _reader("device_chain_ms_per_mrec")(obs)
    assert chain == pytest.approx(got)
    assert window_bytes.merge_bytes(147_456, 5) == 262_144 * 5 * 48
    moved = (262_144 + 131_072) * 5 * 48
    share = _reader("window_merge_hbm_share")(obs)
    assert share == pytest.approx(100 * (moved / 819e9) / 0.0035)
    assert 0 < share < 100
    # the replicas are the cell's own: a tumbling window moves a fifth,
    # and a run that states none reads nothing
    assert _reader("window_merge_hbm_share")(
        obs | {"window_replicas": 1}) == pytest.approx(share / 5)
    assert _reader("window_merge_hbm_share")(
        {k: v for k, v in obs.items() if k != "window_replicas"}) is None


@pytest.mark.parametrize("name", WINDOW_READERS[:2])
def test_window_device_readers_stay_silent(tmp_path, monkeypatch, name):
    read = _reader(name)
    assert read(_device_obs(tmp_path, _fixture_bytes(), 0.0055)) is not None
    assert read({"records_in": 5, "trace": None}) is None      # no traced run
    # a trace whose chain has no window stage: None, not 0
    xs._CACHE.clear()
    assert read(_device_obs(
        tmp_path, _fixture_bytes("trace_agg_small.textproto"), 0.00425)) is None
    xs._CACHE.clear()
    assert read(_device_obs(tmp_path, b"", 0.0055)) is None    # no device plane
    # a program without the scopes (a parent commit): nothing, no raise
    monkeypatch.setattr(xs, "_vocabulary", lambda: None)
    assert read(_device_obs(tmp_path, _fixture_bytes(), 0.0055)) is None


def test_mode_notes_the_configurations_replicas(monkeypatch):
    """`window_replicas` is the cell's window over its slide, not a
    constant of the byte function: 5 for Q5's HOP, 1 for a tumbling one."""
    import asyncio
    import types

    mode = manifest.load_plugin(BENCH, "modes", "drain_eventtime")

    async def drain_run(s):
        return {"records_in": 0}

    real = manifest.load_plugin
    monkeypatch.setattr(
        mode.manifest, "load_plugin",
        lambda d, kind, name: types.SimpleNamespace(run=drain_run)
        if (kind, name) == ("modes", "drain") else real(d, kind, name))
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    s = types.SimpleNamespace(
        config=cfg, cell=types.SimpleNamespace(bench_dir=BENCH), seed=3)
    assert asyncio.run(mode.run(s))["window_replicas"] == 5
    cfg["reference"]["params"] |= {"window_ms": 10000, "slide_ms": 10000}
    assert asyncio.run(mode.run(s))["window_replicas"] == 1


# -- the host readers ----------------------------------------------------------


def test_window_event_readers_count_the_windows_events(monkeypatch):
    late, grown = _reader("window_late_records"), _reader("window_bank_growths")
    obs = {"t_open": 100.0, "t_close": 110.0}
    assert late(obs) == 0 and grown(obs) == 0      # the program books them: a count
    for t, rows in ((99.0, 7), (101.0, 30), (105.5, 12), (111.0, 9)):
        TELEMETRY.add_window_delta("late", rows)
        TELEMETRY.events.recent()[-1].t = t
    TELEMETRY.add_window_delta("invalid", 5)
    TELEMETRY.events.recent()[-1].t = 102.0
    TELEMETRY.add_window_delta("close", 1000)      # no event: not a drop
    for t in (99.5, 103.0):
        TELEMETRY.add_window_grow("bank 1024->2048 emit 1024->1024")
        TELEMETRY.events.recent()[-1].t = t
    TELEMETRY.add_heal()
    assert late(obs) == 30 + 12 + 5 and grown(obs) == 1
    # a ring that overwrote part of the window says nothing
    for _ in range(TELEMETRY.events.capacity):
        TELEMETRY.add_window_grow("x")
        TELEMETRY.events.recent()[-1].t = 109.0
    assert late(obs) is None and grown(obs) is None
    # a program that books no such event (a parent commit): None
    TELEMETRY.reset()
    monkeypatch.delattr(type(TELEMETRY), "add_window_grow")
    assert late(obs) is None and grown(obs) is None


def _windows_close(root, m):
    """92 bids/s: a rehearsal's 2,048 bids are 22 s of event time, and a
    swapped pair of 512-bid batches is 11 s apart, so 12 s of delay."""
    f = root / "benchmark" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(f.read_text())
    cfg["corpus"]["params"]["first_event_rate"] = 100
    cfg["reference"]["params"]["lateness_ms"] = 12000
    cfg["chain"][0]["adhoc"] = cfg["chain"][0]["adhoc"].replace(
        "lateness_ms=4000", "lateness_ms=12000")
    f.write_text(json.dumps(cfg))
    # one stored batch and a little more a slice: several slices a pass
    (root / "benchmark" / "traffic" / f"{MIX}.json").write_text(json.dumps(
        {"mode": "drain_eventtime", "max_bytes": 90_000}))


def test_traced_rehearsal_with_closing_windows(monkeypatch, tmp_path):
    root = harness._tiny_root(tmp_path, extra=_windows_close)
    r = harness._rehearse(monkeypatch, root, CELL, trace=True, seconds=2.0)
    assert r["faults"] == [] and r["correct"] is True
    assert r["attempted"] >= 2 and r["failed"] == 0       # stream re-opens
    # windows closed, a few rows a pass, in some responses and not in
    # others (the window may end inside the last pass: a loaded machine
    # makes few)
    passes = r["attempted"]
    assert 0 < r["counts"]["records_out"] <= 8 * passes
    assert r["counts"]["responses"] >= 3 * (passes - 1) + 1
    assert r["counts"]["compiles"] == 0 and r["counts"]["fallback_slices"] == 0
    assert r["metrics"]["fastpath_share"]["value"] == 100.0
    assert r["metrics"]["spill_records"]["value"] == 0.0
    # the bank was grown in the warm-up (about 1,900 open entries), not here
    assert r["metrics"]["window_bank_growths"]["value"] == 0.0
    assert r["metrics"]["window_late_records"]["value"] == 0.0
    assert any(e.kind == "window-grow" for e in TELEMETRY.events.recent())
    # a CPU trace has no device plane: the device readers stay silent
    assert "device_window_ms_per_mrec" not in r["metrics"]
    assert "window_merge_hbm_share" not in r["metrics"]
