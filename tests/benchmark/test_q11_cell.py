"""What `q11-drain` adds to the benchmark; none needs the chip.

The CPU rehearsal of `test_benchmark_harness.py` picks the cell up by
itself. Here: the plain reference on a case small enough to check by
hand, the manifest entries by membership and floors
(`check_session_entries`, never an index), and the three session readers
on a recorded fixture, on seeded events and through a traced rehearsal
whose bank outgrows its first capacity, each None, never 0, where what
it reads is absent.
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
from spubench import manifest, session_bytes  # noqa: E402
from spubench import xplane_scopes as xs  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

CELL, CONFIG, MIX = "q11-drain", "fluvio-nexmark-q11-1p", "drain-evt-16m"
SESSION_READERS = ("device_session_ms_per_mrec", "session_merge_hbm_share",
                   "session_bank_growths")


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

T0 = 1436918400000                               # 2015-07-15T00:00:00Z


def _bid(bidder, t):
    return b'{"auction":1000,"bidder":%d,"price":5,"dateTime":%d,"extra":""}' % (
        bidder, t)


def _row(bidder, count, start, end):
    return b'{"bidder":%d,"bid_count":%d,"starttime":%d,"endtime":%d}' % (
        bidder, count, start, end)


def test_reference_on_a_case_checked_by_hand():
    """Bidder 7 bids at 0, 10 s (exactly the gap: one session) and 4 s
    (out of order, inside it); bidder 9 at 1 s and 11.001 s (two
    sessions); bidder 7 again at 30 s; a bid 30 s late and one of a
    bidder out of range; bidder 8 at 60 s closes all but its own."""
    ref = manifest.load_plugin(BENCH, "references", "nexmark_q11")
    params = {"gap_ms": 10_000, "lateness_ms": 4000, "window_ms": 10_000,
              "slide_ms": 10_000}
    values = [_bid(7, T0), _bid(9, T0 + 1000), _bid(7, T0 + 10_000),
              _bid(7, T0 + 4000), _bid(9, T0 + 11_001),
              _bid(7, T0 + 30_000),                      # closes 7's first
              _bid(7, T0 + 2000),                        # late: dropped
              _bid(2**31, T0 + 30_000),                  # out of range
              _bid(8, T0 + 60_000)]
    src, out, late, invalid = ref.fold(values, **params)
    assert out == [
        _row(9, 1, T0 + 1000, T0 + 11_000),
        _row(7, 3, T0, T0 + 20_000),
        _row(9, 1, T0 + 11_001, T0 + 21_001),
        _row(7, 1, T0 + 30_000, T0 + 40_000),
    ]
    assert src.tolist() == [5, 5, 5, 8] and (late, invalid) == (1, 1)
    assert ref.OFFSETS == "nondecreasing"
    assert ref.expect(values, **params)[1] == out
    with pytest.raises(ValueError):
        ref.fold(values, **(params | {"slide_ms": 2000}))
    # it imports nothing of the program
    text = (BENCH / "references" / "nexmark_q11.py").read_text()
    assert "import fluvio_tpu" not in text and "from fluvio_tpu" not in text
    assert "spubench" not in text


# -- the manifest: by membership, never by position ----------------------------

SESSION_ENTRIES = {
    "device_session_ms_per_mrec": ("ms/Mrec", "lower", "device_trace", "kernels"),
    "session_merge_hbm_share": ("%", "higher", "device_trace", "kernels"),
    "session_bank_growths": ("growths", "lower", "program_counter", "slice path"),
}
# the aggregate cell's four (`test_aggregate_cell.py:check_aggregate_entries`
# holds their lists EQUAL to that cell's), the window stage's four and the
# group stage's four, whose scopes and events a session stage does not book
NOT_THIS_CELLS = ("chain_acquire_ms_per_stream", "stream_chain_builds",
                  "device_agg_ms_per_mrec", "agg_scan_hbm_share",
                  "device_window_ms_per_mrec", "window_merge_hbm_share",
                  "window_late_records", "window_bank_growths",
                  "device_group_ms_per_mrec", "group_merge_hbm_share",
                  "group_table_growths", "group_invalid_records")


def check_session_entries(m):
    """This PR's additions in the manifest ``m``: the cell, its
    configuration, the three session entries (each there once, moving
    `records_in_per_s`, listing the cell: a floor, a later session cell
    may join) and the cell once in the list of `records_in_per_s` and
    of every general reader. Where in a list anything stands is
    nobody's to assert: a later PR appends its own after these."""
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] and "q11.sql" in entry["source"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cells = {w["name"] for w in m["workloads"]}
    for name, (unit, better, source, layer) in SESSION_ENTRIES.items():
        (e,) = [e for e in m["per_layer"] if e["name"] == name]
        assert (e["unit"], e["better"], e["source"], e["layer"]) == (
            unit, better, source, layer), name
        assert e["moves"] == "records_in_per_s"
        assert CELL in e["workloads"] and set(e["workloads"]) <= cells
    (rate,) = [e for e in m["end_to_end"] if e["name"] == "records_in_per_s"]
    assert rate["workloads"].count(CELL) == 1
    for e in m["per_layer"]:
        if {"ns-drain", "explode-drain", "agg-drain", "q5-drain",
                "q17-drain"} <= set(e["workloads"]):
            assert e["workloads"].count(CELL) == 1, e["name"]
        if e["name"] in NOT_THIS_CELLS:
            assert CELL not in e["workloads"], e["name"]


def _two_more_entries(m):
    """A later PR's additions: a session cell and two readers appended
    after this PR's three, its cell joining two of their lists."""
    m = copy.deepcopy(m)
    m["workloads"].append({
        "name": "q11-paced", "config": CONFIG, "traffic": "drain-16m",
        "chips": 1, "why": "a later session cell"})
    for name in ("session_merge_hbm_share", "session_bank_growths"):
        (e,) = [e for e in m["per_layer"] if e["name"] == name]
        e["workloads"].append("q11-paced")
    for name in ("later_reader_a", "later_reader_b"):
        m["per_layer"].append({
            "name": name, "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "kernels",
            "moves": "records_in_per_s", "workloads": [CELL, "q11-paced"]})
    return m


@pytest.mark.parametrize("later", [False, True], ids=["as-is", "appended-to"])
def test_session_entries_by_membership_and_floors(later):
    import test_aggregate_cell as aggregate
    import test_q5_cell as q5
    import test_q17_cell as q17

    m = _two_more_entries(harness.MANIFEST) if later else harness.MANIFEST
    check_session_entries(m)
    # the accepted cells' checks hold beside it
    q5.check_window_entries(m)
    q17.check_group_entries(m)
    aggregate.check_aggregate_entries(m)
    # and the check does hold something: a cell taken off a list fails it
    broken = copy.deepcopy(m)
    (e,) = [e for e in broken["per_layer"] if e["name"] == "session_bank_growths"]
    e["workloads"].remove(CELL)
    with pytest.raises(AssertionError):
        check_session_entries(broken)


def test_cell_and_configuration_are_as_stated():
    cell = manifest.load_cell(CELL, REPO)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (CONFIG, MIX, 1)
    assert cell.traffic == {"mode": "drain_eventtime", "max_bytes": 16 * 2**20}
    cfg, q5 = cell.config, json.loads(
        (BENCH / "configs" / "fluvio-nexmark-q5-1p.json").read_text())
    assert cfg["reduced"] == [] and "reduced_detail" not in cfg
    assert cfg["backlog_records"] == 1_000_000
    assert cfg["stored_batch_records"] == 16384 and cfg["warm_passes"] == 2
    assert cfg["deployment"] | {"consumer": ""} == q5["deployment"] | {"consumer": ""}
    assert cfg["guarantees"][:3] == q5["guarantees"][:3]
    assert len(cfg["guarantees"]) == 6
    assert [s["kind"] for s in cfg["chain"]] == ["AGGREGATE"]
    adhoc = cfg["chain"][0]["adhoc"]
    assert "dsl.SessionProgram" in adhoc
    for text in ("gap_ms=10000", "lateness_ms=4000", 'key="bidder"',
                 'key="dateTime"', 'count_field="bid_count"'):
        assert text in adhoc
    # the source's shapes: Q5's corpus unchanged
    assert cfg["corpus"] == q5["corpus"]
    assert cfg["reference"] == {
        "name": "nexmark_q11",
        "params": {"gap_ms": 10000, "lateness_ms": 4000,
                   "window_ms": 10000, "slide_ms": 10000}}
    for key in ("bid_topic", "rendering", "extra", "base_time_ms", "writes",
                "merge_at_the_gap", "close_rule", "output_row", "window_ms",
                "empty_bank", "consumer_max_bytes", "warm_passes", "seed_order"):
        assert cfg["assumed"][key]
    (entry,) = [c for c in harness.MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "nexmark/nexmark" in entry["source"]
    assert {e["name"] for e in cell.end_to_end} == {"records_in_per_s", "setup_s"}
    assert set(SESSION_READERS) <= {e["name"] for e in cell.per_layer}
    for name in SESSION_READERS:
        assert callable(_reader(name))


# -- the device readers on a recorded shape ------------------------------------


def _fixture_bytes(name="trace_session_small.textproto") -> bytes:
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
        "shape": {"max_in_len": 120, "max_out_len": 79, "fanout": 1},
        "device_kind": "TPU v5 lite", "window_replicas": 1,
    }


def test_session_device_readers_on_fixture(tmp_path):
    obs = _device_obs(tmp_path, _fixture_bytes(), 0.0055)
    r = xs.reduce_run(obs)
    assert r["scope_s"] == pytest.approx({
        "repad": 0.001, "stage0.session": 0.0005,
        "stage0.session_merge": 0.0035, "stage0.session_close": 0.00025})
    assert session_bytes.session_scope_seconds(r) == pytest.approx(0.00425)
    assert session_bytes.work_scope_seconds(r) == pytest.approx(0.00375)
    got = _reader("device_session_ms_per_mrec")(obs)
    assert got == pytest.approx(0.00425 / 0.010 * 20.0 * 1e3 / 2.0)
    # the accepted chain reader counts the same operations
    assert _reader("device_chain_ms_per_mrec")(obs) == pytest.approx(got)
    # ... and the window and group stages' readers none of them
    assert _reader("device_window_ms_per_mrec")(obs) is None
    assert _reader("device_group_ms_per_mrec")(obs) is None
    assert session_bytes.session_bytes(147_456) == 262_144 * 64
    moved = (262_144 + 131_072) * 64
    share = _reader("session_merge_hbm_share")(obs)
    assert share == pytest.approx(100 * (moved / 819e9) / 0.00375)
    assert 0 < share < 100


@pytest.mark.parametrize("name", SESSION_READERS[:2])
def test_session_device_readers_stay_silent(tmp_path, monkeypatch, name):
    read = _reader(name)
    assert read(_device_obs(tmp_path, _fixture_bytes(), 0.0055)) is not None
    assert read({"records_in": 5, "trace": None}) is None      # no traced run
    # a trace whose chain has no session stage: None, not 0
    xs._CACHE.clear()
    assert read(_device_obs(
        tmp_path, _fixture_bytes("trace_group_small.textproto"), 0.0055)) is None
    xs._CACHE.clear()
    assert read(_device_obs(tmp_path, b"", 0.0055)) is None    # no device plane
    # a program without the scopes (a parent commit): nothing, no raise
    monkeypatch.setattr(xs, "_vocabulary", lambda: None)
    assert read(_device_obs(tmp_path, _fixture_bytes(), 0.0055)) is None


# -- the host reader -----------------------------------------------------------


def test_session_growth_reader_counts_the_windows_events(monkeypatch):
    grown = _reader("session_bank_growths")
    obs = {"t_open": 100.0, "t_close": 110.0}
    assert grown(obs) == 0                     # the program books them: a count
    for t in (99.5, 103.0, 108.0, 111.0):
        TELEMETRY.add_session_grow("bank 1024->2048 emit 1024->1024")
        TELEMETRY.events.recent()[-1].t = t
    TELEMETRY.add_group_grow("bank 1024->2048 emit 8->8")    # not ours
    TELEMETRY.events.recent()[-1].t = 104.0
    TELEMETRY.add_session_slice(12, 1, 3, 2)    # late and invalid rows: drops
    for e in TELEMETRY.events.recent()[-2:]:
        e.t = 105.0
    assert grown(obs) == 2
    assert TELEMETRY.session_counts() == {
        "closed": 12, "bridged": 1, "late": 3, "invalid": 2}
    assert sorted(e.detail for e in TELEMETRY.events.recent()
                  if e.kind == "session-drop") == ["invalid:2", "late:3"]
    # a ring that overwrote part of the window says nothing
    for _ in range(TELEMETRY.events.capacity):
        TELEMETRY.add_session_grow("x")
        TELEMETRY.events.recent()[-1].t = 109.0
    assert grown(obs) is None
    # a program that books no such event (a parent commit): None
    TELEMETRY.reset()
    monkeypatch.delattr(type(TELEMETRY), "add_session_grow")
    assert grown(obs) is None


def _small_slices(root, m):
    """Stored batches of 16 bids, six a slice, at 10 events a second:
    2,048 bids are 222 s of event time, in which about 200 sessions
    close and 13 to 25 are open at a time; a stored batch is 1.7 s, so
    a swapped pair stays under the 4 s delay."""
    f = root / "benchmark" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(f.read_text())
    cfg["stored_batch_records"] = 16
    cfg["corpus"]["params"]["first_event_rate"] = 10
    f.write_text(json.dumps(cfg))
    (root / "benchmark" / "traffic" / f"{MIX}.json").write_text(json.dumps(
        {"mode": "drain_eventtime", "max_bytes": 10_000}))


def test_traced_rehearsal_whose_bank_outgrows_its_capacity(monkeypatch, tmp_path):
    """A bank and emit columns that start at 16 entries (the served
    ones start at 1,024, or a thirty-second of a slice's rows) grow in
    the warm-up, and no more in the window."""
    from fluvio_tpu.smartengine.tpu import window_stage

    monkeypatch.setattr(window_stage, "WINDOW_CAPACITY_START", 16)
    root = harness._tiny_root(tmp_path, extra=_small_slices)
    r = harness._rehearse(monkeypatch, root, CELL, trace=True, seconds=2.0)
    assert r["faults"] == [] and r["correct"] is True
    assert r["attempted"] >= 2 and r["failed"] == 0       # stream re-opens
    assert r["counts"]["compiles"] == 0 and r["counts"]["fallback_slices"] == 0
    assert r["metrics"]["fastpath_share"]["value"] == 100.0
    assert r["metrics"]["spill_records"]["value"] == 0.0
    assert r["metrics"]["session_bank_growths"]["value"] == 0.0
    grown = [e.detail for e in TELEMETRY.events.recent() if e.kind == "session-grow"]
    assert grown and grown[0].startswith("bank 16->")
    assert r["counts"]["records_out"] > 0
    assert TELEMETRY.session_counts()["late"] == 0
    # a CPU trace has no device plane: the device readers stay silent
    assert "device_session_ms_per_mrec" not in r["metrics"]
    assert "session_merge_hbm_share" not in r["metrics"]
    # the general readers it joined read it
    for name in ("slice_out_ms_per_mrec", "materialize_ms_per_mrec",
                 "finish_blocked_ms_per_mrec", "interleave_share",
                 "wire_out_mb_per_s", "exec_up_ms_per_mrec"):
        assert name in r["metrics"], name
