"""ISSUE-39: what `q17-drain` adds to the benchmark; none needs the chip.

The CPU rehearsal of `test_benchmark_harness.py` picks the cell up by
itself. Here: the plain reference on a case small enough to check by
hand, the manifest entries by membership and floors
(`check_group_entries`, never an index), and the four group readers on a
recorded fixture, on seeded events and through a traced rehearsal whose
table outgrows its first capacity, each None, never 0, where what it
reads is absent.
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
from spubench import group_bytes, manifest  # noqa: E402
from spubench import xplane_scopes as xs  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402

CELL, CONFIG, MIX = "q17-drain", "fluvio-nexmark-q17-1p", "drain-evt-16m"
GROUP_READERS = ("device_group_ms_per_mrec", "group_merge_hbm_share",
                 "group_invalid_records", "group_table_growths")


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

DAY = 86_400_000


def _bid(auction, price, t):
    return b'{"auction":%d,"bidder":1,"price":%d,"dateTime":%d,"extra":""}' % (
        auction, price, t)


def _row(auction, day, total, r1, r2, r3, lo, hi, total_price):
    return (b'{"auction":%d,"day":"%s","total_bids":%d,"rank1_bids":%d,'
            b'"rank2_bids":%d,"rank3_bids":%d,"min_price":%d,"max_price":%d,'
            b'"avg_price":%d,"sum_price":%d}') % (
        auction, day, total, r1, r2, r3, lo, hi, total_price // total,
        total_price)


def test_reference_on_a_case_checked_by_hand():
    """Auction 7 bids 9,999, 10,000 and (the next UTC day) 1,000,000;
    auction 9 bids 999,999; one bid names no auction."""
    ref = manifest.load_plugin(BENCH, "references", "nexmark_q17")
    t0 = 1436918400000                           # 2015-07-15T00:00:00Z
    values = [_bid(7, 9_999, t0), _bid(9, 999_999, t0 + 5),
              _bid(7, 10_000, t0 + DAY - 1),
              b'{"bidder":1,"price":5,"dateTime":%d,"extra":""}' % t0,
              _bid(7, 1_000_000, t0 + DAY),      # midnight: another group
              _bid(7, 3, t0 + 7)]                # out of order: the first day's
    src, out, invalid = ref.fold(values, window_ms=DAY, slide_ms=DAY)
    assert out == [
        _row(7, b"2015-07-15", 1, 1, 0, 0, 9_999, 9_999, 9_999),
        _row(9, b"2015-07-15", 1, 0, 1, 0, 999_999, 999_999, 999_999),
        _row(7, b"2015-07-15", 2, 1, 1, 0, 9_999, 10_000, 19_999),
        _row(7, b"2015-07-16", 1, 0, 0, 1, 1_000_000, 1_000_000, 1_000_000),
        _row(7, b"2015-07-15", 3, 2, 1, 0, 3, 10_000, 20_002),
    ]
    assert src.tolist() == [0, 1, 2, 4, 5] and invalid == 1
    assert out[2].count(b'"avg_price":9999,') == 1          # 19,999 // 2
    assert ref.OFFSETS == "exact"
    assert ref.expect(values, window_ms=DAY, slide_ms=DAY)[1] == out
    with pytest.raises(ValueError):
        ref.fold(values, window_ms=DAY, slide_ms=DAY // 2)
    # it imports nothing of the program
    text = (BENCH / "references" / "nexmark_q17.py").read_text()
    assert "import fluvio_tpu" not in text and "from fluvio_tpu" not in text
    assert "spubench" not in text


# -- the manifest: by membership, never by position ----------------------------

GROUP_ENTRIES = {
    "device_group_ms_per_mrec": ("ms/Mrec", "lower", "device_trace", "kernels"),
    "group_merge_hbm_share": ("%", "higher", "device_trace", "kernels"),
    "group_invalid_records": ("records", "lower", "program_counter", "engine"),
    "group_table_growths": ("growths", "lower", "program_counter", "slice path"),
}
# PR 30's four (`test_aggregate_cell.py:check_aggregate_entries` holds
# their lists EQUAL to the aggregate cell's) and PR 37's four, whose
# scopes and events a group stage does not book
NOT_THIS_CELLS = ("chain_acquire_ms_per_stream", "stream_chain_builds",
                  "device_agg_ms_per_mrec", "agg_scan_hbm_share",
                  "device_window_ms_per_mrec", "window_merge_hbm_share",
                  "window_late_records", "window_bank_growths")


def check_group_entries(m):
    """This PR's additions in the manifest ``m``: the cell, its
    configuration, the four group entries (each there once, moving
    `records_in_per_s`, listing the cell: a floor, a later keyed cell
    may join) and the cell once in the list of `records_in_per_s` and
    of every general reader. Where in a list anything stands is
    nobody's to assert: a later PR appends its own after these."""
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["backlog_records"] and "q17.sql" in entry["source"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cells = {w["name"] for w in m["workloads"]}
    for name, (unit, better, source, layer) in GROUP_ENTRIES.items():
        (e,) = [e for e in m["per_layer"] if e["name"] == name]
        assert (e["unit"], e["better"], e["source"], e["layer"]) == (
            unit, better, source, layer), name
        assert e["moves"] == "records_in_per_s"
        assert CELL in e["workloads"] and set(e["workloads"]) <= cells
    (rate,) = [e for e in m["end_to_end"] if e["name"] == "records_in_per_s"]
    assert rate["workloads"].count(CELL) == 1
    for e in m["per_layer"]:
        if {"ns-drain", "explode-drain", "agg-drain", "q5-drain"} <= set(
                e["workloads"]):
            assert e["workloads"].count(CELL) == 1, e["name"]
        if e["name"] in NOT_THIS_CELLS:
            assert CELL not in e["workloads"], e["name"]


def _two_more_entries(m):
    """A later PR's additions: a keyed cell and two readers appended
    after this PR's four, its cell joining two of their lists."""
    m = copy.deepcopy(m)
    m["workloads"].append({
        "name": "q17-paced", "config": CONFIG, "traffic": "drain-16m",
        "chips": 1, "why": "a later keyed cell"})
    for name in ("group_merge_hbm_share", "group_invalid_records"):
        (e,) = [e for e in m["per_layer"] if e["name"] == name]
        e["workloads"].append("q17-paced")
    for name in ("later_reader_a", "later_reader_b"):
        m["per_layer"].append({
            "name": name, "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "kernels",
            "moves": "records_in_per_s", "workloads": [CELL, "q17-paced"]})
    return m


@pytest.mark.parametrize("later", [False, True], ids=["as-is", "appended-to"])
def test_group_entries_by_membership_and_floors(later):
    import test_aggregate_cell as aggregate
    import test_q5_cell as q5

    m = _two_more_entries(harness.MANIFEST) if later else harness.MANIFEST
    check_group_entries(m)
    # the accepted cells' checks hold beside it
    q5.check_window_entries(m)
    aggregate.check_aggregate_entries(m)
    # and the check does hold something: a cell taken off a list fails it
    broken = copy.deepcopy(m)
    (e,) = [e for e in broken["per_layer"] if e["name"] == "group_table_growths"]
    e["workloads"].remove(CELL)
    with pytest.raises(AssertionError):
        check_group_entries(broken)


def test_cell_and_configuration_are_as_the_issue_names_them():
    cell = manifest.load_cell(CELL, REPO)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (CONFIG, MIX, 1)
    assert cell.traffic == {"mode": "drain_eventtime", "max_bytes": 16 * 2**20}
    cfg, q5 = cell.config, json.loads(
        (BENCH / "configs" / "fluvio-nexmark-q5-1p.json").read_text())
    assert cfg["reduced"] == ["backlog_records"] and cfg["reduced_detail"]
    assert cfg["backlog_records"] == 1_000_000
    assert cfg["stored_batch_records"] == 16384 and cfg["warm_passes"] == 2
    assert cfg["deployment"] | {"consumer": ""} == q5["deployment"] | {"consumer": ""}
    assert cfg["guarantees"][:3] == q5["guarantees"][:3]
    assert len(cfg["guarantees"]) == 6
    assert [s["kind"] for s in cfg["chain"]] == ["AGGREGATE"]
    assert "dsl.GroupProgram" in cfg["chain"][0]["adhoc"]
    # the source's shapes: Q5's corpus unchanged, all ten output columns
    assert cfg["corpus"] == q5["corpus"]
    for column in ("total_bids", "rank1_bids", "rank2_bids", "rank3_bids",
                   "min_price", "max_price", "avg_price", "sum_price"):
        assert f'name="{column}"' in cfg["chain"][0]["adhoc"]
    for text in ('b"10000"', 'b"1000000"', "bucket_ms=86400000"):
        assert text in cfg["chain"][0]["adhoc"]
    # the day is the query's tumbling bucket: what the event-time mode reads
    assert cfg["reference"] == {
        "name": "nexmark_q17",
        "params": {"window_ms": 86400000, "slide_ms": 86400000}}
    for key in ("bid_topic", "rendering", "extra", "base_time_ms", "output_row",
                "utc_days", "bigint_avg", "per_record_emission", "empty_table",
                "seed_order", "consumer_max_bytes", "writes", "warm_passes"):
        assert cfg["assumed"][key]
    (entry,) = [c for c in harness.MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "nexmark/nexmark" in entry["source"]
    assert {e["name"] for e in cell.end_to_end} == {"records_in_per_s", "setup_s"}
    assert set(GROUP_READERS) <= {e["name"] for e in cell.per_layer}
    for name in GROUP_READERS:
        assert callable(_reader(name))


# -- the device readers on a recorded shape ------------------------------------


def _fixture_bytes(name="trace_group_small.textproto") -> bytes:
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
        "shape": {"max_in_len": 120, "max_out_len": 183, "fanout": 1},
        "device_kind": "TPU v5 lite", "window_replicas": 1,
    }


def test_group_device_readers_on_fixture(tmp_path):
    obs = _device_obs(tmp_path, _fixture_bytes(), 0.0055)
    r = xs.reduce_run(obs)
    assert r["scope_s"] == pytest.approx({
        "repad": 0.001, "stage0.group": 0.0005,
        "stage0.group_merge": 0.0035, "stage0.group_emit": 0.00025})
    assert group_bytes.group_scope_seconds(r) == pytest.approx(0.00425)
    assert group_bytes.work_scope_seconds(r) == pytest.approx(0.00375)
    got = _reader("device_group_ms_per_mrec")(obs)
    assert got == pytest.approx(0.00425 / 0.010 * 20.0 * 1e3 / 2.0)
    # the accepted chain reader counts the same operations
    assert _reader("device_chain_ms_per_mrec")(obs) == pytest.approx(got)
    # ... and the window stage's readers none of them
    assert _reader("device_window_ms_per_mrec")(obs) is None
    assert group_bytes.group_bytes(147_456) == 262_144 * 128
    moved = (262_144 + 131_072) * 128
    share = _reader("group_merge_hbm_share")(obs)
    assert share == pytest.approx(100 * (moved / 819e9) / 0.00375)
    assert 0 < share < 100


@pytest.mark.parametrize("name", GROUP_READERS[:2])
def test_group_device_readers_stay_silent(tmp_path, monkeypatch, name):
    read = _reader(name)
    assert read(_device_obs(tmp_path, _fixture_bytes(), 0.0055)) is not None
    assert read({"records_in": 5, "trace": None}) is None      # no traced run
    # a trace whose chain has no group stage: None, not 0
    xs._CACHE.clear()
    assert read(_device_obs(
        tmp_path, _fixture_bytes("trace_window_small.textproto"), 0.0055)) is None
    xs._CACHE.clear()
    assert read(_device_obs(tmp_path, b"", 0.0055)) is None    # no device plane
    # a program without the scopes (a parent commit): nothing, no raise
    monkeypatch.setattr(xs, "_vocabulary", lambda: None)
    assert read(_device_obs(tmp_path, _fixture_bytes(), 0.0055)) is None


# -- the host readers ----------------------------------------------------------


def test_group_event_readers_count_the_windows_events(monkeypatch):
    bad, grown = _reader("group_invalid_records"), _reader("group_table_growths")
    obs = {"t_open": 100.0, "t_close": 110.0}
    assert bad(obs) == 0 and grown(obs) == 0       # the program books them: a count
    for t, rows in ((99.0, 7), (101.0, 30), (105.5, 12), (111.0, 9)):
        TELEMETRY.add_group_slice(1000, 50, rows)
        TELEMETRY.events.recent()[-1].t = t
    TELEMETRY.add_group_slice(1000, 50, 0)         # no event: nothing dropped
    TELEMETRY.add_window_delta("invalid", 5)       # a window stage's: not ours
    TELEMETRY.events.recent()[-1].t = 102.0
    for t in (99.5, 103.0):
        TELEMETRY.add_group_grow("bank 1024->2048 emit 8->8")
        TELEMETRY.events.recent()[-1].t = t
    TELEMETRY.add_window_grow("bank 1024->2048 emit 1024->1024")
    TELEMETRY.events.recent()[-1].t = 104.0
    assert bad(obs) == 30 + 12 and grown(obs) == 1
    assert TELEMETRY.group_counts() == {
        "rows": 5000, "keys": 250, "invalid": 58}
    # a ring that overwrote part of the window says nothing
    for _ in range(TELEMETRY.events.capacity):
        TELEMETRY.add_group_grow("x")
        TELEMETRY.events.recent()[-1].t = 109.0
    assert bad(obs) is None and grown(obs) is None
    # a program that books no such event (a parent commit): None
    TELEMETRY.reset()
    monkeypatch.delattr(type(TELEMETRY), "add_group_grow")
    assert bad(obs) is None and grown(obs) is None


def _small_slices(root, m):
    """Stored batches of 128 bids, one a slice: a table starts at half a
    slice's padded rows, so small slices are what lets 2,048 bids (about
    250 groups) outgrow it."""
    f = root / "benchmark" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(f.read_text())
    cfg["stored_batch_records"] = 128
    f.write_text(json.dumps(cfg))
    (root / "benchmark" / "traffic" / f"{MIX}.json").write_text(json.dumps(
        {"mode": "drain_eventtime", "max_bytes": 10_000}))


def test_traced_rehearsal_whose_table_outgrows_its_capacity(monkeypatch, tmp_path):
    """2,048 bids make about 250 (auction, day) groups: a table that
    starts at 64 entries (the served one starts at 1,024, or half a
    slice's rows) grows in the warm-up, and no more in the window."""
    from fluvio_tpu.smartengine.tpu import window_stage

    monkeypatch.setattr(window_stage, "WINDOW_CAPACITY_START", 64)
    root = harness._tiny_root(tmp_path, extra=_small_slices)
    r = harness._rehearse(monkeypatch, root, CELL, trace=True, seconds=2.0)
    assert r["faults"] == [] and r["correct"] is True
    assert r["attempted"] >= 2 and r["failed"] == 0       # stream re-opens
    passes = r["attempted"]
    # one row out per bid in, a slice never cut
    assert r["counts"]["records_out"] == r["counts"]["records_in"]
    assert r["counts"]["responses"] >= 16 * (passes - 1) + 1
    assert r["counts"]["compiles"] == 0 and r["counts"]["fallback_slices"] == 0
    assert r["metrics"]["fastpath_share"]["value"] == 100.0
    assert r["metrics"]["spill_records"]["value"] == 0.0
    assert r["metrics"]["group_table_growths"]["value"] == 0.0
    assert r["metrics"]["group_invalid_records"]["value"] == 0.0
    grown = [e.detail for e in TELEMETRY.events.recent() if e.kind == "group-grow"]
    assert grown and grown[0].startswith("bank 64->128")
    variants = TELEMETRY.link_variant_counts()
    # a slice is fetched before it is encoded: the run ends with one between
    assert 0 < variants["enc-direct-rows"] <= variants["grp-mixed"]
    assert variants["grp-mixed"] - variants["enc-direct-rows"] <= 1
    # a CPU trace has no device plane: the device readers stay silent
    assert "device_group_ms_per_mrec" not in r["metrics"]
    assert "group_merge_hbm_share" not in r["metrics"]
    # the general readers it joined read it
    for name in ("slice_out_ms_per_mrec", "materialize_ms_per_mrec",
                 "finish_blocked_ms_per_mrec", "interleave_share",
                 "wire_out_mb_per_s", "exec_up_ms_per_mrec"):
        assert name in r["metrics"], name
