"""The readers of the stream loop's order (ISSUE 31's two, and ISSUE 36's
of the `materialize` phase), on a seeded flow ring; none needs the chip.
Each returns None, never 0, where the phase or the field it reads is
absent (a program without it). And their manifest entries, wherever in
the list they stand."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH), str(Path(__file__).resolve().parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from test_tracing_readers import _seed_flow, check_workloads_floor  # noqa: E402
from spubench import manifest  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402
from fluvio_tpu.telemetry.flow import SLICE_PHASES, SliceFlow  # noqa: E402

# the entries ISSUE 36 appended: name -> (unit, better)
LOOP_READERS = {"finish_blocked_ms_per_mrec": ("ms/Mrec", "lower"),
                "interleave_share": ("%", "higher"),
                "materialize_ms_per_mrec": ("ms/Mrec", "lower")}
OBS = {"t_open": 100.0, "t_close": 110.0, "records_in": 4000}
# a served slice in the loop's order, and in the order before it
SLICE = (("read", 0.01), ("wire_decode", 0.03), ("stage", 0.02),
         ("dispatch", 0.10), ("finish", 0.05), ("materialize", 0.30),
         ("encode", 0.20), ("send", 0.01), ("ack_wait", 0.02))
OLD_SLICE = tuple(p for p in SLICE if p[0] != "materialize")


def _reader(name):
    return manifest.load_plugin(BENCH, "layer_metrics", name).read


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


def _without_field(monkeypatch):
    """Flows as a program before ISSUE 31 writes them: no `interleaved`."""
    to_dict = SliceFlow.to_dict

    def old(self):
        d = to_dict(self)
        d.pop("interleaved")
        return d

    monkeypatch.setattr(SliceFlow, "to_dict", old)


def test_finish_blocked_reads_the_finish_phase_alone():
    read = _reader("finish_blocked_ms_per_mrec")
    assert read(OBS) is None                                  # no flow at all
    _seed_flow(98.0, SLICE)                                   # before the window
    _seed_flow(101.0, [("chain_acquire", 0.5)], records=0)    # an open alone
    assert read(OBS) == 0.0       # flows with phases, none of them `finish`
    _seed_flow(102.0, SLICE)
    _seed_flow(104.0, OLD_SLICE)
    # 2 x 50 ms over 4,000 records: 25,000 ms a million
    assert read(OBS) == pytest.approx(25_000.0)
    assert read(OBS | {"records_in": 0}) is None
    assert "materialize" in SLICE_PHASES and "finish" in SLICE_PHASES


@pytest.mark.parametrize("name", ["finish_blocked_ms_per_mrec",
                                  "materialize_ms_per_mrec"])
def test_phase_readers_are_silent_without_phases(monkeypatch, name):
    read = _reader(name)
    to_dict = SliceFlow.to_dict
    monkeypatch.setattr(
        SliceFlow, "to_dict",
        lambda self: {k: v for k, v in to_dict(self).items()
                      if k not in ("phases", "phases_ms")})
    _seed_flow(102.0, SLICE)
    assert read(OBS) is None     # a program that records no phases


def test_interleave_share_counts_the_flows_that_had_a_next_slice():
    read = _reader("interleave_share")
    assert read(OBS) is None
    # a pass of three slices: the last has no next one
    for t, on in ((101.0, True), (102.0, True), (103.0, False)):
        _seed_flow(t, SLICE).interleaved = on
    _seed_flow(104.0, [("chain_acquire", 0.5)], records=0)    # not a served slice
    _seed_flow(98.0, SLICE).interleaved = True                # before the window
    assert read(OBS) == pytest.approx(200.0 / 3.0)
    assert [f["interleaved"] for f in TELEMETRY.flows_json()] == [
        True, True, False, False, True]


def test_interleave_share_is_zero_where_no_slice_interleaves():
    read = _reader("interleave_share")
    _seed_flow(101.0, SLICE)
    assert read(OBS) == 0.0       # the field is there and unset: 0 is a share


def test_interleave_share_is_silent_without_the_field(monkeypatch):
    read = _reader("interleave_share")
    _without_field(monkeypatch)
    _seed_flow(101.0, OLD_SLICE)
    _seed_flow(102.0, OLD_SLICE)
    assert read(OBS) is None      # a parent commit: nothing, no raise


def test_materialize_reads_the_materialize_phase_alone():
    read = _reader("materialize_ms_per_mrec")
    assert read(OBS) is None                                  # no flow at all
    _seed_flow(98.0, SLICE)                                   # before the window
    _seed_flow(101.0, [("chain_acquire", 0.5)], records=0)    # an open alone
    assert read(OBS) == 0.0   # flows with phases, none of them `materialize`
    _seed_flow(102.0, SLICE)
    _seed_flow(103.0, SLICE)
    _seed_flow(104.0, OLD_SLICE)          # the order before it: `finish` held it
    # 2 x 300 ms over 4,000 records: 150,000 ms a million; `finish` (3 x 50
    # ms) and `encode` are other readers'
    assert read(OBS) == pytest.approx(150_000.0)
    assert _reader("finish_blocked_ms_per_mrec")(OBS) == pytest.approx(37_500.0)
    assert read(OBS | {"records_in": 0}) is None


# -- the manifest entries ----------------------------------------------------


def check_loop_reader_entry(m, name):
    """The entry ``name`` (one of `LOOP_READERS`) in the manifest ``m``:
    there once, for the slice path, with the three drain cells in its
    `workloads` (a FLOOR: a later PR appends its cell) and a reader that
    loads. No word on where in `per_layer` it stands."""
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    check_workloads_floor(m, entry, {"ns-drain", "explode-drain", "agg-drain"})
    assert (entry["unit"], entry["better"]) == LOOP_READERS[name]
    assert (entry["source"], entry["layer"], entry["moves"]) == (
        "program_span", "slice path", "records_in_per_s")
    assert callable(_reader(name))


@pytest.mark.parametrize("name", LOOP_READERS)
def test_loop_reader_entries_list_the_three_drain_cells(name):
    check_loop_reader_entry(json.loads((REPO / "BENCHMARK.json").read_text()), name)


# -- a rehearsal that reads all three ----------------------------------------


def _several_slices_a_pass(root, m):
    """A mix whose ``max_bytes`` makes a pass several slices: the tiny
    backlog is one slice at 16 MiB, and one slice has no next one."""
    (root / "benchmark" / "traffic" / "drain-16m.json").write_text(
        json.dumps({"mode": "drain", "max_bytes": 20_000}))


@pytest.mark.parametrize("cell", ["ns-drain", "explode-drain", "agg-drain"])
def test_rehearsal_reports_the_readers_in_every_cell(monkeypatch, tmp_path, cell):
    import test_benchmark_harness as harness

    root = harness._tiny_root(tmp_path, extra=_several_slices_a_pass)
    r = harness._rehearse(monkeypatch, root, cell, trace=True, seconds=1.0)
    assert r["correct"] is True and r["faults"] == []
    assert r["metrics"]["fastpath_share"]["value"] == 100.0
    assert r["metrics"]["finish_blocked_ms_per_mrec"]["value"] > 0.0
    assert r["metrics"]["materialize_ms_per_mrec"]["value"] > 0.0
    # every pass is several slices and its last has no next one
    assert 0.0 < r["metrics"]["interleave_share"]["value"] < 100.0
