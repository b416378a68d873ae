"""ISSUE-31: the two readers of the stream loop's order, on a seeded flow
ring; none needs the chip. Each returns None, never 0, where the phase
or the field it reads is absent (a program without it). And what this PR
brings to the benchmark is additions."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH), str(Path(__file__).resolve().parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from test_tracing_readers import _seed_flow  # noqa: E402
from spubench import manifest  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402
from fluvio_tpu.telemetry.flow import SLICE_PHASES, SliceFlow  # noqa: E402

PARENT = "07cf184a05c3e80f87b82b84df34c115ba4b54ba"
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


def test_finish_blocked_is_silent_without_phases(monkeypatch):
    read = _reader("finish_blocked_ms_per_mrec")
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


def test_benchmark_files_of_this_pr_are_additions():
    """No file the accepted benchmark had is edited or deleted, and
    `BENCHMARK.json` loses nothing: entries stay in place and in order,
    lists only grow at their end."""
    def git(*args):
        return subprocess.run(["git", *args], cwd=REPO, capture_output=True,
                              text=True)

    if git("cat-file", "-e", PARENT).returncode != 0:
        pytest.skip("not a checkout that holds the parent commit")
    changed = git("diff", "--name-status", PARENT, "--",
                  "benchmark", "tests/benchmark").stdout.split("\n")
    assert [c for c in changed if c and not c.startswith("A")] == []
    added = {c.split("\t")[1] for c in changed if c}
    assert {"benchmark/layer_metrics/finish_blocked_ms_per_mrec.py",
            "benchmark/layer_metrics/interleave_share.py"} <= added
    was = json.loads(git("show", f"{PARENT}:BENCHMARK.json").stdout)
    now = json.loads((REPO / "BENCHMARK.json").read_text())
    assert {k: was[k] for k in ("command", "paths", "run_seconds")} == {
        k: now[k] for k in ("command", "paths", "run_seconds")}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(now[key]) >= len(was[key])
        for old, new in zip(was[key], now[key]):       # in place, in order
            lists = {k for k in old if isinstance(old[k], list)}
            assert {k: old[k] for k in old if k not in lists} == {
                k: new[k] for k in new if k not in lists}
            for k in lists:
                assert new[k][:len(old[k])] == old[k]


# -- a rehearsal that reads both ---------------------------------------------


def _with_the_two_readers(root, m):
    """The manifest entries a later `benchmark` PR adds (PERF.md §7), and
    a mix whose ``max_bytes`` makes a pass several slices: the tiny
    backlog is one slice at 16 MiB, and one slice has no next one."""
    for name, unit, better in (("finish_blocked_ms_per_mrec", "ms/Mrec", "lower"),
                               ("interleave_share", "%", "higher")):
        m["per_layer"].append({
            "name": name, "unit": unit, "better": better,
            "source": "program_span", "layer": "slice path",
            "moves": "records_in_per_s",
            "workloads": [w["name"] for w in m["workloads"]]})
    (root / "benchmark" / "traffic" / "drain-16m.json").write_text(
        json.dumps({"mode": "drain", "max_bytes": 20_000}))


@pytest.mark.parametrize("cell", ["ns-drain", "explode-drain", "agg-drain"])
def test_rehearsal_reports_both_readers_in_every_cell(monkeypatch, tmp_path, cell):
    import test_benchmark_harness as harness

    root = harness._tiny_root(tmp_path, extra=_with_the_two_readers)
    r = harness._rehearse(monkeypatch, root, cell, trace=True, seconds=1.0)
    assert r["correct"] is True and r["faults"] == []
    assert r["metrics"]["fastpath_share"]["value"] == 100.0
    assert r["metrics"]["finish_blocked_ms_per_mrec"]["value"] > 0.0
    # every pass is several slices and its last has no next one
    assert 0.0 < r["metrics"]["interleave_share"]["value"] < 100.0
