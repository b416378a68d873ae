"""A topic of several partitions on several chips; none needs the chip.

The harness serves `deployment.partitions` partitions at replication 1,
one consumer a partition (`modes/drain_partitions.py`), places them from
the configuration's `deployment.placement`, holds a placed run to the
program's partition gate, and reads a trace over the cell's chips. The
rehearsals run on the CPU's eight virtual devices through the test-side
switch of `test_benchmark_harness`; a rate or a time read here is never
a device number.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_benchmark_harness as harness  # noqa: E402
from spubench import manifest, trace_reduce, window  # noqa: E402
from spubench import xplane_scopes as xs  # noqa: E402

TWO_CHIPS = BENCH / "testdata" / "trace_two_chips_small.textproto"
PART_RECORDS = [1536, 1024, 1024, 512]     # skewed, in 512-record batches


def _add_placed_cell(root, m, partitions=4, chips=4, placement=True,
                     cell_chips=None, records=PART_RECORDS):
    """What the `model_config` PR that adds a placed topic brings, by new
    files and appended manifest entries alone: a configuration of
    ``partitions`` partitions over ``chips`` chips (placement `.*=spread`
    over as many groups), a traffic mix of the partitions' mode, and a
    cell appended to `records_in_per_s` and to every general reader."""
    b = root / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    accepted = {w["name"] for w in m["workloads"]}
    cfg = json.loads((b / "configs" / "fluvio-northstar-1p.json").read_text())
    cfg["name"] = "ns-placed"
    cfg["deployment"] |= {"partitions": partitions, "chips": chips}
    if placement:
        cfg["deployment"]["placement"] = {"groups": chips, "rules": ".*=spread"}
    cfg["stored_batch_records"] = 512
    cfg["partition_records"] = records
    cfg["backlog_records"] = sum(records)
    (b / "configs" / "ns-placed.json").write_text(json.dumps(cfg))
    (b / "traffic" / "drain-parts.json").write_text(
        json.dumps({"mode": "drain_partitions", "max_bytes": 20000}))
    m["configs"].append({
        "name": "ns-placed", "source": cfg["source"],
        "file": "benchmark/configs/ns-placed.json", "reduced": [],
        "why": "test"})
    m["workloads"].append({
        "name": "placed-cell", "config": "ns-placed", "traffic": "drain-parts",
        "chips": chips if cell_chips is None else cell_chips, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e and accepted <= set(e["workloads"]):
            e["workloads"].append("placed-cell")
    assert all(p.read_bytes() == data for p, data in before.items())


def _root(tmp_path, **kw):
    return harness._tiny_root(
        tmp_path, extra=lambda root, m: _add_placed_cell(root, m, **kw))


def test_placed_cell_is_added_by_files_alone(monkeypatch, tmp_path):
    """Four partitions of skewed size over four chips: the manifest checks
    take the four-chip cell, and the rehearsal is `correct` with every
    partition compared against its own reference and served on a device
    group of its own."""
    root = _root(tmp_path)
    m = json.loads((root / "BENCHMARK.json").read_text())
    for check_manifest in harness.MANIFEST_CHECKS:
        check_manifest(m, root)
    r = harness._rehearse(monkeypatch, root, "placed-cell", seconds=0.5)
    assert r["faults"] == [] and r["correct"] is True
    assert r["attempted"] >= 4 and r["failed"] == 0
    by_part = r["counts"]["records_in_by_partition"]
    assert len(by_part) == 4 and min(by_part) > 0
    assert sum(by_part) == r["counts"]["records_in"]
    assert sorted(r["counts"]["partition_groups"]) == [0, 1, 2, 3]
    assert r["compared"]["partition_gate_armed"] == {
        "value": 1, "limit": 1, "at_least": True}
    assert r["compared"]["placement_errors"]["value"] == 0
    assert set(r["metrics"]) == {"records_in_per_s", "setup_s"}
    # the harness put the process's placement variables back
    import os

    from fluvio_tpu import partition

    assert "FLUVIO_PARTITIONS" not in os.environ
    assert partition.gate() is None


def test_one_partition_mode_counts_as_drain(monkeypatch, tmp_path):
    """Over a topic of one partition the partitions' mode serves what
    `drain` serves: a window that closes at its first response counts the
    same records, bytes and responses in both, and both are correct."""
    def extra(root, m):
        _add_placed_cell(root, m, partitions=1, chips=1, placement=False,
                         records=[2048])
        (root / "benchmark" / "traffic" / "drain-one.json").write_text(
            json.dumps({"mode": "drain", "max_bytes": 20000}))
        m["workloads"].append({
            "name": "drain-cell", "config": "ns-placed",
            "traffic": "drain-one", "chips": 1, "why": "test"})
        for e in m["end_to_end"]:
            if "workloads" in e:
                e["workloads"].append("drain-cell")

    root = harness._tiny_root(tmp_path, extra=extra)
    got = {cell: harness._rehearse(monkeypatch, root, cell, seconds=0.0)
           for cell in ("placed-cell", "drain-cell")}
    keys = ("records_in", "records_out", "bytes_out", "responses",
            "fastpath_slices", "fallback_slices", "compiles")
    a, b = got["placed-cell"], got["drain-cell"]
    assert a["correct"] is b["correct"] is True
    assert a["attempted"] == b["attempted"] == 1
    assert {k: a["counts"][k] for k in keys} == {k: b["counts"][k] for k in keys}
    assert a["counts"]["records_in_by_partition"] == [a["counts"]["records_in"]]
    assert a["counts"]["partition_groups"] == [None]
    assert set(a["compared"]) == set(b["compared"])


def test_wrong_count_in_one_partition_is_not_correct(monkeypatch, tmp_path):
    """A reference that states one output more for one partition's inputs
    (its window counts only: the warm-up pass compares its columns)
    fails that partition's passes, and the run."""
    from spubench.session import Session

    made = []
    reference = Session.reference

    def lying(self, flat, off, lo):
        ref = reference(self, flat, off, lo)
        made.append(ref)
        if len(made) == 3:
            true_count = ref.count
            ref.count = lambda a, b: true_count(a, b) + 1
        return ref

    monkeypatch.setattr(Session, "reference", lying)
    r = harness._rehearse(monkeypatch, _root(tmp_path), "placed-cell",
                          seconds=0.3)
    assert len(made) == 4
    assert r["correct"] is False and r["failed"] >= 1
    assert any("other counts" in f for f in r["faults"])
    assert r["compared"]["failed_operations"]["value"] == r["failed"]


def test_unarmed_gate_is_not_correct(monkeypatch, tmp_path):
    """The program's gate logs and stays unarmed where it cannot be built,
    and the topic is then served on one device: not correct."""
    from fluvio_tpu.partition import runtime

    def refuse(self, *a, **k):
        raise RuntimeError("no mesh")

    monkeypatch.setattr(runtime.BrokerPartitionGate, "__init__", refuse)
    r = harness._rehearse(monkeypatch, _root(tmp_path), "placed-cell",
                          seconds=0.2)
    assert r["correct"] is False
    assert any("gate is not armed" in f for f in r["faults"])
    assert r["compared"]["partition_gate_armed"]["value"] == 0
    assert r["counts"]["partition_groups"] == [None] * 4


def test_booked_placement_error_is_not_correct(monkeypatch, tmp_path):
    """A slice whose placement fails is served unplaced and booked as a
    `partition-placement-error` decline: not correct."""
    from fluvio_tpu.partition import runtime

    def fail(self, topic, partition):
        raise ValueError("no rule matched")

    monkeypatch.setattr(runtime.BrokerPartitionGate, "group_for", fail)
    r = harness._rehearse(monkeypatch, _root(tmp_path), "placed-cell",
                          seconds=0.2)
    assert r["correct"] is False
    assert r["compared"]["placement_errors"]["value"] > 0
    assert any(f.startswith("placement_errors moved") for f in r["faults"])


@pytest.mark.parametrize("kw,words", [
    ({"cell_chips": 1}, "deployed on 4"),
    ({"chips": 4, "cell_chips": 4, "partitions": 4}, None),
    ({"records": [1536, 1024, 1024]}, "partition_records"),
    ({"records": [1536, 1024, 1024, 0]}, "partition_records"),
])
def test_loader_holds_cell_to_its_configuration(tmp_path, kw, words):
    root = _root(tmp_path, **kw)
    if words is None:
        assert manifest.load_cell("placed-cell", root).chips == 4
        return
    with pytest.raises(manifest.ManifestError, match=words):
        manifest.load_cell("placed-cell", root)


def test_loader_refuses_placement_over_other_chips(tmp_path):
    def extra(root, m):
        _add_placed_cell(root, m)
        f = root / "benchmark" / "configs" / "ns-placed.json"
        cfg = json.loads(f.read_text())
        cfg["deployment"]["placement"]["groups"] = 2
        f.write_text(json.dumps(cfg))

    root = harness._tiny_root(tmp_path, extra=extra)
    with pytest.raises(manifest.ManifestError, match="places over 2 groups"):
        manifest.load_cell("placed-cell", root)


def test_replication_other_than_one_still_raises(tmp_path):
    import asyncio

    from spubench.broker import Broker

    cfg = json.loads((BENCH / "configs" / "fluvio-northstar-1p.json").read_text())
    cfg["deployment"]["replication"] = 3
    with pytest.raises(ValueError, match="replication 1"):
        asyncio.run(Broker(cfg, str(tmp_path)).start())


# -- the trace over the cell's chips -----------------------------------------


def _two_chips_bytes() -> bytes:
    import jax

    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        TWO_CHIPS.read_text())


@pytest.mark.parametrize("chips,busy,by_device", [
    (1, 0.004, [0.004]),
    (2, 0.002, [0.004, 0.0]),
    (3, (0.004 + 0.007) / 3, [0.004, 0.0, 0.007]),
])
def test_both_reductions_read_the_cells_chips(chips, busy, by_device):
    """Exactly the planes of chips 0 .. chips-1 are read, a chip that ran
    nothing in the window counts as busy 0, and busy is their mean."""
    import jax

    from fluvio_tpu.telemetry.flow import SLICE_PHASES
    from fluvio_tpu.telemetry.spans import DEVICE_SCOPES

    p = trace_reduce.reduce_profile(
        jax.profiler.ProfileData.from_text_proto(TWO_CHIPS.read_text()),
        chips=chips)
    x = xs.reduce_xspace(_two_chips_bytes(), DEVICE_SCOPES, SLICE_PHASES,
                         chips=chips)
    for r in (p, x):
        assert r["devices"] == chips
        assert r["window_s"] == pytest.approx(0.010)
        assert r["busy_s"] == pytest.approx(busy)
        assert r["busy_s_by_device"] == pytest.approx(by_device)
    if chips == 2:
        # the mean chip's scopes; chip 0's idle gaps
        assert x["scope_s"] == pytest.approx(
            {"repad": 0.001, "stage0.filter": 0.001})
        assert sum(x["idle_gaps"].values()) == pytest.approx(0.006)
        assert sum(v for _k, v in p["idle_gaps"]) == pytest.approx(0.006)
        assert window.idle_chips(p) == [1]
        assert window.placement_faults(_Unplaced(), p) != []


class _Unplaced:
    placement = None
    gate = None


def test_roofline_divides_by_the_cells_chips(tmp_path):
    """Bytes summed over the chips, busy the mean chip's: the share is
    over `chips` x the one-chip peak, and on one chip what it was."""
    from spubench.shapes import span_bytes

    read = manifest.load_plugin(BENCH, "layer_metrics", "hbm_roofline_share").read
    shape = {"max_in_len": 41, "max_out_len": 14, "fanout": 1}
    spans = [{"records": 65536}, {"records": 40000}]
    moved = sum(span_bytes(s["records"], shape) for s in spans)
    obs = {"trace": {"busy_s": 0.002, "window_s": 0.010},
           "trace_spans": spans, "shape": shape, "device_kind": "TPU v5 lite"}
    one = read(obs)
    assert one == pytest.approx(100 * (moved / 819e9) / 0.002)
    assert read(obs | {"chips": 1}) == one
    assert read(obs | {"chips": 2}) == pytest.approx(
        100 * (moved / (2 * 819e9)) / 0.002)


def test_traced_run_with_an_idle_chip_is_not_correct():
    """In a traced run every chip of the cell has to have worked."""
    import jax

    r = trace_reduce.reduce_profile(
        jax.profiler.ProfileData.from_text_proto(TWO_CHIPS.read_text()),
        chips=2)
    faults = window.placement_faults(_Unplaced(), r)
    assert faults and "chip(s) [1]" in faults[0]
    c = {"paths": {}, "slices": {"fastpath_slices": 1, "fallback_slices": 0}}
    c |= {k: 0 for k in window.STILL_COUNTERS}
    out = window.compared({"faults": [], "failed": 0}, c, c, _Unplaced(), r)
    assert out["idle_chips"] == {"value": 1, "limit": 0}
    assert "partition_gate_armed" not in out
