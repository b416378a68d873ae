"""`BENCHMARK.json` and the files it names.

The manifest is the single place that says which configuration and
traffic mix make a cell and which metrics a cell reports; everything
else about a configuration, a mix, a loop mode, a corpus, a reference or
a per-layer metric is in the file found here by its name.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

#: the checkout: the directory that holds BENCHMARK.json and benchmark/
ROOT = Path(__file__).resolve().parents[2]


class ManifestError(Exception):
    pass


def load_manifest(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(
        f"{what} {name!r} is not in BENCHMARK.json "
        f"(known: {', '.join(e['name'] for e in entries)})"
    )


def _metrics_of(entries, cell: str):
    return [
        m for m in entries if "workloads" not in m or cell in m["workloads"]
    ]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list   # manifest entries this cell reports with --trace 0
    per_layer: list    # manifest entries this cell reports with --trace 1
    bench_dir: Path    # where plugins (modes, corpora, ...) are looked up


def partition_records(config: dict) -> list:
    """Records of each partition's backlog: the configuration's
    ``partition_records`` (one count a partition of
    ``deployment.partitions``, summing to ``backlog_records``), or the
    whole backlog in the one partition of a configuration that states
    none. Skew between partitions is data."""
    n = int(config["deployment"]["partitions"])
    total = int(config["backlog_records"])
    counts = config.get("partition_records")
    if counts is None:
        if n != 1:
            raise ManifestError(
                f"{config['name']}: {n} partitions need `partition_records`")
        return [total]
    counts = [int(c) for c in counts]
    if len(counts) != n or sum(counts) != total or min(counts) < 1:
        raise ManifestError(
            f"{config['name']}: `partition_records` {counts} is not one "
            f"count a partition of {n} summing to `backlog_records` {total}")
    return counts


def check_chips(chips: int, config: dict) -> None:
    """A cell asks for the chips its configuration is deployed on, and a
    placed configuration places over exactly those chips."""
    dep = config["deployment"]
    if chips != int(dep["chips"]):
        raise ManifestError(
            f"the cell asks for {chips} chip(s), its configuration "
            f"{config['name']} is deployed on {dep['chips']}")
    placement = dep.get("placement")
    if placement is not None and int(placement["groups"]) != chips:
        raise ManifestError(
            f"the cell asks for {chips} chip(s), its configuration "
            f"{config['name']} places over {placement['groups']} groups")


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    manifest = load_manifest(root)
    w = _named(manifest["workloads"], workload, "workload")
    c = _named(manifest["configs"], w["config"], "config")
    bench_dir = root / manifest["paths"][0]
    with open(root / c["file"]) as f:
        config = json.load(f)
    traffic_file = bench_dir / "traffic" / f"{w['traffic']}.json"
    if not traffic_file.is_file():
        raise ManifestError(f"no traffic file {traffic_file}")
    with open(traffic_file) as f:
        traffic = json.load(f)
    check_chips(int(w["chips"]), config)
    partition_records(config)
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=config,
        traffic=traffic,
        end_to_end=_metrics_of(manifest["end_to_end"], workload),
        per_layer=_metrics_of(manifest["per_layer"], workload),
        bench_dir=bench_dir,
    )


def load_plugin(bench_dir: Path, kind: str, name: str):
    """The module `<bench_dir>/<kind>/<name>.py` (names may hold dots)."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"no {kind} file {path}")
    mod_name = "spubench_plugin_%s_%s" % (
        kind, "".join(ch if ch.isalnum() else "_" for ch in name)
    )
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
