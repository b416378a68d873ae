"""The readers of the program's own tracing (PR 26); none needs the chip.

Each host reader over a hand-made `obs` and a seeded `TELEMETRY`, the
xplane reduction (`spubench/xplane_scopes.py`) over a small fixture in
the shape the chip records, the device readers over a run's own trace
file, and all of them once over a real traced rehearsal on the CPU. A
time read here is never a device number: the tests assert arithmetic,
structure and which readers stay silent.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
for _p in (str(REPO), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from spubench import manifest, xplane_scopes as xs  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402
from fluvio_tpu.telemetry.flow import SLICE_PHASES  # noqa: E402
from fluvio_tpu.telemetry.spans import DEVICE_SCOPES, timed  # noqa: E402

FIXTURE = BENCH / "testdata" / "trace_scopes_small.textproto"
NEW_HOST = ("slice_in_ms_per_mrec", "slice_out_ms_per_mrec",
            "send_ack_ms_per_mrec", "exec_wait_ms_per_mrec",
            "serve_unnamed_share")
NEW_DEVICE = ("device_named_share", "device_link_ms_per_mrec",
              "device_chain_ms_per_mrec")


def _reader(name):
    return manifest.load_plugin(BENCH, "layer_metrics", name).read


def _fixture_bytes() -> bytes:
    import jax

    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        FIXTURE.read_text()
    )


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    xs._CACHE.clear()
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


# -- the manifest entries ----------------------------------------------------


def check_workloads_floor(m, entry, floor):
    """An entry's `workloads` is held to a FLOOR: the cells it was accepted
    with are in it, and whatever else is in it is a cell of the manifest
    ``m``, once. A later PR appends its own."""
    cells = {w["name"] for w in m["workloads"]}
    assert floor <= set(entry["workloads"]) <= cells
    assert len(set(entry["workloads"])) == len(entry["workloads"])


def check_new_entry(m, name):
    """PR 26's entry ``name`` in the manifest ``m``, wherever in the list
    it stands."""
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    check_workloads_floor(m, entry, {"ns-drain", "explode-drain"})
    assert entry["moves"] == "records_in_per_s"
    assert entry["source"] == (
        "device_trace" if name in NEW_DEVICE else "program_span")
    assert callable(_reader(name))


@pytest.mark.parametrize("name", NEW_HOST + NEW_DEVICE)
def test_new_entries_are_additions_for_both_drain_cells(name):
    check_new_entry(json.loads((REPO / "BENCHMARK.json").read_text()), name)


# -- the xplane reduction ----------------------------------------------------


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_chain_fn_ragged)/link_decode/while/body/jit(_take)/gather:",
     "link_decode"),
    ("jit(_chain_fn_ragged)/compact/link_encode/scatter:", "link_encode"),
    ("jit(_chain_fn_ragged)/compact/pack/concatenate:", "pack"),
    ("jit(_chain_fn_ragged)/compact/reduce_sum:", "compact"),
    ("jit(_chain_fn_ragged)/stage0.array_map/while/body/closed_call/"
     "jit(_where)/select_n:", "stage0.array_map"),
    ("jit(step)/jit(shmap_body)/stage12.filter/and:", "stage12.filter"),
    ("jit(_chain_fn_striped)/repad/jit(_take)/gather", "repad"),
    ("jit(_fan_probe)/sub:", None),
    ("jit(packer)/jit(pack)/add:", None),        # `jit(pack)` is no scope
    ("", None),
])
def test_scope_of_takes_the_innermost_program_scope(op_name, scope):
    assert xs.scope_of(op_name, DEVICE_SCOPES) == scope


def test_reduction_on_recorded_shape_fixture():
    r = xs.reduce_xspace(_fixture_bytes(), DEVICE_SCOPES, SLICE_PHASES)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.00775)
    assert r["scope_s"] == pytest.approx({
        "link_decode": 0.0053, "repad": 0.0005, "stage0.filter": 0.0005,
        "stage1.map": 0.00025, "link_encode": 0.0005, "pack": 0.0002,
        "compact": 0.0002,
    })
    # a `while` and its body count once: the body's time is the body's
    assert r["unnamed_ops"] == pytest.approx(
        {"%while.1": 0.0002, "%fusion.21": 0.0001})
    assert sum(r["scope_s"].values()) + sum(r["unnamed_ops"].values()) == (
        pytest.approx(r["busy_s"]))
    # gaps: a slice phase names a gap before a chunk phase does (`finish`
    # over `wait`/`fetch`), a renamed phase reads under its booked name,
    # and the overlapping `device` phase labels nothing
    assert r["idle_gaps"] == pytest.approx({
        "ack_wait": 0.0008, "read": 0.0001, "finish": 0.00075,
        "glz_compress": 0.0004, "unnamed": 0.0002,
    })
    assert sum(r["idle_gaps"].values()) + r["busy_s"] == (
        pytest.approx(r["window_s"]))
    assert r["phases_seen"] == ["ack_wait", "fetch", "finish", "glz_compress",
                                "read", "wait"]


def test_reduction_agrees_with_the_accepted_busy_time():
    """Same trace, same markers: this reduction's busy time and window
    are `trace_reduce.reduce_profile`'s (`reduce_run` refuses a file on
    which the two differ by more than rounding)."""
    import jax
    from spubench import trace_reduce

    mine = xs.reduce_xspace(_fixture_bytes(), DEVICE_SCOPES, SLICE_PHASES)
    theirs = trace_reduce.reduce_profile(
        jax.profiler.ProfileData.from_text_proto(FIXTURE.read_text()))
    assert mine["busy_s"] == pytest.approx(theirs["busy_s"])
    assert mine["window_s"] == pytest.approx(theirs["window_s"])
    # and over PR 24's own fixture, which carries no scope at all
    old = (BENCH / "testdata" / "trace_small.textproto").read_text()
    r = xs.reduce_xspace(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(old),
        DEVICE_SCOPES, SLICE_PHASES)
    assert r["busy_s"] == pytest.approx(0.0045) and r["scope_s"] == {}
    assert set(r["idle_gaps"]) == {"unnamed"}


def test_no_device_plane_reads_nothing():
    import jax

    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }')
    assert xs.reduce_xspace(raw, DEVICE_SCOPES, SLICE_PHASES) is None


# -- the device readers over a run's own trace -------------------------------


def _plant_trace(tmp: Path, run: str, raw: bytes) -> Path:
    """A trace file where a session's `Tracer` writes one."""
    d = tmp / f"spubench-{run}" / "trace" / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    p = d / "host.xplane.pb"
    p.write_bytes(raw)
    return p


def _unscoped_bytes() -> bytes:
    import jax

    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        (BENCH / "testdata" / "trace_small.textproto").read_text())


def _device_obs(path=None, busy_s=0.00775):
    # a 20 s window whose traced span is the fixture's 10 ms; 2M records;
    # `path` is the trace file the session's tracer reduced
    trace = {"busy_s": busy_s, "window_s": 0.010}
    if path is not None:
        trace["path"] = str(path)
    return {"trace": trace, "window_s": 20.0, "records_in": 2_000_000}


def test_device_readers_take_this_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    mine = _plant_trace(tmp_path, "aaa", _fixture_bytes())
    # another run's trace in a sibling directory, newer, and one whose
    # busy time is this run's own: found by path, neither is ever opened
    newer = _plant_trace(tmp_path, "bbb", _unscoped_bytes())
    twin = _plant_trace(tmp_path, "ccc", _fixture_bytes())
    os.utime(mine, (1000, 1000))
    os.utime(newer, (2000, 2000))
    os.utime(twin, (3000, 3000))
    obs = _device_obs(mine)
    named = _reader("device_named_share")(obs)
    link = _reader("device_link_ms_per_mrec")(obs)
    chain = _reader("device_chain_ms_per_mrec")(obs)
    assert [path for path, _mtime in xs._CACHE] == [str(mine)]
    assert named == pytest.approx(100 * 0.00745 / 0.00775)
    # seconds of the span / span x window seconds per million records
    assert link == pytest.approx((0.0053 + 0.0005 + 0.0002 + 0.0005) / 0.010
                                 * 20.0 * 1e3 / 2.0)
    assert chain == pytest.approx((0.0005 + 0.00025 + 0.0002) / 0.010
                                  * 20.0 * 1e3 / 2.0)
    busy = manifest.load_plugin(
        BENCH, "layer_metrics", "device_busy_ms_per_mrec").read(obs)
    assert link + chain == pytest.approx(busy * named / 100)


@pytest.mark.parametrize("name", NEW_DEVICE)
def test_device_readers_stay_silent(tmp_path, monkeypatch, name):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    read = _reader(name)
    mine = _plant_trace(tmp_path, "ccc", _fixture_bytes())
    assert read(_device_obs(mine)) is not None
    assert read({"records_in": 5}) is None                   # no traced run
    assert read({"trace": None, "records_in": 5}) is None
    # a reduction that names no file: nothing is looked for, though a
    # trace with this very busy time lies under the temporary directory
    assert read(_device_obs()) is None
    assert read(_device_obs(tmp_path / "spubench-gone" / "x.xplane.pb")) is None
    unscoped = _plant_trace(tmp_path, "ddd", _unscoped_bytes())
    assert read(_device_obs(unscoped, busy_s=0.0045)) is None  # no scope in it
    empty = _plant_trace(tmp_path, "eee", b"")
    assert read(_device_obs(empty)) is None                  # no device plane
    # a program without the vocabulary (a parent commit): nothing, no raise
    monkeypatch.setattr(xs, "_vocabulary", lambda: None)
    assert read(_device_obs(mine)) is None


@pytest.mark.parametrize("off,reads", [
    # `trace_reduce` reads whole nanoseconds (ProfileData), this reader
    # picoseconds: 42.9 us of 5.28 s over `ns-drain`'s 93,064 events
    (+1e-5, True), (-1e-5, True),
    # the two reductions of one file have drifted apart: silence
    (+0.05, False), (-0.05, False),
])
@pytest.mark.parametrize("name", NEW_DEVICE)
def test_device_readers_allow_rounding_and_no_more(tmp_path, name, off, reads):
    mine = _plant_trace(tmp_path, "aaa", _fixture_bytes())
    exact = _reader(name)(_device_obs(mine))
    got = _reader(name)(_device_obs(mine, busy_s=0.00775 * (1 + off)))
    if not reads:
        assert got is None
    elif name == "device_named_share":
        assert got == exact        # a share of the file's own busy time
    else:
        assert got == pytest.approx(exact)


def test_tracer_hands_the_readers_its_own_file(tmp_path):
    """`Tracer.reduce` names the file it reduced (the newest in ITS
    directory) in the dict that becomes `obs["trace"]`."""
    from spubench.trace_reduce import Tracer

    tr = Tracer(str(tmp_path / "spubench-aaa" / "trace"), enabled=True)
    tr.t0, tr.t1 = 100.0, 100.010
    assert tr.reduce() is None                               # nothing written
    old = _plant_trace(tmp_path, "aaa", _unscoped_bytes())
    mine = old.parent.parent / "t1" / "host.xplane.pb"
    mine.parent.mkdir()
    mine.write_bytes(_fixture_bytes())
    os.utime(old, (1000, 1000))
    os.utime(mine, (2000, 2000))
    other = _plant_trace(tmp_path, "bbb", _fixture_bytes())  # a sibling run's
    os.utime(other, (3000, 3000))
    reduced = tr.reduce()
    assert reduced["path"] == str(mine)
    assert reduced["busy_s"] == pytest.approx(0.00775)
    obs = {"trace": reduced, "window_s": 20.0, "records_in": 2_000_000}
    assert _reader("device_named_share")(obs) == pytest.approx(
        100 * 0.00745 / 0.00775)


# -- the host readers over a seeded TELEMETRY --------------------------------


def _seed_flow(t0: float, phases, records=1000):
    """One closed flow whose phases sit back to back from ``t0``."""
    f = TELEMETRY.begin_flow("chain@bench/0")
    t = f.t0 = t0
    for name, secs in phases:
        f.add_phase(name, t, secs)
        t += secs
    TELEMETRY.end_flow(f, records=records)
    f.t_end = t
    return f


def _seed_span(flow, t_end: float, wait_s: float, records=500):
    s = TELEMETRY.begin_batch(chain="chain", flow_id=flow.flow_id)
    s.add("wait", wait_s, start=t_end - wait_s)
    s.add("device", 5.0, start=t_end - 5.0)    # overlaps; never read here
    TELEMETRY.end_batch(s, records=records)
    s.t_end = t_end
    return s


SLICE = (("read", 0.010), ("wire_decode", 0.030), ("stage", 0.020),
         ("dispatch", 0.100), ("finish", 0.400), ("encode", 0.200),
         ("send", 0.005), ("ack_wait", 0.035))     # 0.8 s a slice


def _host_obs(t_open=100.0, t_close=102.0, records_in=2_000_000):
    from spubench import window

    return {"t_open": t_open, "t_close": t_close, "records_in": records_in,
            "window_s": t_close - t_open,
            "window_spans": window.spans_between(t_open, t_close)}


def test_host_readers_over_seeded_flows():
    _seed_flow(98.0, SLICE)                 # ended before the window
    a = _seed_flow(100.0, SLICE)            # 100.0 - 100.8
    b = _seed_flow(101.0, SLICE)            # 101.0 - 101.8
    _seed_flow(101.9, SLICE)                # ends after the close
    _seed_span(a, 100.5, 0.30)
    _seed_span(b, 101.5, 0.25)
    _seed_span(b, 103.0, 9.0)               # ended outside the window
    obs = _host_obs()
    # two flows ended inside; per million of 2M records
    assert _reader("slice_in_ms_per_mrec")(obs) == pytest.approx(2 * 60 / 2)
    assert _reader("slice_out_ms_per_mrec")(obs) == pytest.approx(2 * 200 / 2)
    assert _reader("send_ack_ms_per_mrec")(obs) == pytest.approx(2 * 40 / 2)
    assert _reader("exec_wait_ms_per_mrec")(obs) == pytest.approx(550 / 2)
    # named: 100.0-100.8, 101.0-101.8, and 101.9-102.0 of the flow that
    # ended after the close: 1.7 s of a 2 s window
    assert _reader("serve_unnamed_share")(obs) == pytest.approx(15.0)
    # it cannot exceed the wall, whatever `device` overlaps
    assert _reader("exec_wait_ms_per_mrec")(obs) <= 1000 * obs["window_s"] / 2
    # a stream's last ack wait runs on while the NEXT stream's handler
    # holds the loop: that overlap is not waiting for the consumer
    _seed_flow(100.85, (("ack_wait", 1.0),))        # 100.85 - 101.85
    late = xs.flow_wait_ms_per_mrec(_host_obs())
    # of its 1.0 s, 101.0-101.76 lies under flow b's working phases
    assert late == pytest.approx((2 * 40 + 1000 - 760) / 2)


@pytest.mark.parametrize("name", NEW_HOST)
def test_host_readers_stay_silent_without_the_records(name):
    read = _reader(name)
    assert read(_host_obs()) is None                  # nothing recorded
    # a program that books none of the served phases (a parent commit)
    f = TELEMETRY.begin_flow("chain@bench/0")
    f.t0 = 100.1
    TELEMETRY.end_flow(f, records=10)
    f.t_end = 100.2
    s = TELEMETRY.begin_batch(chain="chain")
    s.add("device", 0.1)
    TELEMETRY.end_batch(s, records=10)
    s.t_end = 100.3
    assert read(_host_obs()) is None
    assert read(_host_obs(records_in=0)) is None


@pytest.mark.parametrize("name", NEW_HOST)
def test_host_readers_refuse_a_window_the_ring_lost(name, monkeypatch):
    from fluvio_tpu.telemetry.flow import FlowRing
    from fluvio_tpu.telemetry.spans import SpanRing

    monkeypatch.setattr(TELEMETRY, "flows", FlowRing(2))
    monkeypatch.setattr(TELEMETRY, "spans", SpanRing(2))
    flows = [_seed_flow(100.0 + 0.5 * i, (("read", 0.1), ("encode", 0.3),
                                          ("ack_wait", 0.05)))
             for i in range(3)]
    for i, f in enumerate(flows):
        _seed_span(f, 100.2 + 0.5 * i, 0.1)
    assert TELEMETRY.flows.dropped == 1 and TELEMETRY.spans.dropped == 1
    # the oldest kept item ended INSIDE the window: something was lost
    assert _reader(name)(_host_obs()) is None
    # a window that opened after it is held whole
    assert _reader(name)(_host_obs(t_open=100.95)) is not None


def test_timed_phase_is_what_the_readers_read():
    """`timed()` books wall position and seconds on flows and spans
    alike, cuts `less`, renames, and is inert without a target."""
    import time

    f = TELEMETRY.begin_flow("c")
    with timed(f, "wire_decode") as ph:
        time.sleep(0.002)
        ph.less = 0.001
    with timed(f, "h2d") as ph:
        ph.rename("stage")
    (n0, s0, d0), (n1, s1, _d1) = f.phases
    assert (n0, n1) == ("wire_decode", "stage") and s1 >= s0 + d0 >= f.t0
    assert 0.0 < d0 < (s1 - s0) - 0.0009     # the cut came off the booking
    s = TELEMETRY.begin_batch(flow_id=f.flow_id)
    with timed(s, "wait"):
        pass
    assert s.phase("wait") > 0 and s.phase_t0[-1] >= s.t0
    with timed(None, "wait") as ph:
        ph.less = 3.0
        ph.rename("x")
    assert ph.less == 0.0


# -- a real traced rehearsal -------------------------------------------------


@pytest.mark.parametrize("cell", ["ns-drain", "explode-drain"])
def test_traced_rehearsal_reads_the_host_metrics(monkeypatch, tmp_path, cell):
    import test_benchmark_harness as harness

    root = harness._tiny_root(tmp_path)
    r = harness._rehearse(monkeypatch, root, cell, trace=True, seconds=2.0)
    assert r["correct"] is True
    for name in NEW_HOST:
        assert name in r["metrics"], name
        assert r["metrics"][name]["value"] >= 0.0
    # no device plane on the CPU: the device readers read nothing
    for name in NEW_DEVICE:
        assert name not in r["metrics"]
    per_mrec = 1000 * r["window_s"] / (r["counts"]["records_in"] / 1e6)
    assert r["metrics"]["exec_wait_ms_per_mrec"]["value"] <= per_mrec
    assert r["metrics"]["serve_unnamed_share"]["value"] < 50.0
