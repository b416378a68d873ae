"""The readers of the consumer's side of the socket; none needs the chip.

`frame_ms_per_mrec`, `consume_decode_ms_per_mrec` and
`consume_recv_ms_per_mrec` over a hand-made `obs` and a seeded
`TELEMETRY` (slice flows and the consumer's records of the same
responses), `idle_consume_ms_per_mrec` and `idle_unnamed_share` over a
small XSpace written here in the shape the chip records, all five silent
for a program without the records (a parent commit), and the host three
once over a real traced rehearsal on the CPU. A time read here is never
a device number: the tests assert arithmetic, structure and silence.
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

from spubench import consume_spans, manifest, xplane_scopes as xs  # noqa: E402

from fluvio_tpu.telemetry import TELEMETRY  # noqa: E402
from fluvio_tpu.telemetry import flow as flow_module  # noqa: E402

HOST = ("frame_ms_per_mrec", "consume_decode_ms_per_mrec",
        "consume_recv_ms_per_mrec")
DEVICE = ("idle_consume_ms_per_mrec", "idle_unnamed_share")
CELLS = ["ns-drain", "explode-drain", "agg-drain", "q5-drain", "q17-drain"]


def _reader(name):
    return manifest.load_plugin(BENCH, "layer_metrics", name).read


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    xs._CACHE.clear()
    consume_spans._CACHE.clear()
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


# -- the manifest entries ----------------------------------------------------


@pytest.mark.parametrize("name", HOST + DEVICE)
def test_entries_are_additions_for_every_cell(name):
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    assert set(CELLS) <= set(entry["workloads"]) <= {
        w["name"] for w in m["workloads"]}
    assert entry["moves"] == "records_in_per_s"
    assert entry["source"] == (
        "device_trace" if name in DEVICE else "program_span")
    assert entry["layer"] == ("device" if name in DEVICE
                              else "client / socket")
    assert callable(_reader(name))


# -- the host readers over a seeded TELEMETRY --------------------------------


SLICE = (("read", 0.010), ("encode", 0.200), ("frame", 0.030),
         ("send", 0.050), ("ack_wait", 0.100))     # 0.39 s a slice


def _seed_flow(t0, phases, stream_id=None, next_offset=None, records=1000):
    f = TELEMETRY.begin_flow("chain@bench/0")
    t = f.t0 = t0
    for name, secs in phases:
        f.add_phase(name, t, secs)
        t += secs
    f.stream_id, f.next_offset = stream_id, next_offset
    TELEMETRY.end_flow(f, records=records)
    f.t_end = t
    return f


def _seed_consume(recv0, stream_id, next_offset, recv=0.080, decode=0.030,
                  ack=0.010, records=1000):
    """A consumer's record: ``consume_recv`` from ``recv0``, then decode
    and ack back to back."""
    c = TELEMETRY.begin_consume()
    c.t0 = recv0
    c.stream_id, c.next_offset, c.records = stream_id, next_offset, records
    t = recv0
    for name, secs in (("consume_recv", recv), ("consume_decode", decode),
                       ("consume_ack", ack)):
        c.add_phase(name, t, secs)
        t += secs
    TELEMETRY.end_consume(c)
    c.t_end = t
    return c


def _host_obs(t_open=100.0, t_close=102.0, records_in=2_000_000):
    return {"t_open": t_open, "t_close": t_close, "records_in": records_in,
            "window_s": t_close - t_open}


def _seed_window():
    _seed_flow(98.0, SLICE, 1, 500)                 # ended before the open
    _seed_consume(98.25, 1, 500)
    _seed_flow(100.0, SLICE, 2, 1000)               # 100.00 - 100.39
    _seed_consume(100.25, 2, 1000)                  # recv 100.25 - 100.33
    _seed_flow(101.0, SLICE, 3, 1000)               # 101.00 - 101.39
    _seed_consume(101.25, 3, 1000)                  # recv 101.25 - 101.33
    # the next stream's first slice holds a thread under that recv
    _seed_flow(101.26, (("read", 0.020),))          # 101.26 - 101.28
    _seed_flow(101.9, SLICE, 4, 1000)               # ends after the close
    _seed_consume(102.15, 4, 1000)


def test_host_readers_over_seeded_records():
    _seed_window()
    obs = _host_obs()
    # two flows with a frame ended inside; per million of 2M records
    assert _reader("frame_ms_per_mrec")(obs) == pytest.approx(2 * 30 / 2)
    # the two records that ended inside: decode 30 + ack 10 each
    assert _reader("consume_decode_ms_per_mrec")(obs) == pytest.approx(
        2 * 40 / 2)
    # recv 80 each, the second net of the 20 ms `read` under it; the
    # server's own `send` / `ack_wait` under a recv are waits, not work
    assert _reader("consume_recv_ms_per_mrec")(obs) == pytest.approx(
        (80 + 60) / 2)
    # the accepted reader reads `send` + `ack_wait` without the frame
    # build: 150 each, the second net of the same 20 ms `read`
    assert _reader("send_ack_ms_per_mrec")(obs) == pytest.approx(
        (150 + 130) / 2)


def test_join_key_is_the_same_on_both_sides():
    _seed_window()
    flows = {(f["stream_id"], f["next_offset"]) for f in TELEMETRY.flows_json()
             if "stream_id" in f}
    recs = [(c["stream_id"], c["next_offset"])
            for c in TELEMETRY.consumes_json()]
    assert len(set(recs)) == len(recs) and set(recs) == flows


@pytest.mark.parametrize("name", HOST)
def test_host_readers_stay_silent_without_the_records(name, monkeypatch):
    read = _reader(name)
    assert read(_host_obs()) is None                  # nothing recorded
    _seed_window()
    assert read(_host_obs()) is not None
    assert read(_host_obs(records_in=0)) is None
    # a window no record ended in
    assert read(_host_obs(t_open=110.0, t_close=112.0)) is None
    # a program without the consumer's records (a parent commit)
    monkeypatch.delattr(flow_module, "CONSUME_PHASES")
    assert read(_host_obs()) is None


def test_frame_reader_silent_where_send_holds_the_frame(monkeypatch):
    """A parent books the frame build inside `send`: no flow has `frame`
    and its vocabulary has none."""
    _seed_flow(100.0, (("encode", 0.2), ("send", 0.08), ("ack_wait", 0.1)))
    _seed_consume(100.30, None, None)
    assert _reader("frame_ms_per_mrec")(_host_obs()) is None
    monkeypatch.setattr(flow_module, "SLICE_PHASES", tuple(
        p for p in flow_module.SLICE_PHASES if p != "frame"))
    for name in HOST:
        assert _reader(name)(_host_obs()) is None, name


@pytest.mark.parametrize("name", HOST)
def test_host_readers_refuse_a_window_the_ring_lost(name, monkeypatch):
    from fluvio_tpu.telemetry.flow import FlowRing

    monkeypatch.setattr(TELEMETRY, "flows", FlowRing(2))
    monkeypatch.setattr(TELEMETRY, "consumes", FlowRing(2))
    for i in range(3):
        _seed_flow(100.0 + 0.5 * i, SLICE, 1, 100 * (i + 1))
        _seed_consume(100.25 + 0.5 * i, 1, 100 * (i + 1))
    assert TELEMETRY.consumes.dropped == 1 and TELEMETRY.flows.dropped == 1
    # the oldest kept record ended INSIDE the window: something was lost
    assert _reader(name)(_host_obs()) is None
    assert _reader(name)(_host_obs(t_open=100.95)) is not None


# -- the device readers over a small XSpace ----------------------------------

# Times in ms on the trace's clock; markers at 1.0 and 11.0 (10 ms).
# Device: 1.0-3.0, 5.0-6.0, 9.0-10.0 busy (4 ms); idle 3.0-5.0, 6.0-9.0,
# 10.0-11.0 (6 ms). Loop thread: send 2.5-3.6, consume_recv 2.7-3.5,
# ack_wait 3.6-5.5, consume_decode 3.7-4.2, consume_ack 4.2-4.3,
# finish 6.0-7.0 with wait 6.2-6.8, read 10.0-10.4; a worker thread:
# encode 4.0-4.5. The consumer's share of the idle time by this
# reader's ranking: 3.0-3.5 consume_recv (it outranks `send`, both
# awaiting), 3.7-4.0 consume_decode (holding, over `ack_wait`), 4.2-4.3
# consume_ack (latest of two holding) = 0.9 ms; 4.0-4.2 and 4.3-4.5
# go to `encode`. Unnamed: 7.0-9.0 and 10.4-11.0 = 2.6 ms.
_HOST_EVENTS = (
    # (metadata id, start ms, duration ms, flow id, line)
    (3, 2.5, 1.1, 1, "python3"), (4, 2.7, 0.8, 10, "python3"),
    (5, 3.6, 1.9, 1, "python3"), (6, 3.7, 0.5, 10, "python3"),
    (7, 4.2, 0.1, 10, "python3"), (8, 6.0, 1.0, 2, "python3"),
    (9, 6.2, 0.6, 2, "python3"), (10, 10.0, 0.4, 3, "python3"),
    (11, 4.0, 0.5, 4, "asyncio_0"),
)
_HOST_NAMES = {1: "spubench_trace_open", 2: "spubench_trace_close",
               3: "fluvio/send", 4: "fluvio/consume_recv",
               5: "fluvio/ack_wait", 6: "fluvio/consume_decode",
               7: "fluvio/consume_ack", 8: "fluvio/finish", 9: "fluvio/wait",
               10: "fluvio/read", 11: "fluvio/encode"}


def _ps(ms):
    return int(round(ms * 1e9))


def _xspace_text() -> str:
    dev = "".join(
        f"    events {{ metadata_id: 1 offset_ps: {_ps(a)} "
        f"duration_ps: {_ps(d)} }}\n"
        for a, d in ((1.0, 2.0), (5.0, 1.0), (9.0, 1.0)))
    lines = {}
    for mid, a, d, fid, line in _HOST_EVENTS:
        lines.setdefault(line, []).append(
            f"    events {{ metadata_id: {mid} offset_ps: {_ps(a)} "
            f"duration_ps: {_ps(d)}\n"
            f"      stats {{ metadata_id: 1 int64_value: {fid} }} }}\n")
    lines["python3"][:0] = [
        f"    events {{ metadata_id: 1 offset_ps: {_ps(1.0)} "
        f"duration_ps: 1000000 }}\n",
        f"    events {{ metadata_id: 2 offset_ps: {_ps(11.0)} "
        f"duration_ps: 1000000 }}\n"]
    host_lines = "".join(
        f"  lines {{\n    id: {i + 7}\n    name: \"{name}\"\n"
        f"    timestamp_ns: 0\n{''.join(evs)}  }}\n"
        for i, (name, evs) in enumerate(lines.items()))
    meta = "".join(
        f"  event_metadata {{ key: {k} value {{ id: {k} name: \"{n}\" }} }}\n"
        for k, n in _HOST_NAMES.items())
    return (
        "planes {\n  id: 1\n  name: \"/device:TPU:0\"\n"
        "  lines {\n    id: 1\n    name: \"XLA Ops\"\n    timestamp_ns: 0\n"
        f"{dev}  }}\n"
        "  event_metadata { key: 1 value { id: 1 name: \"%fusion.1 = "
        "s32[8] fusion(s32[8] %p)\" stats { metadata_id: 1 str_value: "
        "\"jit(_chain_fn_ragged)/stage0.filter/and:\" } } }\n"
        "  stat_metadata { key: 1 value { id: 1 name: \"tf_op\" } }\n}\n"
        "planes {\n  id: 2\n  name: \"/host:CPU\"\n"
        f"{host_lines}{meta}"
        "  stat_metadata { key: 1 value { id: 1 name: \"flow_id\" } }\n}\n"
    )


def _xspace_bytes() -> bytes:
    import jax

    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        _xspace_text())


def _trace_obs(tmp_path, raw=None, busy_s=0.004):
    p = tmp_path / "host.xplane.pb"
    p.write_bytes(_xspace_bytes() if raw is None else raw)
    return {"trace": {"busy_s": busy_s, "window_s": 0.010, "path": str(p)},
            "window_s": 20.0, "records_in": 2_000_000}


def test_idle_split_ranks_holding_over_awaiting():
    from fluvio_tpu.telemetry.flow import CONSUME_PHASES, SLICE_PHASES

    split = consume_spans.idle_by_phase(
        _xspace_bytes(), SLICE_PHASES + CONSUME_PHASES, CONSUME_PHASES)
    assert split == pytest.approx({
        "consume_recv": 0.0005, "send": 0.0001, "ack_wait": 0.0006,
        "consume_decode": 0.0003, "encode": 0.0004, "consume_ack": 0.0001,
        "finish": 0.001, "read": 0.0004, xs.UNNAMED: 0.0026,
    })
    # the accepted reduction cuts the same gaps: the same idle time and
    # the same unnamed pieces, whatever the ranking
    r = xs.reduce_xspace(_xspace_bytes(), *xs._vocabulary())
    assert sum(r["idle_gaps"].values()) == pytest.approx(sum(split.values()))
    assert r["idle_gaps"][xs.UNNAMED] == pytest.approx(split[xs.UNNAMED])
    assert r["busy_s"] == pytest.approx(0.004)


def test_device_readers_over_the_runs_trace(tmp_path):
    obs = _trace_obs(tmp_path)
    # 0.9 ms of a 10 ms span x 20 s window per 2 M records
    assert _reader("idle_consume_ms_per_mrec")(obs) == pytest.approx(
        0.0009 / 0.010 * 20.0 * 1e3 / 2.0)
    assert _reader("idle_unnamed_share")(obs) == pytest.approx(
        100 * 2.6 / 6.0)


@pytest.mark.parametrize("name", DEVICE)
def test_device_readers_stay_silent(tmp_path, monkeypatch, name):
    read = _reader(name)
    assert read(_trace_obs(tmp_path)) is not None
    assert read({"records_in": 5}) is None                  # no traced run
    assert read({"trace": None, "records_in": 5}) is None
    gone = _trace_obs(tmp_path)
    gone["trace"]["path"] = str(tmp_path / "gone.xplane.pb")
    assert read(gone) is None
    assert read(_trace_obs(tmp_path, raw=b"")) is None     # no device plane
    # the two reductions of one file disagree: silence
    assert read(_trace_obs(tmp_path, busy_s=0.005)) is None
    # a program without the consumer's records (a parent commit)
    monkeypatch.delattr(flow_module, "CONSUME_PHASES")
    assert read(_trace_obs(tmp_path)) is None


def test_idle_consume_silent_where_no_consumer_phase_was_traced(tmp_path):
    text = _xspace_text()
    for name in ("consume_recv", "consume_decode", "consume_ack"):
        text = text.replace(f"fluvio/{name}", f"other/{name}")
    import jax

    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)
    obs = _trace_obs(tmp_path, raw=raw)
    assert _reader("idle_consume_ms_per_mrec")(obs) is None
    # here the server's `send` / `ack_wait` cover the consumer's time
    assert _reader("idle_unnamed_share")(obs) == pytest.approx(
        100 * 2.6 / 6.0)


# -- a real traced rehearsal -------------------------------------------------


def test_traced_rehearsal_reads_the_host_metrics(monkeypatch, tmp_path):
    import test_benchmark_harness as harness

    root = harness._tiny_root(tmp_path)
    r = harness._rehearse(monkeypatch, root, "ns-drain", trace=True,
                          seconds=2.0)
    assert r["correct"] is True
    for name in HOST:
        assert name in r["metrics"], name
        assert r["metrics"][name]["value"] > 0.0
    # no device plane on the CPU: the device readers read nothing
    for name in DEVICE:
        assert name not in r["metrics"]
    per_mrec = 1000 * r["window_s"] / (r["counts"]["records_in"] / 1e6)
    for name in HOST:
        assert r["metrics"][name]["value"] <= per_mrec
