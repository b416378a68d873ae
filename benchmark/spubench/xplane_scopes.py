"""Device time by the program's own scope names, and device-idle gaps by
the program's own host phases, from one `*.xplane.pb`.

The program opens a `jax.named_scope` at each stage boundary of a chain
(`fluvio_tpu.telemetry.spans.DEVICE_SCOPES`) and enters every host phase
as a `jax.profiler.TraceAnnotation` named ``fluvio/<phase>``. On a TPU the
scope of an operation is NOT in the event `jax.profiler.ProfileData`
shows (name: the HLO text without metadata; stats: offsets only): it is
the ``tf_op`` stat of the event's METADATA record
(``jit(_chain_fn_ragged)/link_decode/while/body/jit(_take)/gather:``),
which `ProfileData` does not expose. So this file reads the serialized
XSpace itself, with a wire-format reader for the dozen fields it needs.

A run's trace is the file the session's own `trace_reduce.Tracer` wrote
and reduced (`obs["trace"]["path"]`); `reduce_run` opens no other.
"""

from __future__ import annotations

import os
import re
from collections import defaultdict

from spubench.device import chips_of
from spubench.trace_reduce import (
    MARK_CLOSE, MARK_OPEN, OPS_LINE, _union, device_planes, idle_gaps,
)

ANNOTATION_PREFIX = "fluvio/"
UNNAMED = "unnamed"
# scopes whose time is the link's (moving and re-shaping bytes) and the
# chain's own (the stages and the survivor compaction)
LINK_SCOPES = ("link_decode", "repad", "pack", "link_encode")
CHAIN_SCOPES = ("stage", "compact")
_STAGE = re.compile(r"^stage\d+\.\w+$")


# -- the wire format ---------------------------------------------------------


def _varint(buf, i: int):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf: memoryview):
    """(field number, wire type, value) of one serialized message; a
    length-delimited value is a memoryview, a varint an int."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an XSpace")
        yield key >> 3, wt, v


def _stat(buf):
    """XStat -> (metadata id, value): a string, a ('ref', id) or an int."""
    mid, val = 0, None
    for f, wt, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            val = ("ref", v)
        elif f in (3, 4) and wt == 0:
            val = v
    return mid, val


def _map_entry(buf):
    key, value = 0, memoryview(b"")
    for f, _wt, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf) -> dict:
    """XPlane -> name, lines (`_Line`: decoded on first use), event
    names and metadata stats, stat names."""
    plane = {"name": "", "lines": [], "event_name": {}, "event_stats": {},
             "stat_name": {}}
    for f, _wt, v in _fields(buf):
        if f == 2:
            plane["name"] = bytes(v).decode()
        elif f == 3:
            plane["lines"].append(_Line(v))
        elif f == 4:
            key, em = _map_entry(v)
            stats = []
            for g, _w, x in _fields(em):
                if g == 2:
                    plane["event_name"][key] = bytes(x).decode("utf-8", "replace")
                elif g == 5:
                    stats.append(_stat(x))
            plane["event_stats"][key] = stats
        elif f == 5:
            key, sm = _map_entry(v)
            for g, _w, x in _fields(sm):
                if g == 2:
                    plane["stat_name"][key] = bytes(x).decode()
    return plane


class _Line:
    """One XLine, decoded once and only if somebody reads it (a host
    plane has dozens of thread lines; the markers and the program's
    phases live on a few)."""

    def __init__(self, buf):
        self._buf = buf
        self._events = None
        self.name = ""
        for f, _wt, v in _fields(buf):
            if f == 2:
                self.name = bytes(v).decode()
                break

    @property
    def events(self):
        if self._events is None:
            self._events = _line_events(self._buf)
        return self._events


def _line_events(buf):
    """XLine -> [(metadata id, start ns, end ns, [raw XStat])]."""
    t0_ns, raw = 0, []
    for f, _wt, v in _fields(buf):
        if f == 3:
            t0_ns = v
        elif f == 4:
            raw.append(v)
    out = []
    for ev in raw:
        mid = off_ps = dur_ps = 0
        stats = []
        for f, _wt, v in _fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                off_ps = v
            elif f == 3:
                dur_ps = v
            elif f == 4:
                stats.append(v)
        a = t0_ns + off_ps / 1e3
        out.append((mid, a, a + dur_ps / 1e3, stats))
    return out


def parse_xspace(raw: bytes) -> list:
    return [_plane(v) for f, _wt, v in _fields(memoryview(raw)) if f == 1]


# -- scopes ------------------------------------------------------------------


def scope_of(op_name: str, vocabulary) -> str | None:
    """The innermost component of an operation's ``tf_op`` path that is
    one of the program's scopes: ``jit(f)/compact/pack/scatter:`` ->
    ``pack``, ``.../stage1.map/...`` -> ``stage1.map``."""
    for part in reversed(op_name.rstrip(":").split("/")):
        if part in vocabulary or ("stage" in vocabulary and _STAGE.match(part)):
            return part
    return None


def _metadata_scopes(plane: dict, vocabulary) -> dict:
    """{event metadata id: scope} from each metadata record's ``tf_op``."""
    tf_op = {k for k, name in plane["stat_name"].items() if name == "tf_op"}
    out = {}
    for mid, stats in plane["event_stats"].items():
        for sid, val in stats:
            if sid not in tf_op or val is None:
                continue
            if isinstance(val, tuple):
                val = plane["stat_name"].get(val[1], "")
            scope = scope_of(str(val), vocabulary)
            if scope is not None:
                out[mid] = scope
    return out


def _self_times(events, m0, m1):
    """[(metadata id, self ns)] of one ``XLA Ops`` line clipped to
    [m0, m1]: an operation that contains others (a `while` and the
    operations of its body) keeps only the time none of them covers, so
    every instant of busy time is counted once, for its innermost owner."""
    clipped = []
    for mid, a, b, _stats in events:
        if m0 is not None and m1 is not None:
            a, b = max(a, m0), min(b, m1)
        if b > a:
            clipped.append((a, -b, mid))
    clipped.sort()
    out = []
    stack = []      # [end, metadata id, self ns]
    for a, nb, mid in clipped:
        b = -nb
        while stack and stack[-1][0] <= a:
            out.append((stack[-1][1], stack[-1][2]))
            stack.pop()
        if stack:
            b = min(b, stack[-1][0])
            stack[-1][2] -= b - a
        stack.append([b, mid, b - a])
    out.extend((mid, s) for _e, mid, s in stack)
    return out


def _marks(planes):
    found = {}
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        ids = {k: n for k, n in plane["event_name"].items()
               if n in (MARK_OPEN, MARK_CLOSE)}
        if not ids:
            continue
        for line in plane["lines"]:
            for mid, a, _b, _s in line.events:
                if mid in ids and ids[mid] not in found:
                    found[ids[mid]] = a
    return found.get(MARK_OPEN), found.get(MARK_CLOSE)


def _host_phases(planes, m0, m1):
    """[(phase, flow id, start ns, end ns, line)] of the program's
    ``fluvio/<phase>`` annotations that touch [m0, m1]."""
    out = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        names = {k: n[len(ANNOTATION_PREFIX):]
                 for k, n in plane["event_name"].items()
                 if n.startswith(ANNOTATION_PREFIX)}
        if not names:
            continue
        stat_name = plane["stat_name"]
        for line in plane["lines"]:
            for mid, a, b, stats in line.events:
                if mid not in names or (m0 is not None and (b < m0 or a > m1)):
                    continue
                phase, flow = names[mid], 0
                for raw in stats:
                    sid, val = _stat(raw)
                    key = stat_name.get(sid)
                    if isinstance(val, tuple):
                        val = stat_name.get(val[1], "")
                    if key == "flow_id":
                        flow = int(val or 0)
                    elif key == "phase" and val:
                        phase = str(val)    # a renamed phase (`_TimedPhase.rename`)
                out.append((phase, flow, a, b, line.name))
    return out


def reduce_xspace(raw: bytes, vocabulary, slice_phases=(),
                  chips: int = 1) -> dict | None:
    """-> busy_s, busy_s_by_device, devices, window_s, scope_s {scope:
    seconds}, unnamed_ops {operation: seconds}, idle_gaps {phase:
    seconds}, phases_seen; None when no operation ran on any of the
    cell's chips.

    It reads the planes of the cell's ``chips`` as
    `trace_reduce.reduce_profile` does: a chip that ran nothing counts as
    busy 0, and busy_s, scope_s and unnamed_ops are means over the chips
    (the average chip); idle gaps are chip 0's. ``vocabulary`` is the
    program's scope tuple; ``slice_phases`` the names of the slice's own
    phases, which label a gap before a chunk's (executor) phases do."""
    planes = parse_xspace(raw)
    m0, m1 = _marks(planes)
    scope_ns = defaultdict(float)
    unnamed_ns = defaultdict(float)
    names = device_planes(chips)
    intervals = {name: [] for name in names}
    for plane in planes:
        if plane["name"] not in intervals:
            continue
        iv = intervals[plane["name"]]
        scopes = _metadata_scopes(plane, vocabulary)
        for line in plane["lines"]:
            if line.name != OPS_LINE:
                continue
            events = line.events
            for mid, a, b, _s in events:
                if m0 is not None and m1 is not None:
                    a, b = max(a, m0), min(b, m1)
                if b > a:
                    iv.append((a, b))
            for mid, self_ns in _self_times(events, m0, m1):
                scope = scopes.get(mid)
                if scope is None:
                    name = plane["event_name"].get(mid, "?")
                    unnamed_ns[name.partition(" = ")[0][:48]] += self_ns
                else:
                    scope_ns[scope] += self_ns
    per_device = [_union(intervals[name]) for name in names]
    if not any(per_device):
        return None
    if m0 is None or m1 is None:
        m0 = min(d[0][0] for d in per_device if d)
        m1 = max(d[-1][1] for d in per_device if d)
    busy_by_device = [sum(b - a for a, b in d) for d in per_device]
    busy_ns = sum(busy_by_device) / chips

    # device-idle gaps of chip 0 under the host's own phases
    phases = _host_phases(planes, m0, m1)
    by_phase = defaultdict(float)
    for a, b in idle_gaps(per_device[0], m0, m1):
        for label, ns in _split_gap(a, b, phases, slice_phases):
            by_phase[label] += ns
    return {
        "busy_s": busy_ns / 1e9,
        "busy_s_by_device": [ns / 1e9 for ns in busy_by_device],
        "devices": chips,
        "window_s": (m1 - m0) / 1e9,
        "scope_s": {k: v / 1e9 / chips for k, v in scope_ns.items()},
        "unnamed_ops": {k: v / 1e9 / chips for k, v in sorted(
            unnamed_ns.items(), key=lambda kv: -kv[1])[:10]},
        "idle_gaps": {k: v / 1e9 for k, v in by_phase.items()},
        "phases_seen": sorted({p[0] for p in phases}),
    }


def _split_gap(a, b, phases, slice_phases):
    """Cut one device-idle gap at the edges of the host phases that touch
    it and give each piece to the phase that covers it: a slice phase
    before a chunk phase, the innermost (latest started) among equals;
    a piece under none is `UNNAMED`."""
    touching = [p for p in phases if p[2] < b and p[3] > a]
    cuts = sorted({a, b} | {min(max(t, a), b) for p in touching
                            for t in (p[2], p[3])})
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        if hi <= lo:
            continue
        best = None
        for phase, _flow, s, e, _line in touching:
            if s <= lo and e >= hi:
                rank = (phase in slice_phases, s)
                if best is None or rank > best[0]:
                    best = (rank, phase)
        yield (best[1] if best else UNNAMED), hi - lo


# -- a run's own trace -------------------------------------------------------

_CACHE: dict = {}


def _vocabulary():
    """The program's scope and slice-phase names, or None where the
    program has none (a parent commit): the readers then read nothing."""
    try:
        from fluvio_tpu.telemetry.flow import SLICE_PHASES
        from fluvio_tpu.telemetry.spans import DEVICE_SCOPES
    except ImportError:
        return None
    return DEVICE_SCOPES, SLICE_PHASES


# `trace_reduce` reads the file through `jax.profiler.ProfileData`, whose
# event times are whole nanoseconds; this reader keeps the XSpace's
# picoseconds. The two busy times of ONE file then differ by about half a
# nanosecond an event (42.9 us of 5.28 s over `ns-drain`'s 93,064 events:
# 8e-6 of the busy time), and by whole percents where the two reductions
# have drifted apart or the path names another run's file.
BUSY_TOLERANCE = 1e-3


def reduce_run(obs) -> dict | None:
    """The reduction of THIS run's trace, or None: no traced run, a
    program without scopes, or no file at the path the session's tracer
    reduced (`obs["trace"]["path"]`). That file is parsed and no other:
    nothing is looked for under the temporary directory, so another
    run's trace there, newer or not, is never opened.

    One guard stays, so that the two reductions of one file cannot drift
    apart unnoticed: None when this reduction's busy time and
    `trace_reduce`'s differ by more than `BUSY_TOLERANCE` (0.1 %) of the
    busy time. The limit is a share of the busy time and not a time per
    event, because it has to hold for a fixture of a dozen events and
    for a run of 93,064 alike, and needs no event count from either side:
    rounding reads 1e-5 of the busy time at the most, a fault percents."""
    t = obs.get("trace")
    vocab = _vocabulary()
    if not t or vocab is None:
        return None
    path = t.get("path")
    if not path or not os.path.isfile(path):
        return None
    chips = chips_of(obs)
    by_chips = _CACHE.setdefault((path, os.path.getmtime(path)), {})
    if chips not in by_chips:
        with open(path, "rb") as f:
            by_chips[chips] = reduce_xspace(f.read(), *vocab, chips=chips)
    r = by_chips[chips]
    if not r or abs(r["busy_s"] - t["busy_s"]) > BUSY_TOLERANCE * t["busy_s"]:
        return None
    return r


def device_scope_ms_per_mrec(obs, prefixes) -> float | None:
    """Busy time of the traced span under the scopes that start with one
    of ``prefixes``, scaled as `device_busy_ms_per_mrec` is."""
    r = reduce_run(obs)
    if not r or not r["scope_s"]:
        return None
    return per_mrec(obs, scope_seconds(r, prefixes))


def scope_seconds(r: dict, prefixes) -> float:
    return sum(s for scope, s in r["scope_s"].items()
               if scope.startswith(tuple(prefixes)))


def per_mrec(obs, seconds_in_span: float) -> float | None:
    """Scale seconds of the traced span as `device_busy_ms_per_mrec`
    does: share of the span x window seconds per million input records."""
    t = obs.get("trace")
    if not t or t["window_s"] <= 0 or not obs.get("records_in"):
        return None
    share = seconds_in_span / t["window_s"]
    return share * obs["window_s"] * 1e3 / (obs["records_in"] / 1e6)


# -- the program's own records of the window ---------------------------------


def _held_whole(ring, t_open: float) -> bool:
    """Did the ring keep everything that ended inside a window opened at
    ``t_open``? Yes when it never overwrote, or when its oldest item
    ended before the window opened."""
    if not ring.dropped:
        return True
    items = ring.recent(None)
    first = items[0].t_end if items else None
    return first is not None and first <= t_open


def window_flows(obs) -> list | None:
    """The slice flows (as dicts, with wall-positioned ``phases``) whose
    end lies in the window; None when the program records no such phases
    (a parent commit) or its ring did not hold the window whole."""
    from fluvio_tpu.telemetry import TELEMETRY

    if not _held_whole(TELEMETRY.flows, obs["t_open"]):
        return None
    flows = [
        f for f in TELEMETRY.flows_json()
        if "t_end" in f and obs["t_open"] <= f["t_end"] <= obs["t_close"]
    ]
    if not flows or not any("phases" in f for f in flows):
        return None
    return flows


def flow_phase_ms_per_mrec(obs, names) -> float | None:
    """Seconds the window's flows spent in the phases ``names``, per
    million input records."""
    flows = window_flows(obs)
    if flows is None or not obs.get("records_in"):
        return None
    ms = sum(f.get("phases_ms", {}).get(n, 0.0) for f in flows for n in names)
    return ms / (obs["records_in"] / 1e6)


# flow phases in which the serving task AWAITS (the loop runs other tasks
# meanwhile); every other served phase holds its thread
WAITING_PHASES = ("send", "ack_wait")


def flow_wait_ms_per_mrec(obs, names=WAITING_PHASES) -> float | None:
    """Seconds the window's flows spent in the awaiting phases ``names``
    and under NO working phase of any flow, per million input records.
    A wait only ends when its task is scheduled again: the last slice of
    a stream is acked while the consumer's next stream already holds the
    event loop (synchronous dispatches and result syncs of seconds), so
    its `ack_wait` runs on over that stream's work. That overlap is the
    loop being busy, not the consumer being slow, and is cut here."""
    from fluvio_tpu.telemetry import TELEMETRY

    flows = window_flows(obs)
    if flows is None or not obs.get("records_in"):
        return None
    working = _union([
        (start, start + secs)
        for f in TELEMETRY.flows_json()
        for name, start, secs in f.get("phases", ())
        if name not in WAITING_PHASES
    ])
    total = 0.0
    for f in flows:
        for name, start, secs in f["phases"]:
            if name in names:
                a, b = start, start + secs
                total += (b - a) - sum(
                    max(0.0, min(b, w1) - max(a, w0)) for w0, w1 in working
                )
    return total * 1e3 / (obs["records_in"] / 1e6)


def span_phase_ms_per_mrec(obs, name: str) -> float | None:
    """Seconds the window's dispatches (`BatchSpan`s) spent in the phase
    ``name``, per million input records; None where no span has it."""
    from fluvio_tpu.telemetry import TELEMETRY

    spans = obs.get("window_spans") or ()
    if (not obs.get("records_in") or not _held_whole(TELEMETRY.spans,
                                                     obs["t_open"])
            or not any(name in s["phases_ms"] for s in spans)):
        return None
    ms = sum(s["phases_ms"].get(name, 0.0) for s in spans)
    return ms / (obs["records_in"] / 1e6)


def unnamed_share(obs) -> float | None:
    """Share (%) of the window's wall under NO flow phase: the union of
    the phase intervals of every flow the ring holds, cut to the window.
    One serving task serves the stream, so the union is its named time."""
    from fluvio_tpu.telemetry import TELEMETRY

    if window_flows(obs) is None:
        return None
    t0, t1 = obs["t_open"], obs["t_close"]
    iv = []
    for f in TELEMETRY.flows_json():
        for _name, start, secs in f.get("phases", ()):
            a, b = max(start, t0), min(start + secs, t1)
            if b > a:
                iv.append((a, b))
    named = sum(b - a for a, b in _union(iv))
    return 100.0 * (1.0 - named / (t1 - t0))


if __name__ == "__main__":   # python3 xplane_scopes.py <file.xplane.pb> [chips]
    import json
    import sys

    vocab = _vocabulary()
    with open(sys.argv[1], "rb") as _f:
        print(json.dumps(reduce_xspace(
            _f.read(), *vocab,
            chips=int(sys.argv[2]) if len(sys.argv) > 2 else 1), indent=1))
