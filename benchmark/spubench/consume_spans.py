"""The consumer's side of the socket: readers of the program's records of
the responses it received (`TELEMETRY.consumes`, one `ConsumeFlow` a
response: ``consume_recv``, ``consume_decode``, ``consume_ack``), of the
slice flows' ``frame`` phase (the response's frame build, which left
``send``), and of the device-idle time under them in the run's own trace.

The benchmark's consumer shares the SPU's process and event loop
(`broker.py`), so its work runs inside the server's awaiting phases
(``send``, ``ack_wait``); these readers say how much of those is the
frame, the socket, and the consumer's decode and ack.

Every reader returns None where the program has no such record (a
parent commit, whose telemetry has no `CONSUME_PHASES`), as
`xplane_scopes._vocabulary` does, and never raises there.
"""

from __future__ import annotations

import os
from collections import defaultdict

from spubench import xplane_scopes as xs
from spubench.trace_reduce import (
    OPS_LINE, _union, device_planes, idle_gaps,
)

# the consumer's phases that hold the event loop; ``consume_recv``
# awaits the socket, as the server's ``send`` and ``ack_wait`` do
CONSUME_WORKING = ("consume_decode", "consume_ack")
AWAITING = xs.WAITING_PHASES + ("consume_recv",)


def consume_vocabulary():
    """The program's consumer phases, or None where it records none (or
    books the frame build inside ``send``): the readers then read
    nothing."""
    try:
        from fluvio_tpu.telemetry.flow import CONSUME_PHASES, SLICE_PHASES
    except ImportError:
        return None
    if "frame" not in SLICE_PHASES:
        return None
    return CONSUME_PHASES, SLICE_PHASES


def window_consumes(obs) -> list | None:
    """The consumer's records (as dicts, with wall-positioned ``phases``)
    whose end lies in the window; None without the records, or where the
    ring did not hold the window whole."""
    if consume_vocabulary() is None:
        return None
    from fluvio_tpu.telemetry import TELEMETRY

    if not xs._held_whole(TELEMETRY.consumes, obs["t_open"]):
        return None
    recs = [
        c for c in TELEMETRY.consumes_json()
        if "t_end" in c and obs["t_open"] <= c["t_end"] <= obs["t_close"]
    ]
    return recs or None


def frame_ms_per_mrec(obs) -> float | None:
    """The window's slice flows' ``frame`` phase, per million input
    records; None where no flow of the window has one."""
    if consume_vocabulary() is None:
        return None
    flows = xs.window_flows(obs)
    if not flows or not any("frame" in f.get("phases_ms", {}) for f in flows):
        return None
    return xs.flow_phase_ms_per_mrec(obs, ("frame",))


def consume_phase_ms_per_mrec(obs, names) -> float | None:
    """Seconds the window's consumer records spent in ``names``, per
    million input records."""
    recs = window_consumes(obs)
    if recs is None or not obs.get("records_in"):
        return None
    ms = sum(c.get("phases_ms", {}).get(n, 0.0) for c in recs for n in names)
    return ms / (obs["records_in"] / 1e6)


def consume_recv_net_ms_per_mrec(obs) -> float | None:
    """The window's ``consume_recv`` time under NO loop-holding phase of
    either side (slice phases other than ``send`` / ``ack_wait``, and
    ``consume_decode`` / ``consume_ack``), per million input records:
    the socket's own cost on the shared loop, netted as
    `xplane_scopes.flow_wait_ms_per_mrec` nets the server's waits."""
    from fluvio_tpu.telemetry import TELEMETRY

    recs = window_consumes(obs)
    if recs is None or not obs.get("records_in"):
        return None
    working = _union(
        [(start, start + secs)
         for f in TELEMETRY.flows_json()
         for name, start, secs in f.get("phases", ())
         if name not in xs.WAITING_PHASES]
        + [(start, start + secs)
           for c in TELEMETRY.consumes_json()
           for name, start, secs in c.get("phases", ())
           if name in CONSUME_WORKING]
    )
    total = 0.0
    for c in recs:
        for name, start, secs in c.get("phases", ()):
            if name == "consume_recv":
                a, b = start, start + secs
                total += (b - a) - sum(
                    max(0.0, min(b, w1) - max(a, w0)) for w0, w1 in working
                )
    return total * 1e3 / (obs["records_in"] / 1e6)


# -- device-idle time under the consumer's phases ----------------------------

_CACHE: dict = {}


def idle_by_phase(raw: bytes, program_phases, consume_phases) -> dict | None:
    """{phase: seconds} of chip 0's idle gaps (as
    `xplane_scopes.reduce_xspace` cuts them) under the program's
    ``fluvio/<phase>`` annotations, by this reader's own ranking: a piece
    goes to the covering phase that holds its thread before one that
    awaits (``send``, ``ack_wait``, ``consume_recv``); among those, to a
    phase of the program's slice or consumer vocabulary before a chunk
    phase; among awaiting ones, to the consumer's before the server's
    (the server awaits the consumer); then to the latest started. A
    piece under none is `xplane_scopes.UNNAMED`. None where no operation
    ran on chip 0."""
    planes = xs.parse_xspace(raw)
    m0, m1 = xs._marks(planes)
    (chip0,) = device_planes(1)
    iv = []
    for plane in planes:
        if plane["name"] != chip0:
            continue
        for line in plane["lines"]:
            if line.name != OPS_LINE:
                continue
            for _mid, a, b, _s in line.events:
                if m0 is not None and m1 is not None:
                    a, b = max(a, m0), min(b, m1)
                if b > a:
                    iv.append((a, b))
    if not iv:
        return None
    first = _union(iv)
    if m0 is None or m1 is None:
        m0, m1 = first[0][0], first[-1][1]
    gaps = idle_gaps(first, m0, m1)
    phases = xs._host_phases(planes, m0, m1)
    out = defaultdict(float)
    for a, b in gaps:
        touching = [p for p in phases if p[2] < b and p[3] > a]
        cuts = sorted({a, b} | {min(max(t, a), b) for p in touching
                                for t in (p[2], p[3])})
        for lo, hi in zip(cuts[:-1], cuts[1:]):
            if hi <= lo:
                continue
            best = None
            for phase, _flow, s, e, _line in touching:
                if s <= lo and e >= hi:
                    holds = phase not in AWAITING
                    rank = (holds, phase in program_phases,
                            not holds and phase in consume_phases, s)
                    if best is None or rank > best[0]:
                        best = (rank, phase)
            out[best[1] if best else xs.UNNAMED] += (hi - lo) / 1e9
    return dict(out)


def idle_consume_seconds(obs) -> float | None:
    """Device-idle seconds of the traced span that `idle_by_phase` gives
    to a ``consume_*`` phase; None without the vocabulary, without this
    run's trace (`xplane_scopes.reduce_run`'s guards), or where no
    consumer phase lies in the span."""
    vocab = consume_vocabulary()
    r = xs.reduce_run(obs) if vocab is not None else None
    if not r:
        return None
    consume_phases, slice_phases = vocab
    if not set(consume_phases) & set(r["phases_seen"]):
        return None
    path = obs["trace"]["path"]
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        with open(path, "rb") as f:
            _CACHE[key] = idle_by_phase(
                f.read(), tuple(slice_phases) + tuple(consume_phases),
                consume_phases)
    split = _CACHE[key]
    if split is None:
        return None
    return sum(split.get(p, 0.0) for p in consume_phases)


def idle_unnamed_share(obs) -> float | None:
    """% of the traced span's cut device-idle time (the gaps of at least
    `MIN_GAP_S`, `reduce_run(obs)["idle_gaps"]`) under no ``fluvio/``
    annotation; None without the consumer's vocabulary (a parent, where
    the consumer's time is unnamed by construction) or this run's trace."""
    if consume_vocabulary() is None:
        return None
    r = xs.reduce_run(obs)
    if not r:
        return None
    total = sum(r["idle_gaps"].values())
    if total <= 0:
        return None
    return 100.0 * r["idle_gaps"].get(xs.UNNAMED, 0.0) / total
