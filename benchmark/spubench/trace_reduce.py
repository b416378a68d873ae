"""From a `jax.profiler` trace to device busy time, top operations and
attributed idle gaps. Read with `jax.profiler.ProfileData` alone.

Busy is the union of the intervals in which an operation ran on the
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
over the devices; idle share is 1 - busy / window. The window is the
span between the two host markers the `Tracer` writes into the trace
(`MARK_OPEN`, `MARK_CLOSE`), so idle time before the first and after the
last operation counts. The markers also tie the trace's clock to
`time.perf_counter`, which lets a gap be attributed to the program phase
span that covers it on the host.
"""

from __future__ import annotations

import glob
import os
import re
import time
from collections import defaultdict

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MARK_OPEN = "spubench_trace_open"
MARK_CLOSE = "spubench_trace_close"
MIN_GAP_S = 50e-6   # shorter gaps between operations are not attributed
NAME_CHARS = 80     # an operation's name in `breakdown` is cut to this
TOP = 10


class Tracer:
    """Profiler trace of the second half of a traced run's window. The
    start is a timer on the event loop and not a response boundary: a
    cell whose responses are seconds apart is traced on time too."""

    def __init__(self, out_dir: str, enabled: bool):
        self.out_dir = out_dir
        self.enabled = enabled
        self.active = False
        self.t0 = self.t1 = None       # perf_counter at the two markers
        self._timer = None

    def arm(self, seconds: float) -> None:
        """The window opened: start profiling when half of it has passed."""
        if self.enabled:
            import asyncio

            self._timer = asyncio.get_running_loop().call_later(
                seconds / 2, self.start
            )

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # per-call Python events slow the host
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.active = True
        self.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARK_OPEN):
            pass

    def stop(self) -> None:
        import jax

        self.t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARK_CLOSE):
            pass
        jax.profiler.stop_trace()
        self.active = False

    def finish(self) -> None:
        """The window closed (or the run failed): end the trace."""
        if self._timer is not None:
            self._timer.cancel()
        if self.active:
            self.stop()

    def reduce(self, host_spans=()) -> dict | None:
        if not self.enabled or self.t1 is None:
            return None
        paths = glob.glob(
            os.path.join(self.out_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
        if not paths:
            return None
        import jax

        path = max(paths, key=os.path.getmtime)
        reduced = reduce_profile(
            jax.profiler.ProfileData.from_file(path), (self.t0, self.t1),
            host_spans,
        )
        if reduced is not None:
            # the file this run wrote: `xplane_scopes.reduce_run` reads
            # the scopes from it and from no other
            reduced["path"] = path
        return reduced


def short_name(hlo: str) -> str:
    """The trace names a TPU operation by its whole HLO text. Keep the
    result name and what follows it with the layouts (`{...}`) taken out,
    cut to `NAME_CHARS`: `%fusion.136 u8[2621440] fusion(u8[2621440] ...`."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:NAME_CHARS]
    return f"{head} {re.sub(r'[{][^{}]*[}]', '', rest)}"[:NAME_CHARS]


def _union(intervals):
    """Merged, sorted copy of [(start, end)]."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _find_marks(data):
    """Trace-clock ns of the two host markers, or (None, None)."""
    found = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in (MARK_OPEN, MARK_CLOSE) and e.name not in found:
                    found[e.name] = e.start_ns
    return found.get(MARK_OPEN), found.get(MARK_CLOSE)


def reduce_profile(data, host_window=None, host_spans=()) -> dict | None:
    """-> busy_s, window_s, device_ops, idle_gaps; None when no operation
    ran on a device plane (a reader then has nothing to read).

    ``host_window`` is (perf_counter at MARK_OPEN, at MARK_CLOSE);
    ``host_spans`` is [(label, start, end)] on the perf_counter clock."""
    m0, m1 = _find_marks(data)
    per_device = []
    op_seconds = defaultdict(float)
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        iv = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if m0 is not None and m1 is not None:
                    a, b = max(a, m0), min(b, m1)
                    if b <= a:
                        continue
                iv.append((a, b))
                op_seconds[short_name(e.name)] += (b - a) / 1e9
        if iv:
            per_device.append(_union(iv))
    if not per_device:
        return None
    if m0 is None or m1 is None:
        # no markers (a trace not written by `Tracer`): the window is the
        # span of the operations themselves
        m0 = min(d[0][0] for d in per_device)
        m1 = max(d[-1][1] for d in per_device)
    window_s = (m1 - m0) / 1e9
    busy_s = sum(
        sum(b - a for a, b in d) for d in per_device
    ) / 1e9 / len(per_device)

    # idle gaps of the first device, attributed on the host clock
    gaps = []
    edge = m0
    for a, b in per_device[0]:
        if a - edge >= MIN_GAP_S * 1e9:
            gaps.append((edge, a))
        edge = max(edge, b)
    if m1 - edge >= MIN_GAP_S * 1e9:
        gaps.append((edge, m1))
    by_label = defaultdict(float)
    for a, b in gaps:
        by_label[_label(a, b, m0, host_window, host_spans)] += (b - a) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "devices": len(per_device),
        "device_ops": _top(op_seconds),
        "idle_gaps": _top(by_label),
    }


def _top(table) -> list:
    return [
        [k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
    ]


def _label(a_ns, b_ns, m0_ns, host_window, host_spans) -> str:
    """The host span that covers most of a device-idle gap."""
    if host_window is None or not host_spans:
        return "unattributed"
    a = host_window[0] + (a_ns - m0_ns) / 1e9
    b = host_window[0] + (b_ns - m0_ns) / 1e9
    cover = defaultdict(float)
    for label, s0, s1 in host_spans:
        ov = min(b, s1) - max(a, s0)
        if ov > 0:
            cover[label] += ov
    if not cover:
        return "outside-executor-spans"
    label, ov = max(cover.items(), key=lambda kv: kv[1])
    return label if ov >= 0.5 * (b - a) else "outside-executor-spans"
