"""Concurrency correctness pass: differential + regression suite.

Three halves, mirroring the PR-6 pattern of pinning static predictions
to runtime truth:

1. **Injected-hazard differential** — every FLV2xx rule must catch its
   hazard class on synthetic sources fed through
   ``analysis.concurrency.analyze_sources`` (unguarded write, missing
   guard read, lock-order cycle, IO-under-lock, dispatch-under-lock,
   implicit-D2H in dispatch-hot code), and ``# noqa`` must suppress.
2. **Runtime-vs-static lock graph** — `analysis.lockwatch` records the
   REAL acquisition orders while a live engine workload runs with
   ``FLUVIO_LOCKWATCH=assert``; the observed edge set must stay inside
   the statically predicted graph and acyclic.
3. **Targeted regressions** for the shared-state fixes this pass
   surfaced: `_BoundedRing` counter reads under concurrent push, trace
   sink rotation racing appends, metering abandoned-set bookkeeping.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from fluvio_tpu.analysis import lockwatch
from fluvio_tpu.analysis.concurrency import (
    RULES,
    analyze_package,
    analyze_sources,
    static_lock_graph,
)
from fluvio_tpu.analysis.lockwatch import (
    LockOrderViolation,
    find_cycle,
    make_lock,
    observed_edges,
    reset_observations,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(report):
    return [f.code for f in report.findings]


# ---------------------------------------------------------------------------
# The repo gate: the package itself must analyze clean
# ---------------------------------------------------------------------------


def test_package_has_no_concurrency_errors():
    """ISSUE-7 acceptance: `fluvio-tpu analyze --concurrency` exits
    clean on the repo after fixes. Any ERROR-severity FLV2xx finding in
    fluvio_tpu/ fails tier-1 here."""
    report = analyze_package()
    assert not report.errors(), "\n".join(str(f) for f in report.errors())
    assert not report.cycles, report.cycles


def test_static_graph_is_acyclic_and_canonically_named():
    edges = static_lock_graph()
    assert find_cycle(edges) is None
    # the registry snapshot no longer nests ring/memory reads under the
    # registry lock (ISSUE-20 moved them outside to keep the memory
    # ledger ordering flat), so telemetry.registry -> telemetry.ring is
    # gone; the surviving nested acquisitions are the rebalancer tick
    # booking telemetry and the timeseries tick publishing gauges
    assert ("telemetry.registry", "telemetry.ring") not in edges
    assert ("partition.rebalancer", "telemetry.registry") in edges
    assert ("telemetry.timeseries", "telemetry.registry") in edges
    # the memory ledger publishes gauges OUTSIDE its own lock by design:
    # no telemetry.memory -> telemetry.registry edge may ever appear
    assert all(src != "telemetry.memory" for src, _ in edges)


# ---------------------------------------------------------------------------
# Injected-hazard differential (ISSUE-7 acceptance: >= 6 patterns)
# ---------------------------------------------------------------------------


_THREADED_MODULE = """\
import threading
_lock = threading.Lock()
_cache = {}

def worker():
    with _lock:
        _cache["a"] = 1
    refresh()
    peek()

def refresh():
    _cache["b"] = 2

def peek():
    return len(_cache)

def spawn():
    t = threading.Thread(target=worker)
    t.start()
"""


def test_injected_unguarded_write_flags_flv201():
    report = analyze_sources({"mod": _THREADED_MODULE})
    hits = [f for f in report.findings if f.code == "FLV201"]
    assert hits and hits[0].line == 12, _codes(report)
    assert "_cache" in hits[0].message and "_lock" in hits[0].message


def test_injected_missing_guard_read_flags_flv202():
    report = analyze_sources({"mod": _THREADED_MODULE})
    hits = [f for f in report.findings if f.code == "FLV202"]
    assert any(f.line == 15 for f in hits), _codes(report)


def test_guarded_module_is_clean():
    src = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "_cache = {}\n"
        "\n"
        "def worker():\n"
        "    with _lock:\n"
        "        _cache['a'] = 1\n"
        "        n = len(_cache)\n"
        "    return n\n"
        "\n"
        "def spawn():\n"
        "    threading.Thread(target=worker).start()\n"
    )
    report = analyze_sources({"mod": src})
    assert not report.findings, _codes(report)


def test_injected_lock_order_cycle_flags_flv211():
    src = (
        "import threading\n"
        "_a = threading.Lock()\n"
        "_b = threading.Lock()\n"
        "\n"
        "def f():\n"
        "    with _a:\n"
        "        with _b:\n"
        "            pass\n"
        "\n"
        "def g():\n"
        "    with _b:\n"
        "        with _a:\n"
        "            pass\n"
    )
    report = analyze_sources({"mod": src})
    assert "FLV211" in _codes(report)
    assert report.cycles and set(report.cycles[0]) == {"mod._a", "mod._b"}
    # both directions land in the edge set the runtime arm compares to
    assert {("mod._a", "mod._b"), ("mod._b", "mod._a")} <= report.edge_set()


def test_two_independent_cycles_both_reported():
    """Regression: analyze() must surface EVERY lock-order cycle in one
    run, not the first one found — otherwise fixing the reported cycle
    just re-reddens CI on the next."""
    src = (
        "import threading\n"
        "_a = threading.Lock()\n"
        "_b = threading.Lock()\n"
        "_c = threading.Lock()\n"
        "_d = threading.Lock()\n"
        "\n"
        "def f():\n"
        "    with _a:\n"
        "        with _b:\n"
        "            pass\n"
        "\n"
        "def g():\n"
        "    with _b:\n"
        "        with _a:\n"
        "            pass\n"
        "\n"
        "def h():\n"
        "    with _c:\n"
        "        with _d:\n"
        "            pass\n"
        "\n"
        "def k():\n"
        "    with _d:\n"
        "        with _c:\n"
        "            pass\n"
    )
    report = analyze_sources({"mod": src})
    assert len(report.cycles) == 2, report.cycles
    assert {frozenset(c) for c in report.cycles} == {
        frozenset({"mod._a", "mod._b"}),
        frozenset({"mod._c", "mod._d"}),
    }
    assert sum(1 for f in report.findings if f.code == "FLV211") == 2


def test_injected_io_under_lock_flags_flv212():
    src = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "\n"
        "def dump(path, data):\n"
        "    with _lock:\n"
        "        with open(path, 'w') as f:\n"
        "            f.write(data)\n"
    )
    report = analyze_sources({"mod": src})
    assert "FLV212" in _codes(report)


def test_io_designated_lock_exempt_from_flv212():
    """Locks named `*.io` / `*.build` exist to serialize IO — that is
    their documented job (the trace sink, the native g++ builds)."""
    src = (
        "from fluvio_tpu.analysis.lockwatch import make_lock\n"
        "_lock = make_lock('sink.io')\n"
        "\n"
        "def dump(path, data):\n"
        "    with _lock:\n"
        "        with open(path, 'w') as f:\n"
        "            f.write(data)\n"
    )
    report = analyze_sources({"mod": src})
    assert "FLV212" not in _codes(report)


def test_injected_jax_dispatch_under_lock_flags_flv213():
    src = (
        "import threading\n"
        "import jax.numpy as jnp\n"
        "_lock = threading.Lock()\n"
        "\n"
        "def agg(x):\n"
        "    with _lock:\n"
        "        return jnp.sum(x)\n"
    )
    report = analyze_sources({"mod": src})
    assert "FLV213" in _codes(report)


def test_injected_transitive_hazard_through_callee():
    """Holding a lock across a CALL into IO is the same hazard one
    level removed — the may-hazard fixpoint must see through it."""
    src = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "\n"
        "def _flush(path, data):\n"
        "    with open(path, 'w') as f:\n"
        "        f.write(data)\n"
        "\n"
        "def dump(path, data):\n"
        "    with _lock:\n"
        "        _flush(path, data)\n"
    )
    report = analyze_sources({"mod": src})
    hits = [f for f in report.findings if f.code == "FLV212"]
    assert any("_flush" in f.message for f in hits), _codes(report)


def test_injected_implicit_d2h_flags_flv214():
    """The transfer-guard violation, statically: materializing a jit
    result inside a dispatch-side hot function."""
    src = (
        "import numpy as np\n"
        "\n"
        "def _dispatch(buf, _jitted):\n"
        "    out = _jitted(buf)\n"
        "    n = int(out)\n"
        "    return np.asarray(out), n\n"
    )
    report = analyze_sources(
        {"smartengine.tpu.executor": src},
        paths={
            "smartengine.tpu.executor":
                "fluvio_tpu/smartengine/tpu/executor.py"
        },
    )
    assert _codes(report) == ["FLV214", "FLV214"]
    # the same source outside a dispatch-hot context is not flagged
    clean = analyze_sources({"mod": src})
    assert "FLV214" not in _codes(clean)


def test_noqa_suppresses_and_rule_table_is_complete():
    suppressed = _THREADED_MODULE.replace(
        '    _cache["b"] = 2', '    _cache["b"] = 2  # noqa: FLV201'
    )
    report = analyze_sources({"mod": suppressed})
    assert "FLV201" not in _codes(report)
    assert {"FLV201", "FLV202", "FLV211", "FLV212", "FLV213",
            "FLV214"} <= set(RULES)


# ---------------------------------------------------------------------------
# LockWatch runtime arm
# ---------------------------------------------------------------------------


class TestLockWatch:
    def test_disabled_returns_plain_lock(self, monkeypatch):
        """The zero-cost contract: unarmed, `make_lock` returns a PLAIN
        threading primitive — no wrapper, no subclass, nothing per
        acquire (the overhead gate pins the same seam)."""
        monkeypatch.delenv("FLUVIO_LOCKWATCH", raising=False)
        assert type(make_lock("x")) is type(threading.Lock())
        assert isinstance(make_lock("x", rlock=True),
                          type(threading.RLock()))
        assert not lockwatch.enabled()

    def test_record_mode_observes_nesting_order(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_LOCKWATCH", "record")
        reset_observations()
        try:
            a = make_lock("t.alpha")
            b = make_lock("t.beta")
            with a:
                with b:
                    pass
            assert ("t.alpha", "t.beta") in observed_edges()
            assert ("t.beta", "t.alpha") not in observed_edges()
            assert {"t.alpha", "t.beta"} <= lockwatch.observed_locks()
        finally:
            reset_observations()

    def test_reentrant_acquire_records_no_self_edge(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_LOCKWATCH", "record")
        reset_observations()
        try:
            r = make_lock("t.re", rlock=True)
            with r:
                with r:
                    pass
            assert ("t.re", "t.re") not in observed_edges()
        finally:
            reset_observations()

    def test_same_name_distinct_instances_record_self_edge(
        self, monkeypatch
    ):
        """Regression: re-entry is per lock INSTANCE. Two distinct
        locks sharing a canonical name (per-chain metrics locks) are
        NOT re-entry — nesting them is an ambiguous-order ABBA hazard
        (another thread can nest the instances the other way round and
        nothing distinguishes them), recorded as a (name, name)
        self-edge that assert mode raises on."""
        monkeypatch.setenv("FLUVIO_LOCKWATCH", "record")
        reset_observations()
        try:
            a = make_lock("t.chain_metrics")
            b = make_lock("t.chain_metrics")
            with a:
                with b:
                    pass
            assert (
                "t.chain_metrics", "t.chain_metrics"
            ) in observed_edges()
        finally:
            reset_observations()
        monkeypatch.setenv("FLUVIO_LOCKWATCH", "assert")
        reset_observations()
        try:
            c = make_lock("t.chain_metrics2")
            d = make_lock("t.chain_metrics2")
            with c:
                with pytest.raises(LockOrderViolation) as exc:
                    d.acquire()
            assert exc.value.cycle == ["t.chain_metrics2"]
        finally:
            reset_observations()

    def test_assert_mode_raises_on_observed_cycle(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_LOCKWATCH", "assert")
        reset_observations()
        try:
            a = make_lock("t.c1")
            b = make_lock("t.c2")
            with a:
                with b:
                    pass
            with b:
                with pytest.raises(LockOrderViolation) as exc:
                    a.acquire()
            assert set(exc.value.cycle) == {"t.c1", "t.c2"}
            # the violating acquisition must NOT leak the lock held —
            # a raise out of __enter__ never runs __exit__
            assert a.acquire(blocking=False)
            a.release()
        finally:
            reset_observations()

    def test_assert_mode_stale_cycle_does_not_poison_unrelated(
        self, monkeypatch
    ):
        """Regression: a raised-and-caught violation leaves its cycle
        edges in the process-global store. Later correctly-ordered
        nested acquisitions of UNRELATED locks must not re-raise
        against that stale cycle — only an acquisition whose OWN new
        edges close a cycle raises (and the original offending order
        keeps raising every time)."""
        monkeypatch.setenv("FLUVIO_LOCKWATCH", "assert")
        reset_observations()
        try:
            a = make_lock("t.s1")
            b = make_lock("t.s2")
            with a:
                with b:
                    pass
            with b:
                with pytest.raises(LockOrderViolation):
                    a.acquire()
            # the poisoned store must not leak onto innocent nesting
            c = make_lock("t.s3")
            d = make_lock("t.s4")
            with c:
                with d:
                    pass
            # nesting into the tainted graph in a consistent order is
            # also innocent (no cycle through the edge it adds)
            with c:
                with a:
                    pass
            # but the genuinely inverted order still raises every time
            with b:
                with pytest.raises(LockOrderViolation) as exc:
                    a.acquire()
            assert set(exc.value.cycle) == {"t.s1", "t.s2"}
        finally:
            reset_observations()

    def test_find_cycle(self):
        assert find_cycle({("a", "b"), ("b", "c")}) is None
        cyc = find_cycle({("a", "b"), ("b", "c"), ("c", "a")})
        assert cyc is not None and set(cyc) == {"a", "b", "c"}


_WORKLOAD = """\
import json
from fluvio_tpu.analysis import lockwatch
from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.telemetry import TELEMETRY, render_prometheus, trace_json

b = SmartEngine(backend="tpu").builder()
for name, params in (("regex-filter", {"regex": "fluvio"}),
                     ("json-map", {"field": "name"})):
    b.add_smart_module(SmartModuleConfig(params=params), lookup(name))
chain = b.initialize()
assert chain.backend_in_use == "tpu"
records = [Record(value=f'{{"name":"fluvio-{i}","n":{i}}}'.encode())
           for i in range(256)]
for i, r in enumerate(records):
    r.offset_delta = i
buf = RecordBuffer.from_records(records)
for out in chain.tpu_chain.process_stream(iter([buf] * 3)):
    pass
render_prometheus()
trace_json()
snap = TELEMETRY.snapshot()
assert snap["spans_total"] == 3, snap["spans_total"]
print(json.dumps({
    "edges": sorted(list(e) for e in lockwatch.observed_edges()),
    "locks": sorted(lockwatch.observed_locks()),
}))
"""


def test_runtime_lock_graph_matches_static_prediction(tmp_path):
    """The ISSUE-7 differential: a live engine workload run with
    ``FLUVIO_LOCKWATCH=assert`` (armed at process start so module-level
    locks are watched) must observe only acquisition-order edges the
    static analyzer predicted — and the assert mode itself proves the
    observed graph never closed a cycle."""
    script = tmp_path / "workload.py"
    script.write_text(_WORKLOAD)
    env = dict(os.environ)
    env.update({
        "FLUVIO_LOCKWATCH": "assert",
        "JAX_PLATFORMS": "cpu",
        "FLUVIO_TELEMETRY": "1",
        "PYTHONPATH": _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    })
    proc = subprocess.run(
        [sys.executable, str(script)],
        cwd=_REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    observed = json.loads(proc.stdout.strip().splitlines()[-1])
    observed_set = {tuple(e) for e in observed["edges"]}
    predicted = static_lock_graph()
    assert observed_set <= predicted, (
        f"runtime observed acquisition orders the static graph misses: "
        f"{sorted(observed_set - predicted)}"
    )
    # the watched locks carry the canonical make_lock names the static
    # pass keys its graph on — one shared vocabulary by construction
    static_names = set(analyze_package().locks)
    assert set(observed["locks"]) <= static_names
    assert {"telemetry.registry", "telemetry.ring"} <= set(observed["locks"])


# ---------------------------------------------------------------------------
# Targeted regressions for the fixes this pass surfaced
# ---------------------------------------------------------------------------


def test_consume_ring_adds_no_lock_and_no_nesting():
    """The consumer's response records (`TELEMETRY.consumes`) are one
    more `FlowRing`: its lock is the canonical ``telemetry.ring``, the
    registry lock only guards the shared flow sequence, and the ring
    push happens outside it (no registry -> ring edge)."""
    import fluvio_tpu.transport.multiplexing  # noqa: F401 — the new seam

    names = set(analyze_package().locks)
    assert {"telemetry.registry", "telemetry.ring"} <= names
    assert not [n for n in names if "consume" in n or "transport" in n]
    assert ("telemetry.registry", "telemetry.ring") not in static_lock_graph()


def test_admission_locks_in_static_vocabulary():
    """ISSUE-11: the admission layer's locks are created via make_lock
    under canonical names, so the FLV2xx analyzer's graph covers them
    (and the runtime lockwatch differential keys on the same
    vocabulary). Importing the package must register all four."""
    import fluvio_tpu.admission  # noqa: F401 — lock creation side effect

    names = set(analyze_package().locks)
    assert {
        "admission.controller",
        "admission.fairness",
        "admission.batcher",
        "admission.gate",
    } <= names, sorted(n for n in names if "admission" in n)


def test_admission_layer_is_flv2xx_clean():
    """The lock-discipline pass over the whole package (admission
    included) must stay free of ERROR findings — no dispatch or user
    hook under an admission lock, no unguarded shared writes."""
    report = analyze_package()
    errs = [
        f for f in report.errors() if "admission" in (f.path or "")
    ]
    assert not errs, [str(e) for e in errs]


def test_bounded_ring_counters_consistent_under_concurrent_push():
    """Regression: `_BoundedRing.total`/`dropped`/`__len__` used to read
    `_next` unlocked — a scrape racing a push could observe torn
    bookkeeping. Locked reads must stay monotone and in-bounds while
    writers hammer the ring."""
    from fluvio_tpu.telemetry.spans import _BoundedRing

    ring = _BoundedRing(capacity=64)
    n_threads, pushes_each = 4, 2000
    stop = threading.Event()
    failures = []

    def reader():
        last_total = 0
        while not stop.is_set():
            # the single-acquisition triple: exact reconciliation must
            # hold at EVERY instant, not just at quiesce
            total, retained, dropped = ring.stats()
            if total != retained + dropped:
                failures.append(
                    f"torn stats: {total} != {retained}+{dropped}"
                )
            if total < last_total:
                failures.append(f"total went backwards: {total}<{last_total}")
            if retained > ring.capacity:
                failures.append(f"len {retained} > capacity")
            last_total = total
            # the per-property reads stay internally consistent too
            # (dropped before total: both monotone)
            dropped = ring.dropped
            if dropped > ring.total:
                failures.append("property reads inconsistent")

    def writer():
        for i in range(pushes_each):
            ring.push(i)

    threads = [threading.Thread(target=writer) for _ in range(n_threads)]
    watcher = threading.Thread(target=reader)
    watcher.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    watcher.join()
    assert not failures, failures[:5]
    total = n_threads * pushes_each
    assert ring.total == total
    assert len(ring) == ring.capacity
    assert ring.dropped == total - ring.capacity


def test_trace_sink_rotation_racing_concurrent_appends(tmp_path):
    """Regression: the sink's lock serializes append vs flush vs
    rotation (its designated-IO job) — concurrent spans forcing
    rotations must never tear the JSON document or lose the close
    bracket."""
    from fluvio_tpu.telemetry.spans import BatchSpan
    from fluvio_tpu.telemetry.trace import TraceFileSink

    path = tmp_path / "race.json"
    sink = TraceFileSink(str(path), max_bytes=1)  # floors to 4096: rotate often
    sink.FLUSH_INTERVAL_S = 0.0
    sink.BATCH_EVENTS = 1
    errors = []

    def emit(tid):
        try:
            for i in range(40):
                span = BatchSpan(path="fused")
                span.add("stage", 0.001)
                span.add("device", 0.002)
                span.records = tid * 1000 + i
                span.t_end = span.t0 + 0.004
                sink.on_span(span)
        except Exception as e:  # noqa: BLE001 — surfaced to the assert
            errors.append(repr(e))

    threads = [threading.Thread(target=emit, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    assert not errors, errors[:3]
    # the final write may have rotated the live file aside with nothing
    # pushed after it — whichever generations exist must be valid JSON
    generations = [p for p in (path, tmp_path / "race.json.1") if p.exists()]
    assert generations
    for p in generations:
        doc = json.loads(p.read_text())
        assert isinstance(doc, list) and doc


def test_metering_abandoned_bookkeeping_consistent_under_races():
    """Regression: the abandoned-hook registry prunes dead threads and
    counts live ones under one lock; concurrent registration, pruning,
    and quarantine_state scrapes must reconcile exactly at quiesce."""
    from fluvio_tpu.smartengine import metering

    with metering._abandoned_lock:
        metering._abandoned_by_module.clear()
    release = threading.Event()
    spinners = []

    def register(key, n):
        for _ in range(n):
            t = threading.Thread(target=release.wait, daemon=True)
            t.start()
            spinners.append(t)
            with metering._abandoned_lock:
                metering._abandoned_by_module.setdefault(key, []).append(t)
            metering.quarantine_state()  # racing scrape + prune

    workers = [
        threading.Thread(target=register, args=(f"mod{k}", 3))
        for k in range(3)
    ]
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        state = metering.quarantine_state()
        assert state["abandoned_hook_threads"] == 9
        assert state["by_module"] == {f"mod{k}": 3 for k in range(3)}
        assert not state["process_circuit_broken"]
    finally:
        release.set()
        for t in spinners:
            t.join(timeout=5)
    # all spinners dead -> the prune pass must empty the registry
    state = metering.quarantine_state()
    assert state["abandoned_hook_threads"] == 0
    assert state["by_module"] == {}
