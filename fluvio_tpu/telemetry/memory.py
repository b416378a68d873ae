"""Device-memory accounting plane: the per-owner HBM ledger.

HBM is home to far more than staged batches — partition carry banks,
window state banks and their pow2 emit buffers, per-shard staging,
the compiled-executable cache — yet before this
module the only accounting was one gauge bumped at one executor seam.
The :class:`MemoryLedger` is the join: every allocation seam books
``acquire(owner, key, nbytes)`` when bytes land on the device and
``release(key)`` when they retire, under a typed owner vocabulary, so
the engine always knows *who owns device memory, when it leaks, and
how much headroom is left* before the allocator finds out the hard
way. Like the link byte counters and the exactness pins, the ledger is
hardware-independent evidence: the same arrays stage on CPU and on the
real chip, so the balance invariants stay trustworthy while the chip
is unreachable.

Three consumers sit on top:

- **gauges**: every acquire/release republishes the flat gauges
  (``device_memory_bytes``, ``device_memory_peak_bytes``) plus the
  compatibility aliases ``hbm_staged_bytes`` (the staged-batch +
  shard-staging sum — the pre-ledger gauge folded in so it
  cannot drift from the ledger) and ``window_state_bytes`` (the
  ``window_bank`` owner). Per-owner byte totals export through the
  snapshot ``memory`` section and the Prometheus
  ``fluvio_device_memory_bytes{owner=...}`` family.
- **leak detection**: entries older than ``FLUVIO_MEM_LEAK_TTL_S``
  with no release are flagged ONCE — a ``mem-leak`` flight-recorder
  instant event plus the always-on ``memory_leaks_total{owner}``
  counter — and ``assert_drained()`` pins quiesce: transient owners
  must be zero after every drain (the chaos suites' standing
  invariant).
- **headroom shedding**: the ``hbm_headroom`` SLO rule windows
  ``device_memory_bytes`` against the ``FLUVIO_MEM_BUDGET`` ceiling,
  so a runaway window bank sheds new work through the admission
  controller's typed ``Rejected`` declines *before* an OOM kills the
  process — the same control loop ``consumer_lag`` closes for
  backlogs.

Zero-cost contract: the executor/partition/window seams route through
``TELEMETRY.mem_acquire``/``mem_release``, which are one ``enabled``
check when capture is off. The ``window_bank`` owner is the deliberate
exception (`note_window_bank` books ALWAYS, once per batch): state
size is exactness evidence like the delta byte counters, not
observability sugar — but gauge publication stays gated either way.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from fluvio_tpu.analysis.envreg import env_float, env_int
from fluvio_tpu.analysis.lockwatch import make_lock
from fluvio_tpu.telemetry.registry import TELEMETRY, PipelineTelemetry

#: the typed owner vocabulary — acquire() rejects anything else so a
#: typo'd owner fails loudly instead of minting an unbalanced class
OWNERS = (
    "staged_batch",   # single-device staged dispatch (flat + lengths + keys)
    "carry_bank",     # partition runtimes' device-resident aggregate carries
    "window_bank",    # WindowStateBank device arrays (sums/counts/meta)
    "emit_buffer",    # pow2-bucketed window emit/resync fetch buffers
    "shard_staging",  # sharded per-shard staged dispatch
    "compile_cache",  # resident compiled-executable estimates
)

#: owners that must drain to zero at quiesce — batch-scoped
#: allocations whose acquire/release pairs bracket one dispatch.
#: carry/window banks and the compile cache legitimately persist
#: across batches, so assert_drained() exempts them.
TRANSIENT_OWNERS = (
    "staged_batch", "emit_buffer", "shard_staging",
)

#: the SLO rule family this ledger feeds (the memory CLI's breach gate
#: and the socket ``memory`` document filter on exactly this)
MEM_RULES = ("hbm_headroom",)

BUDGET_ENV = "FLUVIO_MEM_BUDGET"
LEAK_TTL_ENV = "FLUVIO_MEM_LEAK_TTL_S"
SAMPLE_ENV = "FLUVIO_MEM_SAMPLE_S"


def budget_bytes(env: Optional[dict] = None) -> int:
    """The HBM ledger ceiling (0 = no budget, headroom rule off)."""
    return int(env_int(BUDGET_ENV, env) or 0)


def leak_ttl_s(env: Optional[dict] = None) -> float:
    return float(env_float(LEAK_TTL_ENV, env))


def sample_interval_s(env: Optional[dict] = None) -> float:
    return float(env_float(SAMPLE_ENV, env))


class MemoryLedger:
    """Per-owner device-memory ledger with leak detection and
    high-watermark tracking. One lock; every public read/write is one
    short critical section, and gauge publication happens OUTSIDE the
    ledger lock (registry-lock ordering mirrors the lag engine)."""

    def __init__(
        self,
        telemetry: Optional[PipelineTelemetry] = None,
        clock=time.monotonic,
    ) -> None:
        self.telemetry = telemetry if telemetry is not None else TELEMETRY
        self.clock = clock
        self._lock = make_lock("telemetry.memory")
        # key -> [owner, nbytes, t_acquire, leak_flagged]
        self._entries: Dict[object, list] = {}
        self._by_owner: Dict[str, int] = {o: 0 for o in OWNERS}
        self._peak = 0          # process-lifetime high watermark
        self._config_peak = 0   # bench per-config watermark (reset_peak)
        self._last_sample_t: Optional[float] = None
        self._reconcile: Dict[str, object] = {}

    # -- the ledger ----------------------------------------------------------

    def acquire(self, owner: str, key, nbytes: int) -> None:
        """Book ``nbytes`` of device memory under ``owner``. Re-acquire
        of a live key is a RESIZE (the old booking retires atomically),
        so growth paths (bank migration, retry re-staging) stay
        balanced without explicit release-then-acquire races."""
        if owner not in self._by_owner:
            raise ValueError(
                f"unknown memory owner {owner!r} (known: {OWNERS})"
            )
        nbytes = max(int(nbytes), 0)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._by_owner[old[0]] -= old[1]
            self._entries[key] = [owner, nbytes, self.clock(), False]
            self._by_owner[owner] += nbytes
            total = sum(self._by_owner.values())
            if total > self._peak:
                self._peak = total
            if total > self._config_peak:
                self._config_peak = total
        self._publish()

    def release(self, key) -> None:
        """Idempotent: finish and discard may both see a handle on the
        recovery ladders — only the first release moves the ledger."""
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                return
            self._by_owner[entry[0]] -= entry[1]
        self._publish()

    def _publish(self) -> None:
        """Republish the flat gauges from the current owner totals.
        Values snapshot under the ledger lock; gauge_set runs after
        release so the ledger never holds two locks at once."""
        t = self.telemetry
        if not t.enabled:
            return
        with self._lock:
            by = self._by_owner
            total = sum(by.values())
            staged = by["staged_batch"] + by["shard_staging"]
            window = by["window_bank"]
            peak = self._peak
        t.gauge_set("device_memory_bytes", float(total))
        t.gauge_set("device_memory_peak_bytes", float(peak))
        t.gauge_set("hbm_staged_bytes", float(staged))
        t.gauge_set("window_state_bytes", float(window))

    # -- reads ---------------------------------------------------------------

    def owner_bytes(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._by_owner)

    def total_bytes(self) -> int:
        with self._lock:
            return sum(self._by_owner.values())

    def peak_bytes(self) -> int:
        with self._lock:
            return self._peak

    def config_peak_bytes(self) -> int:
        with self._lock:
            return self._config_peak

    def owner_entries(self) -> Dict[str, int]:
        """{owner: live entry count} — the snapshot/CLI occupancy view."""
        with self._lock:
            counts = {o: 0 for o in OWNERS}
            for owner, _, _, _ in self._entries.values():
                counts[owner] += 1
            return counts

    def leaked_entries(self) -> List[dict]:
        """Entries the TTL scan has flagged (still unreleased)."""
        now = self.clock()
        with self._lock:
            return [
                {
                    "owner": e[0],
                    "key": repr(k),
                    "bytes": e[1],
                    "age_s": round(now - e[2], 3),
                }
                for k, e in self._entries.items()
                if e[3]
            ]

    # -- leak detection ------------------------------------------------------

    def scan(self, now: Optional[float] = None) -> List[tuple]:
        """Flag every live TRANSIENT entry older than
        ``FLUVIO_MEM_LEAK_TTL_S`` ONCE: the always-on
        ``memory_leaks_total{owner}`` counter moves and a ``mem-leak``
        flight-recorder instant lands next to the batch spans that
        leaked it. Persistent owners (carry/window banks, compile
        cache) legitimately outlive any TTL on an idle engine, so only
        batch-scoped owners can leak — the same partition
        ``assert_drained`` draws. Returns the newly flagged entries as
        ``(owner, key, nbytes, age_s)``."""
        ttl = leak_ttl_s()
        if now is None:
            now = self.clock()
        flagged: List[tuple] = []
        with self._lock:
            for key, entry in self._entries.items():
                if (
                    entry[0] in TRANSIENT_OWNERS
                    and not entry[3]
                    and now - entry[2] >= ttl
                ):
                    entry[3] = True
                    flagged.append(
                        (entry[0], key, entry[1], now - entry[2])
                    )
        for owner, key, nbytes, age in flagged:
            self.telemetry.add_memory_leak(
                owner, f"{owner} {key!r} {nbytes}B unreleased {age:.1f}s"
            )
        return flagged

    def assert_drained(self) -> None:
        """Quiesce invariant: every transient owner must be zero (the
        chaos suites call this after every drain — a fault path that
        strands staged bytes fails HERE, not as a slow HBM leak)."""
        with self._lock:
            bad = {
                o: self._by_owner[o]
                for o in TRANSIENT_OWNERS
                if self._by_owner[o] != 0
            }
            held = [
                (e[0], repr(k), e[1])
                for k, e in self._entries.items()
                if e[0] in TRANSIENT_OWNERS
            ] if bad else []
        if bad:
            raise AssertionError(
                f"transient device-memory owners not drained: {bad}; "
                f"live entries: {held[:8]}"
            )

    # -- reconciliation ------------------------------------------------------

    def reconcile(self) -> Dict[str, object]:
        """Cross-check the ledger total against the jax backend's own
        allocator stats when the backend exposes them (TPU/GPU
        ``memory_stats``). The CPU backend exposes nothing — the doc
        says so honestly and the delta-pinned tests carry the evidence
        instead."""
        ledger = self.total_bytes()
        backend: Optional[int] = None
        try:
            import jax

            stats = jax.devices()[0].memory_stats()
            if stats:
                raw = stats.get("bytes_in_use")
                if raw is not None:
                    backend = int(raw)
        except Exception:  # noqa: BLE001 — reconciliation is best-effort
            backend = None
        if backend is None:
            doc: Dict[str, object] = {
                "ledger_bytes": ledger, "backend": "unavailable",
            }
        else:
            doc = {
                "ledger_bytes": ledger,
                "backend_bytes": backend,
                "unaccounted_bytes": backend - ledger,
            }
        with self._lock:
            self._reconcile = doc
        return doc

    def last_reconcile(self) -> Dict[str, object]:
        with self._lock:
            return dict(self._reconcile)

    # -- the pull sampler ----------------------------------------------------

    def sample(self) -> None:
        """Installed as ``TELEMETRY.mem_sampler``: the time-series tick
        and the Prometheus scrape both pull it (refresh_memory), so
        leak scans and reconciliation keep running while nothing is
        dispatching. Throttled to one real pass per
        ``FLUVIO_MEM_SAMPLE_S`` — the scan walks every live entry."""
        if not self.telemetry.enabled:
            return
        now = self.clock()
        with self._lock:
            interval = sample_interval_s()
            if (
                self._last_sample_t is not None
                and now - self._last_sample_t < interval
            ):
                return
            self._last_sample_t = now
        self.scan(now)
        self.reconcile()
        self._publish()

    # -- lifecycle -----------------------------------------------------------

    def reset_peak(self) -> None:
        """Start a fresh per-config watermark at the CURRENT total
        (bench attribution between configs)."""
        with self._lock:
            self._config_peak = sum(self._by_owner.values())

    def reset(self) -> None:
        with self._lock:
            self._entries = {}
            self._by_owner = {o: 0 for o in OWNERS}
            self._peak = 0
            self._config_peak = 0
            self._last_sample_t = None
            self._reconcile = {}
        self._publish()


# -- process-global ledger (one balance for every surface) -------------------

_ENGINE: Optional[MemoryLedger] = None
_ENGINE_LOCK = make_lock("telemetry.memory_singleton")


def engine() -> MemoryLedger:
    global _ENGINE
    with _ENGINE_LOCK:
        if _ENGINE is None:
            _ENGINE = MemoryLedger()
            if _ENGINE.telemetry.mem_sampler is None:
                _ENGINE.telemetry.mem_sampler = _ENGINE.sample
        return _ENGINE


def peek() -> Optional[MemoryLedger]:
    """The ledger if one exists, WITHOUT creating it — snapshot paths
    must not mint an engine just by looking."""
    with _ENGINE_LOCK:
        return _ENGINE


def reset_engine() -> None:
    """Drop the process-global ledger AND its registry sampler hook
    (tests re-wire on next use)."""
    global _ENGINE
    with _ENGINE_LOCK:
        if _ENGINE is not None:
            _ENGINE.reset()
        _ENGINE = None
    TELEMETRY.mem_sampler = None


# -- always-on seams (the window_state_bytes promotion) ----------------------


def note_window_bank(key, nbytes: int) -> None:
    """Book (or resize) a window bank's device bytes under the
    ``window_bank`` owner. ALWAYS-ON by the same rule as the window
    close counters: state size is exactness evidence the bench pins
    diff around runs. Gauge publication inside the ledger still
    no-ops when capture is off."""
    engine().acquire("window_bank", ("winbank", key), nbytes)


def release_window_bank(key) -> None:
    engine().release(("winbank", key))


# -- the memory document (socket ``memory`` mode / ``fluvio-tpu memory``) ----


def memory_snapshot() -> dict:
    """Per-owner ledger document + the headroom verdict. ``verdict``
    is the worst ``hbm_headroom`` verdict from the SLO engine, floored
    to ``breach`` when the instantaneous total already exceeds the
    budget — the ``fluvio-tpu memory`` exit-code gate, symmetric with
    ``health``/``lag``."""
    if not TELEMETRY.enabled:
        return {"enabled": False, "verdict": "disabled", "owners": {}}
    from fluvio_tpu.telemetry import slo as slo_mod

    eng = engine()
    eng.scan()
    recon = eng.reconcile()
    doc = slo_mod.engine().evaluate()
    verdict = "ok"
    for entry in (doc.get("chains") or {}).values():
        for rule, ev in (entry.get("rules") or {}).items():
            if rule in MEM_RULES:
                verdict = slo_mod.worst([verdict, ev.get("verdict", "ok")])
    budget = budget_bytes()
    total = eng.total_bytes()
    if budget > 0 and total > budget:
        verdict = "breach"
    leaks = TELEMETRY.memory_leak_counts()
    bytes_by = eng.owner_bytes()
    entries_by = eng.owner_entries()
    return {
        "enabled": True,
        "verdict": verdict,
        "owners": {
            o: {"bytes": bytes_by[o], "entries": entries_by[o]}
            for o in OWNERS
        },
        "total_bytes": total,
        "peak_bytes": eng.peak_bytes(),
        "budget_bytes": budget,
        "leaked": eng.leaked_entries(),
        "leaks": leaks,
        "leaks_total": sum(leaks.values()),
        "reconcile": recon,
    }


def bench_block() -> Optional[dict]:
    """Per-config BENCH_DETAIL.json record: the config's peak ledger
    bytes (since the last ``reset_peak``) + non-zero owner totals.
    None when nothing was ever booked (the key stays off entirely)."""
    eng = peek()
    if eng is None:
        return None
    peak = eng.config_peak_bytes()
    owners = {o: b for o, b in eng.owner_bytes().items() if b}
    if not peak and not owners:
        return None
    leaks = TELEMETRY.memory_leak_counts()
    out = {"peak_mb": round(peak / 1e6, 3), "owners": owners}
    if leaks:
        out["leaks"] = sum(leaks.values())
    return out
