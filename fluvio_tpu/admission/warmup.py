"""AOT shape-bucket warmup: precompile before the server admits traffic.

Diba's reconfiguration-cost argument (arXiv:2304.01659) is literal
here: a cold shape bucket costs 0.4–16.5 s of XLA compile on the
serving path. The PR-6 jaxpr lint already enumerates every jit entry
point a chain compiles per width bucket (the "AOT warmup work list");
this module WALKS that list and pays each compile up front:

- `work_list(executor, widths)` — the per-bucket entry-point reports
  (kind + compile-event shape-bucket signature), straight from
  `analysis.jaxpr_lint.trace_chain_entry_points`;
- `warm_executor(executor, widths)` — dispatches a synthetic probe
  batch per width bucket through the REAL `process_buffer` path, so
  the jit trace cache, the XLA executable, and the persistent
  ``.xla_cache`` all populate exactly as serving would populate them.
  Compile events are attributed by the PR-5 instrumentation
  (``compiles_total``/``persistent_cache_*`` move during warmup, then
  stay flat during serving — the acceptance signal). Aggregate chains
  warm safely: device + host carries snapshot before the probes and
  restore after, so warmup records can never leak into production
  aggregates;
- `warm_entries(...)` / the ``fluvio-tpu warmup`` CLI — build a chain
  from registry specs and warm it (populating the persistent cache a
  later serve process will hit).

The serve-time gate: the broker's chain-attach warmup
(`spu/public_service._schedule_chain_warmup`) runs this pass when
``FLUVIO_ADMISSION_WARMUP=1`` and registers the warmed buckets with the
admission batcher, which then pads coalesces into them (never a cold
bucket) and counts any uncovered dispatch.
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from fluvio_tpu.analysis.envreg import env_bool, env_int
from fluvio_tpu.analysis.lockwatch import make_lock
from fluvio_tpu.telemetry import TELEMETRY

logger = logging.getLogger(__name__)

WARMUP_ENV = "FLUVIO_ADMISSION_WARMUP"
WIDTHS_ENV = "FLUVIO_WARMUP_WIDTHS"
ROWS_ENV = "FLUVIO_WARMUP_ROWS"


@dataclass
class WarmupReport:
    """What one warmup pass compiled (the deploy-gate evidence)."""

    chain: str
    widths: Tuple[int, ...] = ()
    buckets: Tuple[int, ...] = ()  # warmed value-matrix width buckets
    entry_points: List[dict] = field(default_factory=list)  # work list
    compiles: int = 0
    compile_s: float = 0.0
    persistent_hits: int = 0
    persistent_misses: int = 0
    jit_cache_hits: int = 0
    wall_s: float = 0.0
    errors: List[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "chain": self.chain,
            "widths": list(self.widths),
            "buckets": list(self.buckets),
            "entry_points": self.entry_points,
            "compiles": self.compiles,
            "compile_s": round(self.compile_s, 3),
            "persistent_hits": self.persistent_hits,
            "persistent_misses": self.persistent_misses,
            "jit_cache_hits": self.jit_cache_hits,
            "wall_s": round(self.wall_s, 3),
            "errors": list(self.errors),
        }


def default_widths() -> Tuple[int, ...]:
    """``FLUVIO_WARMUP_WIDTHS`` (comma-separated bytes) or the analyzer
    default: one narrow and one past-threshold width, so both the
    narrow and the striped program warm."""
    spec = os.environ.get(WIDTHS_ENV, "").strip()
    if spec:
        try:
            widths = tuple(
                int(w) for w in spec.split(",") if w.strip()
            )
            if widths:
                return widths
        except ValueError:
            logger.error("ignoring malformed %s=%r", WIDTHS_ENV, spec)

    threshold = int(env_int("FLUVIO_STRIPE_THRESHOLD"))
    return (1024, threshold + 1)


def default_rows() -> Tuple[int, ...]:
    """Row counts to probe per width. Rows are a traced shape axis
    exactly like width (RecordBuffer buckets them pow2), and so is the
    ragged flat's byte bucket — synthetic probes therefore cover the
    fixed per-chain cost plus the probed (rows, width) buckets, not
    every shape production traffic can arrive in. ``FLUVIO_WARMUP_ROWS``
    (comma-separated) names the row buckets a deployment actually
    serves; for EXACT corpus shapes use `warm_buffer` with a
    representative buffer (the bench does — its serve passes then
    compile nothing)."""
    spec = os.environ.get(ROWS_ENV, "").strip()
    if spec:
        try:
            rows = tuple(int(r) for r in spec.split(",") if r.strip())
            if rows:
                return rows
        except ValueError:
            logger.error("ignoring malformed %s=%r", ROWS_ENV, spec)
    return (8,)


def warmup_enabled(env: Optional[dict] = None) -> bool:
    return env_bool(WARMUP_ENV, env)


def work_list(executor, widths: Sequence[int], rows: int = 8) -> List[dict]:
    """The PR-6 shape-bucket work list for this chain at these widths:
    one entry per (jit entry point, bucket) with its compile-event
    signature — what `warm_executor` is about to pay for."""
    from fluvio_tpu.analysis.jaxpr_lint import trace_chain_entry_points

    return [
        {"kind": r.kind, "signature": r.signature}
        for r in trace_chain_entry_points(executor, widths, rows=rows)
    ]


def _probe_buffer(width: int, rows: int = 8):
    """Synthetic records at ``width`` bytes — benign JSON-ish bytes so
    structural kernels trace real work; values are never served."""
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer, bucket_width

    width = max(width, 1)
    body = b'{"warmup":"' + b"x" * max(width - 16, 1) + b'"}'
    body = body[:width] if len(body) > width else body
    w = bucket_width(width)  # the value matrix stages at bucket widths
    values = np.zeros((rows, w), dtype=np.uint8)
    values[:, : len(body)] = np.frombuffer(body, dtype=np.uint8)
    lengths = np.full(rows, len(body), dtype=np.int32)
    return RecordBuffer.from_arrays(values, lengths, count=rows)


def probe_like(buf):
    """A shape twin of a real buffer: identical rows / width / lengths /
    key and timestamp columns, synthetic value bytes. Dispatching it
    compiles EXACTLY the buckets the real buffer's dispatch would hit —
    rows, width, AND the ragged-flat byte bucket (all three are traced
    shape axes) — without serving any production data."""
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

    dense = buf.dense_values()
    values = np.zeros_like(dense)
    mask = (
        np.arange(dense.shape[1], dtype=np.int32)[None, :]
        < buf.lengths[:, None]
    )
    values[mask] = ord("x")
    return RecordBuffer.from_arrays(
        values,
        buf.lengths.copy(),
        count=buf.count,
        keys=np.zeros_like(buf.keys),
        key_lengths=buf.key_lengths.copy(),
        offset_deltas=buf.offset_deltas.copy(),
        timestamp_deltas=buf.timestamp_deltas.copy(),
        base_offset=buf.base_offset,
        base_timestamp=buf.base_timestamp,
    )


# process-wide registry of distinct (chain sig, width bucket) pairs
# already warmed: the warmed_buckets gauge reads the DISTINCT total, so
# re-warming a chain (reconnects, bench configs sharing a sig) cannot
# inflate it
_WARMED_LOCK = make_lock("admission.warm_registry")
_WARMED: dict = {}


def _register_warmed(chain_sig: str, buckets) -> int:
    """Record warmed buckets; returns the process-wide distinct total
    (the gauge value)."""
    with _WARMED_LOCK:
        _WARMED.setdefault(chain_sig, set()).update(buckets)
        return sum(len(s) for s in _WARMED.values())


def reset_warm_registry() -> None:
    """Test isolation helper — pairs with TELEMETRY.reset()."""
    with _WARMED_LOCK:
        _WARMED.clear()


def _warm_probes(executor, probes, report: WarmupReport) -> None:
    """Dispatch probe buffers through the real path; shared by the
    width-grid and shape-twin entry points. Stateful chains warm
    safely: device + host carries snapshot before and restore after,
    so probes never leak into production aggregates."""
    c0 = TELEMETRY.compile_totals()
    t0 = time.perf_counter()
    carries0 = [tuple(c) for c in executor.carries]
    device_carries0 = executor._device_carries
    # a window chain's probes fold into a bank of their own
    bank0, executor._window_bank = executor._window_bank, None
    buckets = []
    for label, buf in probes:
        try:
            executor.process_buffer(buf)
            buckets.append(buf.width)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 — warm what we can
            report.errors.append(f"{label}: {type(e).__name__}: {e}")
    if executor.agg_configs:
        executor.carries = [tuple(c) for c in carries0]
        executor._device_carries = device_carries0
    executor._window_bank = bank0
    report.buckets = tuple(dict.fromkeys(buckets))
    report.wall_s = time.perf_counter() - t0
    c1 = TELEMETRY.compile_totals()
    report.compiles = c1["compiles"] - c0["compiles"]
    report.compile_s = c1["seconds"] - c0["seconds"]
    report.persistent_hits = c1["persistent_hits"] - c0["persistent_hits"]
    report.persistent_misses = (
        c1["persistent_misses"] - c0["persistent_misses"]
    )
    report.jit_cache_hits = c1["jit_cache_hits"] - c0["jit_cache_hits"]
    total = _register_warmed(executor._chain_sig, report.buckets)
    TELEMETRY.gauge_set("warmed_buckets", total)


def warm_executor(
    executor,
    widths: Optional[Sequence[int]] = None,
    rows=None,
) -> WarmupReport:
    """Precompile the shape buckets this executor would hit at the
    given record widths × row counts (``rows``: int or iterable;
    default ``FLUVIO_WARMUP_ROWS`` or 8), via the real dispatch path.
    Never raises: a probe that fails lands in ``report.errors`` and the
    rest still warm. Width/rows grids are an approximation of real
    traffic shapes — `warm_buffer` covers a corpus exactly."""
    widths = tuple(widths) if widths else default_widths()
    if rows is None:
        rows_list = default_rows()
    elif isinstance(rows, int):
        rows_list = (rows,)
    else:
        rows_list = tuple(rows)
    report = WarmupReport(chain=executor._chain_sig, widths=widths)
    try:
        report.entry_points = work_list(executor, widths, rows=rows_list[0])
    except Exception as e:  # noqa: BLE001 — the list is advisory
        report.errors.append(f"work-list: {type(e).__name__}: {e}")
    probes = []
    for width in widths:
        for r in rows_list:
            try:
                probes.append(
                    (f"width {width} rows {r}", _probe_buffer(width, rows=r))
                )
            except Exception as e:  # noqa: BLE001 — warm what we can
                report.errors.append(
                    f"width {width} rows {r}: {type(e).__name__}: {e}"
                )
    _warm_probes(executor, probes, report)
    return report


def warm_buffer(executor, buf) -> WarmupReport:
    """Precompile EXACTLY the buckets a real buffer's dispatch would
    hit, by dispatching its shape twin (`probe_like`) — rows, width,
    and flat-byte bucket all match, so a subsequent dispatch of the
    real buffer records zero compile events. This is the bench's (and
    any shape-known deployment's) exact-coverage warmup."""
    report = WarmupReport(
        chain=executor._chain_sig, widths=(int(buf.width),)
    )
    try:
        probes = [(f"shape-twin {buf.rows}x{buf.width}", probe_like(buf))]
    except Exception as e:  # noqa: BLE001
        report.errors.append(f"probe-like: {type(e).__name__}: {e}")
        return report
    _warm_probes(executor, probes, report)
    return report


def warm_entries(
    entries, widths: Optional[Sequence[int]] = None, rows: int = 8
):
    """Build the chain executor for registry entries and warm it.
    Returns (executor, report); (None, report-with-error) when the
    chain does not lower (nothing to precompile — every batch would
    interpret, which the analyze gate already flags)."""
    from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor

    executor = TpuChainExecutor.try_build(list(entries))
    if executor is None:
        report = WarmupReport(chain="unlowerable", widths=tuple(widths or ()))
        report.errors.append(
            "chain does not lower to the TPU executor: nothing to warm "
            "(it would serve interpreted — run `fluvio-tpu analyze`)"
        )
        return None, report
    return executor, warm_executor(executor, widths, rows=rows)


def warm_specs(
    specs: Sequence[Tuple[str, Optional[dict]]],
    widths: Optional[Sequence[int]] = None,
    rows: int = 8,
):
    """`warm_entries` over built-in model registry names (the bench /
    CLI spec format ``[(name, params), ...]``)."""
    from fluvio_tpu.models import lookup
    from fluvio_tpu.smartengine.config import SmartModuleConfig

    entries = [
        (lookup(name), SmartModuleConfig(params=dict(params or {})))
        for name, params in specs
    ]
    return warm_entries(entries, widths=widths, rows=rows)
