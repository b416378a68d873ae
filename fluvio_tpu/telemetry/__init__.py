"""Pipeline telemetry: per-batch phase spans, latency histograms, and a
scrapeable metrics surface.

Stream processors live or die by phase-level visibility (Diba,
arXiv:2304.01659 builds reconfiguration decisions on per-stage latency
telemetry); this package gives the engine exactly that without touching
per-record work:

- `spans`     — per-batch pipeline spans with FIXED phase labels,
                captured in a bounded ring buffer (plus the instant-
                event ring the flight recorder draws markers from),
- `histogram` — log-bucketed (HDR-style) latency histograms: fixed
                bucket array, mergeable, percentile interpolation,
- `registry`  — the process-wide `TELEMETRY` singleton the hot paths
                record into and the export surfaces snapshot from,
- `prometheus`— text-format exposition of a snapshot,
- `compiles`  — jit entry-point wrappers that turn trace-cache misses
                into compile events (count/seconds/persistent-cache
                outcome),
- `trace`     — Chrome-trace/Perfetto export: continuous bounded file
                sink via ``FLUVIO_TRACE=<path>`` plus the on-demand
                renderer behind the monitoring socket and CLI,
- `timeseries`— rolling-window layer: bounded ring of cumulative
                snapshots; windowed rate/p50/p99/error-ratio per chain
                and per path family by mergeable-histogram delta,
- `slo`       — declarative SLO targets (``FLUVIO_SLO``) evaluated with
                multi-window burn-rate logic into per-chain
                ok|warn|breach verdicts; breaches emit flight-recorder
                instant events and (``FLUVIO_SLO_PROFILE``) bounded
                jax.profiler captures,
- `memory`    — the per-owner device-memory ledger: typed
                acquire/release handles on every HBM allocation seam,
                TTL leak detection, backend reconciliation, and the
                ``hbm_headroom`` budget feeding admission shedding.

Always-on contract: one monotonic clock pair per phase per batch, no
per-record work; ``FLUVIO_TELEMETRY=0`` disables span/histogram capture
entirely (event counters stay on — they are as cheap as the existing
`SmartModuleChainMetrics` adds).
"""

from fluvio_tpu.telemetry.histogram import LatencyHistogram
from fluvio_tpu.telemetry.flow import (
    CONSUME_PHASES,
    SLICE_PHASES,
    ConsumeFlow,
    FlowRing,
    SliceFlow,
)
from fluvio_tpu.telemetry.spans import (
    PHASES,
    BatchSpan,
    EventRing,
    InstantEvent,
    SpanRing,
)
from fluvio_tpu.telemetry.registry import TELEMETRY, PipelineTelemetry
from fluvio_tpu.telemetry.prometheus import render_prometheus
from fluvio_tpu.telemetry.compiles import instrument_jit
from fluvio_tpu.telemetry.trace import (
    TraceFileSink,
    install_env_sink,
    render_trace,
    trace_json,
)
from fluvio_tpu.telemetry.timeseries import TimeSeries, WindowDelta
from fluvio_tpu.telemetry.slo import SloEngine, health_snapshot
from fluvio_tpu.telemetry.memory import (
    MemoryLedger,
    memory_snapshot,
)

# continuous flight recorder: arm the file sink when FLUVIO_TRACE names
# a path (no-op otherwise; bounded + rotated, see telemetry/trace.py)
install_env_sink()

__all__ = [
    "LatencyHistogram",
    "SLICE_PHASES",
    "CONSUME_PHASES",
    "ConsumeFlow",
    "FlowRing",
    "SliceFlow",
    "PHASES",
    "BatchSpan",
    "EventRing",
    "InstantEvent",
    "SpanRing",
    "TELEMETRY",
    "PipelineTelemetry",
    "render_prometheus",
    "instrument_jit",
    "TraceFileSink",
    "install_env_sink",
    "render_trace",
    "trace_json",
    "TimeSeries",
    "WindowDelta",
    "SloEngine",
    "health_snapshot",
    "MemoryLedger",
    "memory_snapshot",
]
