"""`metrics` subcommand — read an SPU's monitoring socket.

Capability parity: fluvio-cli/src/monitoring.rs (the CLI-side reader of
the SPU metrics unix socket), extended with the telemetry surface:

- default: render the snapshot as a table — broker counters, fast-path
  vs fallback slices WITH the per-reason decline breakdown, heal/spill/
  stripe-fallback counters, and the per-phase latency table,
- ``--format json``: the raw JSON dump (the legacy output),
- ``--format prom``: Prometheus text-format exposition (same snapshot),
- ``--spans``: dump the recent per-batch span ring as JSON.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

from fluvio_tpu.cli.common import CliError


def add_metrics_parser(sub) -> None:
    p = sub.add_parser("metrics", help="dump SPU metrics")
    p.add_argument(
        "--path",
        help="monitoring unix-socket path (default: FLUVIO_METRIC_SPU)",
    )
    p.add_argument(
        "--format",
        choices=("table", "json", "prom"),
        default="table",
        help="output format (default: table)",
    )
    p.add_argument(
        "--spans",
        action="store_true",
        help="dump the recent per-batch phase spans as JSON and exit",
    )
    p.add_argument(
        "--watch",
        type=float,
        metavar="N",
        help="refresh the table every N seconds (ctrl-c to stop) — live "
        "observation of a run without a scraper stack",
    )
    p.add_argument(
        "--watch-count",
        type=int,
        default=0,
        help=argparse.SUPPRESS,  # test hook: stop after K refreshes
    )
    p.set_defaults(fn=metrics)


def _fmt_count(n) -> str:
    return f"{n:,}" if isinstance(n, int) else str(n)


def _rows_to_table(rows, header=None) -> str:
    """Minimal fixed-width table (no external deps)."""
    all_rows = ([header] if header else []) + rows
    widths = [
        max(len(str(r[i])) for r in all_rows) for i in range(len(all_rows[0]))
    ]
    out = []
    for j, r in enumerate(all_rows):
        out.append(
            "  ".join(str(c).ljust(w) for c, w in zip(r, widths)).rstrip()
        )
        if header and j == 0:
            out.append("  ".join("-" * w for w in widths))
    return "\n".join(out)


def render_metrics_table(data: dict) -> str:
    """Snapshot dict (the monitoring JSON) -> operator-facing table.

    Pure function so the endpoint-parity test can compare it against a
    Prometheus scrape of the same instant without a terminal."""
    sections = []

    rows = []
    for direction in ("inbound", "outbound"):
        d = data.get(direction) or {}
        rows.append(
            (direction, _fmt_count(d.get("records", 0)),
             _fmt_count(d.get("bytes", 0)))
        )
    sections.append(
        "broker\n" + _rows_to_table(rows, header=("dir", "records", "bytes"))
    )

    sm = data.get("smartmodule") or {}
    rows = [
        (k, _fmt_count(sm.get(k, 0)))
        for k in (
            "bytes_in", "records_out", "invocation_count", "fuel_used",
            "fastpath_slices", "fallback_slices",
            "stream_chain_hits", "stream_chain_builds",
        )
    ]
    sections.append(
        "smartmodule\n" + _rows_to_table(rows, header=("counter", "value"))
    )
    reasons = sm.get("fallback_reasons") or {}
    if reasons:
        rows = [(r, _fmt_count(n)) for r, n in sorted(reasons.items())]
        sections.append(
            "fallback reasons\n"
            + _rows_to_table(rows, header=("reason", "slices"))
        )

    tel = data.get("telemetry") or {}
    counters = tel.get("counters") or {}
    rows = [
        ("glz_heals", _fmt_count(counters.get("heals", 0))),
        ("stripe_fallbacks", _fmt_count(counters.get("stripe_fallbacks", 0))),
        ("quarantined", _fmt_count(counters.get("quarantined", 0))),
    ]
    for reason, n in sorted((counters.get("spills") or {}).items()):
        rows.append((f"spill[{reason}]", _fmt_count(n)))
    for reason, n in sorted((counters.get("declines") or {}).items()):
        rows.append((f"decline[{reason}]", _fmt_count(n)))
    for point, n in sorted((counters.get("retries") or {}).items()):
        rows.append((f"retry[{point}]", _fmt_count(n)))
    for key, n in sorted((counters.get("slo_breaches") or {}).items()):
        rows.append((f"slo_breach[{key}]", _fmt_count(n)))
    for reason, n in sorted((counters.get("rebalance_moves") or {}).items()):
        rows.append((f"rebalance[{reason}]", _fmt_count(n)))
    windows = tel.get("windows") or {}
    if windows.get("closed") or windows.get("deltas"):
        rows.append(("windows_closed", _fmt_count(windows.get("closed", 0))))
        for kind, n in sorted((windows.get("deltas") or {}).items()):
            rows.append((f"window_delta[{kind}]", _fmt_count(n)))
        full = windows.get("full_bytes", 0)
        if full:
            ratio = windows.get("delta_bytes", 0) / full
            rows.append(("window_downlink_ratio", f"{ratio:.3f}"))
    breaker = counters.get("breaker") or {}
    rows.append(
        ("breaker_short_circuits",
         _fmt_count(breaker.get("short_circuits", 0)))
    )
    for state, n in sorted((breaker.get("transitions") or {}).items()):
        rows.append((f"breaker_to[{state}]", _fmt_count(n)))
    sections.append(
        "pipeline events\n" + _rows_to_table(rows, header=("event", "count"))
    )

    states = breaker.get("states") or {}
    if states:
        rows = [(name, state) for name, state in sorted(states.items())]
        sections.append(
            "breaker state\n" + _rows_to_table(rows, header=("chain", "state"))
        )

    comp = tel.get("compile") or {}
    by_kind = comp.get("by_kind") or {}
    if by_kind:
        secs = comp.get("seconds_by_kind") or {}
        rows = [
            (kind, _fmt_count(n), round(secs.get(kind, 0.0), 3))
            for kind, n in sorted(by_kind.items())
        ]
        rows.append(
            ("(persistent-cache hit/miss)",
             f"{_fmt_count(comp.get('persistent_cache_hits', 0))}/"
             f"{_fmt_count(comp.get('persistent_cache_misses', 0))}",
             "")
        )
        rows.append(
            ("(trace-cache hits)",
             _fmt_count(comp.get("jit_cache_hits", 0)), "")
        )
        sections.append(
            "jit compiles\n"
            + _rows_to_table(rows, header=("kind", "count", "seconds"))
        )

    gauges = tel.get("gauges") or {}
    if gauges:
        rows = [(name, _fmt_count(v)) for name, v in sorted(gauges.items())]
        sections.append(
            "gauges\n" + _rows_to_table(rows, header=("gauge", "value"))
        )
    dropped = tel.get("spans_dropped", 0)
    if dropped:
        sections.append(
            "spans\n"
            + _rows_to_table(
                [("dropped (ring wrapped)", _fmt_count(dropped))],
                header=("spans", "count"),
            )
        )

    batches = tel.get("batches") or {}
    rows = []
    for path, b in sorted(batches.items()):
        if not b.get("count"):
            continue
        rows.append(
            (path, _fmt_count(b.get("count", 0)),
             _fmt_count(b.get("records", 0)),
             b.get("p50_ms", 0), b.get("p99_ms", 0))
        )
    if rows:
        sections.append(
            "batch latency\n"
            + _rows_to_table(
                rows, header=("path", "batches", "records", "p50_ms", "p99_ms")
            )
        )

    chains = tel.get("chains") or {}
    rows = [
        (name, _fmt_count(h.get("count", 0)), h.get("p50_ms", 0),
         h.get("p99_ms", 0))
        for name, h in sorted(chains.items())
    ]
    if rows:
        sections.append(
            "chain latency\n"
            + _rows_to_table(
                rows, header=("chain", "batches", "p50_ms", "p99_ms")
            )
        )

    phases = tel.get("phases") or {}
    rows = [
        (name, _fmt_count(h.get("count", 0)), h.get("p50_ms", 0),
         h.get("p99_ms", 0), h.get("sum_s", 0))
        for name, h in sorted(
            phases.items(), key=lambda kv: -kv[1].get("sum_s", 0)
        )
    ]
    if rows:
        sections.append(
            "phases (by total time)\n"
            + _rows_to_table(
                rows, header=("phase", "count", "p50_ms", "p99_ms", "sum_s")
            )
        )

    quarantine = data.get("hook_quarantine")
    if quarantine:
        sections.append("hook quarantine\n" + json.dumps(quarantine, indent=1))

    return "\n\n".join(sections)


async def metrics(args) -> int:
    from fluvio_tpu.spu.monitoring import (
        read_metrics,
        read_prometheus,
        read_spans,
    )

    if args.spans:
        print(json.dumps(await read_spans(args.path), indent=1))
        return 0
    if getattr(args, "watch", None) is not None:
        if args.watch <= 0:
            raise CliError("--watch interval must be positive seconds")
        return await _watch(args)
    if args.format == "prom":
        print(await read_prometheus(args.path), end="")
        return 0
    data = await read_metrics(args.path)
    if args.format == "json":
        print(json.dumps(data, indent=2))
    else:
        print(render_metrics_table(data))
    return 0


async def _watch(args) -> int:
    """Refresh loop: re-read the socket every ``--watch`` seconds and
    redraw in place (ANSI clear-home — no curses dependency), honoring
    ``--format`` (table/json/prom). Each refresh is its own connection,
    same as a scraper. Stops on ctrl-c (clean exit 0) or after
    ``--watch-count`` refreshes (tests)."""
    from fluvio_tpu.spu.monitoring import read_metrics, read_prometheus

    interval = max(float(args.watch), 0.01)
    drawn = 0
    try:
        while True:
            if args.format == "prom":
                body = (await read_prometheus(args.path)).rstrip("\n")
            else:
                data = await read_metrics(args.path)
                body = (
                    json.dumps(data, indent=2)
                    if args.format == "json"
                    else render_metrics_table(data)
                )
            sys.stdout.write("\x1b[2J\x1b[H")  # clear screen, cursor home
            print(f"fluvio-tpu metrics  (refresh {interval:g}s)\n")
            print(body)
            sys.stdout.flush()
            drawn += 1
            if args.watch_count and drawn >= args.watch_count:
                return 0
            await asyncio.sleep(interval)
    except (KeyboardInterrupt, asyncio.CancelledError):
        return 0
