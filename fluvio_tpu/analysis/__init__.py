"""Chain preflight static analysis.

Three levels, all runnable before a single record is dispatched:

1. **Spec pass** (`analysis.spec`): walk a SmartModule chain spec and
   predict the executed path — fused / striped / interpreter-spill —
   with reasons that use the SAME strings as the runtime decline/spill
   counters, checked against every env/backend gate.
2. **Jaxpr pass** (`analysis.jaxpr_lint`): abstract-trace the jit entry
   points the compile telemetry instruments and walk the eqns for
   hazards (weak 64-bit literals, host callbacks, fusion breakers),
   enumerating the shape buckets an AOT warmup must precompile.
3. **AST lint** (`analysis.ast_lint`): repo-invariant linter for the
   engine modules (pinned kernel literals, no host syncs in dispatch
   hot paths, zero-cost telemetry seams) plus repo-wide hygiene.

Surfaces: the `fluvio-tpu analyze` CLI, a per-config ``preflight``
record in BENCH_DETAIL.json, and differential tests pinning the
predictions to telemetry-observed runtime truth.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from fluvio_tpu.analysis.ast_lint import (
    LintViolation,
    lint_file,
    lint_paths,
    lint_repo,
    lint_source,
)

__all__ = [
    "ERROR", "INFO", "WARN",
    "ChainReport", "Hazard", "PathPrediction", "LintViolation",
    "analyze_entries", "analyze_named", "analyze_chain", "resolve_gates",
    "analyze_partitioned",
    "lint_source", "lint_file", "lint_paths", "lint_repo",
    "preflight_for_specs",
    "ConcurrencyReport", "analyze_concurrency", "static_lock_graph",
    "ValueFlowReport", "analyze_values", "analyze_values_sources",
    "EnvFinding", "lint_env", "lint_env_sources", "warn_unknown_env",
    "registry_report",
]

# spec re-exports resolve lazily (PEP 562): engine modules import the
# lockwatch shim from this package at THEIR import time, and an eager
# spec import here would close a cycle back through ops/regex_dfa
_SPEC_EXPORTS = {
    "ERROR", "INFO", "WARN", "ChainReport", "Hazard", "PathPrediction",
    "analyze_entries", "analyze_named", "analyze_partitioned",
    "resolve_gates",
}
_CONCURRENCY_EXPORTS = {
    "ConcurrencyReport": "ConcurrencyReport",
    "analyze_concurrency": "analyze_package",
    "static_lock_graph": "static_lock_graph",
}
_VALUEFLOW_EXPORTS = {
    "ValueFlowReport": "ValueFlowReport",
    "analyze_values": "analyze_values_package",
    "analyze_values_sources": "analyze_values_sources",
}
_ENVREG_EXPORTS = {
    "EnvFinding": "EnvFinding",
    "lint_env": "lint_env_package",
    "lint_env_sources": "lint_env_sources",
    "warn_unknown_env": "warn_unknown_env",
    "registry_report": "registry_report",
}


def __getattr__(name: str):
    if name in _SPEC_EXPORTS:
        from fluvio_tpu.analysis import spec

        return getattr(spec, name)
    if name in _CONCURRENCY_EXPORTS:
        from fluvio_tpu.analysis import concurrency

        return getattr(concurrency, _CONCURRENCY_EXPORTS[name])
    if name in _VALUEFLOW_EXPORTS:
        from fluvio_tpu.analysis import valueflow

        return getattr(valueflow, _VALUEFLOW_EXPORTS[name])
    if name in _ENVREG_EXPORTS:
        from fluvio_tpu.analysis import envreg

        return getattr(envreg, _ENVREG_EXPORTS[name])
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def analyze_chain(
    entries,
    widths: Optional[Sequence[int]] = None,
    sharded: bool = False,
    jaxpr: bool = False,
    rows: int = 8,
) -> ChainReport:
    """Full preflight for a chain of (SmartModuleDef, SmartModuleConfig)
    entries: the Level-1 spec pass, plus (``jaxpr=True``) the Level-2
    abstract trace of every jit entry point the chain would compile at
    the probed widths."""
    # function-level import: module __getattr__ serves ATTRIBUTE access
    # only, not global-name lookup inside this module's own functions
    from fluvio_tpu.analysis.spec import analyze_entries

    report = analyze_entries(entries, widths=widths, sharded=sharded)
    if not jaxpr:
        return report
    from fluvio_tpu.analysis.jaxpr_lint import (
        dfa_table_reports,
        trace_chain_entry_points,
        window_specs_for_programs,
        window_update_reports,
    )
    from fluvio_tpu.analysis.spec import resolved_programs
    from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor

    programs, _ = resolved_programs(entries)
    report.jaxprs.extend(dfa_table_reports(programs))
    report.jaxprs.extend(
        window_update_reports(window_specs_for_programs(programs), rows=rows)
    )
    executor = TpuChainExecutor.try_build(list(entries))
    if executor is not None:
        trace_widths = [
            p.width for p in report.predictions if p.path != "interpreter"
        ]
        report.jaxprs.extend(
            trace_chain_entry_points(executor, trace_widths, rows=rows)
        )
        for j in report.jaxprs:
            report.hazards.extend(j.hazards)
    return report


def preflight_for_specs(
    specs: Sequence[Tuple[str, Optional[dict]]],
    width: int,
    sharded: bool = False,
) -> dict:
    """Compact per-config preflight record for the bench: the predicted
    path + reason strings for one chain spec at one record width.
    ``specs`` is the bench-matrix format: ``[(model name, params)]``;
    ``sharded`` predicts for the multi-device (shard_map) engine mode."""
    from fluvio_tpu.analysis.spec import analyze_named

    report = analyze_named(specs, widths=(width,), sharded=sharded)
    pred = report.predictions[0]
    out = {
        "path": pred.path,
        "down_variant": pred.down_variant,
    }
    if pred.window_variant != "off":
        out["window_variant"] = pred.window_variant
    if pred.spill_reasons:
        out["spill_reasons"] = list(pred.spill_reasons)
    if pred.declines:
        out["declines"] = list(pred.declines)
    if pred.causes:
        out["causes"] = list(pred.causes)
    errors = report.errors()
    if errors:
        out["errors"] = len(errors)
    return out
