"""Level-2 preflight: abstract-trace jit entry points and lint the jaxpr.

`jax.make_jaxpr` runs the chain body over shape-only avals — no device,
no data, no compile — which makes hazards in the LOWERED program
statically visible before serving:

- **weak 64-bit literals** (the PR-5 bug class): with int64 enabled
  process-wide (smartengine/tpu/__init__.py), an unpinned Python int in
  a value position (e.g. ``jnp.where(c, 1, 0)``) traces as a
  weak-typed i64 — inside a pallas kernel Mosaic's convert lowering
  recurses infinitely on the resulting i64->i32 casts, and in XLA code
  it silently doubles register/VMEM pressure. Detected instead of
  hand-fixed: any weak-typed 64-bit literal or eqn output in the jaxpr.
- **host callbacks** (``pure_callback``/``io_callback``/...): a host
  round trip inside the fused program serializes the pipeline per call.
- **fusion breakers**: ``sort`` (O(n log n) and sequential on the VPU)
  and data-dependent ``while`` loops are flagged as warnings — they are
  sometimes intentional, never free.

Every traced entry point also reports its **shape-bucket signature**
(the executor's compile-event describe string + eqn/primitive counts):
enumerating these per bucket is exactly the work list an ahead-of-time
warmup pass must precompile against the persistent ``.xla_cache``
before serving (ROADMAP: admission control + compile-latency SLOs).
"""

from __future__ import annotations

import functools
from collections import Counter
from dataclasses import dataclass, field
from typing import List, Tuple

import numpy as np

from fluvio_tpu.analysis.spec import ERROR, INFO, WARN, Hazard

# primitives that round-trip to the host from inside a jitted program
CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call",
}
# sequential/fusion-hostile primitives worth surfacing (warn, not error)
SEQUENTIAL_PRIMS = {"sort": WARN, "while": INFO, "scan": INFO}


@dataclass
class JaxprReport:
    """One traced entry point: its shape-bucket signature + hazards."""

    kind: str  # ragged | striped | pallas | sharded
    signature: str  # the compile-event describe string for this bucket
    n_eqns: int = 0
    prims: dict = field(default_factory=dict)  # top primitive counts
    hazards: List[Hazard] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "kind": self.kind,
            "signature": self.signature,
            "n_eqns": self.n_eqns,
            "prims": dict(self.prims),
            "hazards": [h.to_dict() for h in self.hazards],
        }


def _src_of(eqn) -> str:
    """Best-effort in-repo source attribution for an eqn (" at
    kernels.py:406" or "")."""
    tb = getattr(getattr(eqn, "source_info", None), "traceback", None)
    if tb is None:
        return ""
    for frame in tb.frames:
        fname = frame.file_name or ""
        if "fluvio_tpu" in fname:
            return f" at {fname.split('fluvio_tpu/')[-1]}:{frame.line_num}"
    return ""


def _weak_64(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    if dtype is None or not getattr(aval, "weak_type", False):
        return False
    return dtype.kind in "iuf" and dtype.itemsize == 8


def scan_jaxpr(jaxpr) -> Tuple[List[Hazard], Counter, int]:
    """Walk a (Closed)Jaxpr recursively; returns (hazards, primitive
    counter, eqn count). Hazards deduplicate by (code, primitive)."""
    hazards: List[Hazard] = []
    seen = set()
    prims: Counter = Counter()
    n_eqns = 0

    def emit(level, code, msg, key):
        if key in seen:
            return
        seen.add(key)
        hazards.append(Hazard(level, code, msg, source="jaxpr"))

    def walk(jx):
        nonlocal n_eqns
        inner = getattr(jx, "jaxpr", jx)  # ClosedJaxpr -> Jaxpr
        for eqn in inner.eqns:
            n_eqns += 1
            name = eqn.primitive.name
            prims[name] += 1
            if name in CALLBACK_PRIMS:
                emit(
                    ERROR, "host-callback",
                    f"{name} inside the jitted program: a host round "
                    "trip serializes the pipeline per call",
                    ("cb", name),
                )
            elif name in SEQUENTIAL_PRIMS:
                emit(
                    SEQUENTIAL_PRIMS[name], "sequential-" + name,
                    f"{name} in the lowered program: sequential on the "
                    "device, fusion stops at its boundary",
                    ("seq", name),
                )
            # an eqn whose OUTPUT is weak 64-bit means every operand was
            # an unpinned Python literal (a weak literal paired with an
            # array operand defers to the array dtype and is harmless):
            # the PR-5 kernel-literal bug class, caught in the jaxpr
            for ov in eqn.outvars:
                if _weak_64(getattr(ov, "aval", None)):
                    src = _src_of(eqn)
                    emit(
                        ERROR, "weak-64bit-promotion",
                        f"`{name}` produces a weak {ov.aval.dtype}"
                        f"{src}: every operand is an unpinned Python "
                        "literal — pin one (jnp.int32(...)) or the op "
                        "runs 64-bit under process-wide x64",
                        ("weakout", name, str(ov.aval.dtype), src),
                    )
            for p in eqn.params.values():
                for sub in _sub_jaxprs(p):
                    walk(sub)

    walk(jaxpr)
    return hazards, prims, n_eqns


def _sub_jaxprs(param):
    """Yield nested jaxprs hidden in an eqn param (pjit/scan/while/cond/
    pallas_call all stash them under different keys/shapes)."""
    if param is None:
        return
    if hasattr(param, "eqns") or hasattr(param, "jaxpr"):
        yield param
        return
    if isinstance(param, (tuple, list)):
        for item in param:
            yield from _sub_jaxprs(item)


def scan_function(fn, *args, **kwargs) -> Tuple[List[Hazard], Counter, int]:
    """Trace ``fn`` abstractly over the given example args and scan the
    resulting jaxpr (the test surface for the hazard detectors)."""
    import jax

    jaxpr = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    return scan_jaxpr(jaxpr)


# ---------------------------------------------------------------------------
# Chain entry-point tracing
# ---------------------------------------------------------------------------


def _probe_buffer(width: int, rows: int = 8):
    """A synthetic RecordBuffer of ``rows`` records at ``width`` bytes —
    shape carrier only; the trace never reads the values."""
    from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer, bucket_width

    w = bucket_width(max(width, 1))
    values = np.zeros((rows, w), dtype=np.uint8)
    values[:, :width] = ord("x")
    lengths = np.full(rows, width, dtype=np.int32)
    return RecordBuffer.from_arrays(values, lengths, count=rows)


def _trace_report(kind: str, signature: str, trace) -> JaxprReport:
    report = JaxprReport(kind=kind, signature=signature)
    try:
        hazards, prims, n_eqns = trace()
    except Exception as e:  # noqa: BLE001 — a preflight must degrade, not die
        report.hazards.append(
            Hazard(WARN, "trace-failed",
                   f"{kind} entry point did not trace: {e}", source="jaxpr")
        )
        return report
    report.hazards = hazards
    report.n_eqns = n_eqns
    report.prims = dict(prims.most_common(8))
    return report


def trace_chain_entry_points(
    executor, widths, rows: int = 8
) -> List[JaxprReport]:
    """Abstract-trace every jit entry point this chain would compile for
    the given record widths — the same entry points the compile
    telemetry instruments (executor narrow/striped jits, the pallas
    json_get kernel) — and lint each jaxpr. One report per (entry,
    shape bucket): the list doubles as the AOT-warmup work list."""
    import jax.numpy as jnp

    from fluvio_tpu.smartengine.tpu.executor import stage_link_columns

    reports: List[JaxprReport] = []
    for width in widths:
        buf = _probe_buffer(width, rows=rows)
        striped = buf.width > executor._stripe_threshold
        carries = tuple(
            (jnp.int64(acc), jnp.int64(win), jnp.asarray(has))
            for acc, win, has in executor.carries
        )
        if executor._window is not None:
            carries = executor._stream_bank().arrays()
        flat, bucket = executor._flat_and_bucket(buf)
        words = executor._padded(flat, bucket).view(np.int32)
        lengths_up, has_keys, has_offsets, ts_mode, ts_np = (
            stage_link_columns(buf)
        )
        args = (
            words,
            lengths_up,
            buf.keys if has_keys else None,
            buf.key_lengths if has_keys else None,
            buf.offset_deltas if has_offsets else None,
            ts_np,
            np.int32(buf.count),
            np.int64(buf.base_timestamp),
            carries,
        )
        # down-link static axes (ISSUE-12): resolved through the SAME
        # executor helper the dispatch seam uses, so the AOT warmup
        # work list can never warm a program serving won't request
        enc, pack = executor._down_axes(striped)
        kwargs = dict(
            kwidth=buf.keys.shape[1],
            has_keys=has_keys,
            has_offsets=has_offsets,
            ts_mode=ts_mode,
            fanout_cap=executor._fanout_cap(buf),
            enc=enc,
            pack=pack,
        )
        if striped and executor._striped_chain() is not None:
            kwargs.update(
                srows=executor._stripe_rows(buf),
                kmax=executor._stripe_kmax(buf),
            )
            sig = executor._describe_striped(**kwargs)
            reports.append(
                _trace_report(
                    "striped", sig,
                    lambda a=args, k=kwargs: scan_function(
                        executor._chain_fn_striped, *a, **k
                    ),
                )
            )
        elif not striped:
            kwargs["width"] = buf.width
            sig = executor._describe_ragged(**kwargs)
            reports.append(
                _trace_report(
                    "ragged", sig,
                    lambda a=args, k=kwargs: scan_function(
                        executor._chain_fn_ragged, *a, **k
                    ),
                )
            )
        reports.extend(_pallas_reports(executor, buf))
    return reports


def _pallas_reports(executor, buf) -> List[JaxprReport]:
    """Trace the pallas json_get entry point when the lowerer would
    emit it for this width (mirrors `lower._json_span_fn`'s dispatch)."""
    from fluvio_tpu.smartengine.tpu import pallas_kernels
    from fluvio_tpu.smartmodule import dsl

    if not pallas_kernels.pallas_active(buf.width):
        return []
    keys = set()
    for prog in getattr(executor, "_programs", []):
        for expr in _walk_exprs(prog):
            if isinstance(expr, dsl.JsonGet):
                keys.add(expr.key)
    reports = []
    for key in sorted(keys):
        fn = getattr(
            pallas_kernels.json_get_pallas, "__wrapped__",
            pallas_kernels.json_get_pallas,
        )
        reports.append(
            _trace_report(
                "pallas",
                f"json_get key={key} shape=({buf.rows}, {buf.width})",
                lambda k=key: scan_function(
                    fn,
                    np.zeros((buf.rows, buf.width), np.uint8),
                    np.full(buf.rows, buf.width, np.int32),
                    key=k,
                    interpret=pallas_kernels.interpret_mode(),
                ),
            )
        )
    return reports


def _walk_exprs(node):
    """Every dsl.Expr reachable from a program node."""
    from fluvio_tpu.smartmodule import dsl

    if not isinstance(node, dsl.Expr):
        return
    yield node
    for f in ("arg", "left", "right", "predicate", "value", "key",
              "contribution"):
        sub = getattr(node, f, None)
        if isinstance(sub, dsl.Expr):
            yield from _walk_exprs(sub)
    for sub in getattr(node, "args", []) or []:
        yield from _walk_exprs(sub)


def dfa_table_reports(programs) -> List[JaxprReport]:
    """Static size report for every regex DFA table the chain compiles
    (the `dfa_table` compile-event kind): states, byte classes, and
    whether the table clears the associative/pallas gates."""
    from fluvio_tpu.ops.regex_dfa import (
        UnsupportedRegex,
        compile_regex_cached,
        literal_of,
    )
    from fluvio_tpu.smartengine.tpu import kernels, pallas_kernels
    from fluvio_tpu.smartmodule import dsl

    reports = []
    for prog in programs or []:
        for expr in _walk_exprs(prog):
            if not isinstance(expr, dsl.RegexMatch):
                continue
            if literal_of(expr.pattern) is not None:
                continue
            report = JaxprReport(
                kind="dfa_table", signature=f"regex={expr.pattern!r}"
            )
            try:
                dfa = compile_regex_cached(expr.pattern)
            except UnsupportedRegex as e:
                report.hazards.append(
                    Hazard(ERROR, "unsupported-regex", str(e), source="jaxpr")
                )
                reports.append(report)
                continue
            report.prims = {
                "states": dfa.n_states,
                "classes": dfa.n_classes,
                "table_bytes": int(dfa.table.nbytes),
                "packed": bool(dfa.packed),
                "pallas_ok": bool(pallas_kernels.dfa_supported(dfa)),
            }
            limit, reason = kernels.dfa_effective_max_states(dfa)
            if dfa.n_states > limit:
                report.hazards.append(
                    Hazard(
                        WARN, "dfa-states-over-gate",
                        f"{dfa.n_states} states exceeds the associative "
                        f"gate ({limit})"
                        + (
                            " — packed class ceiling reduced the limit"
                            if reason == "dfa-classes-overflow" else ""
                        ),
                        source="jaxpr",
                    )
                )
            reports.append(report)
    return reports


def window_specs_for_programs(programs) -> list:
    """`WindowSpec`s implied by a chain's windowed aggregates (tumbling,
    from the canned kind + window_ms; the sliding/keyed family members
    are authored as explicit specs and traced via
    `window_update_reports` directly)."""
    from fluvio_tpu.smartmodule import dsl
    from fluvio_tpu.windows.spec import KIND_TO_OP, WindowSpec

    specs = []
    for prog in programs or []:
        if (
            isinstance(prog, dsl.AggregateProgram)
            and prog.window_ms
            and prog.kind in KIND_TO_OP
        ):
            specs.append(WindowSpec.from_params(prog.kind, prog.window_ms))
    return specs


def window_update_reports(
    specs, rows: int = 8, width: int = 32
) -> List[JaxprReport]:
    """Abstract-trace the windowed-state update jit for each
    `WindowSpec` — one AOT-warmup work-list entry per (geometry, shape
    bucket), same contract as the chain entry points (the compile
    telemetry instruments these jits under kind="window")."""
    from fluvio_tpu.windows.kernels import trace_update

    return [
        _trace_report(
            "window",
            f"{spec.describe()} rows={rows}x{width}",
            lambda s=spec: trace_update(s, rows=rows, width=width),
        )
        for spec in specs
    ]
