"""Level-1 preflight: predict a chain's executed path from its spec.

The engine's worst production behaviors — interpreter-spill p99
outliers, recompile storms, multi-second first-call compiles — are all
statically knowable before a single record is dispatched. This module
walks a SmartModule chain's resolved DSL programs and predicts, per
record-width bucket, which path the executor will take (``fused`` /
``striped`` / ``interpreter``) and which telemetry counters will move,
using the SAME reason strings the runtime decline/spill counters use
(``dfa-assoc-states``, ``dfa-stripe-states``, ``record-too-wide``,
``record-too-wide-unstripeable``) so a preflight report and a live
metrics scrape speak one vocabulary.

The walk mirrors — without executing — the three runtime decision
layers:

- ``TpuChainExecutor.try_build`` (is the chain narrow-lowerable at
  all, and does any non-literal regex trip the associative state gate),
- ``stripes.try_build_striped`` + the executor's viewable/int-output
  preconditions (can wide batches run striped, or do they spill),
- the dispatch-time width ladder (narrow layout → stripe threshold →
  ``MAX_RECORD_WIDTH`` hard ceiling).

Predictions are test-pinned to runtime truth: ``tests/test_analysis.py``
runs every bench-matrix config on the CPU backend and asserts the
predicted path equals the path the telemetry counters observed. The
mirror MUST NOT fire those counters itself (a preflight must never
perturb the metrics it predicts), which is why this is a re-walk of the
rules rather than a call into the lowering.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from fluvio_tpu.analysis.envreg import env_int
from fluvio_tpu.ops.regex_dfa import (
    UnsupportedRegex,
    compile_regex_cached,
    literal_of,
)
from fluvio_tpu.ops.regex_dfa import classes_enabled as regex_classes_enabled
from fluvio_tpu.smartmodule import dsl

ERROR = "error"
WARN = "warn"
INFO = "info"

_SEVERITY_RANK = {ERROR: 0, WARN: 1, INFO: 2}

# the aggregate kinds the canned narrow lowering accepts (mirror of
# executor._AGG_OP — imported lazily in _gates() to keep this module's
# import cheap); word_count is narrow-only (striped double-counts
# overlap-spanning tokens)
_CANNED_AGG_KINDS = ("sum_int", "count", "word_count", "max_int", "min_int")


@dataclass
class Hazard:
    """One preflight finding. ``level`` is error/warn/info; ``code`` a
    short stable slug; ``source`` names the pass that found it."""

    level: str
    code: str
    message: str
    source: str = "spec"

    def to_dict(self) -> dict:
        return {
            "level": self.level,
            "code": self.code,
            "message": self.message,
            "source": self.source,
        }


@dataclass
class PathPrediction:
    """Predicted executed path for one record-width bucket."""

    width: int  # probed max record value width (pre-bucket)
    width_bucket: int
    path: str  # fused | striped | interpreter
    spill_reasons: Tuple[str, ...] = ()  # expected TELEMETRY.spills keys
    declines: Tuple[str, ...] = ()  # expected TELEMETRY.declines keys
    causes: Tuple[str, ...] = ()  # human explanations for the above
    # predicted D2H (result) form: "down-raw" | "down-packed" |
    # "down-glz-xla" (the TELEMETRY.link_variants keys). This is the
    # CONFIGURED variant; per-batch ratio losses ship packed with
    # `glz-enc-ratio` on the decline counter.
    down_variant: str = "down-raw"
    # predicted windowed-state emission form for chains with a windowed
    # aggregate: "off" (no windowed stage) | "win-delta" (delta-only
    # downlink, the default) | "win-full" (FLUVIO_WINDOW_DELTA=0 full
    # state every batch). Differentially pinned against the runtime's
    # window_deltas counters.
    window_variant: str = "off"

    def to_dict(self) -> dict:
        return {
            "width": self.width,
            "width_bucket": self.width_bucket,
            "path": self.path,
            "spill_reasons": list(self.spill_reasons),
            "declines": list(self.declines),
            "causes": list(self.causes),
            "down_variant": self.down_variant,
            "window_variant": self.window_variant,
        }


@dataclass
class ChainReport:
    """Full preflight report for one chain."""

    chain_sig: str
    gates: Dict
    predictions: List[PathPrediction] = field(default_factory=list)
    hazards: List[Hazard] = field(default_factory=list)
    jaxprs: List = field(default_factory=list)  # JaxprReport (jaxpr pass)

    def errors(self) -> List[Hazard]:
        return [h for h in self.hazards if h.level == ERROR]

    def prediction_for(self, width: int) -> Optional[PathPrediction]:
        for p in self.predictions:
            if p.width == width:
                return p
        return None

    def to_dict(self) -> dict:
        return {
            "chain": self.chain_sig,
            "gates": dict(self.gates),
            "predictions": [p.to_dict() for p in self.predictions],
            "hazards": [
                h.to_dict()
                for h in sorted(
                    self.hazards, key=lambda h: _SEVERITY_RANK[h.level]
                )
            ],
            "jaxprs": [j.to_dict() for j in self.jaxprs],
        }


def resolve_gates() -> dict:
    """Snapshot of every env/backend gate the path decision reads, as
    the runtime resolves them (one vocabulary with the knobs' homes)."""
    import jax

    from fluvio_tpu.smartengine.tpu import kernels
    from fluvio_tpu.smartengine.tpu.buffer import MAX_RECORD_WIDTH
    from fluvio_tpu.smartengine.tpu.lower import _depth_over_work

    return {
        "backend": jax.default_backend(),
        "dfa_assoc": _depth_over_work("FLUVIO_DFA_ASSOC"),
        "fast_json": _depth_over_work("FLUVIO_TPU_FAST_JSON"),
        "dfa_assoc_max_states": kernels.dfa_assoc_max_states(),
        # round-2 DFA engine gate: byte-class table packing (the
        # raised state default is sized for packed tables)
        "dfa_classes": regex_classes_enabled(),
        "stripe_threshold": int(env_int("FLUVIO_STRIPE_THRESHOLD")),
        "max_record_width": MAX_RECORD_WIDTH,
        # down-link gates: the result-side compaction + ENCODE path
        # (FLUVIO_RESULT_COMPACT / FLUVIO_RESULT_COMPRESS), mirrored
        # for the down_variant arm
        "result_compact": _executor().effective_result_compact(),
        "result_compress": _executor().effective_result_compress(),
        # windowed-state gate: delta-only emission vs full-state every
        # batch (FLUVIO_WINDOW_DELTA), mirrored for the window_variant
        # arm of the prediction
        "window_delta": _window_delta_enabled(),
    }


def _window_delta_enabled() -> bool:
    from fluvio_tpu.windows.spec import delta_enabled

    return delta_enabled()


def _executor():
    from fluvio_tpu.smartengine.tpu import executor

    return executor


# ---------------------------------------------------------------------------
# Program resolution
# ---------------------------------------------------------------------------


def resolved_programs(entries) -> Tuple[Optional[list], List[Hazard]]:
    """Param-resolved DSL programs for a chain of (module, config)
    entries, or (None, hazards) when any module has no DSL program —
    the builder then runs the whole chain on the python backend."""
    hazards: List[Hazard] = []
    programs = []
    for module, config in entries:
        kind = module.transform_kind()
        prog = module.dsl_program(kind)
        if prog is None:
            hazards.append(
                Hazard(
                    ERROR,
                    "no-dsl-program",
                    f"module {module.name!r} carries no DSL program: the "
                    "chain cannot lower and every batch runs interpreted",
                )
            )
            return None, hazards
        try:
            programs.append(dsl.resolve_params(prog, config.params))
        except Exception as e:  # mirror: try_build catches KeyError
            hazards.append(
                Hazard(
                    ERROR,
                    "unresolved-params",
                    f"module {module.name!r} params do not resolve: {e}",
                )
            )
            return None, hazards
    return programs, hazards


def chain_sig(programs) -> str:
    """The executor's compile-event chain signature (must render the
    same stage names `TpuChainExecutor._chain_sig` does)."""
    names = {
        dsl.FilterProgram: "filter",
        dsl.MapProgram: "map",
        dsl.FilterMapProgram: "map",  # lowers to a _MapStage
        dsl.AggregateProgram: "aggregate",
        dsl.ArrayMapProgram: "arraymap",
    }
    return (
        "+".join(names.get(type(p), type(p).__name__.lower()) for p in programs)
        or "empty"
    )


# ---------------------------------------------------------------------------
# Narrow-lowering mirror (TpuChainExecutor.try_build / lower.lower_expr)
# ---------------------------------------------------------------------------


def _type_of(expr) -> Optional[str]:
    """Non-raising mirror of `lower.infer_type`."""
    if isinstance(
        expr,
        (dsl.Value, dsl.Key, dsl.Const, dsl.Upper, dsl.Lower, dsl.Concat,
         dsl.JsonGet, dsl.IntToBytes),
    ):
        return "bytes"
    if isinstance(expr, (dsl.Len, dsl.ParseInt)):
        return "int"
    if isinstance(
        expr,
        (dsl.RegexMatch, dsl.Contains, dsl.StartsWith, dsl.EndsWith,
         dsl.Cmp, dsl.And, dsl.Or, dsl.Not),
    ):
        return "bool"
    return None


def _expr_problems(expr, gates, declines: List[str], problems: List[str]) -> None:
    """Mirror of `lower.lower_expr` coverage: append a problem string
    for every sub-expression outside the TPU-compilable subset, and a
    predicted ``dfa-assoc-states`` (or ``dfa-classes-overflow``) decline
    for every non-literal regex whose DFA trips the effective
    associative state gate on a backend that wanted the associative
    path (the exact condition `lower_expr` counts)."""
    if isinstance(expr, (dsl.Value, dsl.Key, dsl.Const)):
        return
    if isinstance(expr, (dsl.Upper, dsl.Lower, dsl.Len, dsl.ParseInt,
                         dsl.IntToBytes, dsl.Not, dsl.JsonGet)):
        if isinstance(expr, dsl.IntToBytes) and _type_of(expr.arg) != "int":
            problems.append("IntToBytes needs an int argument")
        _expr_problems(expr.arg, gates, declines, problems)
        return
    if isinstance(expr, (dsl.Contains, dsl.StartsWith, dsl.EndsWith)):
        _expr_problems(expr.arg, gates, declines, problems)
        return
    if isinstance(expr, dsl.RegexMatch):
        _expr_problems(expr.arg, gates, declines, problems)
        if literal_of(expr.pattern) is not None:
            return  # windowed-compare fast path: no DFA at all
        try:
            dfa = compile_regex_cached(expr.pattern)
        except UnsupportedRegex as e:
            problems.append(f"unsupported regex: {e}")
            return
        if gates["dfa_assoc"]:
            limit, reason = _effective_dfa_limit(dfa)
            if dfa.n_states > limit:
                declines.append(reason or "dfa-assoc-states")
        return
    if isinstance(expr, dsl.Cmp):
        if _type_of(expr.left) != "int" or _type_of(expr.right) != "int":
            problems.append("Cmp lowers only for int operands")
        _expr_problems(expr.left, gates, declines, problems)
        _expr_problems(expr.right, gates, declines, problems)
        return
    if isinstance(expr, (dsl.And, dsl.Or, dsl.Concat)):
        for a in expr.args:
            _expr_problems(a, gates, declines, problems)
        return
    problems.append(f"no lowering for {type(expr).__name__}")


def _is_span_value(value) -> bool:
    """Mirror of `lower.lower_span`: is this map value a (postop-folded)
    view of the record's own bytes?"""
    if isinstance(value, dsl.Value):
        return True
    if isinstance(value, (dsl.Upper, dsl.Lower, dsl.JsonGet)):
        return _is_span_value(value.arg)
    return False


def narrow_report(programs, gates) -> Tuple[bool, List[str], List[str]]:
    """(lowerable, predicted declines, problems) for the narrow build —
    the mirror of `TpuChainExecutor.try_build`. Declines listed here
    fire at CHAIN BUILD time (once per chain construction)."""
    declines: List[str] = []
    problems: List[str] = []
    seen_arraymap = False
    for prog in programs:
        if isinstance(prog, dsl.FilterProgram):
            if _type_of(prog.predicate) != "bool":
                problems.append("filter predicate must be bool")
            _expr_problems(prog.predicate, gates, declines, problems)
        elif isinstance(prog, (dsl.MapProgram, dsl.FilterMapProgram)):
            if isinstance(prog, dsl.FilterMapProgram):
                _expr_problems(prog.predicate, gates, declines, problems)
            if not _is_span_value(prog.value):
                _expr_problems(prog.value, gates, declines, problems)
            if prog.key is not None:
                _expr_problems(prog.key, gates, declines, problems)
        elif isinstance(prog, dsl.AggregateProgram):
            if prog.window_ms and seen_arraymap:
                problems.append("windowed aggregate after array_map")
            if prog.contribution is not None:
                if prog.combine not in dsl.AGGREGATE_COMBINES:
                    problems.append(f"aggregate combine {prog.combine}")
                if _type_of(prog.contribution) != "int":
                    problems.append("aggregate contribution must be int-typed")
                _expr_problems(prog.contribution, gates, declines, problems)
            elif prog.kind not in _CANNED_AGG_KINDS:
                problems.append(f"aggregate kind {prog.kind}")
        elif isinstance(prog, dsl.ArrayMapProgram):
            if prog.mode not in ("json_array", "split"):
                problems.append(f"array_map mode {prog.mode}")
            if seen_arraymap:
                problems.append("one array_map per fused chain")
            seen_arraymap = True
        else:
            problems.append(f"{type(prog).__name__} is not a lowerable program")
    return not problems, declines, problems


# ---------------------------------------------------------------------------
# Striped-lowering mirror (stripes.try_build_striped + executor gating)
# ---------------------------------------------------------------------------


class _NotStriped(Exception):
    """Internal mirror of stripes.Unlowerable (message = cause)."""


def _value_postops_mirror(arg):
    """Mirror of `stripes._value_postops`: () / postop tuple for a
    record-value source, None for key/const (seg-exact instead), raises
    for structural sources (JsonGet etc.)."""
    if isinstance(arg, dsl.Value):
        return ()
    if isinstance(arg, (dsl.Upper, dsl.Lower)):
        inner = _value_postops_mirror(arg.arg)
        if inner is None:
            return None
        return inner + ("upper" if isinstance(arg, dsl.Upper) else "lower",)
    if isinstance(arg, (dsl.Key, dsl.Const)):
        return None
    if isinstance(arg, dsl.JsonGet):
        # the family the ROADMAP names "JsonGet-sourced predicates"
        raise _NotStriped("JsonGet-sourced predicate is not stripeable")
    raise _NotStriped(f"{type(arg).__name__} not stripeable as a byte source")


_SEG_EXACT_NODES = (
    dsl.Cmp, dsl.Len, dsl.ParseInt, dsl.Value, dsl.Key, dsl.Const,
    dsl.Upper, dsl.Lower, dsl.And, dsl.Or, dsl.Not, dsl.Contains,
    dsl.StartsWith, dsl.EndsWith,
)


def _seg_exact_check(expr) -> None:
    """Mirror of `stripes._check_seg_exact`."""
    if not isinstance(expr, _SEG_EXACT_NODES):
        if isinstance(expr, dsl.JsonGet):
            raise _NotStriped("JsonGet-sourced predicate is not stripeable")
        raise _NotStriped(f"{type(expr).__name__} not stripeable")
    for f in ("arg", "left", "right"):
        sub = getattr(expr, f, None)
        if isinstance(sub, dsl.Expr):
            _seg_exact_check(sub)
    for sub in getattr(expr, "args", []) or []:
        _seg_exact_check(sub)
    if isinstance(expr, (dsl.Contains, dsl.StartsWith, dsl.EndsWith)):
        if _value_postops_mirror(expr.arg) is not None:
            raise _NotStriped("value search must lower striped")


def _striped_literal_check(kind: str, lit: bytes, s: int, v: int) -> None:
    """Mirror of `stripes._lower_striped_literal`'s overlap gate."""
    limit = s if kind in ("startswith", "equals") else v
    if len(lit) > limit:
        raise _NotStriped(
            f"literal of {len(lit)} bytes exceeds the stripe "
            f"{'width' if limit == s else 'overlap'} ({limit})"
        )


def _jsonget_source_mirror(arg) -> Optional[str]:
    """Mirror of `stripes._jsonget_source`: the JsonGet key when ``arg``
    is a (postop-folded) single-level JsonGet over the record value,
    None otherwise; raises for nested/structural JsonGet args."""
    expr = arg
    while isinstance(expr, (dsl.Upper, dsl.Lower)):
        expr = expr.arg
    if not isinstance(expr, dsl.JsonGet):
        return None
    pre = _value_postops_mirror(expr.arg)
    if pre is None:
        raise _NotStriped("striped JsonGet must read the record value")
    return expr.key


def _striped_json_literal_check(lit: bytes, v: int) -> None:
    """Mirror of `stripes._lower_striped_json_literal`'s overlap gate
    (every kind needs containment — the field can start anywhere)."""
    if len(lit) > v:
        raise _NotStriped(
            f"JsonGet-sourced literal of {len(lit)} bytes exceeds the "
            f"stripe overlap ({v})"
        )


def _striped_predicate_check(expr, gates, s: int, v: int, declines) -> None:
    """Mirror of `stripes.lower_striped_predicate` (argument order
    included, so predicted declines count like runtime ones)."""
    if isinstance(expr, (dsl.And, dsl.Or)):
        for a in expr.args:
            _striped_predicate_check(a, gates, s, v, declines)
        return
    if isinstance(expr, dsl.Not):
        _striped_predicate_check(expr.arg, gates, s, v, declines)
        return
    if isinstance(expr, dsl.Cmp):
        _seg_exact_check(expr)
        return
    if isinstance(expr, (dsl.Contains, dsl.StartsWith, dsl.EndsWith)):
        kind = {
            dsl.Contains: "contains",
            dsl.StartsWith: "startswith",
            dsl.EndsWith: "endswith",
        }[type(expr)]
        if _jsonget_source_mirror(expr.arg) is not None:
            try:
                _striped_json_literal_check(expr.literal, v)
                return
            except _NotStriped:
                pass  # overlap-exceeding: in-span DFA
            _striped_dfa_gate_check(
                _striped_literal_regex(expr.literal, kind), declines
            )
            return
        postops = _value_postops_mirror(expr.arg)
        if postops is None:
            _seg_exact_check(expr)
            return
        try:
            _striped_literal_check(kind, expr.literal, s, v)
            return
        except _NotStriped:
            pass  # overlap-exceeding literal: chains as a DFA
        _striped_dfa_gate_check(
            _striped_literal_regex(expr.literal, kind), declines
        )
        return
    if isinstance(expr, dsl.RegexMatch):
        if _jsonget_source_mirror(expr.arg) is not None:
            info = literal_of(expr.pattern)
            if info is not None:
                try:
                    _striped_json_literal_check(info[0], v)
                    return
                except _NotStriped:
                    pass  # overlap-exceeding: in-span DFA
            _striped_dfa_gate_check(expr.pattern, declines)
            return
        postops = _value_postops_mirror(expr.arg)
        if postops is None:
            raise _NotStriped("striped regex must read the record value")
        info = literal_of(expr.pattern)
        if info is not None:
            lit, a_start, a_end = info
            if a_start and a_end:
                kind = "equals"
            elif a_start:
                kind = "startswith"
            elif a_end:
                kind = "endswith"
            else:
                kind = "contains"
            try:
                _striped_literal_check(kind, lit, s, v)
                return
            except _NotStriped:
                pass  # overlap-exceeding literal: chains as a DFA
        _striped_dfa_gate_check(expr.pattern, declines)
        return
    raise _NotStriped(f"{type(expr).__name__} not stripeable as a predicate")


def _striped_literal_regex(lit: bytes, kind: str) -> str:
    """Mirror of `stripes._literal_regex` (keep byte-for-byte equal —
    the compiled DFA's state count must match the runtime's)."""
    body = "".join(f"\\x{b:02x}" for b in lit)
    pre = "^" if kind in ("startswith", "equals") else ""
    post = "$" if kind in ("endswith", "equals") else ""
    return pre + body + post


def _striped_dfa_gate_check(pattern: str, declines) -> None:
    """Mirror of `stripes._striped_dfa_gate`: the runtime fires the
    decline AND abandons the striped build (distinct reason from
    dfa-assoc-states: the consequence is an interpreter spill, not a
    slower scan; dfa-classes-overflow when the packed class ceiling
    reduced the limit)."""
    try:
        dfa = compile_regex_cached(pattern)
    except UnsupportedRegex as e:
        raise _NotStriped(str(e)) from e
    limit, reason = _effective_dfa_limit(dfa)
    if dfa.n_states > limit:
        declines.append(reason or "dfa-stripe-states")
        raise _NotStriped(
            f"DFA of {dfa.n_states} states exceeds the associative "
            "gate (FLUVIO_DFA_ASSOC_MAX_STATES)"
        )


def _effective_dfa_limit(dfa):
    """The runtime's per-DFA gate, verbatim (class-ceiling fallback
    included) — predictions must stay differential-exact."""
    from fluvio_tpu.smartengine.tpu import kernels

    return kernels.dfa_effective_max_states(dfa)


def _striped_view_mirror(value):
    """Mirror of `stripes._striped_view` classification."""
    expr = value
    while isinstance(expr, (dsl.Upper, dsl.Lower)):
        expr = expr.arg
    if isinstance(expr, dsl.JsonGet):
        pre = _value_postops_mirror(expr.arg)
        if pre is None:
            raise _NotStriped("striped JsonGet must read the record value")
        return "span"
    post = _value_postops_mirror(value)
    if post is None:
        raise _NotStriped("striped map must transform the record value")
    return "postops"


def striped_report(
    programs, gates
) -> Tuple[bool, List[str], List[str], bool]:
    """(stripeable, predicted declines, causes, has_fanout) for the
    striped build — the mirror of the executor's `_striped_chain`
    preconditions plus `stripes.try_build_striped`. Declines listed
    here fire at the LAZY striped build (the first wide batch)."""
    from fluvio_tpu.smartengine.tpu.stripes import stripe_params

    declines: List[str] = []
    causes: List[str] = []
    s, v = stripe_params()

    has_fanout = any(isinstance(p, dsl.ArrayMapProgram) for p in programs)
    has_agg = any(isinstance(p, dsl.AggregateProgram) for p in programs)
    map_writes_keys = any(
        isinstance(p, (dsl.MapProgram, dsl.FilterMapProgram))
        and p.key is not None
        for p in programs
    )
    # the executor only attempts the striped build for chains whose
    # outputs ship as descriptors/masks/ints (viewable or int-output)
    viewable = not has_agg and all(
        isinstance(p, (dsl.FilterProgram, dsl.ArrayMapProgram))
        or (
            isinstance(p, (dsl.MapProgram, dsl.FilterMapProgram))
            and _is_span_value(p.value)
            and p.key is None
        )
        for p in programs
    )
    int_output = (
        bool(programs)
        and isinstance(programs[-1], dsl.AggregateProgram)
        and not has_fanout
        and not map_writes_keys
    )
    if not (viewable or int_output):
        causes.append(
            "chain outputs are not descriptor/mask/int-shippable "
            "(striped build never attempted)"
        )
        return False, declines, causes, has_fanout

    span = False
    agg = False
    fanout = False
    try:
        for prog in programs:
            if fanout or (agg and not isinstance(prog, dsl.AggregateProgram)):
                raise _NotStriped("stage after a striped terminal stage")
            if isinstance(prog, dsl.FilterProgram):
                if span:
                    raise _NotStriped("filter after a striped span map")
                _striped_predicate_check(prog.predicate, gates, s, v, declines)
            elif isinstance(prog, (dsl.MapProgram, dsl.FilterMapProgram)):
                if isinstance(prog, dsl.FilterMapProgram):
                    if span:
                        raise _NotStriped("filter after a striped span map")
                    _striped_predicate_check(
                        prog.predicate, gates, s, v, declines
                    )
                if prog.key is not None:
                    raise _NotStriped("striped map cannot rewrite keys")
                if _striped_view_mirror(prog.value) == "span":
                    if span:
                        raise _NotStriped("one striped span map per chain")
                    span = True
            elif isinstance(prog, dsl.AggregateProgram):
                if span:
                    raise _NotStriped("aggregate after a striped span map")
                if prog.contribution is not None:
                    _seg_exact_check(prog.contribution)
                elif prog.kind == "word_count":
                    raise _NotStriped("word_count is not stripeable")
                agg = True
            elif isinstance(prog, dsl.ArrayMapProgram):
                if prog.mode != "split" or len(prog.sep) != 1:
                    # the "json_array explode" spill family
                    raise _NotStriped(
                        "striped array_map supports single-byte split only"
                    )
                if agg or span:
                    raise _NotStriped("striped fan-out after aggregate/span")
                fanout = True
            else:
                raise _NotStriped(f"{type(prog).__name__} not stripeable")
    except _NotStriped as e:
        causes.append(str(e))
        return False, declines, causes, has_fanout
    return True, declines, causes, has_fanout


# ---------------------------------------------------------------------------
# Path prediction
# ---------------------------------------------------------------------------


def _bucketed(width: int) -> int:
    from fluvio_tpu.smartengine.tpu.buffer import bucket_width

    return bucket_width(max(width, 1))


def predict_path(
    width: int,
    gates: dict,
    narrow_ok: bool,
    narrow_declines: Sequence[str],
    striped_ok: bool,
    striped_declines: Sequence[str],
    striped_causes: Sequence[str],
    has_fanout: bool,
    sharded: bool = False,
) -> PathPrediction:
    """The dispatch-time width ladder, as one pure function."""
    bucket = _bucketed(width)
    if not narrow_ok:
        return PathPrediction(
            width, bucket, "interpreter",
            causes=("chain is not TPU-lowerable",),
        )
    if bucket > gates["max_record_width"]:
        # RecordBuffer refuses to stage: TpuSpill("record-too-wide")
        return PathPrediction(
            width, bucket, "interpreter",
            spill_reasons=("record-too-wide",),
            causes=(
                f"record bucket {bucket} exceeds the striped layout's "
                f"hard ceiling ({gates['max_record_width']})",
            ),
        )
    if bucket > gates["stripe_threshold"]:
        if sharded and has_fanout:
            return PathPrediction(
                width, bucket, "interpreter",
                spill_reasons=("record-too-wide-unstripeable",),
                causes=("sharded fan-out cannot stage striped",),
            )
        if striped_ok:
            return PathPrediction(
                width, bucket, "striped",
                declines=tuple(striped_declines),
            )
        return PathPrediction(
            width, bucket, "interpreter",
            spill_reasons=("record-too-wide-unstripeable",),
            declines=tuple(striped_declines),
            causes=tuple(striped_causes),
        )
    return PathPrediction(
        width, bucket, "fused", declines=tuple(narrow_declines)
    )


def down_profile(programs) -> str:
    """Which D2H representation family a chain's results ship in — the
    static mirror of the executor's `_viewable`/`_identity_view`/
    `_int_output` build-time flags. Returns one of:

    - "identity": filter-only — the 1-bit mask is the whole download
    - "ints": chain ends in an aggregate — delta-narrowed int columns
    - "desc": view/fan-out survivors — (start, len) descriptor blocks
      (the encode ladder's first target)
    - "bytes": byte-mode value columns (packs to ONE flat payload; the
      encode ladder's second target)
    """
    has_agg = any(isinstance(p, dsl.AggregateProgram) for p in programs)
    if not has_agg and all(
        isinstance(p, dsl.FilterProgram) for p in programs
    ):
        return "identity"
    if programs and isinstance(programs[-1], dsl.AggregateProgram):
        # int-output excludes chains where a map rewrote keys on device
        if not any(
            isinstance(p, dsl.ArrayMapProgram) for p in programs
        ) and not any(
            isinstance(p, (dsl.MapProgram, dsl.FilterMapProgram))
            and p.key is not None
            for p in programs
        ):
            return "ints"
    if not has_agg and all(
        isinstance(p, (dsl.FilterProgram, dsl.ArrayMapProgram))
        or (
            isinstance(p, (dsl.MapProgram, dsl.FilterMapProgram))
            and p.key is None
            and _span_lowerable(p)
        )
        for p in programs
    ):
        return "desc"
    return "bytes"


def _mentions_jsonget(e) -> bool:
    """Generic expr walk: does this DSL expression contain a JsonGet?
    (The striped builder only ships span DESCRIPTORS for JsonGet views;
    whole-record views ship the mask alone — `stripes.has_span`.)"""
    if isinstance(e, dsl.JsonGet):
        return True
    if hasattr(e, "__dataclass_fields__"):
        for f in e.__dataclass_fields__:
            v = getattr(e, f, None)
            if isinstance(v, dsl.Expr) and _mentions_jsonget(v):
                return True
    return False


def _span_lowerable(prog) -> bool:
    """Does this map's value lower as a VIEW of the record's own bytes
    (the executor's `lower_span`)? Mirrored without lowering."""
    from fluvio_tpu.smartengine.tpu.lower import lower_span

    try:
        return lower_span(prog.value) is not None
    except Exception:  # noqa: BLE001 — mirror of try_build's tolerance
        return False


def predict_down_variant(
    gates: dict, path: str, profile: str, sharded: bool,
    striped_span: bool = False,
) -> str:
    """Which form a batch's results cross the D2H link in on this path
    — the mirror of the executor's fetch-side variant selection
    (`_count_down_variant`). Interpreter batches never fetch ("down-
    raw"); identity/int chains always ship their packed representation;
    descriptor and payload streams encode when the ladder is armed
    (sharded: narrow descriptor chains only — sharded striped and
    sharded byte-mode keep their raw/packed ship)."""
    if path == "interpreter":
        return "down-raw"
    if profile in ("identity", "ints"):
        return "down-packed"
    if profile == "bytes":
        if sharded or not gates.get("result_compact"):
            return "down-raw"
        if not gates.get("result_compress"):
            return "down-packed"
    else:  # desc
        if path == "striped" and (sharded or not striped_span):
            # striped whole-record views ship the mask alone; sharded
            # striped keeps the raw descriptor ship
            return "down-packed"
        if not gates.get("result_compress"):
            return "down-packed"
    return "down-glz-xla"


def predict_window_variant(programs, gates: dict) -> str:
    """Which emission form a windowed aggregate ships its state in —
    the mirror of `windows.spec.delta_enabled` applied to chains that
    actually carry a windowed stage. "off" when nothing is windowed."""
    windowed = any(
        isinstance(p, dsl.AggregateProgram) and getattr(p, "window_ms", 0)
        for p in programs
    )
    if not windowed:
        return "off"
    return "win-delta" if gates.get("window_delta") else "win-full"


def analyze_entries(
    entries,
    widths: Optional[Sequence[int]] = None,
    sharded: bool = False,
) -> ChainReport:
    """Level-1 report for a chain of (SmartModuleDef, SmartModuleConfig)
    entries. ``widths`` are the max record value widths to probe (the
    default probes one narrow and one past-threshold width so the report
    covers both regimes)."""
    gates = resolve_gates()
    if widths is None:
        widths = (1024, gates["stripe_threshold"] + 1)
    programs, hazards = resolved_programs(entries)
    if programs is None:
        report = ChainReport("unlowerable", gates, hazards=hazards)
        report.predictions = [
            PathPrediction(w, _bucketed(w), "interpreter",
                           causes=("chain is not TPU-lowerable",))
            for w in widths
        ]
        return report

    narrow_ok, narrow_declines, problems = narrow_report(programs, gates)
    striped_ok, striped_declines, striped_causes, has_fanout = striped_report(
        programs, gates
    )
    report = ChainReport(chain_sig(programs), gates, hazards=hazards)
    for p in problems:
        report.hazards.append(
            Hazard(ERROR, "unlowerable",
                   f"chain cannot lower ({p}): every batch runs interpreted")
        )
    for reason in narrow_declines:
        if reason == "dfa-classes-overflow":
            detail = (
                "regex DFA's byte-class count exceeds the packed ceiling, "
                "so only the legacy state gate applies: the narrow build "
                "declines the associative path and keeps the O(L) "
                "sequential scan"
            )
        else:
            detail = (
                "regex DFA exceeds FLUVIO_DFA_ASSOC_MAX_STATES "
                f"({gates['dfa_assoc_max_states']}): the narrow build "
                "declines the associative path and keeps the O(L) "
                "sequential scan"
            )
        report.hazards.append(Hazard(WARN, "decline:" + reason, detail))
    for prog in programs:
        if isinstance(prog, dsl.ArrayMapProgram) and prog.mode == "json_array":
            report.hazards.append(
                Hazard(
                    INFO, "data-dependent-spill",
                    "json_array explode: a malformed array spills the "
                    "batch to the interpreter (transform-error)",
                )
            )
    for w in widths:
        pred = predict_path(
            w, gates, narrow_ok, narrow_declines,
            striped_ok, striped_declines, striped_causes,
            has_fanout, sharded=sharded,
        )
        pred.window_variant = predict_window_variant(programs, gates)
        pred.down_variant = predict_down_variant(
            gates, pred.path, down_profile(programs), sharded,
            striped_span=any(
                isinstance(p, (dsl.MapProgram, dsl.FilterMapProgram))
                and _mentions_jsonget(p.value)
                for p in programs
            ),
        )
        report.predictions.append(pred)
        if pred.path == "interpreter" and narrow_ok:
            report.hazards.append(
                Hazard(
                    ERROR, "spill:" + (pred.spill_reasons or ("unknown",))[0],
                    f"records of width {w} spill to the interpreter: "
                    + "; ".join(pred.causes),
                )
            )
        if pred.declines and pred.path == "striped":
            for reason in pred.declines:
                report.hazards.append(
                    Hazard(WARN, "decline:" + reason,
                           f"striped build declines at width {w}: {reason}")
                )
    return report


def analyze_named(
    specs: Sequence[Tuple[str, Optional[dict]]],
    widths: Optional[Sequence[int]] = None,
    sharded: bool = False,
) -> ChainReport:
    """`analyze_entries` over built-in model registry names (the bench
    matrix's spec format): ``[(name, params), ...]``."""
    from fluvio_tpu.models import lookup
    from fluvio_tpu.smartengine.config import SmartModuleConfig

    entries = [
        (lookup(name), SmartModuleConfig(params=dict(params or {})))
        for name, params in specs
    ]
    return analyze_entries(entries, widths=widths, sharded=sharded)


def analyze_partitioned(
    entries_by_topic: Dict[str, Sequence],
    plan,
    widths: Optional[Sequence[int]] = None,
    sharded: bool = False,
) -> dict:
    """Partitioned-path preflight: per-partition chain families.

    One :func:`analyze_entries` report per topic's chain family, fanned
    out over the placement plan's partitions. Placement changes nothing
    about a chain's lowering — every partition of a topic executes the
    SAME predicted path ladder — so the fan-out is pure identity: each
    row names the partition's ``chain@topic/partition`` telemetry
    family (what the differential tests and SLO verdicts key on) and
    its device group. ``errors`` aggregates ERROR hazards across the
    families (the ``analyze --partitions`` rc-1 gate).
    """
    reports = {
        topic: analyze_entries(entries, widths=widths, sharded=sharded)
        for topic, entries in entries_by_topic.items()
    }
    rows: List[dict] = []
    for key, group in plan.rows():
        topic = key.rsplit("/", 1)[0]
        report = reports.get(topic)
        if report is None:
            continue
        for pred in report.predictions:
            rows.append(
                {
                    "partition": key,
                    "group": group,
                    "chain": f"{report.chain_sig}@{key}",
                    **pred.to_dict(),
                }
            )
    return {
        "plan": plan.to_dict(),
        "chains": {t: r.to_dict() for t, r in reports.items()},
        "rows": rows,
        "errors": sum(len(r.errors()) for r in reports.values()),
    }
