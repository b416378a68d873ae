"""DSL expression -> JAX kernel lowering.

Compiles resolved (param-substituted) DSL expression trees into functions
over the chain state (values/lengths/keys/key_lengths arrays). Types are
inferred: ``bytes`` results are (values u8[N, W], lengths i32[N]) pairs,
``int`` is i64[N], ``bool`` is bool[N]. Regex-family predicates compile to
DFA tables at lowering time; an unsupported pattern raises
:class:`Unlowerable` and the builder falls back to the python backend.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax.numpy as jnp

from fluvio_tpu.analysis.envreg import env_raw
from fluvio_tpu.ops.regex_dfa import UnsupportedRegex, compile_regex_cached, literal_of
from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.smartengine.tpu import kernels, pallas_kernels
from fluvio_tpu.telemetry import TELEMETRY


class Unlowerable(Exception):
    """Expression/program outside the TPU-compilable subset."""


# state dict keys: values, lengths, keys, key_lengths
BytesVal = Tuple[jnp.ndarray, jnp.ndarray]


def _depth_over_work(env: str) -> bool:
    """Resolve an auto/1/0 kernel-policy knob by backend: "auto"
    (default) picks the log-depth parallel kernel off-CPU only — the
    TPU's VPU is latency-bound on sequential column
    scans, while CPU lanes are work-bound and the parallel forms' S x
    work multiplier measurably loses there (4-20x on the headline
    shapes). Explicit off values pin the sequential kernel; anything
    else pins the parallel one."""
    mode = (env_raw(env) or "auto").lower()
    if mode in ("auto", ""):
        import jax

        return jax.default_backend() != "cpu"
    return mode not in ("0", "off", "false", "no")


def _json_span_fn(key: str):
    """Span kernel chooser shared by byte and descriptor lowering.

    Both XLA kernels are bit-identical on every input (the
    structural-index kernel's string/escape automaton runs on the exact
    transition-composition engine), so the choice is pure policy:
    ``FLUVIO_TPU_FAST_JSON`` auto/1/0 via `_depth_over_work` — scan-free
    structural indexing off-CPU, the sequential scan on CPU.
    """
    fast = _depth_over_work("FLUVIO_TPU_FAST_JSON")
    xla_kernel = kernels.json_get_parallel_span if fast else kernels.json_get_span

    def span(v, l):
        # single-pass pallas state machine when the platform has it:
        # collapses ~12 XLA primitives into one kernel AND carries the
        # exact sequential semantics (dsl.json_get_bytes)
        if pallas_kernels.pallas_active(v.shape[1]):
            return pallas_kernels.json_get_span_pallas(
                v, l, key, interpret=pallas_kernels.interpret_mode()
            )
        return xla_kernel(v, l, key)

    return span


def materialize_span(values: jnp.ndarray, start: jnp.ndarray, lengths: jnp.ndarray):
    """Per-record substring gather — single home for the pallas/XLA
    extract dispatch shared by byte-mode JsonGet, view-stage
    materialization, and the fan-out stage."""
    if pallas_kernels.pallas_active(values.shape[1]):
        return pallas_kernels.extract_pallas(
            values, start, lengths, interpret=pallas_kernels.interpret_mode()
        )
    return kernels.extract_span(values, start, lengths)


def lower_span(expr: dsl.Expr):
    """Descriptor lowering: ``(fn, postops)`` where ``fn(state) ->
    (start, length)`` within the CURRENT value bytes, or ``None`` when
    the expression's output is not a (position-wise transformed) view of
    them.

    This is what makes late materialization possible: chains whose final
    values are views of the stored record bytes ship (row, start, length)
    descriptors over the host link instead of the bytes themselves, and
    the host rebuilds outputs from the slab it already holds. ``postops``
    is a static tuple of length-preserving byte-wise transforms
    (``"upper"``/``"lower"``) the host applies after the gather — they
    commute with slicing, so spans computed on folded bytes are valid
    positions in the original.
    """
    if isinstance(expr, dsl.Value):
        return (lambda s: (jnp.zeros_like(s["lengths"]), s["lengths"])), ()

    if isinstance(expr, (dsl.Upper, dsl.Lower)):
        inner = lower_span(expr.arg)
        if inner is None:
            return None
        fn, post = inner
        tag = "upper" if isinstance(expr, dsl.Upper) else "lower"
        return fn, post + (tag,)

    if isinstance(expr, dsl.JsonGet):
        inner = lower_span(expr.arg)
        if inner is None:
            return None
        inner_fn, inner_post = inner
        inner_bytes = lower_expr(expr.arg)
        span = _json_span_fn(expr.key)

        def fn(s):
            v, l = inner_bytes(s)
            st, ln = span(v, l)
            ist, _ = inner_fn(s)
            return ist + st, ln

        return fn, inner_post

    return None


def apply_postops(values: jnp.ndarray, postops) -> jnp.ndarray:
    """Apply static span postops on device (host mirror:
    `buffer.apply_postops_host`)."""
    for op in postops:
        values = (
            kernels.ascii_upper(values) if op == "upper" else kernels.ascii_lower(values)
        )
    return values


def infer_type(expr: dsl.Expr) -> str:
    if isinstance(expr, (dsl.Value, dsl.Key, dsl.Const, dsl.Upper, dsl.Lower,
                         dsl.Concat, dsl.JsonGet, dsl.IntToBytes)):
        return "bytes"
    if isinstance(expr, (dsl.Len, dsl.ParseInt)):
        return "int"
    if isinstance(expr, (dsl.RegexMatch, dsl.Contains, dsl.StartsWith,
                         dsl.EndsWith, dsl.Cmp, dsl.And, dsl.Or, dsl.Not)):
        return "bool"
    raise Unlowerable(f"cannot type {type(expr).__name__}")


def lower_expr(expr: dsl.Expr) -> Callable[[Dict[str, jnp.ndarray]], object]:
    """Lower one expression; returns fn(state) -> typed result."""

    if isinstance(expr, dsl.Value):
        return lambda s: (s["values"], s["lengths"])

    if isinstance(expr, dsl.Key):
        # null key reads as b"" (parity with the interpreter)
        return lambda s: (s["keys"], jnp.maximum(s["key_lengths"], 0))

    if isinstance(expr, dsl.Const):
        import numpy as np

        data = np.frombuffer(expr.data, dtype=np.uint8)
        width = max(len(data), 1)

        def const_fn(s):
            n = s["values"].shape[0]
            vals = jnp.broadcast_to(jnp.asarray(data), (n, len(data))) if len(data) else jnp.zeros((n, width), dtype=jnp.uint8)
            lens = jnp.full((n,), len(data), dtype=jnp.int32)
            return vals, lens

        return const_fn

    if isinstance(expr, (dsl.Upper, dsl.Lower)):
        inner = lower_expr(expr.arg)
        op = kernels.ascii_upper if isinstance(expr, dsl.Upper) else kernels.ascii_lower

        def case_fn(s):
            v, l = inner(s)
            return op(v), l

        return case_fn

    if isinstance(expr, dsl.JsonGet):
        inner = lower_expr(expr.arg)
        span = _json_span_fn(expr.key)

        def json_fn(s):
            v, l = inner(s)
            st, ln = span(v, l)
            return materialize_span(v, st, ln), ln

        return json_fn

    if isinstance(expr, (dsl.RegexMatch, dsl.Contains, dsl.StartsWith, dsl.EndsWith)):
        inner = lower_expr(expr.arg)

        def _literal_fn(lit: bytes, anchor_start: bool, anchor_end: bool):
            def fn(s):
                v, l = inner(s)
                if anchor_start and anchor_end:
                    return kernels.literal_startswith(v, l, lit) & (l == len(lit))
                if anchor_start:
                    return kernels.literal_startswith(v, l, lit)
                if anchor_end:
                    return kernels.literal_endswith(v, l, lit)
                return kernels.literal_search(v, l, lit)

            return fn

        if isinstance(expr, dsl.Contains):
            return _literal_fn(expr.literal, False, False)
        if isinstance(expr, dsl.StartsWith):
            return _literal_fn(expr.literal, True, False)
        if isinstance(expr, dsl.EndsWith):
            return _literal_fn(expr.literal, False, True)

        # RegexMatch: windowed-compare fast path for pure literals,
        # DFA execution otherwise
        lit_info = literal_of(expr.pattern)
        if lit_info is not None:
            return _literal_fn(*lit_info)
        try:
            dfa = compile_regex_cached(expr.pattern)
        except UnsupportedRegex as e:
            raise Unlowerable(str(e)) from e
        # backend policy first (FLUVIO_DFA_ASSOC auto/1/0: the S x work
        # multiplier loses on the work-bound CPU backend — same policy
        # as the JSON kernel above), then the state-count gate; only a
        # gate trip on a backend that WANTED the associative path counts
        # as a decline
        assoc_ok = _depth_over_work("FLUVIO_DFA_ASSOC")
        if assoc_ok:
            limit, reason = kernels.dfa_effective_max_states(dfa)
            if dfa.n_states > limit:
                assoc_ok = False
                TELEMETRY.add_decline(reason or "dfa-assoc-states")

        def regex_fn(s):
            v, l = inner(s)
            # pallas select-chain scan (2 primitives) over any XLA path
            # when the platform + DFA size allow
            if pallas_kernels.pallas_active(v.shape[1]) and pallas_kernels.dfa_supported(dfa):
                return pallas_kernels.dfa_match_pallas(
                    v, l, dfa, interpret=pallas_kernels.interpret_mode()
                )
            if assoc_ok:
                # associative transition composition: O(log L) depth
                # instead of the sequential scan's O(L) steps
                return kernels.dfa_match_assoc(v, l, dfa)
            return kernels.dfa_match(v, l, dfa)

        return regex_fn

    if isinstance(expr, dsl.Len):
        inner = lower_expr(expr.arg)

        def len_fn(s):
            _, l = inner(s)
            return l.astype(jnp.int64)

        return len_fn

    if isinstance(expr, dsl.ParseInt):
        inner = lower_expr(expr.arg)

        def parse_fn(s):
            v, l = inner(s)
            return kernels.parse_int(v, l)

        return parse_fn

    if isinstance(expr, dsl.IntToBytes):
        inner = lower_expr(expr.arg)
        if infer_type(expr.arg) != "int":
            raise Unlowerable("IntToBytes needs an int argument")

        def render_fn(s):
            return kernels.int_to_ascii(inner(s))

        return render_fn

    if isinstance(expr, dsl.Cmp):
        lt, rt = infer_type(expr.left), infer_type(expr.right)
        if lt != "int" or rt != "int":
            raise Unlowerable("Cmp lowers only for int operands")
        lf, rf = lower_expr(expr.left), lower_expr(expr.right)
        ops = {
            "eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less,
            "le": jnp.less_equal, "gt": jnp.greater, "ge": jnp.greater_equal,
        }
        op = ops[expr.cmp]
        return lambda s: op(lf(s), rf(s))

    if isinstance(expr, dsl.And):
        fns = [lower_expr(a) for a in expr.args]

        def and_fn(s):
            out = fns[0](s)
            for f in fns[1:]:
                out = out & f(s)
            return out

        return and_fn

    if isinstance(expr, dsl.Or):
        fns = [lower_expr(a) for a in expr.args]

        def or_fn(s):
            out = fns[0](s)
            for f in fns[1:]:
                out = out | f(s)
            return out

        return or_fn

    if isinstance(expr, dsl.Not):
        inner = lower_expr(expr.arg)
        return lambda s: ~inner(s)

    if isinstance(expr, dsl.Concat):
        fns = [lower_expr(a) for a in expr.args]

        def concat_fn(s):
            parts = [f(s) for f in fns]
            widths = [p[0].shape[1] for p in parts]
            total_w = sum(widths)
            n = parts[0][0].shape[0]
            out_len = sum(p[1] for p in parts).astype(jnp.int32)
            out = jnp.zeros((n, total_w), dtype=jnp.uint8)
            # write each part at its running start offset via scatter-free
            # gather: out[:, j] selects from the part covering position j
            j = jnp.arange(total_w, dtype=jnp.int32)[None, :]
            starts = jnp.zeros((n,), dtype=jnp.int32)
            for (pv, pl) in parts:
                pl = pl.astype(jnp.int32)
                rel = j - starts[:, None]
                in_part = (rel >= 0) & (rel < pl[:, None])
                gathered = jnp.take_along_axis(
                    pv, jnp.clip(rel, 0, pv.shape[1] - 1), axis=1
                )
                out = jnp.where(in_part, gathered, out)
                starts = starts + pl
            return out, out_len

        return concat_fn

    raise Unlowerable(f"no lowering for {type(expr).__name__}")
