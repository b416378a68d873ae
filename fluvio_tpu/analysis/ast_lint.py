"""Level-3 preflight: an `ast`-based linter for engine invariants.

PR 5 hand-fixed a whole bug class — weak Python-int literals lowering
to i64 inside pallas kernels (Mosaic's convert lowering recurses
infinitely on the resulting i64->i32 casts under the package-wide
x64). This linter turns that class, and the other invariants the
TPU engine modules must hold, into mechanical CI checks:

Kernel rules (``smartengine/tpu/`` — kernels.py, pallas_kernels.py,
stripes.py, lower.py):

- **FLV001** ``jnp.where``/``jnp.select``/``lax.select`` with BOTH
  value branches bare numeric literals: both-weak promotion produces a
  64-bit result under process-wide x64 (a weak literal paired with an
  array operand safely defers to the array dtype — only the
  both-literal form promotes).
- **FLV002** inside pallas kernel bodies (functions named ``*_kernel``),
  ANY bare int literal in a value position — ``jnp.where`` branches,
  ``fori_loop`` bounds, ``jnp.full``/``full_like`` fill without an
  explicit ``dtype=`` — must be pinned (``jnp.int32(...)``): Mosaic
  cannot lower the i64 converts an unpinned literal drags in.
- **FLV003** no host syncs in device/trace code: ``.item()``,
  ``.block_until_ready()``, ``jax.device_get(...)`` are forbidden in
  the kernel modules and in the executor's dispatch-side hot functions
  (the fetch side legitimately materializes).
- **FLV004** telemetry seams stay zero-cost: engine modules may touch
  ``TELEMETRY`` only through the guarded seam API (counter adds,
  begin/end batch, gauge_add/gauge_set, ``enabled``) — never registry
  internals, whose cost is not covered by the ``FLUVIO_TELEMETRY=0``
  zero-cost contract.

Repo-wide hygiene rules (the curated subset `ruff` would enforce,
kept native so the gate holds even where ruff is not installed):

- **FLV101** mutable default argument (list/dict/set literal or call).
- **FLV102** unused import (module scope; ``__init__.py`` re-export
  surfaces exempt; ``# noqa`` honored).

Suppression: a ``# noqa`` comment on the flagged line silences any
rule; ``# noqa: FLV002`` silences one.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import List, Optional, Sequence

from fluvio_tpu.analysis.noqa import line_suppresses

KERNEL_MODULES = ("kernels.py", "pallas_kernels.py", "stripes.py", "lower.py")

# executor functions on the dispatch side of the pipeline (stage ->
# h2d -> device): a host sync here stalls the async dispatch overlap
DISPATCH_HOT_FUNCS = {
    "_dispatch", "_dispatch_inner", "dispatch_buffer", "_stage_flat",
    "_flat_and_bucket",
    "_chain_fn", "_chain_fn_ragged", "_chain_fn_striped",
    "ragged_repad_words", "derived_meta_columns", "stage_link_columns",
}

# the zero-cost-safe telemetry seam API (registry methods that are
# single-truthiness-check no-ops when capture is off, plus the always-on
# counter adds whose cost contract telemetry/registry.py documents)
ALLOWED_TELEMETRY_SEAMS = {
    "enabled", "begin_batch", "end_batch", "add_phase",
    "add_spill", "add_decline", "add_link_variant", "add_heal",
    "add_stripe_fallback",
    "add_retry", "add_quarantine", "add_compile", "add_jit_hit",
    "add_interp_instance", "add_breaker_short_circuit", "record_breaker",
    "add_slo_breach", "add_admission",
    "add_windows_closed", "add_window_delta", "add_window_downlink",
    "add_window_slice", "add_window_grow",
    "add_group_slice", "add_group_grow",
    "gauge_add", "gauge_set",
    "mem_acquire", "mem_release",
}

_WHERE_FUNCS = {"where", "select"}
_HOST_SYNC_METHODS = {"item", "block_until_ready"}


@dataclass
class LintViolation:
    path: str
    line: int
    col: int
    code: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.code} {self.message}"

    def to_dict(self) -> dict:
        return {
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "code": self.code,
            "message": self.message,
        }


def _names_in_string(text: str) -> set:
    """Identifier tokens of a quoted forward-reference annotation."""
    try:
        tree = ast.parse(text, mode="eval")
    except SyntaxError:
        return set()
    return {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}


def _is_bare_number(node: ast.AST) -> bool:
    """An unpinned numeric literal: ``0``, ``-1``, ``2**62``-style
    constant expressions of bare numbers."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float)) and not isinstance(
            node.value, bool
        )
    if isinstance(node, ast.UnaryOp) and isinstance(
        node.op, (ast.USub, ast.UAdd)
    ):
        return _is_bare_number(node.operand)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Pow):
        return _is_bare_number(node.left) and _is_bare_number(node.right)
    return False


def _call_name(node: ast.Call) -> Optional[str]:
    """Trailing attribute name of the called function ("where" for
    ``jnp.where``), or the bare name for ``where(...)``."""
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _call_root(node: ast.Call) -> Optional[str]:
    fn = node.func
    while isinstance(fn, ast.Attribute):
        fn = fn.value
    return fn.id if isinstance(fn, ast.Name) else None


class _FileLinter(ast.NodeVisitor):
    def __init__(
        self,
        path: str,
        tree: ast.Module,
        lines: List[str],
        kernel_module: bool,
        engine_module: bool,
        check_imports: bool,
    ):
        self.path = path
        self.tree = tree
        self.lines = lines
        self.kernel_module = kernel_module
        self.engine_module = engine_module
        self.check_imports = check_imports
        self.is_executor = os.path.basename(path) == "executor.py"
        self.violations: List[LintViolation] = []
        self._func_stack: List[str] = []

    # -- plumbing -----------------------------------------------------------

    def _suppressed(self, line: int, code: str) -> bool:
        # shared grammar (analysis/noqa.py): ruff/pyflakes aliases and
        # combined multi-analyzer comments both resolve there
        return line_suppresses(self.lines, line, code)

    def _flag(self, node: ast.AST, code: str, message: str) -> None:
        line = getattr(node, "lineno", 1)
        if self._suppressed(line, code):
            return
        self.violations.append(
            LintViolation(self.path, line, getattr(node, "col_offset", 0),
                          code, message)
        )

    def _in_kernel_body(self) -> bool:
        return any(name.endswith("_kernel") for name in self._func_stack)

    def _in_dispatch_hot(self) -> bool:
        return self.is_executor and any(
            name in DISPATCH_HOT_FUNCS for name in self._func_stack
        )

    # -- visitors -----------------------------------------------------------

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_mutable_defaults(node)
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _check_mutable_defaults(self, node) -> None:
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]
        for d in defaults:
            mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call)
                and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set")
            )
            if mutable:
                self._flag(
                    d, "FLV101",
                    f"mutable default argument in {node.name}(): evaluated "
                    "once and shared across calls",
                )

    def visit_Call(self, node: ast.Call) -> None:
        name = _call_name(node)
        root = _call_root(node)
        if self.kernel_module or self.is_executor:
            self._check_host_sync(node, name, root)
        if self.kernel_module:
            self._check_weak_literals(node, name, root)
        # TELEMETRY.<attr>(...) calls are covered by visit_Attribute via
        # generic_visit — a call-side check here would double-flag them.
        self.generic_visit(node)

    def _check_host_sync(self, node: ast.Call, name, root) -> None:
        in_scope = self.kernel_module or self._in_dispatch_hot()
        if not in_scope:
            return
        if name in _HOST_SYNC_METHODS and isinstance(node.func, ast.Attribute):
            self._flag(
                node, "FLV003",
                f".{name}() in device/dispatch code: a host sync here "
                "stalls the async pipeline",
            )
        elif name == "device_get" and root == "jax":
            self._flag(
                node, "FLV003",
                "jax.device_get in device/dispatch code: a host sync here "
                "stalls the async pipeline",
            )

    def _check_weak_literals(self, node: ast.Call, name, root) -> None:
        in_kernel = self._in_kernel_body()
        if name in _WHERE_FUNCS and root in ("jnp", "lax", "jax", "np"):
            value_args = node.args[1:3]
            if len(value_args) == 2 and all(
                _is_bare_number(a) for a in value_args
            ):
                self._flag(
                    node, "FLV001",
                    f"{root}.{name} with two bare literal branches promotes "
                    "weak 64-bit under process-wide x64: pin at least one "
                    "(jnp.int32(...)/jnp.int64(...))",
                )
            elif in_kernel and any(_is_bare_number(a) for a in value_args):
                self._flag(
                    node, "FLV002",
                    f"bare int literal in a {root}.{name} value branch "
                    "inside a pallas kernel body: pin it (jnp.int32(...)) — "
                    "Mosaic cannot lower the i64 converts weak literals "
                    "drag in",
                )
        if in_kernel and name == "fori_loop":
            for a in node.args[:2]:
                if _is_bare_number(a):
                    self._flag(
                        node, "FLV002",
                        "bare int fori_loop bound inside a pallas kernel "
                        "body: pin it (jnp.int32(...)) — the i64 index "
                        "poisons every use site",
                    )
        if in_kernel and name in ("full", "full_like"):
            has_dtype = any(kw.arg == "dtype" for kw in node.keywords)
            fill_idx = 1
            if not has_dtype and len(node.args) > fill_idx and _is_bare_number(
                node.args[fill_idx]
            ):
                self._flag(
                    node, "FLV002",
                    f"{name} with a bare literal fill and no dtype= inside "
                    "a pallas kernel body: the fill's weak dtype decides "
                    "the array dtype",
                )

    def visit_Attribute(self, node: ast.Attribute) -> None:
        # TELEMETRY.<internal> reads outside calls (e.g. TELEMETRY.spans)
        if (
            self.engine_module
            and isinstance(node.value, ast.Name)
            and node.value.id == "TELEMETRY"
            and node.attr not in ALLOWED_TELEMETRY_SEAMS
        ):
            self._flag(
                node, "FLV004",
                f"TELEMETRY.{node.attr} is outside the guarded seam API: "
                "engine modules must stay zero-cost under FLUVIO_TELEMETRY=0",
            )
        self.generic_visit(node)

    # -- unused imports -----------------------------------------------------

    def run_import_check(self) -> None:
        if not self.check_imports:
            return
        bound = []  # (name, node)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split(".")[0]
                    bound.append((name, node))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound.append((alias.asname or alias.name, node))
        if not bound:
            return
        used = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Name):
                used.add(node.id)
        # quoted forward references ("FileSlice", "Future[Tuple[int,
        # int]]") count as uses — but only strings in ANNOTATION
        # position, so a name mentioned in a docstring does not mask a
        # genuinely unused import
        for ann in self._annotation_nodes():
            for node in ast.walk(ann):
                if isinstance(node, ast.Constant) and isinstance(
                    node.value, str
                ):
                    used.update(_names_in_string(node.value))
        # names exported via __all__ strings count as used
        for node in self.tree.body:
            if (
                isinstance(node, ast.Assign)
                and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in node.targets
                )
                and isinstance(node.value, (ast.List, ast.Tuple))
            ):
                for elt in node.value.elts:
                    if isinstance(elt, ast.Constant) and isinstance(
                        elt.value, str
                    ):
                        used.add(elt.value)
        for name, node in bound:
            if name in used or name == "_":
                continue
            self._flag(
                node, "FLV102",
                f"import {name!r} is never used",
            )

    def _annotation_nodes(self):
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                for a in (
                    args.posonlyargs + args.args + args.kwonlyargs
                    + [args.vararg, args.kwarg]
                ):
                    if a is not None and a.annotation is not None:
                        yield a.annotation
                if node.returns is not None:
                    yield node.returns
            elif isinstance(node, ast.AnnAssign):
                yield node.annotation

    def run(self) -> List[LintViolation]:
        self.visit(self.tree)
        self.run_import_check()
        return self.violations


def lint_source(
    source: str,
    path: str = "<string>",
    kernel_module: Optional[bool] = None,
    engine_module: Optional[bool] = None,
    check_imports: Optional[bool] = None,
) -> List[LintViolation]:
    """Lint one source blob. Role flags default from the path: kernel
    rules for the four kernel modules, telemetry-seam rules for
    everything under ``smartengine/tpu/``, hygiene rules everywhere
    (``__init__.py`` re-export surfaces skip the unused-import rule)."""
    base = os.path.basename(path)
    norm = path.replace(os.sep, "/")
    in_tpu = "smartengine/tpu/" in norm
    if kernel_module is None:
        kernel_module = in_tpu and base in KERNEL_MODULES
    if engine_module is None:
        engine_module = in_tpu
    if check_imports is None:
        check_imports = base != "__init__.py"
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [
            LintViolation(path, e.lineno or 1, e.offset or 0, "FLV000",
                          f"syntax error: {e.msg}")
        ]
    return _FileLinter(
        path, tree, source.splitlines(), kernel_module, engine_module,
        check_imports,
    ).run()


def lint_paths(paths: Sequence[str]) -> List[LintViolation]:
    """Lint files and directories (recursing into ``*.py``)."""
    out: List[LintViolation] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [
                    d for d in dirnames
                    if d not in ("__pycache__", ".git", ".xla_cache")
                ]
                for f in sorted(filenames):
                    if f.endswith(".py"):
                        out.extend(lint_file(os.path.join(dirpath, f)))
        else:
            out.extend(lint_file(p))
    return out


def lint_file(path: str) -> List[LintViolation]:
    with open(path, "r", encoding="utf-8") as f:
        return lint_source(f.read(), path=path)


def lint_repo(root: Optional[str] = None) -> List[LintViolation]:
    """Lint the whole ``fluvio_tpu`` package (the CI gate's scope)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return lint_paths([root])
