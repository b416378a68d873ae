"""Level-4 preflight: whole-package lock-discipline analysis.

The engine is genuinely concurrent — the pipelined dispatch/finish
paths, the fetch materialization worker, metering watchdog threads, the
monitoring socket accept loop, and the native-build threads all share
mutable state behind ``threading.Lock``s — and PR 6's linter only
checks single-threaded kernel invariants. This pass makes the
concurrency layer itself statically checkable (the "verify before you
reconfigure" argument of arxiv 2304.01659 applied to our own broker):

1. **Guard-map inference.** Starting from every thread entry point
   (``threading.Thread`` targets, executor pool ``submit`` callables,
   asyncio socket handlers, plus the executor's pipelined
   dispatch/finish/heal/retry paths), walk the package call graph and
   infer which lock protects which shared attribute: state written
   under lock L somewhere is GUARDED BY L, and any access reachable
   from a thread root that skips L is a finding —

   - **FLV201** (error) unguarded WRITE to lock-guarded shared state,
   - **FLV202** (warn) unguarded READ of lock-guarded shared state.

2. **Lock-acquisition-order graph.** Every ``with lock:`` nesting and
   every call made while holding a lock (against a fixpoint
   may-acquire summary of the callee) contributes an edge; a cycle is
   a potential deadlock —

   - **FLV211** (error) lock-order cycle.

   The runtime arm (`analysis/lockwatch.py`) records the REAL
   acquisition orders during tier-1 and the differential suite pins
   observed ⊆ predicted (same pattern as the PR-6 path-vs-telemetry
   pins).

3. **Hazardous work under a lock.** Holding an engine lock across
   slow/blocking work stalls every thread behind it —

   - **FLV212** (error) blocking file/socket IO, ``subprocess``, or
     ``time.sleep`` under a lock (locks whose dotted name ends in
     ``io`` or ``build`` are DESIGNATED IO locks — serializing IO is
     their documented job — and are exempt),
   - **FLV213** (error) JAX dispatch (``jax.*``/``jnp.*``/``lax.*`` or
     a ``*_jit*`` entry point) or metered user-hook execution under a
     lock: a first-call XLA compile can hold it for seconds.

4. **Transfer-guard strictness.** The dynamic arm wraps executor
   dispatch in ``jax.transfer_guard_device_to_host`` (see
   ``FLUVIO_TRANSFER_GUARD``); the static arm catches the syntactic
   class —

   - **FLV214** (error) implicit D2H materialization (``np.asarray`` /
     ``int()`` / ``float()`` / ``bytes()`` / ``memoryview``) of a jit
     result inside a dispatch-side hot function.

Lock identity: locks created via `lockwatch.make_lock("name")` take the
literal as their canonical name — the SAME string the runtime watchdog
records — so the static and observed graphs share one vocabulary by
construction. Raw ``threading.Lock()`` assignments get a derived
``module.Class.attr`` name.

Suppression: ``# noqa: FLV2xx`` on the flagged line, same vocabulary as
the PR-6 linter. A suppression is the mechanical documentation of a
DELIBERATE relaxation (GIL-atomic monitoring counters, double-checked
lazy init, copy-on-write snapshot reads) — grep for them to audit every
place the engine steps outside strict lock discipline.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from fluvio_tpu.analysis.ast_lint import DISPATCH_HOT_FUNCS
from fluvio_tpu.analysis.lockwatch import find_cycle
from fluvio_tpu.analysis.noqa import line_suppresses

ERROR = "error"
WARN = "warn"

RULES = {
    "FLV201": (ERROR, "unguarded write to lock-guarded shared state"),
    "FLV202": (WARN, "unguarded read of lock-guarded shared state"),
    "FLV211": (ERROR, "lock-acquisition-order cycle (potential deadlock)"),
    "FLV212": (ERROR, "blocking IO while holding a lock"),
    "FLV213": (ERROR, "JAX dispatch / user-hook execution under a lock"),
    "FLV214": (ERROR, "implicit D2H materialization of a jit result in "
                      "dispatch-hot code"),
}

#: an unresolvable-but-lock-shaped `with` target: suppresses guard
#: findings for the accesses it covers without feeding the order graph
UNKNOWN_LOCK = "?"

#: dotted-name last segments that designate a lock as an IO serializer
#: (the build locks exist to serialize g++; the trace sink's io lock
#: exists to serialize file appends) — exempt from FLV212
IO_LOCK_SEGMENTS = ("io", "build")

#: pipelined engine paths that behave as thread entry points even
#: though no `threading.Thread(target=...)` names them: the broker's
#: stream loop drives dispatch/finish concurrently with the fetch
#: worker, scrapes, and metering watchdogs
EXTRA_THREAD_ROOTS = (
    "smartengine.tpu.executor.TpuChainExecutor.dispatch_buffer",
    "smartengine.tpu.executor.TpuChainExecutor.dispatch_buffers",
    "smartengine.tpu.executor.TpuChainExecutor.finish_buffer",
    "smartengine.tpu.executor.TpuChainExecutor.discard_dispatch",
    "smartengine.tpu.executor.TpuChainExecutor.process_stream",
    "smartengine.tpu.executor.TpuChainExecutor._finish_retry",
    "smartengine.tpu.executor.TpuChainExecutor._redispatch_refetch",
    "spu.smart_chain.tpu_stage_dispatch",
    "spu.smart_chain.tpu_finish",
    "spu.smart_chain.tpu_stage",
    "spu.smart_chain.tpu_dispatch",
    "spu.smart_chain.tpu_fetch",
    "spu.smart_chain.tpu_materialize",
    "spu.monitoring.MonitoringServer._handle",
    "smartengine.metering.run_metered",
)

_MUTATING_METHODS = {
    "append", "extend", "insert", "add", "update", "clear", "pop",
    "popitem", "remove", "discard", "setdefault", "push", "sort",
    "appendleft", "rotate",
}

_IO_OS_FUNCS = {
    "replace", "remove", "rename", "unlink", "makedirs", "mkdir",
    "listdir", "fsync", "open",
}
_IO_METHODS = {
    "write", "read", "readline", "flush", "recv", "send", "sendall",
    "accept", "connect", "bind", "listen", "drain", "read_bytes",
    "read_text", "write_bytes", "write_text",
}
_D2H_CONVERTERS = {"asarray", "array", "copy", "int", "float", "bytes",
                   "memoryview"}


@dataclass
class Finding:
    path: str
    line: int
    code: str
    level: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.code} [{self.level}] {self.message}"

    def to_dict(self) -> dict:
        return {
            "path": self.path, "line": self.line, "code": self.code,
            "level": self.level, "message": self.message,
        }


@dataclass
class LockEdge:
    src: str
    dst: str
    path: str
    line: int

    def to_dict(self) -> dict:
        return {"from": self.src, "to": self.dst, "path": self.path,
                "line": self.line}


@dataclass
class ConcurrencyReport:
    findings: List[Finding] = field(default_factory=list)
    locks: List[str] = field(default_factory=list)
    edges: List[LockEdge] = field(default_factory=list)
    cycles: List[List[str]] = field(default_factory=list)
    roots: List[str] = field(default_factory=list)
    guard_map: Dict[str, dict] = field(default_factory=dict)

    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.level == ERROR]

    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.level == WARN]

    def edge_set(self) -> Set[Tuple[str, str]]:
        return {(e.src, e.dst) for e in self.edges}

    def to_dict(self) -> dict:
        return {
            "findings": [f.to_dict() for f in self.findings],
            "locks": list(self.locks),
            "edges": [e.to_dict() for e in self.edges],
            "cycles": [list(c) for c in self.cycles],
            "roots": list(self.roots),
            "guards": dict(self.guard_map),
        }


# ---------------------------------------------------------------------------
# module models
# ---------------------------------------------------------------------------


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """['self', '_lock'] for ``self._lock``; None for non-name chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


def _is_lock_ctor(node: ast.AST) -> Optional[str]:
    """'' for a raw threading.Lock()/RLock(), the literal name for
    make_lock("name"), None otherwise."""
    if not isinstance(node, ast.Call):
        return None
    chain = _attr_chain(node.func)
    if chain is None:
        return None
    tail = chain[-1]
    if tail in ("Lock", "RLock") and chain[0] in ("threading",) or (
        len(chain) == 1 and tail in ("Lock", "RLock")
    ):
        return ""
    if tail == "make_lock":
        if node.args and isinstance(node.args[0], ast.Constant) and isinstance(
            node.args[0].value, str
        ):
            return node.args[0].value
        return ""
    if tail == "field":
        for kw in node.keywords:
            if kw.arg == "default_factory":
                v = kw.value
                if isinstance(v, ast.Lambda):
                    return _is_lock_ctor(v.body)
                chain2 = _attr_chain(v)
                if chain2 and chain2[-1] in ("Lock", "RLock"):
                    return ""
        return None
    return None


def _mutable_literal(node: ast.AST) -> bool:
    if isinstance(node, (ast.Dict, ast.List, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        return bool(chain) and chain[-1] in ("dict", "list", "set",
                                             "defaultdict", "deque")
    return False


@dataclass
class FuncModel:
    qual: str  # module.Class.name or module.name (or parent.name nested)
    module: str
    cls: Optional[str]
    name: str
    node: ast.AST
    path: str
    local_locks: Dict[str, str] = field(default_factory=dict)
    # facts (state_key, is_write, held frozenset, line)
    accesses: List[Tuple[str, bool, frozenset, int]] = field(default_factory=list)
    calls: List[Tuple[str, frozenset, int]] = field(default_factory=list)
    acquires: List[Tuple[str, int]] = field(default_factory=list)
    direct_edges: List[Tuple[str, str, int]] = field(default_factory=list)
    io_under: List[Tuple[str, frozenset, int]] = field(default_factory=list)
    jax_under: List[Tuple[str, frozenset, int]] = field(default_factory=list)
    d2h_sites: List[Tuple[str, int]] = field(default_factory=list)
    spawn_targets: List[str] = field(default_factory=list)


@dataclass
class ClassModel:
    qual: str  # module.Class
    module: str
    name: str
    bases: List[str]
    methods: Dict[str, FuncModel] = field(default_factory=dict)
    attr_locks: Dict[str, str] = field(default_factory=dict)  # attr -> lock name
    attr_types: Dict[str, str] = field(default_factory=dict)  # attr -> class qual
    # default-singleton idiom: `self.X = x if x is not None else SINGLETON`
    # records the candidate global names here; resolved to attr_types
    # after singleton binding (build() post-pass)
    attr_singleton_defaults: Dict[str, List[str]] = field(default_factory=dict)


@dataclass
class ModuleModel:
    key: str  # dotted, package-relative ("telemetry.registry")
    path: str
    tree: ast.Module
    lines: List[str]
    imports: Dict[str, str] = field(default_factory=dict)  # name -> module key or "key:symbol"
    global_locks: Dict[str, str] = field(default_factory=dict)
    mutable_globals: Set[str] = field(default_factory=set)
    classes: Dict[str, ClassModel] = field(default_factory=dict)
    functions: Dict[str, FuncModel] = field(default_factory=dict)
    singletons: Dict[str, str] = field(default_factory=dict)  # name -> class local name


class PackageAnalyzer:
    """Builds the models for a set of sources and runs the passes."""

    def __init__(self, sources: Dict[str, Tuple[str, str]]):
        # sources: module key -> (path, source text)
        self.modules: Dict[str, ModuleModel] = {}
        self.funcs: Dict[str, FuncModel] = {}
        self.classes: Dict[str, ClassModel] = {}
        self.singleton_classes: Dict[str, str] = {}  # global NAME -> class qual
        self.findings: List[Finding] = []
        self.lock_names: Set[str] = set()
        for key, (path, src) in sorted(sources.items()):
            try:
                tree = ast.parse(src, filename=path)
            except SyntaxError as e:
                self.findings.append(Finding(
                    path, e.lineno or 1, "FLV000", ERROR,
                    f"syntax error: {e.msg}",
                ))
                continue
            self.modules[key] = ModuleModel(
                key, path, tree, src.splitlines()
            )

    # -- pass 1: declarations ------------------------------------------------

    def build(self) -> None:
        for mod in self.modules.values():
            self._scan_module_decls(mod)
        self._resolve_export_origins()
        for mod in self.modules.values():
            self._bind_singletons(mod)
        # default-singleton attr types resolve only after singletons
        # are bound (the IfExp's Name branch is a cross-module global)
        for cm in self.classes.values():
            for attr, names in cm.attr_singleton_defaults.items():
                for name in names:
                    cq = self.singleton_classes.get(name)
                    if cq is not None:
                        cm.attr_types.setdefault(attr, cq)
                        break
        for mod in self.modules.values():
            self._scan_function_bodies(mod)

    def _scan_module_decls(self, mod: ModuleModel) -> None:
        for node in mod.tree.body:
            self._collect_import(mod, node, mod.imports)
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                value = node.value
                if value is None:
                    continue
                lock = _is_lock_ctor(value)
                for t in targets:
                    if not isinstance(t, ast.Name):
                        continue
                    if lock is not None:
                        canon = lock or f"{mod.key}.{t.id}"
                        mod.global_locks[t.id] = canon
                        self.lock_names.add(canon)
                    elif _mutable_literal(value):
                        mod.mutable_globals.add(t.id)
                    elif isinstance(value, ast.Call) and isinstance(
                        value.func, ast.Name
                    ):
                        # module-level singleton: NAME = ClassName()
                        mod.singletons[t.id] = value.func.id
            elif isinstance(node, ast.ClassDef):
                self._scan_class_decl(mod, node)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{mod.key}.{node.name}"
                fm = FuncModel(qual, mod.key, None, node.name, node, mod.path)
                mod.functions[node.name] = fm
                self.funcs[qual] = fm

    def _scan_class_decl(self, mod: ModuleModel, node: ast.ClassDef) -> None:
        qual = f"{mod.key}.{node.name}"
        bases = []
        for b in node.bases:
            chain = _attr_chain(b)
            if chain:
                bases.append(chain[-1])
        cm = ClassModel(qual, mod.key, node.name, bases)
        mod.classes[node.name] = cm
        self.classes[qual] = cm
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fq = f"{qual}.{item.name}"
                fm = FuncModel(fq, mod.key, node.name, item.name, item,
                               mod.path)
                cm.methods[item.name] = fm
                self.funcs[fq] = fm
            elif isinstance(item, ast.AnnAssign) and isinstance(
                item.target, ast.Name
            ) and item.value is not None:
                lock = _is_lock_ctor(item.value)
                if lock is not None:
                    canon = lock or f"{qual}.{item.target.id}"
                    cm.attr_locks[item.target.id] = canon
                    self.lock_names.add(canon)
        # self.X = Lock() / self.X = Class() assignments anywhere in the
        # class body bind attr locks and attr types
        for item in ast.walk(node):
            if not isinstance(item, ast.Assign):
                continue
            for t in item.targets:
                chain = _attr_chain(t)
                if not chain or len(chain) != 2 or chain[0] != "self":
                    continue
                lock = _is_lock_ctor(item.value)
                if lock is not None:
                    canon = lock or f"{qual}.{chain[1]}"
                    cm.attr_locks.setdefault(chain[1], canon)
                    self.lock_names.add(canon)
                elif isinstance(item.value, ast.Call) and isinstance(
                    item.value.func, ast.Name
                ):
                    cm.attr_types.setdefault(chain[1], item.value.func.id)
                elif isinstance(item.value, ast.IfExp):
                    # `self.X = x if x is not None else DEFAULT`: type
                    # the attr from whichever branch resolves — a bare
                    # Name binds through the module-singleton table
                    # (post-pass, after singletons exist), a
                    # ClassName(...) call binds like the plain-call case
                    for branch in (item.value.body, item.value.orelse):
                        if isinstance(branch, ast.Name):
                            cm.attr_singleton_defaults.setdefault(
                                chain[1], []
                            ).append(branch.id)
                        elif isinstance(branch, ast.Call) and isinstance(
                            branch.func, ast.Name
                        ):
                            cm.attr_types.setdefault(
                                chain[1], branch.func.id
                            )

    def _collect_import(self, mod: ModuleModel, node: ast.AST,
                        into: Dict[str, str]) -> None:
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.name
                if name.startswith("fluvio_tpu"):
                    key = name[len("fluvio_tpu"):].lstrip(".")
                    into[alias.asname or name.split(".")[-1]] = key
        elif isinstance(node, ast.ImportFrom):
            src = node.module or ""
            if not src.startswith("fluvio_tpu"):
                return
            key = src[len("fluvio_tpu"):].lstrip(".")
            for alias in node.names:
                if alias.name == "*":
                    continue
                into[alias.asname or alias.name] = f"{key}:{alias.name}"

    def _resolve_export_origins(self) -> None:
        """Follow `from fluvio_tpu.a import X` re-export chains so a
        symbol imported through a package __init__ resolves to the
        module that actually defines it (bounded hops)."""
        for _ in range(4):
            changed = False
            for mod in self.modules.values():
                for name, target in list(mod.imports.items()):
                    if ":" not in target:
                        continue
                    src_key, sym = target.split(":", 1)
                    src = self.modules.get(src_key) or self.modules.get(
                        f"{src_key}.__init__" if src_key else "__init__"
                    )
                    if src is None:
                        continue
                    if sym in src.functions or sym in src.classes or (
                        sym in src.singletons or sym in src.global_locks
                    ):
                        new = f"{src.key}:{sym}"
                    elif sym in src.imports and ":" in src.imports[sym]:
                        new = src.imports[sym]
                    else:
                        continue
                    if new != target:
                        mod.imports[name] = new
                        changed = True
            if not changed:
                break

    def _bind_singletons(self, mod: ModuleModel) -> None:
        for name, clsname in mod.singletons.items():
            cq = self._resolve_class(mod, clsname)
            if cq is not None:
                self.singleton_classes[name] = cq

    def _resolve_class(self, mod: ModuleModel, clsname: str) -> Optional[str]:
        if clsname in mod.classes:
            return mod.classes[clsname].qual
        target = mod.imports.get(clsname)
        if target and ":" in target:
            src_key, sym = target.split(":", 1)
            src = self.modules.get(src_key)
            if src and sym in src.classes:
                return src.classes[sym].qual
        return None

    def _iter_hierarchy(self, class_qual: str):
        """The class and its (first-listed package-internal) bases."""
        seen: Set[str] = set()
        cur: Optional[str] = class_qual
        while cur is not None and cur not in seen:
            seen.add(cur)
            cm = self.classes.get(cur)
            if cm is None:
                return
            yield cm
            nxt = None
            mod = self.modules.get(cm.module)
            if mod is not None:
                for b in cm.bases:
                    bq = self._resolve_class(mod, b)
                    if bq is not None:
                        nxt = bq
                        break
            cur = nxt

    def _find_method(self, class_qual: str, name: str) -> Optional[str]:
        for cm in self._iter_hierarchy(class_qual):
            if name in cm.methods:
                return cm.methods[name].qual
        return None

    def _find_attr_lock(self, class_qual: str, attr: str) -> Optional[str]:
        for cm in self._iter_hierarchy(class_qual):
            if attr in cm.attr_locks:
                return cm.attr_locks[attr]
        return None

    def _locked_class(self, class_qual: str) -> Optional[str]:
        """The class (self or base) that owns a lock attr, making
        instances of ``class_qual`` self-synchronized monitors."""
        for cm in self._iter_hierarchy(class_qual):
            if cm.attr_locks:
                return cm.qual
        return None

    # -- pass 2: function bodies --------------------------------------------

    def _scan_function_bodies(self, mod: ModuleModel) -> None:
        for fm in list(mod.functions.values()):
            _FuncScanner(self, mod, fm).run()
        for cm in mod.classes.values():
            for fm in list(cm.methods.values()):
                _FuncScanner(self, mod, fm).run()

    # -- suppression ---------------------------------------------------------

    def _suppressed(self, mod: ModuleModel, line: int, code: str) -> bool:
        # shared grammar (analysis/noqa.py): one comment listing codes
        # from several analyzers (``noqa: FLV201,FLV301``) satisfies each
        return line_suppresses(mod.lines, line, code)

    def _flag(self, fm: FuncModel, line: int, code: str, message: str,
              level: Optional[str] = None) -> None:
        mod = self.modules[fm.module]
        if self._suppressed(mod, line, code):
            return
        self.findings.append(Finding(
            fm.path, line, code, level or RULES[code][0], message
        ))

    # -- pass 3: analyses ----------------------------------------------------

    def analyze(self) -> ConcurrencyReport:
        self.build()
        roots = self._thread_roots()
        reachable = self._reachable(roots)
        may_acquire = self._may_acquire_fixpoint()
        edges = self._lock_edges(may_acquire)
        self._entry_held = self._entry_held_fixpoint(roots)
        by_key = self._collect_accesses()
        self._guard_findings(reachable, by_key)
        self._work_under_lock_findings()
        self._call_hazard_findings()
        self._d2h_findings()
        cycles = []
        # report EVERY cycle, not just the first: peel each reported
        # cycle's edges off and re-search, so two independent deadlock
        # loops surface in one run instead of one-per-CI-iteration
        edge_pairs = {(e.src, e.dst) for e in edges}
        while True:
            cyc = find_cycle(edge_pairs)
            if cyc is None:
                break
            cycles.append(cyc)
            site = next(
                (e for e in edges if e.src in cyc and e.dst in cyc), edges[0]
            )
            self.findings.append(Finding(
                site.path, site.line, "FLV211", ERROR,
                "lock-order cycle: " + " -> ".join(cyc + cyc[:1]),
            ))
            edge_pairs -= set(zip(cyc, cyc[1:] + cyc[:1]))
        report = ConcurrencyReport(
            findings=sorted(self.findings, key=lambda f: (f.path, f.line)),
            locks=sorted(self.lock_names),
            edges=edges,
            cycles=cycles,
            roots=sorted(roots),
            guard_map=self._guard_map_summary(reachable, by_key),
        )
        return report

    # -- roots + reachability ------------------------------------------------

    def _thread_roots(self) -> Set[str]:
        roots: Set[str] = set()
        for fm in self.funcs.values():
            for target in fm.spawn_targets:
                roots.add(target)
        for suffix in EXTRA_THREAD_ROOTS:
            for qual in self.funcs:
                if qual == suffix or qual.endswith("." + suffix):
                    roots.add(qual)
        return {r for r in roots if r in self.funcs}

    def _reachable(self, roots: Set[str]) -> Set[str]:
        seen = set(roots)
        stack = list(roots)
        while stack:
            cur = stack.pop()
            fm = self.funcs.get(cur)
            if fm is None:
                continue
            for callee, _held, _line in fm.calls:
                if callee in self.funcs and callee not in seen:
                    seen.add(callee)
                    stack.append(callee)
        return seen

    # -- lock graph ----------------------------------------------------------

    def _may_acquire_fixpoint(self) -> Dict[str, Set[str]]:
        acq: Dict[str, Set[str]] = {
            q: {lock for lock, _ in fm.acquires if lock != UNKNOWN_LOCK}
            for q, fm in self.funcs.items()
        }
        for _ in range(len(self.funcs) + 1):
            changed = False
            for q, fm in self.funcs.items():
                for callee, _held, _line in fm.calls:
                    callee_acq = acq.get(callee)
                    if callee_acq and not callee_acq <= acq[q]:
                        acq[q] |= callee_acq
                        changed = True
            if not changed:
                break
        return acq

    def _lock_edges(self, may_acquire: Dict[str, Set[str]]) -> List[LockEdge]:
        edges: Dict[Tuple[str, str], LockEdge] = {}
        for fm in self.funcs.values():
            for a, b, line in fm.direct_edges:
                if UNKNOWN_LOCK in (a, b):
                    continue
                edges.setdefault((a, b), LockEdge(a, b, fm.path, line))
            for callee, held, line in fm.calls:
                if not held:
                    continue
                for b in may_acquire.get(callee, ()):
                    for a in held:
                        if a == UNKNOWN_LOCK or a == b:
                            continue
                        edges.setdefault((a, b), LockEdge(a, b, fm.path, line))
        return list(edges.values())

    # -- guard map -----------------------------------------------------------

    def _entry_held_fixpoint(self, roots: Set[str]) -> Dict[str, frozenset]:
        """Locks provably held at a function's ENTRY: the intersection of
        the held sets across every recorded call site (transitively).
        This models the caller-holds-lock idiom (`_foo_locked` helpers
        whose contract is "caller holds the guard") without annotations:
        a helper only ever invoked under lock L analyzes as holding L,
        and one call site that skips L dissolves the guarantee. Thread
        roots are pinned to the empty set — a thread entry point starts
        with nothing held, whatever its other callers do."""
        NOT_CALLED = None  # optimistic top: no call site seen yet
        entry: Dict[str, Optional[frozenset]] = {
            q: NOT_CALLED for q in self.funcs
        }
        for r in roots:
            entry[r] = frozenset()
        for _ in range(len(self.funcs) + 1):
            changed = False
            for q, fm in self.funcs.items():
                base = entry[q] or frozenset()
                for callee, held, _line in fm.calls:
                    if callee not in entry or callee in roots:
                        continue
                    at_call = frozenset(
                        h for h in (held | base) if h != UNKNOWN_LOCK
                    )
                    cur = entry[callee]
                    new = at_call if cur is NOT_CALLED else (cur & at_call)
                    if new != cur:
                        entry[callee] = new
                        changed = True
            if not changed:
                break
        return {q: (s or frozenset()) for q, s in entry.items()}

    def _effective_held(self, fm: FuncModel, held: frozenset) -> frozenset:
        return held | getattr(self, "_entry_held", {}).get(
            fm.qual, frozenset()
        )

    def _collect_accesses(self) -> Dict[str, List[Tuple[FuncModel, bool, frozenset, int]]]:
        by_key: Dict[str, List] = {}
        for fm in self.funcs.values():
            for key, is_write, held, line in fm.accesses:
                by_key.setdefault(key, []).append(
                    (fm, is_write, self._effective_held(fm, held), line)
                )
        return by_key

    def _guard_of(self, accesses) -> Optional[str]:
        counts: Dict[str, int] = {}
        for _fm, _w, held, _line in accesses:
            for lock in held:
                if lock != UNKNOWN_LOCK:
                    counts[lock] = counts.get(lock, 0) + 1
        if not counts:
            return None
        return max(sorted(counts), key=lambda k: counts[k])

    def _guard_findings(self, reachable: Set[str], by_key=None) -> None:
        for key, accesses in (by_key or self._collect_accesses()).items():
            # state participates in the concurrency analysis when at
            # least one access happens on a spawned-thread path; the
            # main thread races those, so every access is then checked
            if not any(fm.qual in reachable for fm, _w, _h, _l in accesses):
                continue
            guard = self._guard_of(accesses)
            if guard is None:
                continue
            # only lock-DISCIPLINED state gets findings: some write must
            # hold the guard (pure read-side caching is not a discipline)
            if not any(w and guard in h for _f, w, h, _l in accesses):
                continue
            attr = key.rsplit(".", 1)[-1]
            for fm, is_write, held, line in accesses:
                if fm.name in ("__init__", "__new__", "__post_init__"):
                    continue  # construction happens-before publication
                if guard in held or UNKNOWN_LOCK in held:
                    continue
                if is_write:
                    self._flag(
                        fm, line, "FLV201",
                        f"write to {key} without holding {guard!r} "
                        f"(guarded elsewhere; racing threads can corrupt "
                        f"{attr!r})",
                    )
                else:
                    self._flag(
                        fm, line, "FLV202",
                        f"read of {key} without holding {guard!r} "
                        f"(guarded elsewhere; may observe torn state)",
                    )

    def _guard_map_summary(self, reachable: Set[str], by_key=None) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for key, accesses in (by_key or self._collect_accesses()).items():
            guard = self._guard_of(accesses)
            if guard is None:
                continue
            out[key] = {
                "lock": guard,
                "accesses": len(accesses),
                "unguarded": sum(
                    1 for _f, _w, h, _l in accesses
                    if guard not in h and UNKNOWN_LOCK not in h
                ),
                "concurrent": any(
                    fm.qual in reachable for fm, _w, _h, _l in accesses
                ),
            }
        return out

    # -- work under lock -----------------------------------------------------

    @staticmethod
    def _hot_locks(held: frozenset) -> List[str]:
        return [
            h for h in held
            if h != UNKNOWN_LOCK
            and h.rsplit(".", 1)[-1] not in IO_LOCK_SEGMENTS
        ]

    def _work_under_lock_findings(self) -> None:
        for fm in self.funcs.values():
            for desc, held, line in fm.io_under:
                hot = self._hot_locks(held)
                if hot:
                    self._flag(
                        fm, line, "FLV212",
                        f"blocking IO ({desc}) while holding "
                        f"{sorted(hot)}: every thread behind the lock "
                        "stalls on the device/disk/socket",
                    )
            for desc, held, line in fm.jax_under:
                hot = [h for h in held if h != UNKNOWN_LOCK]
                if hot:
                    self._flag(
                        fm, line, "FLV213",
                        f"JAX dispatch / user-hook work ({desc}) while "
                        f"holding {sorted(hot)}: a first-call compile can "
                        "hold it for seconds",
                    )

    def _may_hazard_fixpoint(self, direct: Dict[str, bool]) -> Dict[str, bool]:
        """Transitive 'may perform the hazard outside an IO-designated
        lock' summary over the call graph."""
        may = dict(direct)
        for _ in range(len(self.funcs) + 1):
            changed = False
            for q, fm in self.funcs.items():
                if may.get(q):
                    continue
                for callee, held, _line in fm.calls:
                    if may.get(callee) and not any(
                        h != UNKNOWN_LOCK
                        and h.rsplit(".", 1)[-1] in IO_LOCK_SEGMENTS
                        for h in held
                    ):
                        may[q] = True
                        changed = True
                        break
            if not changed:
                break
        return may

    def _call_hazard_findings(self) -> None:
        """A call made while holding a hot lock into a callee that
        (transitively) blocks on IO or dispatches JAX is the same hazard
        one level removed."""
        direct_io = {
            q: any(
                not any(
                    h != UNKNOWN_LOCK
                    and h.rsplit(".", 1)[-1] in IO_LOCK_SEGMENTS
                    for h in held
                )
                for _d, held, _l in fm.io_under
            )
            for q, fm in self.funcs.items()
        }
        direct_jax = {
            q: bool(fm.jax_under) for q, fm in self.funcs.items()
        }
        may_io = self._may_hazard_fixpoint(direct_io)
        may_jax = self._may_hazard_fixpoint(direct_jax)
        for fm in self.funcs.values():
            for callee, held, line in fm.calls:
                hot = self._hot_locks(held)
                if not hot:
                    continue
                if may_io.get(callee):
                    self._flag(
                        fm, line, "FLV212",
                        f"call into {callee} (which performs blocking IO) "
                        f"while holding {sorted(hot)}",
                    )
                locked = [h for h in held if h != UNKNOWN_LOCK]
                if locked and may_jax.get(callee):
                    self._flag(
                        fm, line, "FLV213",
                        f"call into {callee} (which dispatches JAX / user "
                        f"hooks) while holding {sorted(locked)}",
                    )

    def _d2h_findings(self) -> None:
        for fm in self.funcs.values():
            for desc, line in fm.d2h_sites:
                self._flag(
                    fm, line, "FLV214",
                    f"{desc} forces an implicit D2H sync on a jit result "
                    "inside a dispatch-side hot function — run it behind "
                    "the fetch seam (FLUVIO_TRANSFER_GUARD=disallow "
                    "rejects this at runtime)",
                )


# ---------------------------------------------------------------------------
# function-body scanning
# ---------------------------------------------------------------------------


class _FuncScanner:
    """Walks one function body tracking the held-lock set per statement
    and extracting accesses / calls / acquisitions / hazards."""

    def __init__(self, pkg: PackageAnalyzer, mod: ModuleModel,
                 fm: FuncModel, parent_locals: Optional[Dict[str, str]] = None):
        self.pkg = pkg
        self.mod = mod
        self.fm = fm
        self.local_imports: Dict[str, str] = dict(mod.imports)
        self.local_locks: Dict[str, str] = dict(parent_locals or {})
        self.nested: Dict[str, FuncModel] = {}
        self.taint: Set[str] = set()
        self.in_hot = (
            fm.name in DISPATCH_HOT_FUNCS
            and os.path.basename(fm.path) == "executor.py"
        )

    def run(self) -> None:
        body = getattr(self.fm.node, "body", [])
        # pre-pass: local lock bindings + function-level imports so a
        # later `with lock:` resolves regardless of statement order
        for node in ast.walk(self.fm.node):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                self.pkg._collect_import(self.mod, node, self.local_imports)
            elif isinstance(node, ast.Assign):
                lock = _is_lock_ctor(node.value)
                if lock is not None:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            canon = lock or f"{self.fm.qual}.{t.id}"
                            self.local_locks[t.id] = canon
                            self.pkg.lock_names.add(canon)
        self.fm.local_locks = dict(self.local_locks)
        self._stmts(body, frozenset())

    # -- statement walk ------------------------------------------------------

    def _stmts(self, body: Sequence[ast.stmt], held: frozenset) -> None:
        for stmt in body:
            self._stmt(stmt, held)

    def _stmt(self, stmt: ast.stmt, held: frozenset) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            qual = f"{self.fm.qual}.{stmt.name}"
            nested = FuncModel(qual, self.fm.module, self.fm.cls, stmt.name,
                               stmt, self.fm.path)
            self.nested[stmt.name] = nested
            self.pkg.funcs[qual] = nested
            _FuncScanner(self.pkg, self.mod, nested,
                         parent_locals=self.local_locks).run()
            return
        if isinstance(stmt, ast.ClassDef):
            return
        if isinstance(stmt, ast.With):
            self._with(stmt, held)
            return
        if isinstance(stmt, ast.AsyncWith):
            # asyncio locks serialize coroutines, not threads: treat as
            # an unknown guard (suppresses guard findings underneath)
            self._exprs(stmt, held)
            self._stmts(stmt.body, held | {UNKNOWN_LOCK})
            return
        # expression-bearing parts of this statement
        self._exprs(stmt, held)
        for field_name in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field_name, None)
            if sub:
                self._stmts(sub, held)
        for handler in getattr(stmt, "handlers", []) or []:
            self._stmts(handler.body, held)

    def _with(self, stmt: ast.With, held: frozenset) -> None:
        acquired: List[str] = []
        for item in stmt.items:
            lock = self._resolve_lock(item.context_expr)
            if lock is not None:
                self.fm.acquires.append(
                    (lock, getattr(item.context_expr, "lineno", stmt.lineno))
                )
                for h in held | frozenset(acquired):
                    if h != UNKNOWN_LOCK and lock != UNKNOWN_LOCK and h != lock:
                        self.fm.direct_edges.append((h, lock, stmt.lineno))
                acquired.append(lock)
            else:
                # non-lock context manager: scan its expression normally
                self._expr_tree(item.context_expr, held)
            if item.optional_vars is not None:
                self._expr_tree(item.optional_vars, held)
        self._stmts(stmt.body, held | frozenset(acquired))

    # -- expression walk -----------------------------------------------------

    def _exprs(self, stmt: ast.stmt, held: frozenset) -> None:
        for field_name, value in ast.iter_fields(stmt):
            if field_name in ("body", "orelse", "finalbody", "handlers",
                              "items"):
                continue
            if isinstance(value, ast.AST):
                self._expr_tree(value, held)
            elif isinstance(value, list):
                for v in value:
                    if isinstance(v, ast.AST):
                        self._expr_tree(v, held)
        # writes: assignment / augassign targets
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                self._record_store(t, held)
            self._record_taint(stmt)
        elif isinstance(stmt, ast.AugAssign):
            self._record_store(stmt.target, held)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._record_store(stmt.target, held)
        elif isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                self._record_store(t, held)

    def _expr_tree(self, node: ast.AST, held: frozenset) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self._call(sub, held)
            elif isinstance(sub, ast.Attribute) and isinstance(
                sub.ctx, ast.Load
            ):
                self._record_attr_load(sub, held)
            elif isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                self._record_global_load(sub, held)

    # -- access recording ----------------------------------------------------

    def _state_key(self, chain: List[str]) -> Optional[str]:
        """Map an attribute chain to a shared-state key, or None."""
        if len(chain) < 2:
            return None
        base, attr = chain[0], chain[1]
        if base == "self" and self.fm.cls is not None:
            own_qual = f"{self.fm.module}.{self.fm.cls}"
            if self.pkg._find_attr_lock(own_qual, attr) is not None:
                return None  # the lock itself, not guarded state
            cm = self.mod.classes.get(self.fm.cls)
            if cm is not None:
                # attribute holding a self-synchronized object (its own
                # class defines a lock): method calls on it are safe
                if self._attr_type_qual(cm, attr) is not None:
                    return None
            return f"{self.fm.module}.{self.fm.cls}.{attr}"
        cq = self.pkg.singleton_classes.get(base)
        if cq is not None:
            if self.pkg._find_attr_lock(cq, attr) is not None:
                return None
            return f"{cq}.{attr}"
        return None

    def _attr_type_qual(self, cm: ClassModel, attr: str) -> Optional[str]:
        """The lock-owning class of a self-synchronized attribute (the
        attr's class, or the base that actually defines its lock)."""
        tq = self._attr_type_qual_any(cm, attr)
        if tq is None:
            return None
        return self.pkg._locked_class(tq)

    def _record_attr_load(self, node: ast.Attribute, held: frozenset) -> None:
        chain = _attr_chain(node)
        if chain is None:
            return
        key = self._state_key(chain)
        if key is not None:
            self.fm.accesses.append((key, False, held, node.lineno))
        # property reads on a self-synchronized attr dispatch into its
        # class (the getter may acquire the monitor's lock)
        if (
            len(chain) >= 3
            and chain[0] == "self"
            and self.fm.cls is not None
        ):
            cm = self.mod.classes.get(self.fm.cls)
            if cm is not None:
                tq = self._attr_type_qual_any(cm, chain[1])
                if tq is not None:
                    meth = self.pkg._find_method(tq, chain[2])
                    if meth is not None:
                        self.fm.calls.append((meth, held, node.lineno))

    def _record_global_load(self, node: ast.Name, held: frozenset) -> None:
        name = node.id
        if name in self.mod.mutable_globals or (
            name in self._declared_globals()
        ):
            self.fm.accesses.append(
                (f"{self.fm.module}.{name}", False, held, node.lineno)
            )

    _globals_cache: Optional[Set[str]] = None

    def _declared_globals(self) -> Set[str]:
        if self._globals_cache is None:
            names: Set[str] = set()
            for sub in ast.walk(self.fm.node):
                if isinstance(sub, ast.Global):
                    names.update(sub.names)
            self._globals_cache = names
        return self._globals_cache

    def _record_store(self, target: ast.AST, held: frozenset) -> None:
        # unwrap tuple targets and subscripts: x[...] = is a write to x
        if isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._record_store(el, held)
            return
        line = getattr(target, "lineno", 1)
        while isinstance(target, ast.Subscript):
            target = target.value
        if isinstance(target, ast.Name):
            name = target.id
            if name in self.mod.mutable_globals or name in self._declared_globals():
                self.fm.accesses.append(
                    (f"{self.fm.module}.{name}", True, held, line)
                )
        elif isinstance(target, ast.Attribute):
            chain = _attr_chain(target)
            if chain:
                key = self._state_key(chain)
                if key is not None:
                    self.fm.accesses.append((key, True, held, line))

    def _record_taint(self, stmt: ast.Assign) -> None:
        value = stmt.value
        if not isinstance(value, ast.Call):
            return
        chain = _attr_chain(value.func)
        if not chain or not any("jit" in part for part in chain):
            return
        for t in stmt.targets:
            if isinstance(t, ast.Name):
                self.taint.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for el in t.elts:
                    if isinstance(el, ast.Name):
                        self.taint.add(el.id)

    # -- call handling -------------------------------------------------------

    def _call(self, node: ast.Call, held: frozenset) -> None:
        chain = _attr_chain(node.func)
        self._detect_spawn(node, chain)
        callee = self._resolve_call(node, chain)
        if callee is not None:
            self.fm.calls.append((callee, held, node.lineno))
        if chain is not None:
            # mutating method on shared state counts as a write access
            if len(chain) >= 3 and chain[-1] in _MUTATING_METHODS:
                key = self._state_key(chain[:-1])
                if key is not None:
                    self.fm.accesses.append((key, True, held, node.lineno))
            elif (
                len(chain) == 2
                and chain[-1] in _MUTATING_METHODS
                and (chain[0] in self.mod.mutable_globals
                     or chain[0] in self._declared_globals())
            ):
                # GLOBAL.setdefault(...)/append(...): a write to the
                # module-level container itself
                self.fm.accesses.append(
                    (f"{self.fm.module}.{chain[0]}", True, held, node.lineno)
                )
            self._detect_io(node, chain, held)
            self._detect_jax(node, chain, held)
        if self.in_hot:
            self._detect_d2h(node, chain)

    def _detect_spawn(self, node: ast.Call, chain) -> None:
        if chain is None:
            return
        tail = chain[-1]
        target_expr = None
        if tail == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    target_expr = kw.value
        elif tail == "submit" and node.args:
            target_expr = node.args[0]
        elif tail in ("start_unix_server", "start_server") and node.args:
            target_expr = node.args[0]
        if target_expr is None:
            return
        tchain = _attr_chain(target_expr)
        if tchain is None:
            return
        qual = self._callable_qual(tchain)
        if qual is not None:
            self.fm.spawn_targets.append(qual)

    def _callable_qual(self, chain: List[str]) -> Optional[str]:
        if len(chain) == 1:
            name = chain[0]
            if name in self.nested:
                return self.nested[name].qual
            if name in self.mod.functions:
                return self.mod.functions[name].qual
            target = self.local_imports.get(name)
            if target and ":" in target:
                src_key, sym = target.split(":", 1)
                src = self.pkg.modules.get(src_key)
                if src and sym in src.functions:
                    return src.functions[sym].qual
            return None
        base, attr = chain[0], chain[1]
        if base == "self" and self.fm.cls is not None:
            meth = self.pkg._find_method(
                f"{self.fm.module}.{self.fm.cls}", attr
            )
            if meth is not None:
                return meth
        cq = self.pkg.singleton_classes.get(base)
        if cq is not None:
            meth = self.pkg._find_method(cq, attr)
            if meth is not None:
                return meth
        # module attr: faults.maybe_fire
        target = self.local_imports.get(base)
        if target and ":" not in target:
            src = self.pkg.modules.get(target)
            if src and attr in src.functions:
                return src.functions[attr].qual
        # ClassName.staticmethod
        ccq = self.pkg._resolve_class(self.mod, base)
        if ccq is not None:
            meth = self.pkg._find_method(ccq, attr)
            if meth is not None:
                return meth
        return None

    def _resolve_call(self, node: ast.Call, chain) -> Optional[str]:
        if chain is None:
            return None
        # len(self.X) on a self-synchronized attr dispatches __len__
        if chain == ["len"] and node.args:
            achain = _attr_chain(node.args[0])
            if achain and achain[0] == "self" and self.fm.cls is not None:
                cm = self.mod.classes.get(self.fm.cls)
                if cm is not None and len(achain) == 2:
                    tq = self._attr_type_qual_any(cm, achain[1])
                    if tq is not None:
                        return self.pkg._find_method(tq, "__len__")
            return None
        if len(chain) >= 3 and chain[0] == "self" and self.fm.cls is not None:
            # self.X.m(): dispatch into the attr's inferred class
            cm = self.mod.classes.get(self.fm.cls)
            if cm is not None:
                tq = self._attr_type_qual_any(cm, chain[1])
                if tq is not None:
                    return self.pkg._find_method(tq, chain[2])
            return None
        return self._callable_qual(chain)

    def _attr_type_qual_any(self, cm: ClassModel, attr: str) -> Optional[str]:
        tname = cm.attr_types.get(attr)
        if tname is None:
            return None
        if tname in self.pkg.classes:  # pre-resolved qual (singleton default)
            return tname
        return self.pkg._resolve_class(self.mod, tname)

    def _resolve_lock(self, expr: ast.AST) -> Optional[str]:
        chain = _attr_chain(expr)
        if chain is None:
            return None
        if len(chain) == 1:
            name = chain[0]
            if name in self.local_locks:
                return self.local_locks[name]
            if name in self.mod.global_locks:
                return self.mod.global_locks[name]
            target = self.local_imports.get(name)
            if target and ":" in target:
                src_key, sym = target.split(":", 1)
                src = self.pkg.modules.get(src_key)
                if src and sym in src.global_locks:
                    return src.global_locks[sym]
            if "lock" in name.lower():
                return UNKNOWN_LOCK
            return None
        base, attr = chain[0], chain[-1]
        if base == "self" and self.fm.cls is not None:
            lock = self.pkg._find_attr_lock(
                f"{self.fm.module}.{self.fm.cls}", attr
            )
            if lock is not None:
                return lock
        cq = self.pkg.singleton_classes.get(base)
        if cq is not None:
            lock = self.pkg._find_attr_lock(cq, attr)
            if lock is not None:
                return lock
        if "lock" in attr.lower():
            return UNKNOWN_LOCK
        return None

    # -- hazard detectors ----------------------------------------------------

    def _detect_io(self, node: ast.Call, chain: List[str],
                   held: frozenset) -> None:
        tail = chain[-1]
        desc = ".".join(chain)
        if chain == ["open"]:
            self.fm.io_under.append((desc, held, node.lineno))
        elif chain[0] in ("subprocess", "shutil") and len(chain) > 1:
            self.fm.io_under.append((desc, held, node.lineno))
        elif chain[0] == "os" and tail in _IO_OS_FUNCS:
            self.fm.io_under.append((desc, held, node.lineno))
        elif chain[0] == "time" and tail == "sleep":
            self.fm.io_under.append((desc, held, node.lineno))
        elif len(chain) >= 2 and tail in _IO_METHODS:
            self.fm.io_under.append((desc, held, node.lineno))

    def _detect_jax(self, node: ast.Call, chain: List[str],
                    held: frozenset) -> None:
        desc = ".".join(chain)
        if chain[0] in ("jax", "jnp", "lax") or any(
            part.startswith("_jit") for part in chain
        ) or chain[-1] == "run_metered":
            self.fm.jax_under.append((desc, held, node.lineno))

    def _detect_d2h(self, node: ast.Call, chain) -> None:
        if chain is None:
            return
        tail = chain[-1]
        if tail not in _D2H_CONVERTERS:
            return
        if len(chain) > 1 and chain[0] not in ("np", "numpy"):
            return
        if not node.args:
            return
        arg = node.args[0]
        while isinstance(arg, ast.Subscript):
            arg = arg.value
        if isinstance(arg, ast.Name) and arg.id in self.taint:
            self.fm.d2h_sites.append(
                (f"{'.'.join(chain)}({arg.id})", node.lineno)
            )


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _module_key(rel_path: str) -> str:
    key = rel_path[:-3] if rel_path.endswith(".py") else rel_path
    key = key.replace(os.sep, "/").replace("/", ".")
    if key.endswith(".__init__"):
        key = key[: -len(".__init__")]
    return key


def package_sources(root: Optional[str] = None) -> Dict[str, Tuple[str, str]]:
    """{module key: (path, source)} for the installed package."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out: Dict[str, Tuple[str, str]] = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [
            d for d in dirnames
            if d not in ("__pycache__", ".git", ".xla_cache", "_build")
        ]
        for f in sorted(filenames):
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            rel = os.path.relpath(path, root)
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    out[_module_key(rel)] = (path, fh.read())
            except OSError:  # pragma: no cover — unreadable source file
                continue
    return out


def analyze_sources(
    sources: Dict[str, str], paths: Optional[Dict[str, str]] = None
) -> ConcurrencyReport:
    """Analyze a synthetic {module key: source} set (the differential
    suite injects hazard patterns through this)."""
    packed = {
        key: ((paths or {}).get(key, key.replace(".", "/") + ".py"), src)
        for key, src in sources.items()
    }
    return PackageAnalyzer(packed).analyze()


def analyze_package(root: Optional[str] = None) -> ConcurrencyReport:
    """Whole-package lock-discipline analysis (the CI gate's scope)."""
    return PackageAnalyzer(package_sources(root)).analyze()


def static_lock_graph(root: Optional[str] = None) -> Set[Tuple[str, str]]:
    """The predicted lock-acquisition-order edge set, keyed by the same
    canonical names `lockwatch` records at runtime."""
    return analyze_package(root).edge_set()
