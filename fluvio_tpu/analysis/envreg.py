"""Central registry + lint for every ``FLUVIO_*`` configuration flag.

The engine grew one env knob at a time, and by PR 13 the package read
62 distinct ``FLUVIO_*`` variables through ad-hoc ``os.environ.get``
calls with per-site literal defaults — the config surface equivalent
of the pre-PR-7 lock layer: real, load-bearing, and checkable by
nobody. This module makes configuration a first-class, statically
lintable subsystem:

1. **The registry.** One :class:`EnvFlag` row per flag: name, value
   kind, default, grammar, consumer modules, one-line description.
   The README's environment table is GENERATED from this registry
   (`render_readme_table`) and drift-gated (FLV402), so docs cannot
   rot silently.

2. **Typed accessors.** ``env_raw`` / ``env_int`` / ``env_float`` /
   ``env_bool`` resolve a flag's default from the registry — call
   sites stop carrying their own literals, which is what makes
   FLV403 (divergent defaults) structurally impossible for hoisted
   flags. A malformed value falls back to the registered default: an
   env typo must never crash a serving broker (the
   ``admission/types.env_float`` contract, now repo-wide).

3. **The lint** (``fluvio-tpu analyze --env``):

   - **FLV401** (error) env read of a ``FLUVIO_*`` name that is not in
     the registry — a typo'd flag name reads as "new unregistered
     flag" and fails the gate instead of silently never matching.
   - **FLV402** (error) registry entry missing from the README env
     table, or the generated table block is stale (docs drift).
   - **FLV403** (error) a flag read with a literal default that
     diverges from the registered default (two modules parsing one
     flag with different fallbacks is the two-defaults bug this
     subsumes).

4. **`warn_unknown_env()`** — startup hook: any ``FLUVIO_*`` variable
   SET in the process environment that no module reads is warned
   about once (a typo'd deploy manifest surfaces at boot, not after a
   silent week of the intended flag never applying).

Suppression uses the shared grammar (``analysis/noqa.py``):
``# noqa: FLV401`` on the read line documents a deliberately
unregistered read (there are none in-repo today).
"""

from __future__ import annotations

import ast
import os
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from fluvio_tpu.analysis.noqa import line_suppresses

ERROR = "error"
WARN = "warn"

RULES = {
    "FLV401": (ERROR, "env read not in the flag registry (typo'd or "
                      "unregistered flag)"),
    "FLV402": (ERROR, "registry entry missing from the README env table "
                      "(docs drift)"),
    "FLV403": (ERROR, "env read default diverges from the registered "
                      "default"),
}

#: kinds: how the raw string is interpreted at the call site
#:   int / float  — numeric knobs (safe-fallback parse)
#:   bool01       — "0"/"off"-family truthiness gates
#:   mode         — auto/1/0-style policy selectors (site keeps grammar)
#:   path         — filesystem location
#:   spec         — structured mini-grammar (rules, fault plans, lists)
KINDS = ("int", "float", "bool01", "mode", "path", "spec")


@dataclass(frozen=True)
class EnvFlag:
    name: str
    kind: str
    default: Optional[str]  # None: computed at the site / unset means off
    grammar: str
    consumers: Tuple[str, ...]
    note: str


def _f(name, kind, default, grammar, consumers, note) -> EnvFlag:
    if isinstance(consumers, str):
        consumers = (consumers,)
    return EnvFlag(name, kind, default, grammar, tuple(consumers), note)


#: every FLUVIO_* flag the package reads — the single source of truth
#: for defaults, the README table, and the FLV401 membership check
REGISTRY: Tuple[EnvFlag, ...] = (
    _f("FLUVIO_ADMISSION", "bool01", "0", "0|1|off|false",
       "admission/controller.py",
       "arm the broker admission controller (shed/backpressure gate)"),
    _f("FLUVIO_ADMISSION_BATCH_DEADLINE_MS", "float", "25", "ms",
       "admission/batcher.py",
       "batcher flush deadline when traffic cannot fill a bucket"),
    _f("FLUVIO_ADMISSION_BATCH_ROWS", "int", "4096", "rows",
       "admission/batcher.py",
       "batcher bucket-full row target per (chain, width bucket)"),
    _f("FLUVIO_ADMISSION_QUEUE", "int", "64", "slices",
       "admission/fairness.py",
       "bounded per-chain admission queue depth"),
    _f("FLUVIO_ADMISSION_REFILL", "float", "32", "tokens/s",
       "admission/controller.py",
       "token-bucket refill rate (scaled by the chain's SLO verdict)"),
    _f("FLUVIO_ADMISSION_REFRESH_S", "float", "1", "seconds",
       "admission/controller.py",
       "health-verdict refresh period for shed decisions"),
    _f("FLUVIO_ADMISSION_TOKENS", "float", "64", "tokens",
       "admission/controller.py", "per-chain token-bucket capacity"),
    _f("FLUVIO_ADMISSION_WARMUP", "bool01", "0", "0|1|off",
       "admission/warmup.py",
       "serve-time warm gate: shed cold-chain until buckets precompile"),
    _f("FLUVIO_ADMISSION_WARN_SHED", "float", "0.5", "probability",
       "admission/controller.py",
       "probabilistic shed fraction under a warn verdict"),
    _f("FLUVIO_BREAKER_COOLDOWN_S", "float", "5", "seconds",
       "resilience/policy.py", "circuit breaker open -> half-open delay"),
    _f("FLUVIO_BREAKER_PROBES", "int", "2", "count",
       "resilience/policy.py", "half-open passes required to re-close"),
    _f("FLUVIO_BREAKER_THRESHOLD", "int", "5", "failures",
       "resilience/policy.py", "failures in window that trip the breaker"),
    _f("FLUVIO_BREAKER_WINDOW_S", "float", "30", "seconds",
       "resilience/policy.py", "sliding failure window"),
    _f("FLUVIO_COMPILE_STORM_N", "int", "8", "compiles",
       "telemetry/registry.py",
       "compile events inside the window that flag a recompile storm"),
    _f("FLUVIO_COMPILE_STORM_WINDOW_S", "float", "60", "seconds",
       "telemetry/registry.py", "recompile-storm detection window"),
    _f("FLUVIO_DEADLETTER_DIR", "path", "/tmp/fluvio-tpu-deadletter",
       "directory", "resilience/deadletter.py",
       "quarantined-batch spool directory"),
    _f("FLUVIO_DEADLETTER_MAX", "int", "64", "entries",
       "resilience/deadletter.py",
       "dead-letter spool capacity (oldest evicted)"),
    _f("FLUVIO_DFA_ASSOC", "mode", "auto", "auto|1|0",
       ("smartengine/tpu/lower.py", "analysis/spec.py"),
       "associative-scan DFA compose kernel policy (auto: off-CPU only)"),
    _f("FLUVIO_DFA_ASSOC_MAX_STATES", "int", "64", "states",
       "smartengine/tpu/kernels.py",
       "largest DFA state count the striped compose engine accepts "
       "(sized for packed tables; falls back to 16 when "
       "FLUVIO_DFA_CLASSES=0 or the class ceiling overflows)"),
    _f("FLUVIO_DFA_CLASSES", "mode", "auto", "auto|0",
       ("ops/regex_dfa.py", "smartengine/tpu/kernels.py"),
       "byte-equivalence-class DFA table packing (0: unpacked "
       "258-column tables + legacy state gate)"),
    _f("FLUVIO_DONATE", "mode", "auto", "auto|1|0",
       "smartengine/tpu/executor.py",
       "donate_argnums on the chain jits (auto: off-CPU only)"),
    _f("FLUVIO_FAULTS", "spec", "", "stage:first=N,every=M,exc=KIND;...",
       "resilience/faults.py", "deterministic fault-injection plan"),
    _f("FLUVIO_FETCH_OVERLAP", "mode", "auto", "auto|1|0",
       "smartengine/tpu/executor.py",
       "defer pure split-back materialization to the overlap worker"),
    _f("FLUVIO_FLOW_TRACE", "bool01", "1", "1|0",
       "telemetry/registry.py",
       "per-slice causal flow tracing (arms with telemetry capture)"),
    _f("FLUVIO_GLZ_CHUNK", "int", "262144", "bytes",
       "smartengine/tpu/glz.py",
       "device result encoder's chunk size (GLZ_CHUNK)"),
    _f("FLUVIO_LOCKWATCH", "mode", "0", "0|1|record|assert",
       "analysis/lockwatch.py",
       "runtime lock-order watchdog (assert: raise on new edges)"),
    _f("FLUVIO_MEM_BUDGET", "int", "0", "bytes (0 = no budget)",
       ("telemetry/memory.py", "telemetry/slo.py"),
       "device-memory ledger ceiling: arms the hbm_headroom SLO rule "
       "(admission sheds before the allocator fails)"),
    _f("FLUVIO_MEM_LEAK_TTL_S", "float", "120", "seconds",
       "telemetry/memory.py",
       "ledger entries unreleased past this age flag as mem-leaks"),
    _f("FLUVIO_MEM_SAMPLE_S", "float", "10", "seconds",
       "telemetry/memory.py",
       "min interval between ledger leak-scan/reconcile passes"),
    _f("FLUVIO_METRIC_SPU", "path", "/tmp/fluvio-spu.sock", "socket path",
       "spu/monitoring.py", "SPU monitoring unix-socket location"),
    _f("FLUVIO_PARTITIONS", "int", None, "group count (unset/0 = off)",
       ("partition/__init__.py", "spu/server.py"),
       "arm the partitioned-topic execution layer with N device groups"),
    _f("FLUVIO_PARTITION_RULES", "spec", "", "pattern=N|hash|spread;...",
       "partition/placement.py",
       "partition -> device-group placement rules"),
    _f("FLUVIO_REBALANCE", "bool01", "1", "1|0|off",
       ("partition/rebalancer.py", "soak/generator.py"),
       "arm the lag-driven elastic partition rebalancer daemon"),
    _f("FLUVIO_REBALANCE_BURN", "float", "1.0", "records/s",
       "partition/rebalancer.py",
       "required lag drain rate; a backlogged partition not draining "
       "this fast counts as hot"),
    _f("FLUVIO_REBALANCE_COOLDOWN_S", "float", "5", "seconds",
       "partition/rebalancer.py",
       "per-partition refractory window between voluntary moves"),
    _f("FLUVIO_REBALANCE_HYSTERESIS", "float", "4", "records",
       "partition/rebalancer.py",
       "absolute-lag floor below which a partition never migrates"),
    _f("FLUVIO_REBALANCE_INTERVAL_S", "float", "0.25", "seconds",
       "partition/rebalancer.py",
       "rebalancer daemon tick period (burn-rate sampling cadence)"),
    _f("FLUVIO_REBALANCE_MAX_MOVES", "int", "2", "moves",
       "partition/rebalancer.py",
       "voluntary-move budget per tick (max concurrent migrations)"),
    _f("FLUVIO_RESULT_COMPACT", "mode", "auto", "auto|1|0",
       "smartengine/tpu/executor.py",
       "device-side result compaction (flat packed payload, auto: on)"),
    _f("FLUVIO_RESULT_COMPRESS", "mode", "auto", "auto|1|0",
       "smartengine/tpu/executor.py",
       "device glz ENCODE of the down link (auto: off-CPU only)"),
    _f("FLUVIO_RETRY_BASE_MS", "float", "2", "ms",
       "resilience/policy.py", "first retry backoff delay"),
    _f("FLUVIO_RETRY_CAP_MS", "float", "200", "ms",
       "resilience/policy.py", "retry backoff ceiling"),
    _f("FLUVIO_RETRY_JITTER", "float", "0.25", "fraction",
       "resilience/policy.py", "randomized fraction of each backoff"),
    _f("FLUVIO_RETRY_MAX", "int", "2", "attempts",
       "resilience/policy.py", "retries after the first attempt"),
    _f("FLUVIO_SLICE_RING", "int", "2048", "flows",
       "telemetry/registry.py",
       "completed per-slice flow records retained for the trace export"),
    _f("FLUVIO_SLO", "spec", "", "rule:param=v;rule:param=v",
       "telemetry/slo.py", "declarative SLO rules (burn-rate verdicts)"),
    _f("FLUVIO_SLO_PROFILE", "path", "", "directory",
       "telemetry/slo.py", "bounded profiler capture dir on breach"),
    _f("FLUVIO_SLO_PROFILE_COOLDOWN_S", "float", "60", "seconds",
       "telemetry/slo.py", "min gap between breach profile captures"),
    _f("FLUVIO_SLO_PROFILE_MS", "float", "0", "ms",
       "telemetry/slo.py", "profiler capture dwell window"),
    _f("FLUVIO_SLO_WINDOWS", "int", "30", "windows",
       "telemetry/timeseries.py", "rolling time-series window count"),
    _f("FLUVIO_SLO_WINDOW_S", "float", "10", "seconds",
       "telemetry/timeseries.py", "rolling time-series window length"),
    _f("FLUVIO_SOAK_SCENARIO", "spec", "nominal",
       "name or key=value[,key=value...]",
       "cli/soak.py",
       "default soak scenario when the CLI gets no positional spec"),
    _f("FLUVIO_SOAK_TENANT_CAP", "int", "128", "tenant labels",
       "telemetry/registry.py",
       "per-tenant label cardinality cap (overflow folds to _overflow)"),
    _f("FLUVIO_STRIPE_OVERLAP", "int", "128", "bytes (4-aligned)",
       "smartengine/tpu/stripes.py",
       "shared bytes between consecutive stripes"),
    _f("FLUVIO_STRIPE_THRESHOLD", "int", "65536", "bytes (MAX_WIDTH)",
       ("smartengine/tpu/executor.py", "analysis/spec.py",
        "admission/warmup.py"),
       "record width above which batches take the striped layout"),
    _f("FLUVIO_STRIPE_WIDTH", "int", "8192", "bytes (pow2, 4-aligned)",
       "smartengine/tpu/stripes.py", "bytes per stripe device row"),
    _f("FLUVIO_TELEMETRY", "bool01", "1", "1|0",
       "telemetry/registry.py",
       "telemetry capture master switch (0: zero-cost contract)"),
    _f("FLUVIO_TPU_CHANNEL_FILE", "path", "~/.fluvio-tpu/channel.json",
       "file", "channel.py", "release-channel pin file"),
    _f("FLUVIO_TPU_CONFIG", "path", "", "file",
       "client/config.py", "client profile config override"),
    _f("FLUVIO_TPU_DISPATCH_CHUNK", "int", "65536", "rows",
       "spu/smart_chain.py", "stream-fetch dispatch slice rows"),
    _f("FLUVIO_TPU_FAST_JSON", "mode", "auto", "auto|1|0",
       ("smartengine/tpu/lower.py", "analysis/spec.py"),
       "scan-free structural JSON indexing policy (auto: off-CPU)"),
    _f("FLUVIO_TPU_HUB_DIR", "path", "~/.fluvio-tpu/hub", "directory",
       "hub/registry.py", "local hub package store"),
    _f("FLUVIO_TPU_HUB_KEY", "path", "~/.fluvio-tpu/hub-ed25519.key",
       "file", "hub/package.py", "hub package signing key"),
    _f("FLUVIO_TPU_MAX_STAGING", "int", "536870912", "bytes",
       "spu/smart_chain.py",
       "staging-buffer byte cap per dispatch (1<<29)"),
    _f("FLUVIO_TPU_NATIVE_BUILD", "path", None, "directory (default: "
       "package _build)",
       ("protocol/native_codecs.py", "smartengine/native_backend.py",
        "smartengine/tpu/glz.py"),
       "native codec/backend build directory"),
    _f("FLUVIO_TPU_PALLAS", "mode", "auto", "auto|1|0",
       "smartengine/tpu/pallas_kernels.py",
       "pallas kernel family policy (auto: TPU only)"),
    _f("FLUVIO_TPU_VERSIONS_DIR", "path", "~/.fluvio-tpu/versions",
       "directory", "fvm.py", "fvm toolchain versions store"),
    _f("FLUVIO_TPU_XLA_CACHE", "mode", None, "off (unset: repo "
       ".xla_cache, or JAX_COMPILATION_CACHE_DIR when set)",
       "smartengine/tpu/__init__.py",
       "disable the in-checkout persistent XLA compile cache"),
    _f("FLUVIO_TRACE", "path", "", "file",
       "telemetry/trace.py", "Perfetto trace sink (unset: disabled)"),
    _f("FLUVIO_TRACE_MAX_MB", "float", "64", "MB",
       "telemetry/trace.py", "trace sink rotation bound"),
    _f("FLUVIO_TRANSFER_GUARD", "mode", "", "''|log|disallow",
       "smartengine/tpu/executor.py",
       "jax transfer-guard strictness around executor dispatch"),
    _f("FLUVIO_WARMUP_ROWS", "spec", "", "comma-separated row buckets",
       "admission/warmup.py", "AOT warmup row-bucket probe override"),
    _f("FLUVIO_WARMUP_WIDTHS", "spec", "", "comma-separated widths",
       "admission/warmup.py", "AOT warmup width probe override"),
    _f("FLUVIO_WINDOW_CAPACITY", "int", "1024", "entries",
       "windows/spec.py",
       "device window-state bank slots (open (key, window) entries)"),
    _f("FLUVIO_WINDOW_DELTA", "bool01", "1", "1|0|off",
       "windows/spec.py",
       "delta-only window emission (0: full-state every batch, the "
       "debugging escape hatch / preflight win-full variant)"),
    _f("FLUVIO_WINDOW_EMIT", "int", "1024", "rows",
       "windows/spec.py",
       "per-batch delta emit columns (overflow degrades to one "
       "full-state resync delta, never silent loss)"),
    _f("FLUVIO_WINDOW_LATENESS_MS", "int", "0", "ms",
       "windows/spec.py",
       "allowed event-time lateness before a window closes; later "
       "records are counted late and dropped"),
)

BY_NAME: Dict[str, EnvFlag] = {f.name: f for f in REGISTRY}

#: helper call names that count as env READ sites for the lint (first
#: argument is the flag name) — the registry accessors plus the legacy
#: shims that now delegate to them
ACCESSOR_FUNCS = {
    "env_raw", "env_int", "env_float", "env_bool", "env_value",
    "env_default", "env_flag",
    # legacy/per-module helpers that take (name, ...) and read environ
    # ("env" covers the `env = os.environ.get` local-alias idiom)
    "_depth_over_work", "env",
}


# ---------------------------------------------------------------------------
# Typed accessors — every hoisted flag resolves its default HERE
# ---------------------------------------------------------------------------


def env_default(name: str) -> Optional[str]:
    """The registered default string (None: computed/unset-means-off)."""
    return BY_NAME[name].default


def env_raw(name: str, env: Optional[dict] = None) -> Optional[str]:
    """The raw string value: environment first, registry default second.

    Unregistered names raise ``KeyError`` — the accessor IS the
    registry membership check at runtime, mirroring FLV401 statically.
    """
    flag = BY_NAME[name]  # KeyError on typo = the runtime FLV401
    e = os.environ if env is None else env
    v = e.get(name)
    return flag.default if v is None else v


def env_int(name: str, env: Optional[dict] = None) -> Optional[int]:
    """Int knob with the safe-fallback contract: a malformed value
    falls back to the registered default (an env typo must never crash
    a server)."""
    v = env_raw(name, env)
    d = env_default(name)
    for candidate in (v, d):
        if candidate is None or candidate == "":
            continue
        try:
            return int(float(candidate))
        except ValueError:
            continue
    return None


def env_float(name: str, env: Optional[dict] = None) -> Optional[float]:
    v = env_raw(name, env)
    d = env_default(name)
    for candidate in (v, d):
        if candidate is None or candidate == "":
            continue
        try:
            return float(candidate)
        except ValueError:
            continue
    return None


#: the "off" vocabulary shared by every bool01 gate in the package
OFF_WORDS = ("0", "", "off", "false")


def env_bool(name: str, env: Optional[dict] = None) -> bool:
    """bool01 gate: the union off-vocabulary (``0``/``''``/``off``/
    ``false``) reads false, anything else true."""
    v = env_raw(name, env)
    return (v or "").strip().lower() not in OFF_WORDS


# ---------------------------------------------------------------------------
# Startup hook
# ---------------------------------------------------------------------------


def unknown_env(env: Optional[dict] = None) -> List[str]:
    """``FLUVIO_*`` names SET in the environment that nothing reads."""
    e = os.environ if env is None else env
    return sorted(
        k for k in e if k.startswith("FLUVIO_") and k not in BY_NAME
    )


def warn_unknown_env(env: Optional[dict] = None) -> List[str]:
    """Warn once per set-but-unread ``FLUVIO_*`` var (deploy-manifest
    typo surfacing at boot). Returns the offending names."""
    names = unknown_env(env)
    for name in names:
        warnings.warn(
            f"{name} is set but no fluvio_tpu module reads it "
            "(unregistered flag — typo'd deploy config?)",
            stacklevel=2,
        )
    return names


# ---------------------------------------------------------------------------
# The lint (FLV401 / FLV403 over sources, FLV402 over the README)
# ---------------------------------------------------------------------------


@dataclass
class EnvFinding:
    path: str
    line: int
    code: str
    level: str
    message: str

    def __str__(self) -> str:
        return (
            f"{self.path}:{self.line}: {self.code} [{self.level}] "
            f"{self.message}"
        )

    def to_dict(self) -> dict:
        return {
            "path": self.path, "line": self.line, "code": self.code,
            "level": self.level, "message": self.message,
        }


def _const_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _literal_default(node) -> Optional[str]:
    """A comparable string for a literal default argument (str/num)."""
    if isinstance(node, ast.Constant) and isinstance(
        node.value, (str, int, float)
    ) and not isinstance(node.value, bool):
        return str(node.value)
    if (
        isinstance(node, ast.BinOp)
        and isinstance(node.op, (ast.LShift, ast.Pow, ast.Mult))
        and isinstance(node.left, ast.Constant)
        and isinstance(node.right, ast.Constant)
        and isinstance(node.left.value, int)
        and isinstance(node.right.value, int)
    ):
        # the `1 << 29` / `256 * 1024`-style size literal
        op = node.op
        a, b = node.left.value, node.right.value
        if isinstance(op, ast.LShift):
            return str(a << b)
        if isinstance(op, ast.Pow):
            return str(a ** b)
        return str(a * b)
    return None


def _defaults_equal(a: str, b: str, kind: str) -> bool:
    if a == b:
        return True
    if kind in ("int", "float"):
        try:
            return float(a) == float(b)
        except ValueError:
            return False
    return False


class _EnvScanner(ast.NodeVisitor):
    """Env read sites of one module: ``os.environ.get/[]``,
    ``os.getenv``, ``(env or os.environ).get``, accessor calls, and
    ``X_ENV = "FLUVIO_..."`` indirection constants."""

    def __init__(self, path: str, tree: ast.Module, lines: List[str]):
        self.path = path
        self.tree = tree
        self.lines = lines
        #: (flag name, line, literal default or None)
        self.reads: List[Tuple[str, int, Optional[str]]] = []
        self._env_consts: Dict[str, str] = {}
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                v = _const_str(node.value)
                if v is not None and v.startswith("FLUVIO_"):
                    self._env_consts[node.targets[0].id] = v

    def _flag_name(self, node) -> Optional[str]:
        v = _const_str(node)
        if v is not None and v.startswith("FLUVIO_"):
            return v
        if isinstance(node, ast.Name) and node.id in self._env_consts:
            return self._env_consts[node.id]
        return None

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        attr = fn.attr if isinstance(fn, ast.Attribute) else None
        name = fn.id if isinstance(fn, ast.Name) else None
        flag = self._flag_name(node.args[0]) if node.args else None
        if flag is not None:
            default = (
                _literal_default(node.args[1])
                if len(node.args) > 1 else None
            )
            if attr in ("get", "pop", "setdefault") or name == "getenv" or (
                attr == "getenv"
            ):
                self.reads.append((flag, node.lineno, default))
            elif (attr or name) in ACCESSOR_FUNCS:
                # registry accessors carry no site default by design
                self.reads.append((flag, node.lineno, default))
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        flag = self._flag_name(node.slice)
        if flag is not None and isinstance(node.value, ast.Attribute) and (
            node.value.attr == "environ"
        ):
            self.reads.append((flag, node.lineno, None))
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        # "FLUVIO_X" in os.environ
        flag = self._flag_name(node.left)
        if flag is not None and any(
            isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
        ):
            self.reads.append((flag, node.lineno, None))
        self.generic_visit(node)


def scan_env_reads(
    source: str, path: str = "<string>"
) -> List[Tuple[str, int, Optional[str]]]:
    """(flag, line, literal default) env-read sites of one source blob."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return []
    sc = _EnvScanner(path, tree, source.splitlines())
    sc.visit(tree)
    return sc.reads


def lint_env_sources(
    sources: Dict[str, str],
    registry: Optional[Dict[str, EnvFlag]] = None,
) -> List[EnvFinding]:
    """FLV401/FLV403 over ``{path: source}`` (synthetic-module testable,
    mirroring ``concurrency.analyze_sources``)."""
    reg = BY_NAME if registry is None else registry
    findings: List[EnvFinding] = []
    seen_defaults: Dict[str, List[Tuple[str, int, str]]] = {}
    for path, src in sorted(sources.items()):
        lines = src.splitlines()
        for flag, line, default in scan_env_reads(src, path):
            if flag not in reg:
                if not line_suppresses(lines, line, "FLV401"):
                    findings.append(EnvFinding(
                        path, line, "FLV401", ERROR,
                        f"{flag} is read here but not in the env-flag "
                        "registry (typo, or register it in "
                        "analysis/envreg.py)",
                    ))
                continue
            entry = reg[flag]
            if default is not None:
                if line_suppresses(lines, line, "FLV403"):
                    continue
                seen_defaults.setdefault(flag, []).append(
                    (path, line, default)
                )
                if entry.default is not None and not _defaults_equal(
                    default, entry.default, entry.kind
                ):
                    findings.append(EnvFinding(
                        path, line, "FLV403", ERROR,
                        f"{flag} parsed with literal default "
                        f"{default!r} but the registry says "
                        f"{entry.default!r} — hoist onto the "
                        "envreg accessor or fix the registry",
                    ))
    # divergent literal defaults ACROSS modules (both may disagree with
    # a computed/None registry default and still disagree with each
    # other — the original two-modules bug class)
    for flag, sites in sorted(seen_defaults.items()):
        kind = reg[flag].kind if flag in reg else "str"
        first_path, first_line, first_default = sites[0]
        for path, line, default in sites[1:]:
            if not _defaults_equal(default, first_default, kind):
                findings.append(EnvFinding(
                    path, line, "FLV403", ERROR,
                    f"{flag} default {default!r} here diverges from "
                    f"{first_default!r} at {first_path}:{first_line}",
                ))
    return findings


# -- README drift (FLV402) --------------------------------------------------

TABLE_BEGIN = "<!-- envreg:begin (generated by fluvio_tpu.analysis.envreg) -->"
TABLE_END = "<!-- envreg:end -->"


def render_readme_table() -> str:
    """The generated README env table — regenerate with
    ``python -m fluvio_tpu.analysis.envreg``."""
    lines = [
        TABLE_BEGIN,
        "| flag | kind | default | grammar | consumer |",
        "|---|---|---|---|---|",
    ]
    for f in REGISTRY:
        default = "(computed)" if f.default is None else (
            f.default if f.default != "" else "(unset)"
        )
        lines.append(
            f"| `{f.name}` | {f.kind} | `{default}` | {f.grammar} | "
            f"`{f.consumers[0]}` |"
        )
    lines.append(TABLE_END)
    return "\n".join(lines)


def check_readme(text: str, path: str = "README.md") -> List[EnvFinding]:
    """FLV402: every registry flag documented + generated block fresh."""
    findings: List[EnvFinding] = []
    begin = text.find(TABLE_BEGIN)
    end = text.find(TABLE_END)
    if begin < 0 or end < 0:
        findings.append(EnvFinding(
            path, 1, "FLV402", ERROR,
            "README has no generated env table (envreg:begin/end "
            "markers) — run python -m fluvio_tpu.analysis.envreg",
        ))
        return findings
    block = text[begin:end + len(TABLE_END)]
    fresh = render_readme_table()
    if block.strip() != fresh.strip():
        findings.append(EnvFinding(
            path, text[:begin].count("\n") + 1, "FLV402", ERROR,
            "README env table is stale — regenerate with "
            "python -m fluvio_tpu.analysis.envreg",
        ))
    for f in REGISTRY:
        if f.name not in text:
            findings.append(EnvFinding(
                path, 1, "FLV402", ERROR,
                f"registry flag {f.name} is missing from the README",
            ))
    return findings


# -- package scan -----------------------------------------------------------


def _package_sources(root: Optional[str] = None) -> Dict[str, str]:
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out: Dict[str, str] = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [
            d for d in dirnames
            if d not in ("__pycache__", ".git", ".xla_cache", "_build")
        ]
        for f in sorted(filenames):
            if f.endswith(".py"):
                p = os.path.join(dirpath, f)
                try:
                    with open(p, "r", encoding="utf-8") as fh:
                        out[p] = fh.read()
                except OSError:
                    continue
    return out


def lint_env_package(root: Optional[str] = None) -> List[EnvFinding]:
    """The deploy gate: FLV401/403 over the whole package plus FLV402
    against the repo README when one is present (source checkouts;
    installed wheels skip the docs half)."""
    findings = lint_env_sources(_package_sources(root))
    pkg = root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    readme = os.path.join(os.path.dirname(pkg), "README.md")
    if os.path.exists(readme):
        with open(readme, "r", encoding="utf-8") as fh:
            findings.extend(check_readme(fh.read(), path=readme))
    return findings


def registry_report() -> dict:
    """The machine-readable registry (CLI ``analyze --env`` payload)."""
    return {
        "flags": [
            {
                "name": f.name, "kind": f.kind, "default": f.default,
                "grammar": f.grammar, "consumers": list(f.consumers),
                "note": f.note,
            }
            for f in REGISTRY
        ],
        "count": len(REGISTRY),
    }


if __name__ == "__main__":  # pragma: no cover - doc generator
    print(render_readme_table())
