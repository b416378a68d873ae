"""SmartModule authoring decorators and artifact loading.

Capability parity: `fluvio-smartmodule-derive` — the `#[smartmodule(...)]`
attribute macros that turn user functions into engine-callable transforms
(fluvio-smartmodule-derive/src/generator/). Here the authoring surface is
Python decorators; a SmartModule artifact is Python source text (the analog
of the reference's WASM payload), loaded with :func:`load_source`, or an
imported module object via :func:`from_python_module`.

User function contracts (mirroring the Rust SDK signatures):

- ``@smartmodule.filter``      ``fn(record) -> bool``
- ``@smartmodule.map``         ``fn(record) -> bytes | (key, value)``
- ``@smartmodule.filter_map``  ``fn(record) -> None | bytes | (key, value)``
- ``@smartmodule.array_map``   ``fn(record) -> list[bytes | (key, value)]``
- ``@smartmodule.aggregate``   ``fn(acc: bytes, record) -> bytes``
- ``@smartmodule.init``        ``fn(params: dict) -> None``
- ``@smartmodule.look_back``   ``fn(record) -> None``

``record`` is a :class:`~fluvio_tpu.smartmodule.types.SmartModuleRecord`.
Raising inside a user fn is the analog of returning ``Err`` in Rust: the
engine records a transform runtime error at that record and short-circuits.

A transform may also attach a declarative DSL program (``dsl=``) describing
the same computation; the TPU engine backend requires it to lower the module
to JAX kernels, and tests assert DSL-vs-Python equivalence. A transform
stated by its program alone, which every backend then interprets or lowers
(a `dsl.WindowProgram` has no per-record hook form: its outputs are its
closed windows'), decorates ``None``: ``smartmodule.aggregate(dsl=...)(None)``.
"""

from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass, field as dc_field
from typing import Any, Callable, Dict, Optional

from fluvio_tpu.smartmodule.types import TRANSFORM_KIND_ORDER, SmartModuleKind


@dataclass
class SmartModuleDef:
    """A compiled SmartModule: hooks by kind + optional DSL programs."""

    name: str = "adhoc"
    #: stable identity for metering quarantine: the source hash when the
    #: module came from payload bytes, else the name. Names collide
    #: (every adhoc invocation defaults to "adhoc"), hashes do not — a
    #: quarantine keyed on this stays scoped to the hostile module.
    meter_key: str = ""
    hooks: Dict[SmartModuleKind, Callable] = dc_field(default_factory=dict)
    dsl: Dict[SmartModuleKind, Any] = dc_field(default_factory=dict)

    def transform_kind(self) -> SmartModuleKind:
        """Detect the module's transform kind.

        Parity with the engine's export probing order
        (transforms/mod.rs:24-52): filter -> map -> filter_map -> array_map
        -> aggregate.
        """
        for kind in TRANSFORM_KIND_ORDER:
            if kind in self.hooks or kind in self.dsl:
                return kind
        raise ValueError(
            f"SmartModule {self.name!r} exports no transform "
            f"(expected one of filter/map/filter_map/array_map/aggregate)"
        )

    def hook(self, kind: SmartModuleKind) -> Optional[Callable]:
        return self.hooks.get(kind)

    def dsl_program(self, kind: SmartModuleKind):
        return self.dsl.get(kind)

    def has_init(self) -> bool:
        return SmartModuleKind.INIT in self.hooks

    def has_look_back(self) -> bool:
        return SmartModuleKind.LOOK_BACK in self.hooks


# ---------------------------------------------------------------------------
# Decorators
# ---------------------------------------------------------------------------

# Modules under construction, keyed per-thread so concurrent source loads
# don't interleave.
_BUILDING = threading.local()


def _current() -> SmartModuleDef:
    m = getattr(_BUILDING, "module", None)
    if m is None:
        m = SmartModuleDef()
        _BUILDING.module = m
    return m


def current_module(reset: bool = True) -> SmartModuleDef:
    """Collect the module assembled by decorator use since the last call."""
    m = _current()
    if reset:
        _BUILDING.module = None
    return m


class _SmartModuleNamespace:
    """The ``smartmodule`` decorator namespace."""

    @staticmethod
    def _register(kind: SmartModuleKind, fn: Callable, dsl: Any = None) -> Callable:
        m = _current()
        if kind in m.hooks or (dsl is not None and kind in m.dsl):
            raise ValueError(f"duplicate #[smartmodule({kind.value})] export")
        if fn is not None:
            m.hooks[kind] = fn
        elif dsl is None:
            raise ValueError(f"#[smartmodule({kind.value})] exports nothing")
        if dsl is not None:
            m.dsl[kind] = dsl
        return fn

    def _make(self, kind: SmartModuleKind):
        def decorator(fn: Callable = None, *, dsl: Any = None):
            if fn is None:
                return lambda f: self._register(kind, f, dsl)
            return self._register(kind, fn, dsl)

        decorator.__name__ = kind.value
        return decorator

    def __init__(self) -> None:
        self.filter = self._make(SmartModuleKind.FILTER)
        self.map = self._make(SmartModuleKind.MAP)
        self.filter_map = self._make(SmartModuleKind.FILTER_MAP)
        self.array_map = self._make(SmartModuleKind.ARRAY_MAP)
        self.aggregate = self._make(SmartModuleKind.AGGREGATE)
        self.init = self._make(SmartModuleKind.INIT)
        self.look_back = self._make(SmartModuleKind.LOOK_BACK)


smartmodule = _SmartModuleNamespace()


# ---------------------------------------------------------------------------
# Artifact loading
# ---------------------------------------------------------------------------


def load_source(source: str | bytes, name: str = "adhoc") -> SmartModuleDef:
    """Compile a SmartModule from Python source text.

    The analog of instantiating a WASM payload: the source runs in a fresh
    namespace with the SDK pre-imported, and the decorators it uses assemble
    the module definition.
    """
    if isinstance(source, bytes):
        source = source.decode("utf-8")
    # Flush any partial module left by an earlier failed load.
    current_module(reset=True)
    import fluvio_tpu.smartmodule.dsl as dsl_mod

    namespace: Dict[str, Any] = {
        "smartmodule": smartmodule,
        "dsl": dsl_mod,
        "__name__": f"smartmodule_{name}",
    }
    code = compile(source, f"<smartmodule:{name}>", "exec")
    exec(code, namespace)
    module = current_module(reset=True)
    module.name = name
    module.meter_key = hashlib.sha256(source.encode("utf-8")).hexdigest()[:16]
    module.transform_kind()  # validate: must export a transform
    return module


def from_python_module(py_module, name: Optional[str] = None) -> SmartModuleDef:
    """Build a SmartModuleDef from an already-imported Python module.

    The module is expected to expose a ``module()`` factory (our built-ins
    under ``fluvio_tpu.models`` do) or to have used the decorators at import
    time (in which case the collected defs are returned).
    """
    if hasattr(py_module, "module"):
        m = py_module.module()
    else:
        m = current_module(reset=True)
    if name:
        m.name = name
    elif m.name == "adhoc":
        m.name = getattr(py_module, "__name__", "adhoc")
    m.transform_kind()
    return m
