"""Built-in SmartModules — the canonical module zoo.

These are the analogs of the reference's example modules
(`smartmodule/regex-filter`, the cargo template kinds, and the benchmark
chains from HOST_BASELINE.md). Each submodule exposes ``module() ->
SmartModuleDef`` carrying a DSL program (TPU-lowerable) and, where the
reference's example does interesting host-side work (regex compile in init),
equivalent Python hooks so hook-vs-DSL equivalence is tested.

Registry for name-based resolution (the analog of the SmartModule store
lookup a broker does for `uses:` names in a TransformationConfig).
"""

from __future__ import annotations

from typing import Callable, Dict

from fluvio_tpu.smartmodule.sdk import SmartModuleDef

_REGISTRY: Dict[str, Callable[[], SmartModuleDef]] = {}


def register(name: str, factory: Callable[[], SmartModuleDef]) -> None:
    _REGISTRY[name] = factory


def lookup(name: str) -> SmartModuleDef:
    """Instantiate a built-in module by registry name."""
    from fluvio_tpu.models import (  # noqa: F401 — populate registry
        aggregate_sum,
        array_map_explode,
        dedup_filter,
        json_map,
        json_regex_filter,
        regex_filter,
        windowed_aggregate,
    )

    if name not in _REGISTRY:
        raise KeyError(
            f"unknown built-in SmartModule {name!r}; have {sorted(_REGISTRY)}"
        )
    return _REGISTRY[name]()


def builtin_sources() -> Dict[str, bytes]:
    """Source-artifact payloads for modules brokers pre-provision.

    The analog of hub-provided standard modules (the reference's
    `dedup-filter`): every SPU seeds its SmartModule local store with
    these at startup so topic configs can name them without an explicit
    `smartmodule create`. An SC-pushed module with the same name
    overrides the bundled copy.
    """
    from fluvio_tpu.models import dedup_filter

    return {"dedup-filter": dedup_filter.SOURCE.encode()}


def builtin_names() -> list:
    from fluvio_tpu.models import (  # noqa: F401
        aggregate_sum,
        array_map_explode,
        dedup_filter,
        json_map,
        json_regex_filter,
        regex_filter,
        windowed_aggregate,
    )

    return sorted(_REGISTRY)
