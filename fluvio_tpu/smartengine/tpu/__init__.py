"""TPU engine backend: DSL chains lowered to fused JAX/XLA programs.

Architecture (the north star; see SURVEY.md §7 step 2):

- records stage into a padded, bucketed `RecordBuffer` (uint8[N, L] values
  + lengths + key/offset/timestamp columns) that lives in HBM,
- each DSL transform lowers to vectorized kernels over that buffer
  (regex -> DFA byte-class scan, JSON field access -> structural-scan
  state machine, aggregate -> segmented prefix scans with a
  device-resident carry),
- a whole chain compiles into ONE jitted function (filters become lazy
  validity masks — no mid-chain compaction or host round-trips),
- aggregate accumulator/window state crosses `process()` calls on device.

int64 is enabled process-wide here: offsets/timestamps/aggregates are
64-bit in the protocol and must not silently truncate.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: a broker must not stall ~25s on the
# first consume of each chain/shape bucket in every process. Compiled
# executables persist across processes keyed by HLO hash.
#
# Placement comes from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is set
# (jax reads it itself; no directory is set in code, so the caller's
# choice is never overridden); otherwise the fixed ``<checkout>/.xla_cache``
# (the path is part of the cache key, so it must not move).
# ``FLUVIO_TPU_XLA_CACHE=off`` disables the in-checkout default.


def _resolve_cache_dir() -> str:
    """The persistent-cache directory this process compiles into ("" =
    none), setting it in code only when the environment did not."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if outside:
        return outside
    if os.environ.get("FLUVIO_TPU_XLA_CACHE") == "off":
        return ""
    fixed = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "..", ".xla_cache")
    )
    jax.config.update("jax_compilation_cache_dir", fixed)
    return fixed


#: the resolved persistent-cache directory ("" when disabled) — the single
#: source of truth for telemetry/compiles.py and bench.py's cache evidence
XLA_CACHE_DIR = _resolve_cache_dir()
if XLA_CACHE_DIR:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
