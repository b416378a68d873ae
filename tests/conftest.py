"""Test harness config: force an 8-device virtual CPU mesh for JAX tests.

Tests must be hermetic on the CPU whatever the caller's environment
says, so the platform is pinned in the jax config before any backend
initializes (that is what tests are for; only chip_smoke.py and bench.py
target the real chip).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full soak scenarios; tier-1 runs with -m 'not slow'",
    )
