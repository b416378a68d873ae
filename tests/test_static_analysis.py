"""Tier-1 static-analysis gate + AST linter unit tests.

The gate half makes regressions CI failures: the custom AST linter
(fluvio_tpu/analysis/ast_lint.py) must run clean over the whole
package — an unpinned kernel literal, a host sync in a dispatch hot
path, an unguarded telemetry seam, a mutable default, or an unused
import anywhere in fluvio_tpu/ fails tier-1 — and ``ruff check`` (the
curated rule set in pyproject.toml) runs too when the binary exists.

The unit half pins each rule's detection on synthetic sources, so the
gate cannot silently weaken.
"""

from __future__ import annotations

import os
import shutil
import subprocess

import pytest

from fluvio_tpu.analysis.ast_lint import lint_repo, lint_source

_KERNEL_PATH = "fluvio_tpu/smartengine/tpu/pallas_kernels.py"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# The gate
# ---------------------------------------------------------------------------


def test_repo_lint_is_clean():
    """The CI gate: the whole fluvio_tpu package passes the invariant
    linter. A regression anywhere — including a fresh unpinned weak
    literal in a kernel module — fails tier-1 here."""
    violations = lint_repo()
    assert not violations, "\n".join(str(v) for v in violations)


def test_ruff_clean_when_available():
    """`ruff check` over the curated pyproject rule set, wired into
    tier-1 wherever the binary exists (the native linter above keeps
    the same classes enforced where it does not)."""
    ruff = shutil.which("ruff")
    if ruff is None:
        pytest.skip("ruff not installed in this environment")
    proc = subprocess.run(
        [ruff, "check", "fluvio_tpu"],
        cwd=_REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_pyproject_carries_ruff_config():
    with open(os.path.join(_REPO_ROOT, "pyproject.toml")) as f:
        text = f.read()
    assert "[tool.ruff" in text
    assert "F401" in text and "B006" in text


def test_cli_full_analysis_gate_is_clean():
    """The CI deploy gate, all four repo passes through the one CLI the
    operator runs: `analyze --lint --concurrency --values --env` must
    exit 0 — AST invariants, lock discipline (FLV2xx), value flow
    (FLV3xx), and the env-config registry (FLV4xx)."""
    import json
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "fluvio_tpu.cli",
         "analyze", "--lint", "--concurrency", "--values", "--env",
         "--format", "json"],
        cwd=_REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    # combined passes must emit ONE parseable document, not four
    # concatenated dumps
    doc = json.loads(proc.stdout)
    assert doc["lint"] == []
    assert doc["concurrency"]["cycles"] == []
    assert not [
        f for f in doc["concurrency"]["findings"] if f["level"] == "error"
    ]
    assert doc["values"]["findings"] == []
    assert doc["env"]["findings"] == []
    assert doc["env"]["registry"]["count"] >= 60


def test_valueflow_pass_clean_in_process():
    """Same gate without the subprocess: unsuppressed FLV3xx findings
    anywhere in the registered engine modules fail tier-1."""
    from fluvio_tpu.analysis import analyze_values

    report = analyze_values()
    assert not report.findings, "\n".join(str(f) for f in report.findings)


def test_env_lint_clean_in_process():
    """FLV401/402/403 over the package + README fail tier-1 here."""
    from fluvio_tpu.analysis import lint_env

    findings = lint_env()
    assert not findings, "\n".join(str(f) for f in findings)


def test_concurrency_pass_clean_in_process():
    """Same gate without the subprocess: ERROR-severity FLV2xx findings
    anywhere in fluvio_tpu/ fail tier-1."""
    from fluvio_tpu.analysis import analyze_concurrency

    report = analyze_concurrency()
    assert not report.errors(), "\n".join(str(f) for f in report.errors())


# ---------------------------------------------------------------------------
# FLV001/FLV002 — kernel literal pinning
# ---------------------------------------------------------------------------


def _codes(violations):
    return [v.code for v in violations]


def test_both_literal_where_flags_anywhere_in_kernel_module():
    src = (
        "import jax.numpy as jnp\n"
        "def helper(mask):\n"
        "    return jnp.where(mask, 1, 0)\n"
    )
    vs = lint_source(src, path=_KERNEL_PATH)
    assert "FLV001" in _codes(vs)


def test_single_literal_where_ok_outside_kernel_bodies():
    # a weak literal paired with an array operand defers to the array
    # dtype — only the both-literal form promotes
    src = (
        "import jax.numpy as jnp\n"
        "def helper(mask, x):\n"
        "    return jnp.where(mask, x, 0)\n"
    )
    assert not lint_source(src, path=_KERNEL_PATH)


def test_pinned_where_is_clean():
    src = (
        "import jax.numpy as jnp\n"
        "def _scan_kernel(ref):\n"
        "    return jnp.where(ref[0] > 0, jnp.int32(1), jnp.int32(0))\n"
    )
    assert not lint_source(src, path=_KERNEL_PATH)


def test_kernel_body_flags_any_bare_value_literal():
    src = (
        "import jax.numpy as jnp\n"
        "def _scan_kernel(ref, out):\n"
        "    out[:] = jnp.where(ref[0] > 0, 1, ref[1])\n"
    )
    vs = lint_source(src, path=_KERNEL_PATH)
    assert "FLV002" in _codes(vs)


def test_kernel_body_flags_bare_fori_bounds():
    src = (
        "import jax\n"
        "def _scan_kernel(ref):\n"
        "    return jax.lax.fori_loop(0, 8, lambda i, c: c, ref[0])\n"
    )
    vs = lint_source(src, path=_KERNEL_PATH)
    assert _codes(vs).count("FLV002") == 2  # both bounds


def test_kernel_body_flags_undtyped_full():
    src = (
        "import jax.numpy as jnp\n"
        "def _x_kernel(ref):\n"
        "    a = jnp.full((1, 8), 3)\n"
        "    b = jnp.full((1, 8), 3, dtype=jnp.int32)\n"
        "    return a, b\n"
    )
    vs = lint_source(src, path=_KERNEL_PATH)
    assert _codes(vs) == ["FLV002"]


def test_non_kernel_module_skips_kernel_rules():
    src = (
        "import jax.numpy as jnp\n"
        "def _scan_kernel(ref):\n"
        "    return jnp.where(ref[0] > 0, 1, ref[1])\n"
    )
    assert not lint_source(src, path="fluvio_tpu/telemetry/registry.py")


# ---------------------------------------------------------------------------
# FLV003 — host syncs
# ---------------------------------------------------------------------------


def test_host_sync_flags_in_kernel_module():
    src = (
        "def fetch(x):\n"
        "    n = x.item()\n"
        "    x.block_until_ready()\n"
        "    return n\n"
    )
    vs = lint_source(src, path=_KERNEL_PATH)
    assert _codes(vs) == ["FLV003", "FLV003"]


def test_host_sync_flags_in_executor_dispatch_side_only():
    exec_path = "fluvio_tpu/smartengine/tpu/executor.py"
    hot = (
        "import jax\n"
        "class E:\n"
        "    def _dispatch(self, buf):\n"
        "        return jax.device_get(buf)\n"
    )
    assert _codes(lint_source(hot, path=exec_path)) == ["FLV003"]
    fetch_side = (
        "import jax\n"
        "class E:\n"
        "    def _fetch(self, h):\n"
        "        return jax.device_get(h)\n"
    )
    assert not lint_source(fetch_side, path=exec_path)


# ---------------------------------------------------------------------------
# FLV004 — telemetry seams
# ---------------------------------------------------------------------------


def test_telemetry_seam_allows_guarded_api():
    src = (
        "from fluvio_tpu.telemetry import TELEMETRY\n"
        "def f(n):\n"
        "    if not TELEMETRY.enabled:\n"
        "        return\n"
        "    TELEMETRY.gauge_add('x', n)\n"
        "    TELEMETRY.add_spill('r')\n"
    )
    assert not lint_source(src, path="fluvio_tpu/smartengine/tpu/buffer.py")


def test_telemetry_seam_rejects_registry_internals():
    src = (
        "from fluvio_tpu.telemetry import TELEMETRY\n"
        "def f():\n"
        "    TELEMETRY.spans.push(None)\n"
        "    return TELEMETRY.gauges\n"
    )
    vs = lint_source(src, path="fluvio_tpu/smartengine/tpu/buffer.py")
    assert _codes(vs) == ["FLV004", "FLV004"]


def test_telemetry_seam_covers_the_client_socket_layer():
    """The consumer's records of stream responses go through
    `begin_consume` / `end_consume`; the consume ring itself is a
    registry internal there as anywhere."""
    ok = (
        "from fluvio_tpu.telemetry import TELEMETRY\n"
        "def f():\n"
        "    rec = TELEMETRY.begin_consume()\n"
        "    TELEMETRY.end_consume(rec)\n"
    )
    path = "fluvio_tpu/transport/multiplexing.py"
    assert not lint_source(ok, path=path)
    bad = (
        "from fluvio_tpu.telemetry import TELEMETRY\n"
        "def f(rec):\n"
        "    TELEMETRY.consumes.push(rec)\n"
    )
    assert _codes(lint_source(bad, path=path)) == ["FLV004"]
    # outside the seam's layers the rule does not apply
    assert not lint_source(bad, path="fluvio_tpu/spu/monitoring.py")


# ---------------------------------------------------------------------------
# FLV101/FLV102 — hygiene
# ---------------------------------------------------------------------------


def test_mutable_default_flags():
    src = "def f(a, b=[], c={}, d=dict()):\n    return a\n"
    vs = lint_source(src, path="fluvio_tpu/x.py")
    assert _codes(vs) == ["FLV101", "FLV101", "FLV101"]


def test_unused_import_flags_and_noqa_suppresses():
    src = "import os\nimport sys  # noqa: F401\n"
    vs = lint_source(src, path="fluvio_tpu/x.py")
    assert len(vs) == 1 and vs[0].code == "FLV102"
    assert "os" in vs[0].message


def test_quoted_annotation_counts_as_use():
    src = (
        "from typing import List\n"
        "from foo import Bar\n"
        "def f(x: 'List[Bar]'):\n"
        "    return x\n"
    )
    assert not lint_source(src, path="fluvio_tpu/x.py")


def test_docstring_mention_does_not_mask_unused_import():
    src = '"""Uses Bar for things."""\nfrom foo import Bar\n'
    vs = lint_source(src, path="fluvio_tpu/x.py")
    assert _codes(vs) == ["FLV102"]


def test_init_py_exempt_from_unused_imports():
    src = "from foo import Bar\n"
    assert not lint_source(src, path="fluvio_tpu/sub/__init__.py")


def test_syntax_error_reports_flv000():
    vs = lint_source("def broken(:\n", path="fluvio_tpu/x.py")
    assert _codes(vs) == ["FLV000"]
