"""Env-flag registry (FLV4xx): completeness, typed accessors, lint
pins, README drift gate, and the boot hook.

The registry (`analysis/envreg.py`) is the single source of truth for
every ``FLUVIO_*`` flag's default; typed accessors resolve through it
(so divergent per-site defaults are structurally impossible for
hoisted flags), FLV401/402/403 make the remaining drift classes CI
failures, and `warn_unknown_env` surfaces deploy-manifest typos at
boot.
"""

from __future__ import annotations

import warnings

import pytest

from fluvio_tpu.analysis.envreg import (
    BY_NAME,
    REGISTRY,
    check_readme,
    env_bool,
    env_float,
    env_int,
    env_raw,
    lint_env_package,
    lint_env_sources,
    render_readme_table,
    scan_env_reads,
    unknown_env,
    warn_unknown_env,
)

# ---------------------------------------------------------------------------
# The repo gate + registry invariants
# ---------------------------------------------------------------------------


def test_package_env_lint_is_clean():
    """ISSUE-14 acceptance: zero FLV401/402/403 across the package AND
    the README (every read registered, docs fresh, no divergent
    defaults)."""
    findings = lint_env_package()
    assert not findings, "\n".join(str(f) for f in findings)


def test_registry_covers_every_package_read():
    """Structural completeness: every FLUVIO_* env read anywhere in
    fluvio_tpu/ resolves to a registry row (the FLV401 predicate,
    asserted directly so the gate cannot weaken)."""
    import os

    import fluvio_tpu

    root = os.path.dirname(os.path.abspath(fluvio_tpu.__file__))
    seen = set()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fname), encoding="utf-8") as fh:
                for flag, _, _ in scan_env_reads(fh.read()):
                    seen.add(flag)
    unregistered = seen - set(BY_NAME)
    assert not unregistered, unregistered
    # and the registry carries no dead rows nothing reads
    dead = set(BY_NAME) - seen
    assert not dead, dead


def test_registry_is_sorted_unique_and_well_formed():
    names = [f.name for f in REGISTRY]
    assert names == sorted(names)
    assert len(names) == len(set(names))
    assert len(REGISTRY) >= 60  # the full flag surface, not a sample
    for f in REGISTRY:
        assert f.name.startswith("FLUVIO_")
        assert f.kind in ("int", "float", "bool01", "mode", "path", "spec")
        assert f.consumers and f.note and f.grammar


def test_numeric_defaults_parse():
    for f in REGISTRY:
        if f.kind == "int" and f.default not in (None, ""):
            int(float(f.default))
        if f.kind == "float" and f.default not in (None, ""):
            float(f.default)


def test_registry_defaults_match_code_constants():
    """The registry duplicates a handful of engine constants by value;
    pin them so the single-source claim stays true."""
    from fluvio_tpu.admission.batcher import SLICE_STRIDE  # noqa: F401
    from fluvio_tpu.smartengine.tpu.buffer import MAX_WIDTH
    from fluvio_tpu.smartengine.tpu.glz import GLZ_CHUNK
    from fluvio_tpu.smartengine.tpu.kernels import DFA_ASSOC_MAX_STATES
    from fluvio_tpu.smartengine.tpu.stripes import (
        STRIPE_OVERLAP,
        STRIPE_WIDTH,
    )

    from fluvio_tpu.resilience.deadletter import DEFAULT_DEADLETTER_DIR
    from fluvio_tpu.spu.monitoring import SPU_MONITORING_UNIX_SOCKET
    from fluvio_tpu.telemetry.timeseries import (
        DEFAULT_WINDOW_S,
        DEFAULT_WINDOWS,
    )
    from fluvio_tpu.telemetry.trace import DEFAULT_TRACE_MAX_MB

    assert int(BY_NAME["FLUVIO_STRIPE_THRESHOLD"].default) == MAX_WIDTH
    assert int(BY_NAME["FLUVIO_STRIPE_WIDTH"].default) == STRIPE_WIDTH
    assert int(BY_NAME["FLUVIO_STRIPE_OVERLAP"].default) == STRIPE_OVERLAP
    assert int(BY_NAME["FLUVIO_GLZ_CHUNK"].default) == GLZ_CHUNK
    assert int(BY_NAME["FLUVIO_DFA_ASSOC_MAX_STATES"].default) == (
        DFA_ASSOC_MAX_STATES
    )
    assert float(BY_NAME["FLUVIO_SLO_WINDOW_S"].default) == DEFAULT_WINDOW_S
    assert int(BY_NAME["FLUVIO_SLO_WINDOWS"].default) == DEFAULT_WINDOWS
    assert float(BY_NAME["FLUVIO_TRACE_MAX_MB"].default) == (
        DEFAULT_TRACE_MAX_MB
    )
    assert BY_NAME["FLUVIO_DEADLETTER_DIR"].default == DEFAULT_DEADLETTER_DIR
    assert BY_NAME["FLUVIO_METRIC_SPU"].default == SPU_MONITORING_UNIX_SOCKET


# ---------------------------------------------------------------------------
# Typed accessors
# ---------------------------------------------------------------------------


def test_env_raw_resolves_default_and_override():
    assert env_raw("FLUVIO_ADMISSION_QUEUE", {}) == "64"
    assert env_raw("FLUVIO_ADMISSION_QUEUE",
                   {"FLUVIO_ADMISSION_QUEUE": "9"}) == "9"


def test_env_raw_raises_on_unregistered_name():
    # the runtime FLV401: a typo'd accessor call fails loudly
    with pytest.raises(KeyError):
        env_raw("FLUVIO_NOT_A_FLAG", {})


def test_numeric_accessors_fall_back_on_garbage():
    # the admission env_float contract, now repo-wide: an env typo
    # must never crash a serving broker
    assert env_int("FLUVIO_ADMISSION_QUEUE",
                   {"FLUVIO_ADMISSION_QUEUE": "banana"}) == 64
    assert env_float("FLUVIO_ADMISSION_WARN_SHED",
                     {"FLUVIO_ADMISSION_WARN_SHED": ""}) == 0.5
    assert env_int("FLUVIO_SLO_WINDOWS", {"FLUVIO_SLO_WINDOWS": "12"}) == 12


def test_env_bool_off_vocabulary():
    for off in ("0", "", "off", "false", "OFF", "False"):
        assert env_bool("FLUVIO_ADMISSION", {"FLUVIO_ADMISSION": off}) is (
            False
        )
    assert env_bool("FLUVIO_ADMISSION", {"FLUVIO_ADMISSION": "1"})
    assert env_bool("FLUVIO_TELEMETRY", {})  # default-on gate


def test_admission_env_float_shim_delegates_to_registry():
    from fluvio_tpu.admission.types import env_float as adm_env_float

    assert adm_env_float("FLUVIO_ADMISSION_TOKENS") == 64.0


# ---------------------------------------------------------------------------
# Injected-hazard pins (FLV401 / FLV403)
# ---------------------------------------------------------------------------


def test_unregistered_read_flags_flv401():
    src = 'import os\nx = os.environ.get("FLUVIO_TYPO_FLAG", "1")\n'
    findings = lint_env_sources({"m.py": src})
    assert [f.code for f in findings] == ["FLV401"]
    assert "FLUVIO_TYPO_FLAG" in findings[0].message


def test_env_const_indirection_is_scanned():
    # the TRACE_ENV = "FLUVIO_..." idiom counts as a read site
    src = (
        "import os\n"
        'X_ENV = "FLUVIO_BOGUS_INDIRECT"\n'
        "y = os.environ.get(X_ENV)\n"
    )
    findings = lint_env_sources({"m.py": src})
    assert [f.code for f in findings] == ["FLV401"]


def test_noqa_suppresses_flv401():
    src = (
        "import os\n"
        'x = os.environ.get("FLUVIO_TYPO_FLAG", "1")  # noqa: FLV401\n'
    )
    assert not lint_env_sources({"m.py": src})


def test_site_default_diverging_from_registry_flags_flv403():
    src = 'import os\nq = int(os.environ.get("FLUVIO_ADMISSION_QUEUE", "32"))\n'
    findings = lint_env_sources({"m.py": src})
    assert [f.code for f in findings] == ["FLV403"]
    assert "'64'" in findings[0].message


def test_two_modules_two_defaults_flags_flv403():
    # the original bug class, against a computed-default registry row
    # (no per-site-vs-registry check possible — only the pairwise one)
    from fluvio_tpu.analysis.envreg import BY_NAME as real

    reg = dict(real)
    a = 'import os\nx = os.environ.get("FLUVIO_TPU_NATIVE_BUILD", "/a")\n'
    b = 'import os\nx = os.environ.get("FLUVIO_TPU_NATIVE_BUILD", "/b")\n'
    findings = lint_env_sources({"a.py": a, "b.py": b}, registry=reg)
    assert [f.code for f in findings] == ["FLV403"]
    assert "a.py" in findings[0].message


def test_matching_site_default_is_clean():
    src = 'import os\nq = int(os.environ.get("FLUVIO_ADMISSION_QUEUE", "64"))\n'
    assert not lint_env_sources({"m.py": src})


# ---------------------------------------------------------------------------
# FLV402 — README drift gate
# ---------------------------------------------------------------------------


def test_missing_table_flags_flv402():
    findings = check_readme("# README\nno table here\n")
    assert findings and findings[0].code == "FLV402"


def test_stale_table_flags_flv402():
    fresh = render_readme_table()
    stale = fresh.replace("| `FLUVIO_ADMISSION` |", "| `FLUVIO_ADMISSION_X` |")
    findings = check_readme("# README\n" + stale + "\n")
    assert any(f.code == "FLV402" for f in findings)


def test_fresh_table_is_clean():
    text = "# README\n" + render_readme_table() + "\n"
    # every flag name appears inside the table itself
    assert not check_readme(text)


def test_repo_readme_carries_the_generated_table():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "README.md"), encoding="utf-8") as fh:
        text = fh.read()
    assert not check_readme(text)
    assert render_readme_table() in text


# ---------------------------------------------------------------------------
# warn_unknown_env — the boot hook
# ---------------------------------------------------------------------------


def test_unknown_env_reports_set_but_unread_flags():
    env = {"FLUVIO_NOT_A_FLAG": "1", "FLUVIO_TELEMETRY": "0", "PATH": "x"}
    assert unknown_env(env) == ["FLUVIO_NOT_A_FLAG"]
    assert unknown_env({"FLUVIO_TELEMETRY": "0"}) == []


def test_warn_unknown_env_warns_once_per_flag():
    env = {"FLUVIO_TPYO": "1"}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        names = warn_unknown_env(env)
    assert names == ["FLUVIO_TPYO"]
    assert len(caught) == 1 and "FLUVIO_TPYO" in str(caught[0].message)


def test_deleted_link_compress_flag_warns_like_a_typo():
    """The up-link has one form since PR 33: an operator who still sets
    the deleted flag is told so at boot, like any other unread name."""
    env = {"FLUVIO_LINK_COMPRESS": "on", "FLUVIO_RESULT_COMPRESS": "on"}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        names = warn_unknown_env(env)
    assert names == ["FLUVIO_LINK_COMPRESS"]
    assert len(caught) == 1 and "FLUVIO_LINK_COMPRESS" in str(caught[0].message)


def test_registry_has_77_flags_and_none_is_the_link_chooser():
    assert len(REGISTRY) == 77
    assert not any("LINK_COMPRESS" in f.name for f in REGISTRY)
    assert "encoder" in BY_NAME["FLUVIO_GLZ_CHUNK"].note


def test_server_start_invokes_the_hook():
    import inspect

    from fluvio_tpu.spu import server as spu_server

    src = inspect.getsource(spu_server)
    assert "warn_unknown_env" in src
