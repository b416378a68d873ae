"""Rehearsal of `chip_smoke.py` without the chip (`on-chip-measurement`
guide §2, rehearsals 1 and 2) + the compile-cache placement rule.

The script proves the chip path, so un-steered it must FAIL here: the
rehearsals steer it IN THE TEST (module constants by monkeypatch, the
`auto` policies by env) — the program has no option that lets it pass
without a TPU. The env steer mirrors what `auto` resolves to on a TPU:
Pallas kernels (interpreted here), the glz result encoder, the
associative DFA, the fast JSON kernel.
"""

from __future__ import annotations

import json
import os

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TPU_LIKE_ENV = {
    "FLUVIO_TPU_PALLAS": "interpret",
    "FLUVIO_RESULT_COMPRESS": "on",
    "FLUVIO_DFA_ASSOC": "1",
    "FLUVIO_TPU_FAST_JSON": "1",
    "FLUVIO_RETRY_BASE_MS": "0",
}


@pytest.fixture
def tiny(monkeypatch):
    """Steer the smoke to a tiny size on the CPU backend."""
    from fluvio_tpu.telemetry import TELEMETRY

    for k, v in TPU_LIKE_ENV.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "RECORDS", 6_000)
    monkeypatch.setattr(chip_smoke, "FAT_RECORDS", 12)
    monkeypatch.setattr(chip_smoke, "WINDOW_BATCH", 1_024)
    monkeypatch.setattr(chip_smoke, "WINDOW_BATCHES", 2)
    monkeypatch.setattr(chip_smoke, "SLICE", 256)
    monkeypatch.setattr(chip_smoke, "FAT_SLICE", 4)
    monkeypatch.setattr(chip_smoke, "WIRE_BATCH", 2_048)
    monkeypatch.setattr(chip_smoke, "_REFERENCE_RECORDS", [0])
    # the truth phase asserts process-global counters: earlier tests in
    # this worker healed and spilled on purpose
    TELEMETRY.reset()
    yield
    TELEMETRY.reset()


def _result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_unsteered_smoke_fails_on_cpu(capsys):
    """No TPU: non-zero exit, the result line is never printed."""
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "not 'tpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_rehearsal(tiny, capsys):
    """broker -> chains -> truth -> drill, end to end at a tiny size."""
    assert chip_smoke.main(["--seed", "7"]) == 0
    out = capsys.readouterr().out
    res = _result_line(out)
    assert res["ok"] is True and res["device"]["platform"] == "cpu"
    for marker in (
        "broker: cold pass", "broker: warm pass", "fallback_slices=0",
        "chains: 1_filter", "chains: 3_aggregate", "chains: 4_array_map",
        "chains: 5_windowed(classic)", "chains: 5_windowed(runtime)",
        "chains: 10_regex_json_fat", "truth: heals=0", "drill: injected",
    ):
        assert marker in out, marker
    # nothing but the contract's keys on the last line
    assert set(res) == {"ok", "device"}
    assert set(res["device"]) == {"platform", "kind", "count"}


def test_a_failing_phase_fails_the_script(tiny, monkeypatch, capsys):
    """No try/except turns a phase failure into a printed note."""

    real = chip_smoke.north_star_host_reference
    monkeypatch.setattr(
        chip_smoke, "north_star_host_reference", lambda v: real(v)[:-1]
    )
    with pytest.raises(AssertionError, match="broker cold pass"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_rehearsal_on_virtual_devices(tiny, capsys):
    """`--chips 4` runs ONLY the multi-chip path and its comparison, and
    really spreads over four (virtual) devices."""
    assert chip_smoke.main(["--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert _result_line(out)["ok"] is True
    assert "record-sharded north-star over 4 devices" in out
    assert "9_partitioned 4 partitions over 4 device groups" in out
    assert "broker:" not in out and "chains:" not in out


def test_four_chip_phase_refuses_a_folded_mesh(tiny, monkeypatch):
    """Fewer devices than asked is a failure, not a silent fold."""
    import jax

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    with pytest.raises(SystemExit, match="--chips 4 asked"):
        chip_smoke.main(["--chips", "4"])


# ---------------------------------------------------------------------------
# compile cache placed from outside
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_updates(monkeypatch):
    """Record (not apply) what `_resolve_cache_dir` sets in jax.config."""
    import jax

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v))
    )
    return calls


def test_cache_dir_from_environment_sets_nothing_in_code(
    monkeypatch, cache_updates, tmp_path
):
    from fluvio_tpu.smartengine import tpu

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert tpu._resolve_cache_dir() == str(tmp_path)
    assert cache_updates == [], "a directory given from outside is jax's to read"


def test_cache_dir_defaults_to_fixed_in_checkout_path(
    monkeypatch, cache_updates
):
    from fluvio_tpu.smartengine import tpu

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("FLUVIO_TPU_XLA_CACHE", raising=False)
    fixed = os.path.join(REPO, ".xla_cache")
    assert tpu._resolve_cache_dir() == fixed
    assert cache_updates == [("jax_compilation_cache_dir", fixed)]
    monkeypatch.setenv("FLUVIO_TPU_XLA_CACHE", "off")
    assert tpu._resolve_cache_dir() == ""
