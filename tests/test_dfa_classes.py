"""Byte-equivalence-class DFA packing.

ISSUE-16 differential suite. The packed table (one column per byte
EQUIVALENCE class instead of 258 raw symbols) must be bit-equal to the
unpacked legacy table on every input — pinned three ways: column-wise
table equivalence, fuzzed verdict equivalence against Python ``re``
(boundary bytes 0x00/0x7f/0xff planted), and chain-level equivalence
across narrow / striped / sharded layouts. The raised default state
gate (64, packed) with its class-ceiling reduction
(``dfa-classes-overflow``), the ``FLUVIO_DFA_CLASSES=0`` zero-cost
tripwire (legacy tables byte-for-byte + legacy 16-state gate), the
compose stage's fault seams (a runtime fault heals, a lowering error
propagates — ISSUE 22) and its compile-size smoke gate ride along.
"""

from __future__ import annotations

import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.ops.regex_dfa import (
    EOS,
    PAD,
    classes_enabled,
    compile_regex,
    compile_regex_cached,
)
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu import kernels
from fluvio_tpu.smartmodule import SmartModuleInput, dsl
from fluvio_tpu.smartmodule.sdk import SmartModuleDef
from fluvio_tpu.smartmodule.types import SmartModuleKind
from fluvio_tpu.telemetry import TELEMETRY

STRIPE_ENV = {
    "FLUVIO_STRIPE_THRESHOLD": "64",
    "FLUVIO_STRIPE_WIDTH": "64",
    "FLUVIO_STRIPE_OVERLAP": "16",
}

# >32 packed classes AND >16 states: trips the class-ceiling reduction
# of the raised default gate (dfa_effective_max_states)
OVERFLOW_PATTERN = "abcdefghijklmnopqrstuvwxyz0123456789ABCD[0-9]?"


@pytest.fixture
def small_stripes(monkeypatch):
    for k, v in STRIPE_ENV.items():
        monkeypatch.setenv(k, v)


def _pack(data):
    w = max(max((len(d) for d in data), default=1), 1)
    m = np.zeros((len(data), w), np.uint8)
    lens = np.zeros(len(data), np.int32)
    for i, d in enumerate(data):
        m[i, : len(d)] = np.frombuffer(d, np.uint8)
        lens[i] = len(d)
    return jnp.asarray(m), jnp.asarray(lens)


def filter_module(pattern: str) -> SmartModuleDef:
    m = SmartModuleDef(name="dfa-filter")
    m.dsl[SmartModuleKind.FILTER] = dsl.FilterProgram(
        predicate=dsl.RegexMatch(arg=dsl.Value(), pattern=pattern)
    )
    return m


def _build(backend: str, mods, mesh=None):
    eng = (
        SmartEngine(backend=backend, mesh_devices=mesh)
        if mesh
        else SmartEngine(backend=backend)
    )
    b = eng.builder()
    for mod, params in mods:
        b.add_smart_module(SmartModuleConfig(params=params or {}), mod)
    return b.initialize()


def _run(chain, vals):
    records = [Record(value=v) for v in vals]
    for i, r in enumerate(records):
        r.offset_delta = i
    out = chain.process(SmartModuleInput.from_records(records, 0, 1_000_000))
    assert out.error is None, out.error
    return [(r.value, r.key, r.offset_delta) for r in out.successes]


def _declines(name: str) -> int:
    return TELEMETRY.snapshot()["counters"]["declines"].get(name, 0)


PATTERNS = [
    "fluvio",
    "flu[vV]io",
    "[fF][lL][uU][vV][iI][oO]",  # case-insensitive classes
    "a+b",
    "(ab)+c?",
    "[0-9]+-[0-9]+",
    "^top[ic]*",
    "fluvio$",
    r"\d{2,4}x?",
    r"(foo|ba[rz])\s+\w+",
    r"\x00+[\x7e-\xff]x?",  # boundary-byte classes
    "^(fluvio|kafka|pulsar)-[0-3]$",
]


def _boundary_corpus(rng, n: int = 200):
    """Random bytes over the FULL 0-255 range plus planted seeds with
    the boundary bytes (0x00, 0x7f, 0xff) the class map must keep in
    distinct (or correctly merged) equivalence classes."""
    data = [
        bytes(rng.integers(0, 256, size=int(rng.integers(0, 60))).astype(np.uint8))
        for _ in range(n)
    ]
    seeds = [
        b"fluvio", b"fluVio", b"FLUVIO", b"aab", b"ababc", b"12-34",
        b"topic", b"foo  bar", b"baz x1", b"99x", b"kafka-2", b"fluvio-0",
        b"\x00\x00\xffx", b"\x00\x7f\xff", b"\x7e\x7f", b"\xfe\xff",
    ]
    for s in seeds:
        pad = bytes(rng.integers(0, 256, size=int(rng.integers(0, 20))).astype(np.uint8))
        data.append(pad + s + pad)
    data += [b"", b"\x00", b"\xff" * 59, b"a"]
    return data


class TestPackedTables:
    def test_column_equivalence_packed_vs_unpacked(self):
        """Every raw symbol column of the unpacked table equals its
        class column in the packed table — the packing is a pure
        column-identity merge, never a semantic change."""
        for pattern in PATTERNS:
            packed = compile_regex(pattern, packed=True)
            full = compile_regex(pattern, packed=False)
            assert packed.packed and not full.packed
            assert packed.n_states == full.n_states, pattern
            for sym in range(256):
                np.testing.assert_array_equal(
                    packed.table[:, packed.byte_class[sym]],
                    full.table[:, sym],
                    err_msg=f"{pattern} byte {sym:#x}",
                )
            np.testing.assert_array_equal(
                packed.table[:, packed.eos_class], full.table[:, EOS]
            )
            np.testing.assert_array_equal(
                packed.table[:, packed.pad_class], full.table[:, PAD]
            )
            assert packed.table_bytes <= full.table_bytes

    def test_verdict_fuzz_packed_vs_unpacked_vs_re(self):
        """Sequential + associative kernels over BOTH table modes agree
        with Python ``re`` on full-range fuzz corpora."""
        rng = np.random.default_rng(1600)
        for pattern in PATTERNS:
            data = _boundary_corpus(rng)
            values, lengths = _pack(data)
            pyref = np.array(
                [re.search(pattern.encode("latin-1"), d) is not None
                 for d in data]
            )
            for packed in (True, False):
                dfa = compile_regex(pattern, packed=packed)
                seq = np.asarray(kernels.dfa_match(values, lengths, dfa))
                assoc = np.asarray(
                    kernels.dfa_match_assoc(values, lengths, dfa)
                )
                assert (seq == pyref).all(), (pattern, packed)
                assert (assoc == pyref).all(), (pattern, packed)

    def test_cache_keyed_by_class_mode(self, monkeypatch):
        a = compile_regex_cached("pack[ed]?-key")
        assert a.packed is classes_enabled()
        monkeypatch.setenv("FLUVIO_DFA_CLASSES", "0")
        b = compile_regex_cached("pack[ed]?-key")
        assert not b.packed and b is not a
        monkeypatch.delenv("FLUVIO_DFA_CLASSES")
        assert compile_regex_cached("pack[ed]?-key") is a


class TestStateGate:
    def test_default_gate_is_64_packed(self, monkeypatch):
        monkeypatch.delenv("FLUVIO_DFA_ASSOC_MAX_STATES", raising=False)
        monkeypatch.delenv("FLUVIO_DFA_CLASSES", raising=False)
        assert kernels.dfa_assoc_max_states() == 64
        dfa = compile_regex("[0-9]{14}[a-z]{4}")  # 20 states, 4 classes
        assert kernels.dfa_effective_max_states(dfa) == (64, None)

    def test_classes_off_restores_legacy_gate_16(self, monkeypatch):
        monkeypatch.delenv("FLUVIO_DFA_ASSOC_MAX_STATES", raising=False)
        monkeypatch.setenv("FLUVIO_DFA_CLASSES", "0")
        assert kernels.dfa_assoc_max_states() == 16

    def test_class_overflow_reduces_gate_with_reason(self, monkeypatch):
        monkeypatch.delenv("FLUVIO_DFA_ASSOC_MAX_STATES", raising=False)
        dfa = compile_regex(OVERFLOW_PATTERN)
        assert dfa.n_classes > kernels.DFA_MAX_CLASSES
        assert dfa.n_states > 16
        assert kernels.dfa_effective_max_states(dfa) == (
            16, "dfa-classes-overflow"
        )
        # an explicit env gate overrides the ceiling: the operator asked
        monkeypatch.setenv("FLUVIO_DFA_ASSOC_MAX_STATES", "64")
        assert kernels.dfa_effective_max_states(dfa) == (64, None)

    def test_overflow_decline_fires_in_narrow_lowering(self, monkeypatch):
        """The narrow lowering attributes the class-ceiling spill to its
        own reason — distinguishable from the plain state-gate decline."""
        monkeypatch.setenv("FLUVIO_DFA_ASSOC", "1")
        monkeypatch.delenv("FLUVIO_DFA_ASSOC_MAX_STATES", raising=False)
        from fluvio_tpu.smartengine.tpu.lower import lower_expr

        before = _declines("dfa-classes-overflow")
        fn = lower_expr(
            dsl.RegexMatch(arg=dsl.Value(), pattern=OVERFLOW_PATTERN)
        )
        assert _declines("dfa-classes-overflow") == before + 1
        data = [b"abcdefghijklmnopqrstuvwxyz0123456789ABCD7", b"nope", b""]
        values, lengths = _pack(data)
        got = np.asarray(fn({"values": values, "lengths": lengths}))
        assert got.tolist() == [True, False, False]

    def test_raised_gate_runs_22_state_dfa_striped(self, small_stripes):
        """Acceptance pin: a 22-state pattern (past the LEGACY 16 gate)
        now lowers striped under the packed default — no interpreter
        spill, byte-equal to the interpreting backend."""
        pattern = "^(fluvio|kafka|pulsar)-[0-3]$"
        assert compile_regex(pattern).n_states == 22
        vals = [
            f"{name}-{i % 8}".encode()
            for i, name in enumerate(
                ["fluvio", "kafka", "pulsar", "redpanda"] * 40
            )
        ] + [b"x" * 100 + b"fluvio-1", b""]
        mods = lambda: [(filter_module(pattern), None)]
        tpu = _build("tpu", mods())
        assert tpu.tpu_chain._striped_chain() is not None
        pr0 = TELEMETRY.path_records()
        got = _run(tpu, vals)
        pr1 = TELEMETRY.path_records()
        assert got == _run(_build("python", mods()), vals)
        assert pr1["interpreter"] == pr0["interpreter"]


class TestZeroCostTripwire:
    def test_flags_off_reproduce_legacy_tables_and_paths(self, monkeypatch):
        """FLUVIO_DFA_CLASSES=0 is byte-for-byte
        legacy: identity class map, full 258-column table, 16-state
        gate, identical chain verdicts, and NO new ISSUE-16 declines."""
        monkeypatch.setenv("FLUVIO_DFA_CLASSES", "0")
        monkeypatch.delenv("FLUVIO_DFA_ASSOC_MAX_STATES", raising=False)
        dfa = compile_regex_cached("flu[vV]io")
        assert not dfa.packed
        assert dfa.table.shape[1] == 258
        np.testing.assert_array_equal(
            dfa.byte_class, np.arange(256, dtype=dfa.byte_class.dtype)
        )
        assert (dfa.eos_class, dfa.pad_class) == (EOS, PAD)
        assert kernels.dfa_assoc_max_states() == 16
        d0 = _declines("dfa-classes-overflow")
        vals = [b"x" * n + (b"fluVio" if n % 3 else b"flub") + b"y" * 10
                for n in range(60)]
        mods = lambda: [(filter_module("flu[vV]io"), None)]
        assert _run(_build("tpu", mods()), vals) == _run(
            _build("python", mods()), vals
        )
        assert _declines("dfa-classes-overflow") == d0


class TestComposeFaults:
    def test_runtime_fault_heals_exactly(self, small_stripes, monkeypatch):
        """An injected TRANSIENT dispatch fault on the striped compose
        chain retries against the same staged batch and completes
        exactly — device weather heals as before."""
        from fluvio_tpu.resilience import faults

        monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
        vals = [b"x" * n + (b"fluVio" if n % 2 else b"kafka") + b"y" * 40
                for n in range(80)]
        mods = lambda: [(filter_module("flu[vV]io"), None)]
        tpu = _build("tpu", mods())
        r0 = dict(TELEMETRY.snapshot()["counters"]["retries"])
        faults.FAULTS.inject("dispatch", first=1)
        try:
            got = _run(tpu, vals)
        finally:
            faults.FAULTS.clear()
        assert got == _run(_build("python", mods()), vals)
        r1 = TELEMETRY.snapshot()["counters"]["retries"]
        assert r1.get("dispatch", 0) == r0.get("dispatch", 0) + 1

    def test_lowering_error_raises_through_process(
        self, small_stripes, monkeypatch
    ):
        """A compose stage the compiler refuses is a program fault: it
        raises through `process()` under backend="tpu" — no heal, no
        fused-error spill, no interpreter re-run."""

        def refuse(*a, **k):
            raise NotImplementedError(
                "Unimplemented primitive in Pallas TPU lowering: dynamic_slice"
            )

        monkeypatch.setattr(kernels, "dfa_compose_columns", refuse)
        c0 = TELEMETRY.snapshot()["counters"]
        vals = [b"x" * n + b"fluVio" + b"y" * 40 for n in range(80)]
        tpu = _build("tpu", [(filter_module("flu[vV]io"), None)])
        with pytest.raises(NotImplementedError):
            _run(tpu, vals)
        c1 = TELEMETRY.snapshot()["counters"]
        assert c1["heals"] == c0["heals"]
        assert c1["spills"].get("fused-error", 0) == c0["spills"].get(
            "fused-error", 0
        )

    def test_compose_compile_time_bounded(self):
        """Compile-size smoke gate: the associative compose at the
        headline shape must jit in bounded time on CPU CI."""
        dfa = compile_regex("fluvio[0-9]+")
        cls = jnp.zeros((2048, 512), jnp.int32)
        table_t = jnp.asarray(dfa.table.T.astype(np.int32))
        fn = jax.jit(
            lambda c: kernels.dfa_compose_columns(c, table_t, dfa.n_states)
        )
        t0 = time.time()
        fn(cls).block_until_ready()
        elapsed = time.time() - t0
        assert elapsed < 60.0, f"compose compiled in {elapsed:.1f}s"


class TestJsonGetDfa:
    MODS = staticmethod(
        lambda: [
            (lookup("json-regex-filter"),
             {"key": "name", "regex": "^(fluvio|kafka)-[0-9]+$"}),
        ]
    )

    def test_field_values_straddle_stripe_joints(self, small_stripes):
        """The in-span DFA chains state across stripe joints: the name
        field lands across the 48-byte stripe step at every offset."""
        vals = []
        for pad in range(0, 100, 3):
            vals.append(
                (
                    f'{{"pad":"{"p" * pad}","name":"fluvio-{pad:03d}"'
                    f',"n":{pad}}}'
                ).encode()
            )
            vals.append(
                (f'{{"pad":"{"q" * pad}","name":"flub-{pad}"}}').encode()
            )
        vals += [b"", b"not json", b'{"name":"kafka-7"}', b'{"n":1}']
        tpu = _build("tpu", self.MODS())
        assert tpu.tpu_chain._striped_chain() is not None
        pr0 = TELEMETRY.path_records()
        got = _run(tpu, vals)
        pr1 = TELEMETRY.path_records()
        assert got == _run(_build("python", self.MODS()), vals)
        assert pr1["interpreter"] == pr0["interpreter"]  # no spill

    @pytest.mark.skipif(
        len(jax.devices()) < 4, reason="needs 4 virtual devices"
    )
    def test_sharded_in_span_dfa(self, small_stripes):
        rng = np.random.default_rng(160)
        vals = [
            (
                f'{{"name":"{"fluvio" if i % 2 else "flub"}-{i}",'
                f'"pad":"{"x" * int(rng.integers(10, 120))}"}}'
            ).encode()
            for i in range(300)
        ]
        tpu = _build("tpu", self.MODS(), mesh=4)
        assert tpu.tpu_chain._sharded is not None
        assert _run(tpu, vals) == _run(_build("python", self.MODS()), vals)
