"""Differential tests: preflight predictions vs runtime truth.

The analyzer (fluvio_tpu/analysis/) is only trustworthy if its
predictions are pinned to what the engine ACTUALLY does, so every test
here runs the real chain on the CPU backend and compares:

- the predicted path (fused / striped / interpreter) against the path
  the telemetry per-path record counters observed,
- predicted spill/decline reason strings against the deltas of the
  runtime ``TELEMETRY.spills`` / ``TELEMETRY.declines`` counters,

across the full bench matrix (every config in bench.py's CONFIGS) and
the gate matrix (FLUVIO_DFA_ASSOC x FLUVIO_DFA_ASSOC_MAX_STATES), plus
the Level-2 jaxpr pass (hazard detectors + clean bench chains).
"""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np
import pytest

from fluvio_tpu.analysis import analyze_entries, analyze_named, preflight_for_specs
from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartmodule import SmartModuleInput, dsl
from fluvio_tpu.smartmodule.sdk import SmartModuleDef
from fluvio_tpu.smartmodule.types import SmartModuleKind
from fluvio_tpu.telemetry import TELEMETRY

_BENCH_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py"
)


def _bench():
    if "bench" in sys.modules:
        return sys.modules["bench"]
    spec = importlib.util.spec_from_file_location("bench", _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    return mod


def _build_chain(specs):
    b = SmartEngine(backend="tpu").builder()
    for name, params in specs:
        b.add_smart_module(
            SmartModuleConfig(params=dict(params or {})), lookup(name)
        )
    return b.initialize()


def _entries(mods):
    """[(SmartModuleDef, params)] -> builder entries + an initialized
    chain, for ad-hoc modules outside the registry."""
    b = SmartEngine(backend="tpu").builder()
    for module, params in mods:
        b.add_smart_module(SmartModuleConfig(params=dict(params or {})), module)
    chain = b.initialize()
    entries = [
        (module, SmartModuleConfig(params=dict(params or {})))
        for module, params in mods
    ]
    return entries, chain


def _run(chain, values, ts=None):
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
        if ts is not None:
            r.timestamp_delta = int(ts[i])
    inp = SmartModuleInput.from_records(
        records, base_timestamp=1_000_000 if ts is not None else -1
    )
    out = chain.process(inp)
    assert out.error is None
    return out


def _observed_path(pr0) -> str:
    deltas = {
        k: v - pr0.get(k, 0)
        for k, v in TELEMETRY.path_records().items()
        if v - pr0.get(k, 0) > 0
    }
    return max(deltas, key=deltas.get) if deltas else "unknown"


def _spill_delta(s0) -> dict:
    return {
        k: v - s0.get(k, 0)
        for k, v in TELEMETRY.spills.items()
        if v - s0.get(k, 0) > 0
    }


def _decline_delta(d0) -> dict:
    return {
        k: v - d0.get(k, 0)
        for k, v in TELEMETRY.declines.items()
        if v - d0.get(k, 0) > 0
    }


# ---------------------------------------------------------------------------
# Bench-matrix differential: 100% of configs, predicted == observed
# ---------------------------------------------------------------------------


_BENCH_SMALL_N = {"7_fat70k": 4, "6_wide300": 32, "8_sharded_fat": 4}


@pytest.mark.parametrize("name", list(_bench().CONFIGS))
def test_bench_matrix_predicted_path_matches_observed(name):
    """For every config in the bench matrix, the Level-1 prediction for
    the corpus's actual width must equal the telemetry-observed executed
    path — the acceptance pin for the whole analyzer."""
    b = _bench()
    cfg = b.CONFIGS[name]
    n = _BENCH_SMALL_N.get(name, 48)
    values = cfg["corpus"](n)
    ts = cfg["ts"](n) if "ts" in cfg else None

    pred = preflight_for_specs(cfg["specs"], max(len(v) for v in values))
    chain = _build_chain(cfg["specs"])
    assert chain.backend_in_use == "tpu", name
    pr0 = TELEMETRY.path_records()
    s0 = dict(TELEMETRY.spills)
    _run(chain, values, ts)
    observed = _observed_path(pr0)
    assert pred["path"] == observed, (
        f"{name}: predicted {pred['path']}, telemetry observed {observed}"
    )
    # a config predicted clean must not have spilled; one predicted to
    # spill must show exactly the predicted reasons on the counters
    spilled = _spill_delta(s0)
    assert sorted(spilled) == sorted(pred.get("spill_reasons", [])), name


@pytest.mark.parametrize("name", list(_bench().CONFIGS))
def test_bench_matrix_predicted_down_variant_matches_observed(
    name, monkeypatch
):
    """ISSUE-12 acceptance pin: with the result-encode ladder armed,
    the predicted D2H variant must be differential-exact against the
    telemetry ``down-*`` counters for every bench-matrix config — the
    one tolerated divergence is a per-batch ratio/size decline, which
    must then show on the `glz-enc-ratio`/decline surface."""
    monkeypatch.setenv("FLUVIO_RESULT_COMPRESS", "on")
    b = _bench()
    cfg = b.CONFIGS[name]
    if cfg.get("mesh"):
        pytest.skip("sharded config: single-device differential here")
    n = _BENCH_SMALL_N.get(name, 48)
    values = cfg["corpus"](n)
    ts = cfg["ts"](n) if "ts" in cfg else None
    pred = preflight_for_specs(cfg["specs"], max(len(v) for v in values))
    chain = _build_chain(cfg["specs"])
    lv0 = TELEMETRY.link_variant_counts()
    d0 = dict(TELEMETRY.declines)
    _run(chain, values, ts)
    moved = sorted(
        k
        for k, v in TELEMETRY.link_variant_counts().items()
        if v > lv0.get(k, 0) and k.startswith("down-")
    )
    assert moved, f"{name}: no down-variant counter moved"
    if moved != [pred["down_variant"]]:
        declines = _decline_delta(d0)
        assert pred["down_variant"].startswith("down-glz") and set(
            moved
        ) <= {"down-packed", pred["down_variant"]}, (
            f"{name}: predicted {pred['down_variant']}, observed {moved}"
        )
        assert any(k.startswith("glz-enc") for k in declines), (
            f"{name}: down divergence without a decline: {declines}"
        )


def test_bench_preflight_record_shape():
    """The record bench.py embeds per config: path + down-link variant
    + optional reasons. On the CPU test backend the result-encode
    ladder resolves off (auto), so the predicted D2H variant is
    down-packed (the headline chain is a descriptor-shipping span
    chain; compaction is on everywhere)."""
    b = _bench()
    pred = preflight_for_specs(
        b.CONFIGS["2_filter_map"]["specs"], 64
    )
    assert pred == {
        "path": "fused",
        "down_variant": "down-packed",
    }


# ---------------------------------------------------------------------------
# Gate matrix: FLUVIO_DFA_ASSOC x FLUVIO_DFA_ASSOC_MAX_STATES
# ---------------------------------------------------------------------------


_MULTI_STATE_REGEX = "cat|dog|bird"  # non-literal: compiles to a DFA


def _regex_filter_module(pattern: str) -> SmartModuleDef:
    m = SmartModuleDef(name="adhoc-regex")
    m.dsl[SmartModuleKind.FILTER] = dsl.FilterProgram(
        predicate=dsl.RegexMatch(arg=dsl.Value(), pattern=pattern)
    )
    return m


@pytest.mark.parametrize(
    "assoc,tiny_gate",
    [("1", True), ("1", False), ("0", True)],
)
def test_gate_matrix_narrow_decline(monkeypatch, assoc, tiny_gate):
    """Narrow chains: the dfa-assoc-states decline fires exactly when
    the backend WANTS the associative path and the gate is under the
    pattern's state count — predicted and observed must agree on both
    the decline delta and the (always fused) path."""
    from fluvio_tpu.ops.regex_dfa import compile_regex_cached

    n_states = compile_regex_cached(_MULTI_STATE_REGEX).n_states
    gate = 2 if tiny_gate else n_states + 8
    monkeypatch.setenv("FLUVIO_DFA_ASSOC", assoc)
    monkeypatch.setenv("FLUVIO_DFA_ASSOC_MAX_STATES", str(gate))

    specs = [(_regex_filter_module(_MULTI_STATE_REGEX), None)]
    entries, chain = _entries(specs)
    report = analyze_entries(entries, widths=(64,))
    pred = report.predictions[0]
    expect_decline = assoc == "1" and tiny_gate
    assert pred.path == "fused"
    assert (pred.declines == ("dfa-assoc-states",)) == expect_decline

    # observe: the decline fires at chain BUILD time (the chain above
    # was built before the baseline — build another and diff)
    d0 = dict(TELEMETRY.declines)
    pr0 = TELEMETRY.path_records()
    _, chain2 = _entries(specs)
    values = [b"a cat sat", b"nothing here", b"big dog energy"] * 4
    _run(chain2, values)
    assert _observed_path(pr0) == "fused"
    delta = _decline_delta(d0)
    assert (delta.get("dfa-assoc-states", 0) > 0) == expect_decline, delta


_SMALL_STRIPES = {
    "FLUVIO_STRIPE_THRESHOLD": "64",
    "FLUVIO_STRIPE_WIDTH": "64",
    "FLUVIO_STRIPE_OVERLAP": "16",
}


def _wide_values(n=24, width=200):
    pad = "y" * (width - 40)
    return [
        f'a cat sat on {pad} mat {i}'.encode() for i in range(n)
    ]


@pytest.mark.parametrize("tiny_gate", [True, False])
def test_gate_matrix_striped_dfa_spill(monkeypatch, tiny_gate):
    """Wide chains with a non-literal regex: under the state gate the
    striped build declines ``dfa-stripe-states`` and the batch spills
    (``record-too-wide-unstripeable``); over it the chain runs striped.
    Predicted reasons must equal the observed counter deltas."""
    from fluvio_tpu.ops.regex_dfa import compile_regex_cached

    for k, v in _SMALL_STRIPES.items():
        monkeypatch.setenv(k, v)
    n_states = compile_regex_cached(_MULTI_STATE_REGEX).n_states
    gate = 2 if tiny_gate else n_states + 8
    monkeypatch.setenv("FLUVIO_DFA_ASSOC_MAX_STATES", str(gate))

    specs = [(_regex_filter_module(_MULTI_STATE_REGEX), None)]
    entries, chain = _entries(specs)
    values = _wide_values()
    width = max(len(v) for v in values)
    report = analyze_entries(entries, widths=(width,))
    pred = report.predictions[0]

    d0 = dict(TELEMETRY.declines)
    s0 = dict(TELEMETRY.spills)
    pr0 = TELEMETRY.path_records()
    _run(chain, values)
    observed = _observed_path(pr0)

    assert pred.path == observed
    if tiny_gate:
        assert pred.path == "interpreter"
        assert pred.spill_reasons == ("record-too-wide-unstripeable",)
        assert pred.declines == ("dfa-stripe-states",)
        assert _spill_delta(s0).get("record-too-wide-unstripeable", 0) > 0
        assert _decline_delta(d0).get("dfa-stripe-states", 0) > 0
    else:
        assert pred.path == "striped"
        assert not _spill_delta(s0)
        assert "dfa-stripe-states" not in _decline_delta(d0)


# ---------------------------------------------------------------------------
# The ROADMAP spill families, differentially pinned
# ---------------------------------------------------------------------------


def _predicate_module(predicate) -> SmartModuleDef:
    m = SmartModuleDef(name="adhoc-predicate")
    m.dsl[SmartModuleKind.FILTER] = dsl.FilterProgram(predicate=predicate)
    return m


def _spill_family_case(monkeypatch, mods, values, expect_causes_substr):
    for k, v in _SMALL_STRIPES.items():
        monkeypatch.setenv(k, v)
    entries, chain = _entries(mods)
    width = max(len(v) for v in values)
    report = analyze_entries(entries, widths=(width,))
    pred = report.predictions[0]
    assert pred.path == "interpreter"
    assert pred.spill_reasons == ("record-too-wide-unstripeable",)
    assert any(expect_causes_substr in c for c in pred.causes), pred.causes

    s0 = dict(TELEMETRY.spills)
    pr0 = TELEMETRY.path_records()
    _run(chain, values)
    assert _observed_path(pr0) == "interpreter"
    assert _spill_delta(s0).get("record-too-wide-unstripeable", 0) > 0


def test_jsonget_sourced_literal_predicate_runs_striped(monkeypatch):
    """ISSUE-11 satellite: the "JsonGet-sourced predicates" spill
    family shrank — literal predicates over a single-level JsonGet now
    lower striped (the cross-stripe span machine pins the field, a
    windowed compare matches inside it). Predicted AND observed path
    must both be striped, with no spill."""
    for k, v in _SMALL_STRIPES.items():
        monkeypatch.setenv(k, v)
    pad = "p" * 160
    values = [
        f'{{"name":"fluvio-{i}","pad":"{pad}"}}'.encode() for i in range(16)
    ]
    mods = [(
        _predicate_module(
            dsl.Contains(
                arg=dsl.JsonGet(arg=dsl.Value(), key="name"),
                literal=b"fluvio",
            )
        ),
        None,
    )]
    entries, chain = _entries(mods)
    width = max(len(v) for v in values)
    report = analyze_entries(entries, widths=(width,))
    pred = report.predictions[0]
    assert pred.path == "striped"
    assert not pred.spill_reasons

    s0 = dict(TELEMETRY.spills)
    pr0 = TELEMETRY.path_records()
    out = _run(chain, values)
    assert _observed_path(pr0) == "striped"
    assert not _spill_delta(s0)
    # survivor exactness vs the reference engine
    py = SmartEngine(backend="python").builder()
    for module, params in mods:
        py.add_smart_module(
            SmartModuleConfig(params=dict(params or {})), module
        )
    ref_out = _run(py.initialize(), values)
    assert [r.value for r in out.successes] == [
        r.value for r in ref_out.successes
    ]


def _despilled_family_case(monkeypatch, mods, values):
    """Predicted AND observed striped, no spill, survivors bit-equal to
    the python reference engine — the pin shape for families ISSUE-16
    moved off the interpreter."""
    for k, v in _SMALL_STRIPES.items():
        monkeypatch.setenv(k, v)
    entries, chain = _entries(mods)
    width = max(len(v) for v in values)
    report = analyze_entries(entries, widths=(width,))
    pred = report.predictions[0]
    assert pred.path == "striped", (pred.path, pred.causes)
    assert not pred.spill_reasons

    s0 = dict(TELEMETRY.spills)
    pr0 = TELEMETRY.path_records()
    out = _run(chain, values)
    assert _observed_path(pr0) == "striped"
    assert not _spill_delta(s0)
    py = SmartEngine(backend="python").builder()
    for module, params in mods:
        py.add_smart_module(
            SmartModuleConfig(params=dict(params or {})), module
        )
    ref_out = _run(py.initialize(), values)
    assert [r.value for r in out.successes] == [
        r.value for r in ref_out.successes
    ]


def test_jsonget_predicate_overlap_exceeding_literal_runs_striped(monkeypatch):
    """ISSUE-16: a literal longer than the stripe overlap has no
    containment argument inside the extracted span, so it used to
    spill — now it chains as an in-span DFA (escaped-literal regex;
    its ~1-state-per-byte DFA needs the raised 64-state gate)."""
    pad = "p" * 160
    lit = b"x" * 20  # > the 16-byte test overlap
    values = [
        (
            f'{{"name":"{"x" * 24}","pad":"{pad}"}}'
            if i % 2 == 0
            else f'{{"name":"{"y" * 24}","pad":"{pad}"}}'
        ).encode()
        for i in range(8)
    ]
    mods = [(
        _predicate_module(
            dsl.Contains(
                arg=dsl.JsonGet(arg=dsl.Value(), key="name"), literal=lit
            )
        ),
        None,
    )]
    _despilled_family_case(monkeypatch, mods, values)


def test_jsonget_sourced_regex_predicate_runs_striped(monkeypatch):
    """ISSUE-16: non-literal regexes over a JsonGet source left the
    spill set — the in-span DFA chain (`stripes.striped_dfa_in_span`)
    masks the class stream to the span the cross-stripe machine
    resolves."""
    pad = "p" * 160
    values = [
        f'{{"name":"{"cat" if i % 3 == 0 else "bird"}-{i}","pad":"{pad}"}}'.encode()
        for i in range(12)
    ]
    mods = [(
        _predicate_module(
            dsl.RegexMatch(
                arg=dsl.JsonGet(arg=dsl.Value(), key="name"),
                pattern="cat|dog",
            )
        ),
        None,
    )]
    _despilled_family_case(monkeypatch, mods, values)


def test_nested_jsonget_regex_still_spills(monkeypatch):
    """The family's remaining boundary: a regex over a NESTED JsonGet
    source (two structural levels) stays in the spill set — the span
    machine carries one structural level across stripes."""
    pad = "p" * 160
    values = [
        f'{{"outer":{{"name":"fluvio-{i}"}},"pad":"{pad}"}}'.encode()
        for i in range(8)
    ]
    mods = [(
        _predicate_module(
            dsl.RegexMatch(
                arg=dsl.JsonGet(
                    arg=dsl.JsonGet(arg=dsl.Value(), key="outer"),
                    key="name",
                ),
                pattern="cat|dog",
            )
        ),
        None,
    )]
    _spill_family_case(monkeypatch, mods, values, "JsonGet")


def test_word_count_spills_wide(monkeypatch):
    values = [(b"word " * 40) + str(i).encode() for i in range(16)]
    _spill_family_case(
        monkeypatch, [(lookup("word-count"), None)], values, "word_count"
    )


def test_json_array_explode_spills_wide(monkeypatch):
    inner = ",".join(f'"e{i}"' for i in range(40))
    values = [f"[{inner}]".encode() for _ in range(8)]
    _spill_family_case(
        monkeypatch, [(lookup("array-map-json"), None)], values,
        "single-byte split",
    )


def test_hard_ceiling_record_too_wide(monkeypatch):
    """Past MAX_RECORD_WIDTH even striped staging refuses: predicted and
    observed spill reason is the plain ``record-too-wide``."""
    from fluvio_tpu.smartengine.tpu.buffer import MAX_RECORD_WIDTH

    specs = [("regex-filter", {"regex": "fluvio"})]
    width = MAX_RECORD_WIDTH + 1
    pred = preflight_for_specs(specs, width)
    assert pred["path"] == "interpreter"
    assert pred["spill_reasons"] == ["record-too-wide"]

    chain = _build_chain(specs)
    s0 = dict(TELEMETRY.spills)
    pr0 = TELEMETRY.path_records()
    _run(chain, [b"fluvio" + b"x" * width])
    assert _observed_path(pr0) == "interpreter"
    assert _spill_delta(s0).get("record-too-wide", 0) > 0


def test_sharded_fanout_stays_narrow_in_prediction():
    """The sharded engine cannot stage fan-out striped: the analyzer
    mirrors `max_stageable_width`'s conservative exclusion."""
    specs = [("array-map-json", None)]
    report = analyze_named(specs, widths=(100_000,), sharded=True)
    pred = report.predictions[0]
    assert pred.path == "interpreter"
    assert pred.spill_reasons == ("record-too-wide-unstripeable",)
    assert any("sharded fan-out" in c for c in pred.causes)


def test_unlowerable_chain_predicts_interpreter():
    m = SmartModuleDef(name="hook-only")
    m.hooks[SmartModuleKind.FILTER] = lambda record: True
    entries = [(m, SmartModuleConfig())]
    report = analyze_entries(entries, widths=(64,))
    assert report.predictions[0].path == "interpreter"
    assert any(h.code == "no-dsl-program" for h in report.errors())


# ---------------------------------------------------------------------------
# Level-2 jaxpr pass
# ---------------------------------------------------------------------------


def test_jaxpr_detects_weak_64bit_promotion():
    import fluvio_tpu.smartengine.tpu  # noqa: F401 — enables x64
    import jax.numpy as jnp

    from fluvio_tpu.analysis.jaxpr_lint import scan_function

    def bad(x):
        return jnp.where(x > 0, 1, 0)  # both-literal: weak i64 select

    hazards, _, _ = scan_function(bad, np.zeros(8, np.int32))
    assert any(h.code == "weak-64bit-promotion" for h in hazards)

    def good(x):
        return jnp.where(x > 0, jnp.int32(1), jnp.int32(0))

    hazards, _, _ = scan_function(good, np.zeros(8, np.int32))
    assert not hazards


def test_jaxpr_detects_host_callback():
    import jax

    from fluvio_tpu.analysis.jaxpr_lint import scan_function

    def cb(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    hazards, _, _ = scan_function(cb, np.zeros(8, np.int32))
    assert any(
        h.code == "host-callback" and h.level == "error" for h in hazards
    )


@pytest.mark.parametrize(
    "name", ["1_filter", "2_filter_map", "3_aggregate", "4_array_map",
             "5_windowed"]
)
def test_jaxpr_pass_clean_on_bench_chains(name):
    """After the PR's kernel-literal pinning, every bench chain's traced
    entry points must carry zero error-severity jaxpr hazards — an
    unpinned weak literal anywhere in the lowered program fails here."""
    from fluvio_tpu.analysis import analyze_chain

    b = _bench()
    cfg = b.CONFIGS[name]
    entries = [
        (lookup(n), SmartModuleConfig(params=dict(p or {})))
        for n, p in cfg["specs"]
    ]
    report = analyze_chain(entries, widths=(256,), jaxpr=True)
    errors = [
        h for j in report.jaxprs for h in j.hazards if h.level == "error"
    ]
    assert not errors, [h.message for h in errors]
    # the traced entry points double as the AOT-warmup work list: every
    # report names its kind and shape-bucket signature
    assert report.jaxprs, "no entry points traced"
    for j in report.jaxprs:
        if j.kind == "dfa_table":
            continue
        assert j.signature and j.n_eqns > 0, j.to_dict()


def test_jaxpr_fast_json_path_clean(monkeypatch):
    """The parallel structural-index JSON kernel (FLUVIO_TPU_FAST_JSON=1
    forces it on CPU) traces clean too — the string-state automaton's
    pinned literals stay pinned."""
    from fluvio_tpu.analysis import analyze_chain

    monkeypatch.setenv("FLUVIO_TPU_FAST_JSON", "1")
    entries = [
        (lookup("regex-filter"), SmartModuleConfig(params={"regex": "fluvio"})),
        (lookup("json-map"), SmartModuleConfig(params={"field": "name"})),
    ]
    report = analyze_chain(entries, widths=(256,), jaxpr=True)
    errors = [
        h for j in report.jaxprs for h in j.hazards if h.level == "error"
    ]
    assert not errors, [h.message for h in errors]


def test_dfa_table_report():
    from fluvio_tpu.analysis.jaxpr_lint import dfa_table_reports
    from fluvio_tpu.analysis.spec import resolved_programs

    entries = [
        (lookup("regex-filter"),
         SmartModuleConfig(params={"regex": _MULTI_STATE_REGEX})),
    ]
    programs, _ = resolved_programs(entries)
    reports = dfa_table_reports(programs)
    assert len(reports) == 1
    assert reports[0].kind == "dfa_table"
    assert reports[0].prims["states"] > 1


# ---------------------------------------------------------------------------
# Report plumbing
# ---------------------------------------------------------------------------


def test_report_to_dict_round_trips():
    import json

    report = analyze_named([("regex-filter", {"regex": "fluvio"})])
    d = report.to_dict()
    json.dumps(d)  # serializable
    assert d["chain"] == "filter"
    assert {p["path"] for p in d["predictions"]} <= {
        "fused", "striped", "interpreter"
    }
    assert "dfa_assoc_max_states" in d["gates"]


def test_gates_resolve_like_runtime(monkeypatch):
    from fluvio_tpu.analysis import resolve_gates
    from fluvio_tpu.smartengine.tpu import kernels

    monkeypatch.setenv("FLUVIO_DFA_ASSOC_MAX_STATES", "7")
    gates = resolve_gates()
    assert gates["dfa_assoc_max_states"] == kernels.dfa_assoc_max_states() == 7
    assert gates["backend"] == "cpu"
    assert gates["dfa_assoc"] is False  # auto resolves off on CPU


def test_jaxpr_traces_pallas_entry_in_interpret_mode(monkeypatch):
    """With pallas forced on (interpret mode on CPU), the json_get
    pallas kernel joins the traced entry points and traces clean — its
    kernel literals are pinned and the x64-off trace window holds."""
    from fluvio_tpu.analysis import analyze_chain

    monkeypatch.setenv("FLUVIO_TPU_PALLAS", "interpret")
    entries = [
        (lookup("regex-filter"), SmartModuleConfig(params={"regex": "fluvio"})),
        (lookup("json-map"), SmartModuleConfig(params={"field": "name"})),
    ]
    report = analyze_chain(entries, widths=(256,), jaxpr=True)
    kinds = {j.kind for j in report.jaxprs}
    assert "pallas" in kinds
    errors = [
        h for j in report.jaxprs for h in j.hazards if h.level == "error"
    ]
    assert not errors, [h.message for h in errors]


def test_jaxpr_traces_striped_entry(monkeypatch):
    """Past-threshold widths trace the STRIPED chain body (its own
    compile signature — a distinct AOT-warmup bucket) and it is clean."""
    from fluvio_tpu.analysis import analyze_chain

    for k, v in _SMALL_STRIPES.items():
        monkeypatch.setenv(k, v)
    entries = [
        (lookup("regex-filter"), SmartModuleConfig(params={"regex": "fluvio"}))
    ]
    report = analyze_chain(entries, widths=(200,), jaxpr=True)
    striped = [j for j in report.jaxprs if j.kind == "striped"]
    assert striped and striped[0].n_eqns > 0
    assert "srows=" in striped[0].signature
    errors = [
        h for j in report.jaxprs for h in j.hazards if h.level == "error"
    ]
    assert not errors, [h.message for h in errors]
