"""The up-link's one form: the staged flat ships raw.

Every cell of the benchmark stages through `_flat_and_bucket` /
`_bucket_bytes` / `_stage_flat` and nothing else held them: the bucket
rule as properties, the staging of one flat, one compiled program per
bucket, the operands of the lowered chain programs, and byte-for-byte
parity with the `python` backend over the byte patterns the deleted
link compressor was tested on, as record VALUES.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor
from fluvio_tpu.smartmodule import SmartModuleInput
from fluvio_tpu.telemetry import TELEMETRY

# the benchmark's three chains (`benchmark/configs/*.json`), by model name
CHAINS = {
    "northstar": [("regex-filter", {"regex": "fluvio"}),
                  ("json-map", {"field": "name"})],
    "explode": [("array-map-json", None)],
    "aggregate": [("aggregate-field", {"field": "n", "combine": "add"})],
}


def _chain(backend, specs):
    b = SmartEngine(backend=backend).builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    chain = b.initialize()
    assert chain.backend_in_use == backend
    return chain


def _records(values):
    out = [Record(value=v) for v in values]
    for i, r in enumerate(out):
        r.offset_delta = i
    return out


def _buf(values):
    return RecordBuffer.from_records(_records(values))


# ---------------------------------------------------------------------------
# the bucket rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", range(10, 25))
def test_bucket_rule_inside_one_doubling(k):
    """Over (2^k, 2^(k+1)]: a bucket holds its flat, is whole i32 words,
    pads by less than an eighth of the enclosing power of two, never
    shrinks as the flat grows, and the doubling has four buckets (so a
    stream whose slices vary compiles a bounded set of programs)."""
    lo, hi = 1 << k, 1 << (k + 1)
    rng = np.random.default_rng(k)
    sizes = sorted({lo + 1, lo + 4, hi - 4, hi - 1, hi,
                    *(int(x) for x in rng.integers(lo + 1, hi + 1, 200))})
    buckets = [TpuChainExecutor._bucket_bytes(n) for n in sizes]
    step = max(1024, hi >> 3)
    for n, b in zip(sizes, buckets):
        assert b >= n and b % 4 == 0
        assert b - n < step and b % step == 0
        assert b <= hi
    assert buckets == sorted(buckets)
    assert len(set(buckets)) <= 4
    assert TpuChainExecutor._bucket_bytes(hi) == hi


@pytest.mark.parametrize("floor,n,want", [
    (1024, 1, 1024),      # n <= floor: the floor itself
    (1024, 1025, 2048),   # below 8 floors the step IS the floor
    (256, 2049, 2560),    # a smaller floor (token and row buckets): step 512
], ids=["n-under-floor", "floor-1024-step", "floor-256"])
def test_bucket_rule_floors(floor, n, want):
    got = TpuChainExecutor._bucket_bytes(n, floor=floor)
    assert got == want
    assert got % floor == 0 and got >= n
    assert got - n < max(floor, TpuChainExecutor._pad_slice(n, floor) >> 3)


# ---------------------------------------------------------------------------
# staging one flat
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("length,bucket", [
    (4, 1024), (1020, 1024), (1024, 1024), (1028, 2048),
    ((1 << 20) - 4, 1 << 20), (1 << 20, 1 << 20),
    ((1 << 20) + 4, (1 << 20) + (1 << 18)),
])
def test_stage_flat_pads_to_the_bucket_as_words(length, bucket):
    flat = np.random.default_rng(length).integers(1, 256, length).astype(np.uint8)
    assert TpuChainExecutor._bucket_bytes(max(len(flat), 4)) == bucket
    words, h2d = TpuChainExecutor._stage_flat(flat, bucket)
    assert isinstance(words, jax.Array) and words.dtype == np.int32
    assert words.shape == (bucket // 4,)
    assert h2d == bucket  # the bytes booked to the up-link
    staged = np.asarray(words).view(np.uint8)
    assert np.array_equal(staged[:length], flat)
    assert not staged[length:].any()


# ---------------------------------------------------------------------------
# one compiled program per bucket
# ---------------------------------------------------------------------------

_SHORT = {
    "northstar": b'{"name":"fluvio-1","n":12}',
    "explode": b'["a","b","c","d","e","f"]',
    "aggregate": b'{"name":"kafka-22","n":12}',
}
_LONG = {
    "northstar": b'{"name":"fluvio-1","n":12,"pad":"' + b"x" * 24 + b'"}',
    "explode": b'["a","b","c","d","e","' + b"f" * 24 + b'"]',
    "aggregate": b'{"name":"kafka-22","n":12,"pad":"' + b"x" * 24 + b'"}',
}


def _mixed_buf(name, n_long, n=512):
    """``n`` records, ``n_long`` of them the long form: same rows, same
    width (one long record pins it), another flat length."""
    return _buf([_LONG[name]] * n_long + [_SHORT[name]] * (n - n_long))


def _ragged_compiles() -> int:
    return TELEMETRY.compile_totals()["by_kind"].get("ragged", 0)


@pytest.mark.parametrize("name", sorted(CHAINS))
def test_one_compiled_program_per_bucket(name):
    ex = _chain("tpu", CHAINS[name]).tpu_chain
    in_bucket = [_mixed_buf(name, k) for k in (1, 8, 16)]
    buckets = {ex._flat_and_bucket(b)[1] for b in in_bucket}
    lengths = {len(ex._flat_and_bucket(b)[0]) for b in in_bucket}
    assert len(buckets) == 1 and len(lengths) == 3
    k = 17
    while ex._flat_and_bucket(_mixed_buf(name, k))[1] in buckets:
        k += 8
    beyond = _mixed_buf(name, k)
    assert {b.rows for b in in_bucket} == {beyond.rows}
    assert {b.width for b in in_bucket} == {beyond.width}

    # the fan-out chain learns its output capacity on its first batch
    # (a program of its own): warm that up outside the count
    ex.process_buffer(in_bucket[0])
    ex.process_buffer(in_bucket[0])
    c0 = _ragged_compiles()
    for b in in_bucket:
        assert ex.process_buffer(b).count > 0
    assert _ragged_compiles() == c0, "one bucket, one program"
    assert ex.process_buffer(beyond).count > 0
    assert _ragged_compiles() == c0 + 1, "the next bucket adds exactly one"


# ---------------------------------------------------------------------------
# the lowered programs' operands
# ---------------------------------------------------------------------------


def _spied_call(ex, attr, buf):
    """Run ``buf`` with a spy on the jit entry ``attr``: the jitted
    function and the (args, kwargs) the dispatch called it with."""
    seen = {}
    wrapped = getattr(ex, attr)

    def spy(*args, **kwargs):
        seen["call"] = (args, kwargs)
        return wrapped(*args, **kwargs)

    setattr(ex, attr, spy)
    try:
        ex.process_buffer(buf)
    finally:
        setattr(ex, attr, wrapped)
    return wrapped.__wrapped__, *seen["call"]


def _assert_raw_operands(ex, attr, buf):
    jit, args, kwargs = _spied_call(ex, attr, buf)
    flat, bucket = ex._flat_and_bucket(buf)
    # positional operands: the staged flat, the lengths, four absent
    # derivable columns, count, base timestamp, carries — nothing else
    assert len(args) == 9 and args[2:6] == (None,) * 4
    assert args[0].dtype == np.int32 and args[0].shape == (bucket // 4,)
    assert np.array_equal(
        np.asarray(args[0]).view(np.uint8)[: len(flat)], flat
    )
    leaves = jax.tree_util.tree_leaves(args)
    assert len(leaves) == 4 + 3 * len(ex.carries)
    lowered = jit.lower(*args, **kwargs)
    main = re.search(r"func\.func public @main\((.*?)\) ->", lowered.as_text(), re.S)
    params = re.findall(r"%arg\d+: tensor<([^>]*)>", main.group(1))
    # jit drops an operand the chain never reads (the base timestamp of
    # an unwindowed chain); what is left is the staged arrays, in order
    assert params[0] == f"{bucket // 4}xi32"
    assert params[1].startswith(f"{args[1].shape[0]}xui")
    assert 3 <= len(params) <= len(leaves)
    hlo = lowered.compile().as_text()
    scopes = {p for op in re.findall(r'op_name="([^"]*)"', hlo) for p in op.split("/")}
    assert "repad" in scopes and "link_decode" not in scopes


@pytest.mark.parametrize("name", sorted(CHAINS))
def test_ragged_program_takes_exactly_the_staged_arrays(name):
    ex = _chain("tpu", CHAINS[name]).tpu_chain
    _assert_raw_operands(ex, "_jit_ragged", _mixed_buf(name, 4, n=256))


def test_striped_program_takes_exactly_the_staged_arrays(monkeypatch):
    monkeypatch.setenv("FLUVIO_STRIPE_THRESHOLD", "64")
    monkeypatch.setenv("FLUVIO_STRIPE_WIDTH", "64")
    monkeypatch.setenv("FLUVIO_STRIPE_OVERLAP", "16")
    ex = _chain("tpu", [("regex-filter", {"regex": "flu[vV]io"})]).tpu_chain
    buf = _buf([
        b'{"name":"%s-%d","pad":"%s"}' % (n, i, b"p" * 240)
        for i, n in enumerate([b"fluvio", b"kafka", b"fluVio", b"pulsar"] * 10)
    ])
    assert ex._needs_stripes(buf) and ex._striped_chain() is not None
    _assert_raw_operands(ex, "_jit_striped", buf)


# ---------------------------------------------------------------------------
# raw parity by byte pattern
# ---------------------------------------------------------------------------


def _json_vals(n, seed=7):
    rng = np.random.default_rng(seed)
    names = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "redpanda", "flink"]
    return [
        f'{{"name":"{names[rng.integers(0, 6)]}-{i & 255}",'
        f'"n":{rng.integers(0, 100000)}}}'.encode()
        for i in range(n)
    ]


def _arrays(elements):
    """JSON-array record values, one per element list."""
    return [
        b"[" + b",".join(b'"' + e + b'"' for e in els) + b"]" for els in elements
    ]


def _mixed_lengths(n=600, top=300, seed=3):
    return [int(x) for x in np.random.default_rng(seed).integers(0, top, n)]


# the deleted compressor's corpora, as record VALUES. The fan-out chain
# takes each pattern inside JSON arrays (a malformed array is a spill,
# not a staging case); the other two take the bytes as they are.
PATTERNS = {
    "zeros": {
        "values": [b"", b"\0" * 40, b"", b"\0" * 4, b"\0"] * 60,
        "arrays": _arrays([[], [b""], [b"", b"", b""], [b"0" * 40]] * 75),
    },
    "run": {
        "values": [b"ab" * k for k in (1, 7, 40, 100, 3)] * 80,
        "arrays": _arrays([[b"ab" * k, b"ab"] for k in (1, 7, 40, 100, 3)] * 80),
    },
    # one 27-byte record over and over: the 4-aligned flat has period 28
    "period28": {
        "values": [b'{"name":"fluvio-1","n":123}'] * 1000,
        "arrays": [b'["fluvio-1",123,"abcdefghi"]'] * 1000,
    },
    "mixed": {
        "values": [
            (b'{"name":"fluvio","n":%d,"p":"' % k) + b"m" * k + b'"}'
            if k % 3 else b"x" * k
            for k in _mixed_lengths()
        ],
        "arrays": _arrays(
            [[b"e" * (k % 17)] * (k % 5) + [b"w" * k] for k in _mixed_lengths()]
        ),
    },
    # the north star's chain stripes: its records go past the (shrunken)
    # stripe threshold. The other two chains cannot stripe (a wide batch
    # of theirs is the interpreter's), so theirs are the deleted corpus's
    # own shape: eight records of about 30 KB on the narrow layout.
    "wide": {
        "striped": [
            b'{"name":"%s-%d","n":%d,"body":"%s"}'
            % ((b"fluvio", b"kafka")[i & 1], i & 7, i, b"x" * (200 + 8 * i))
            for i in range(24)
        ],
        "values": [
            b'{"name":"fluvio-%d","n":%d,"body":"%s"}' % (i & 7, i, b"x" * 30000)
            for i in range(8)
        ],
        "arrays": _arrays(
            [[b"x" * 30000, b"tail-%d" % i, b"y" * (i + 1)] for i in range(8)]
        ),
    },
    "json": {
        "values": _json_vals(2000),
        "arrays": [
            f'["a{i & 255}",{i},{i * 3},"x"]'.encode() for i in range(2000)
        ],
    },
}


@pytest.mark.parametrize("name", sorted(CHAINS))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_raw_staging_parity_by_byte_pattern(monkeypatch, pattern, name):
    striped = pattern == "wide" and name == "northstar"
    if striped:
        monkeypatch.setenv("FLUVIO_STRIPE_THRESHOLD", "64")
        monkeypatch.setenv("FLUVIO_STRIPE_WIDTH", "64")
        monkeypatch.setenv("FLUVIO_STRIPE_OVERLAP", "16")
    form = "striped" if striped else "arrays" if name == "explode" else "values"
    values = PATTERNS[pattern][form]
    ex = _chain("tpu", CHAINS[name]).tpu_chain
    buf = _buf(values)
    assert ex._needs_stripes(buf) == striped
    h0 = ex.h2d_bytes_total
    got = ex.process_buffer(buf)
    flat, bucket = ex._flat_and_bucket(buf)
    assert ex.h2d_bytes_total - h0 >= bucket >= len(flat)
    ref = _chain("python", CHAINS[name]).process(
        SmartModuleInput.from_records(_records(values))
    )
    assert ref.error is None
    assert [(r.value, r.key, r.offset_delta) for r in got.to_records()] == [
        (r.value, r.key, r.offset_delta) for r in ref.successes
    ]
