"""array_map (fan-out) TPU lowering — equivalence vs the interpreter.

Covers BASELINE config #4 (JSON-array explode, ref transform kind
array_map, fluvio-smartengine transforms/mod.rs:24-52): bounds-kernel
fuzz against the DSL reference semantics, engine-level chain equivalence
(values/keys/offsets/timestamps and first-error parity), capacity
overflow retry, and the broker fast path across batches with differing
base offsets/timestamps.
"""

from __future__ import annotations

import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.codec import ByteWriter
from fluvio_tpu.protocol.record import Batch, Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu import kernels
from fluvio_tpu.smartengine.tpu.executor import _FanoutOverflow
from fluvio_tpu.smartmodule import SmartModuleInput, dsl
from fluvio_tpu.spu.smart_chain import process_batches


def _chain(backend, *specs):
    b = SmartEngine(backend=backend).builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    return b.initialize()


def _pad(vals, width=64):
    import jax.numpy as jnp

    n = len(vals)
    rows = 8
    while rows < n:
        rows *= 2
    arr = np.zeros((rows, width), np.uint8)
    lens = np.zeros(rows, np.int32)
    for i, v in enumerate(vals):
        arr[i, : len(v)] = np.frombuffer(v, np.uint8)
        lens[i] = len(v)
    return jnp.asarray(arr), jnp.asarray(lens), n


def _elements(bounds, vals, n):
    flag, sg, lg, ff, fs, fl, err = map(np.asarray, bounds)
    out = []
    for i in range(n):
        if err[i]:
            out.append(None)
            continue
        els = [
            vals[i][sg[i][t] : sg[i][t] + lg[i][t]] for t in np.flatnonzero(flag[i])
        ]
        if ff[i]:
            els.append(vals[i][fs[i] : fs[i] + fl[i]])
        out.append(els)
    return out


class TestBoundsKernels:
    def test_json_array_fuzz(self):
        rng = np.random.default_rng(7)
        atoms = [
            b"1", b"25", b'"ab"', b'"a,b"', b'"a\\"b"', b'{"x":[1,2]}',
            b"[3,4]", b'""', b"  7 ", b"null", b'"q\\\\"',
        ]
        cases = []
        for _ in range(150):
            k = rng.integers(0, 6)
            body = b",".join(
                bytes(atoms[rng.integers(0, len(atoms))]) for _ in range(k)
            )
            cases.append(
                b" " * rng.integers(0, 3) + b"[" + body + b"]" + b" " * rng.integers(0, 3)
            )
        cases += [
            b"not array", b"", b"[]", b"[ ]", b"[,]", b"[,,1,]",
            b"[[1,2],[3]]", b'["a",]', b"[1,2] x", b"[1,2] ]", b"x [1]",
        ]
        vals, lens, n = _pad(cases)
        got = _elements(kernels.json_array_bounds(vals, lens), cases, n)
        for i, v in enumerate(cases):
            assert got[i] == dsl.json_array_elements(v), v

    @pytest.mark.parametrize("sep", [b"\n", b"ab"])
    def test_split_fuzz(self, sep):
        rng = np.random.default_rng(11)
        alph = b"axb\nb" if sep == b"\n" else b"aabbab"
        cases = [
            bytes(alph[rng.integers(0, len(alph))] for _ in range(rng.integers(0, 30)))
            for _ in range(150)
        ]
        vals, lens, n = _pad(cases)
        got = _elements(kernels.split_bounds(vals, lens, sep), cases, n)
        for i, v in enumerate(cases):
            assert got[i] == [s for s in v.split(sep) if s], (sep, v)


ARRS = [
    b"[1,2,3]",
    b'["a","b"]',
    b"[]",
    b'[ "x y" , 5 ,{"n":[1,2]}]',
    b"[7]",
    b'["a,b","c\\"d"]',
]


def _records(values, keyed=False):
    out = []
    for i, v in enumerate(values):
        r = Record(value=v)
        if keyed:
            r.key = f"k{i}".encode()
        r.offset_delta = i
        r.timestamp_delta = i * 3
        out.append(r)
    return out


def _run_both(mods, values, keyed=False):
    tc = _chain("tpu", *mods)
    pc = _chain("python", *mods)
    assert tc.tpu_chain is not None, "chain must lower to TPU"
    t_out = tc.process(
        SmartModuleInput.from_records(_records(values, keyed), 7, 500)
    )
    p_out = pc.process(
        SmartModuleInput.from_records(_records(values, keyed), 7, 500)
    )
    tv = [(r.value, r.key, r.offset_delta, r.timestamp_delta) for r in t_out.successes]
    pv = [(r.value, r.key, r.offset_delta, r.timestamp_delta) for r in p_out.successes]
    assert tv == pv
    te = None if t_out.error is None else (t_out.error.offset, t_out.error.kind)
    pe = None if p_out.error is None else (p_out.error.offset, p_out.error.kind)
    assert te == pe
    return tv, te, tc


class TestEngineEquivalence:
    def test_explode_json(self):
        tv, te, tc = _run_both([("array-map-json", None)], ARRS)
        assert len(tv) == 11 and te is None
        assert tc.tpu_chain._viewable  # explode outputs are views

    def test_explode_keys_inherited(self):
        tv, _, _ = _run_both([("array-map-json", None)], ARRS, keyed=True)
        assert all(k is not None for _, k, _, _ in tv)

    def test_filter_then_explode(self):
        _run_both([("regex-filter", {"regex": "a"}), ("array-map-json", None)], ARRS)

    def test_explode_then_filter(self):
        _run_both(
            [("array-map-json", None), ("regex-filter", {"regex": "[0-9]"})], ARRS
        )

    def test_split_lines(self):
        _run_both(
            [("array-map-lines", None)],
            [b"a\nb\nc", b"", b"x\n\ny", b"\n\n", b"solo"],
        )

    def test_error_spills_with_exact_offset(self):
        tv, te, _ = _run_both(
            [("array-map-json", None)], [b"[1,2]", b"not array", b"[3]"]
        )
        assert len(tv) == 2  # partial output before the failing record
        assert te is not None and te[0] == 8  # base 7 + delta 1

    def test_explode_then_aggregate_carries(self):
        tv, _, tc = _run_both(
            [("array-map-json", None), ("aggregate-count", None)], ARRS
        )
        assert tv[-1][0] == b"11"
        # device carry mirrors the interpreter accumulator
        tc.tpu_chain._ensure_host_state()
        assert tc.tpu_chain.carries[0][0] == 11

    def test_windowed_aggregate_after_explode_not_lowered(self):
        # fan-out rows carry fresh timestamps, so this combination must
        # refuse to lower (auto backend falls back to the interpreter)
        c = _chain(
            "auto",
            ("array-map-json", None),
            ("windowed-sum", {"kind": "sum_int", "window_ms": "100"}),
        )
        assert c.tpu_chain is None


class TestOverflowRetry:
    def test_small_capacity_retries_to_exact(self):
        tc = _chain("tpu", ("array-map-json", None))
        ex = tc.tpu_chain
        # force a tiny first capacity so the exact-total retry path runs
        ex._fanout_cap = lambda buf: 1024  # bucket floor
        values = [b"[" + b",".join(b"1" for _ in range(200)) + b"]"] * 8
        out = tc.process(SmartModuleInput.from_records(_records(values)))
        assert len(out.successes) == 1600
        # learned density: >= 200 elements per source row with headroom
        assert ex._cap_ratio >= 200

    def test_dispatch_overflow_signal(self):
        tc = _chain("tpu", ("array-map-json", None))
        ex = tc.tpu_chain
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

        buf = RecordBuffer.from_records(_records([b"[1,2,3]"] * 8))
        header, packed = ex._dispatch(buf, fanout_cap=1024)
        out = ex._fetch(buf, header, packed)  # no overflow at ample cap
        assert out.count == 24
        header, packed = ex._dispatch(buf, fanout_cap=8)
        with pytest.raises(_FanoutOverflow):
            ex._fetch(buf, header, packed)


class TestLearnedBound:
    """The chain places at most a learned bound of elements a record
    (`kernels.fanout_place`); a record over it re-runs its slice at the
    next power of two, once, and the bound stays with the chain."""

    @staticmethod
    def _grows():
        from fluvio_tpu.telemetry import TELEMETRY

        return sum(1 for e in TELEMETRY.events.recent() if e.kind == "fanout-grow")

    @pytest.mark.parametrize("path", ["engine", "slices"])
    def test_record_over_the_bound_grows_it_once(self, path):
        from fluvio_tpu.smartengine.metrics import SmartModuleChainMetrics

        wide = ("[" + ",".join(str(i) for i in range(20)) + "]").encode()
        stream = [
            [b"[1,2,3]", b'["a","b"]'] * 4,           # within the first bound
            [b"[1,2]", wide, b"[3]", b'["x"]'] * 2,   # a record over it
            [wide, b"[4,5]"] * 4,                      # at the grown bound
        ]
        tc = _chain("tpu", ("array-map-json", None))
        pc = _chain("python", ("array-map-json", None))
        ex = tc.tpu_chain
        assert ex._fan_bound == kernels.FANOUT_MIN_BOUND
        metrics = SmartModuleChainMetrics()
        grows0 = self._grows()
        for k, values in enumerate(stream):
            if path == "engine":
                got = tc.process(
                    SmartModuleInput.from_records(_records(values), 7, 500), metrics
                ).successes
                want = pc.process(
                    SmartModuleInput.from_records(_records(values), 7, 500)
                ).successes
                assert [r.value for r in got] == [r.value for r in want]
            else:
                base = 10 * k
                fast = process_batches(
                    tc, _encode_batches([_records(values)], [base], [1000]),
                    1 << 20, metrics,
                )
                slow = process_batches(
                    pc, _encode_batches([_records(values)], [base], [1000]),
                    1 << 20,
                )
                assert _flat(fast) == _flat(slow)
                assert fast.next_offset == slow.next_offset == base + len(values)
        # 20 elements in one record: the bound grew 8 -> 32, once
        assert ex._fan_bound == 32
        assert self._grows() - grows0 == 1
        assert metrics.fanout_grows == 1
        assert metrics.to_dict()["fanout_grows"] == 1


def _encode_batches(record_groups, bases, first_ts):
    w = ByteWriter()
    for recs, base, ts in zip(record_groups, bases, first_ts):
        for i, r in enumerate(recs):
            r.offset_delta = i
        Batch.from_records(recs, base_offset=base, first_timestamp=ts).encode(w)
    from fluvio_tpu.protocol.codec import ByteReader

    r = ByteReader(w.bytes())
    out = []
    while r.remaining() > 0:
        out.append(Batch.decode(r, parse_records=False))
    return out


def _flat(result):
    out = []
    for b in result.records.batches:
        ts = b.header.first_timestamp
        for rec in b.memory_records():
            out.append(
                (rec.value, rec.key, ts + rec.timestamp_delta,
                 b.base_offset + rec.offset_delta)
            )
    return out


class TestBrokerFastPath:
    def test_multi_batch_explode_equivalence(self):
        groups = [
            [Record(value=b'["a","b"]'), Record(value=b"[1]")],
            [Record(value=b"[2,3,4]")],
        ]
        groups2 = [[Record(value=r.value) for r in g] for g in groups]
        batches = _encode_batches(groups, [0, 2], [1000, 2000])
        batches2 = _encode_batches(groups2, [0, 2], [1000, 2000])
        fast_chain = _chain("tpu", ("array-map-json", None))
        slow_chain = _chain("python", ("array-map-json", None))
        fast = process_batches(fast_chain, batches, 1 << 20)
        slow = process_batches(slow_chain, batches2, 1 << 20)
        assert fast_chain.tpu_chain is not None
        assert _flat(fast) == _flat(slow)
        assert fast.next_offset == slow.next_offset == 3

    def test_broker_error_falls_back(self):
        groups = [[Record(value=b"[1]"), Record(value=b"nope")]]
        batches = _encode_batches(groups, [5], [1000])
        chain = _chain("tpu", ("array-map-json", None))
        res = process_batches(chain, batches, 1 << 20)
        assert res.error is not None
        assert res.error.offset == 6
        assert len(_flat(res)) == 1  # partial output kept
