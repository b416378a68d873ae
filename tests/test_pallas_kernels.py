"""Pallas kernel equivalence (interpret mode on the CPU mesh).

The pallas kernels carry the engine's hot-path semantics on real TPU;
tests run them through the pallas interpreter and assert bit-equality
against the pinned DSL byte semantics and the XLA kernels. The lowerer's
platform selection is also covered: FLUVIO_TPU_PALLAS=interpret must
route a built chain through the pallas kernels and keep outputs
identical to the XLA-kernel chain.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.ops.regex_dfa import compile_regex
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu import kernels, pallas_kernels
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.smartmodule import dsl
from tests.test_tpu_kernels import JSON_DOCS, stage

class TestJsonGetPallas:
    @pytest.mark.parametrize("key", ["name", "q", ""])
    def test_matches_reference(self, key):
        buf = stage(JSON_DOCS)
        out_v, out_l = pallas_kernels.json_get_pallas(
            buf.values, buf.lengths, key, interpret=True
        )
        out_v, out_l = np.asarray(out_v), np.asarray(out_l)
        for i, doc in enumerate(JSON_DOCS):
            expected = dsl.json_get_bytes(doc, key)
            got = out_v[i, : out_l[i]].tobytes()
            assert got == expected, f"doc={doc!r}: {got!r} != {expected!r}"

    def test_fuzz_random_json(self):
        rng = np.random.default_rng(11)
        docs = []
        for _ in range(64):
            n_fields = int(rng.integers(0, 5))
            fields = []
            for _ in range(n_fields):
                k = "".join(
                    chr(c) for c in rng.integers(97, 110, size=int(rng.integers(1, 4)))
                )
                kind = rng.integers(0, 4)
                if kind == 0:
                    v = f'"{k}-val"'
                elif kind == 1:
                    v = str(int(rng.integers(-99, 99)))
                elif kind == 2:
                    v = '{"in":1}'
                else:
                    v = "[1,2]"
                fields.append(f'"{k}":{v}')
            docs.append(("{" + ",".join(fields) + "}").encode())
        buf = stage(docs)
        for key in ["a", "ab", "name"]:
            out_v, out_l = pallas_kernels.json_get_pallas(
                buf.values, buf.lengths, key, interpret=True
            )
            out_v, out_l = np.asarray(out_v), np.asarray(out_l)
            for i, doc in enumerate(docs):
                expected = dsl.json_get_bytes(doc, key)
                got = out_v[i, : out_l[i]].tobytes()
                assert got == expected, f"doc={doc!r} key={key!r}"


REGEX_CORPUS = [
    b"",
    b"fluvio",
    b"xfluviox",
    b"fluvi",
    b"kafka",
    b"aab",
    b"abab",
    b"hello world",
    b"123-456",
    b"a" * 31,
    b"fluvio at end fluvio",
]


class TestDfaMatchPallas:
    @pytest.mark.parametrize(
        "pattern",
        ["fluvio", "^fluvio", "fluvio$", "a+b", "(ab)+", "[0-9]+-[0-9]+", "a.c"],
    )
    def test_matches_xla_kernel(self, pattern):
        dfa = compile_regex(pattern)
        if not pallas_kernels.dfa_supported(dfa):
            pytest.skip("DFA above select-chain bound")
        buf = stage(REGEX_CORPUS)
        xla = np.asarray(kernels.dfa_match(buf.values, buf.lengths, dfa))
        pls = np.asarray(
            pallas_kernels.dfa_match_pallas(buf.values, buf.lengths, dfa, interpret=True)
        )
        np.testing.assert_array_equal(xla, pls, err_msg=pattern)

    def test_matches_python_re(self):
        import re

        pattern = "fl(u|a)vio"
        dfa = compile_regex(pattern)
        buf = stage(REGEX_CORPUS)
        got = np.asarray(
            pallas_kernels.dfa_match_pallas(buf.values, buf.lengths, dfa, interpret=True)
        )
        for i, data in enumerate(REGEX_CORPUS):
            expected = re.search(pattern.encode(), data) is not None
            assert bool(got[i]) == expected, data

    def test_width_exactly_record_length(self):
        """Records filling the full padded width still get their EOS."""
        dfa = compile_regex("abc$")
        values = [b"zzabc", b"abczz"]
        # craft a buffer whose width equals the longest record
        width = max(len(v) for v in values)
        vals = np.zeros((8, width), dtype=np.uint8)
        lens = np.zeros(8, dtype=np.int32)
        for i, v in enumerate(values):
            vals[i, : len(v)] = np.frombuffer(v, dtype=np.uint8)
            lens[i] = len(v)
        got = np.asarray(pallas_kernels.dfa_match_pallas(vals, lens, dfa, interpret=True))
        assert bool(got[0]) and not bool(got[1])


class TestLowererSelection:
    def _chain_outputs(self):
        b = SmartEngine(backend="tpu").builder()
        b.add_smart_module(
            SmartModuleConfig(params={"regex": "flu(v|b)io"}), lookup("regex-filter")
        )
        b.add_smart_module(
            SmartModuleConfig(params={"field": "name"}), lookup("json-map")
        )
        chain = b.initialize()
        assert chain.tpu_chain is not None
        records = []
        for i in range(24):
            name = "fluvio" if i % 3 else "flubio"
            records.append(Record(value=f'{{"name":"{name}-{i}"}}'.encode()))
        for i, r in enumerate(records):
            r.offset_delta = i
        buf = RecordBuffer.from_records(records, base_offset=0, base_timestamp=0)
        out = chain.tpu_chain.process_buffer(buf)
        # result compaction may hand back a flat-backed buffer: read
        # through the record surface, not the padded matrix
        return [r.value for r in out.to_records()]

    def test_pallas_chain_matches_xla_chain(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_TPU_PALLAS", "0")
        xla_out = self._chain_outputs()
        monkeypatch.setenv("FLUVIO_TPU_PALLAS", "interpret")
        pallas_out = self._chain_outputs()
        assert xla_out == pallas_out
        assert len(xla_out) == 24  # every record matches flu(v|b)io
