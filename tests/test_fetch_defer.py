"""ISSUE-31, executor plumbing: `_fetch_ints` and `_fetch_bytes` take the
``defer`` their view-mode sibling has. With ``defer=True`` the fetch
returns a thunk that is numpy over the downloaded arrays: it writes
nothing of the executor, and its result is byte-equal to what
``defer=False`` gives on the same handle. CPU; values only."""

from __future__ import annotations

import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer
from fluvio_tpu.smartengine.tpu.executor import (
    TpuChainExecutor,
    _StreamExecutor,
)
from fluvio_tpu.telemetry import TELEMETRY


def _chain(*specs) -> TpuChainExecutor:
    b = SmartEngine(backend="tpu").builder()
    for name, params in specs:
        b.add_smart_module(SmartModuleConfig(params=params or {}), lookup(name))
    chain = b.initialize()
    assert chain.backend_in_use == "tpu"
    return chain.tpu_chain


def _buf(values) -> RecordBuffer:
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
    return RecordBuffer.from_records(records)


def _columns(out: RecordBuffer) -> dict:
    cols = out.to_columns()
    return {k: np.asarray(v).tobytes() if k != "count" else v
            for k, v in cols.items()}


def _both_ways(ex: TpuChainExecutor, buf: RecordBuffer, monkeypatch):
    """One dispatch, fetched twice: plainly, and deferred with every
    attribute write on the executor tripwired while the thunk runs."""
    _prev, header, packed, spec = ex.dispatch_buffer(buf)
    plain = ex._fetch(buf, header, packed, dict(spec), defer=False)
    assert isinstance(plain, RecordBuffer)
    thunk = ex._fetch(buf, header, packed, dict(spec), defer=True)
    assert callable(thunk) and not isinstance(thunk, RecordBuffer)
    counters = (ex.d2h_bytes_total, ex.h2d_bytes_total,
                TELEMETRY.link_variant_counts())

    def tripwire(self, name, value):
        raise AssertionError(f"the split-back thunk wrote executor.{name}")

    with monkeypatch.context() as m:
        m.setattr(TpuChainExecutor, "__setattr__", tripwire, raising=False)
        m.setattr(_StreamExecutor, "__setattr__", tripwire)
        deferred = thunk()
    assert counters == (ex.d2h_bytes_total, ex.h2d_bytes_total,
                        TELEMETRY.link_variant_counts())
    assert _columns(deferred) == _columns(plain)
    assert deferred.count == plain.count > 0
    return plain


@pytest.fixture(autouse=True)
def _fresh_registry():
    TELEMETRY.reset()
    prior = TELEMETRY.enabled
    TELEMETRY.enabled = True
    yield
    TELEMETRY.enabled = prior
    TELEMETRY.reset()


@pytest.mark.parametrize("step,variant", [
    (7, "agg-delta-int16"),
    (70_000, "agg-delta-int32"),
    (3_000_000_000, "agg-full"),
], ids=["delta-int16", "delta-int32", "full"])
def test_fetch_ints_deferred_is_pure_and_equal(monkeypatch, step, variant):
    ex = _chain(("aggregate-sum", None))
    assert ex._int_output
    values = [str(step + (i % 5)).encode() for i in range(600)]
    plain = _both_ways(ex, _buf(values), monkeypatch)
    # the column crossed in the form the test names, both times
    assert TELEMETRY.link_variant_counts().get(variant) == 2
    total = sum(int(v) for v in values)
    assert plain.to_records()[-1].value == str(total).encode()


@pytest.mark.parametrize("compact", ["on", "off"],
                         ids=["packed-payload", "padded-matrix"])
def test_fetch_bytes_deferred_is_pure_and_equal(monkeypatch, compact):
    monkeypatch.setenv("FLUVIO_RESULT_COMPACT", compact)
    ex = _chain(("aggregate-sum", None), ("regex-filter", {"regex": "1"}))
    assert not ex._viewable and not ex._int_output
    values = [str(100 + i).encode() for i in range(600)]
    plain = _both_ways(ex, _buf(values), monkeypatch)
    sums = np.cumsum([int(v) for v in values])
    assert [r.value for r in plain.to_records()] == [
        str(s).encode() for s in sums if "1" in str(s)]
