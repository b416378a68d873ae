"""A view-mode fetch copies no byte: its output is VIEW-BACKED, spans of
the input's 4-aligned flat (`RecordBuffer._view`), which the served
encoder copies straight into the wire records. Every other consumer
renders the flat of its own (`buffer.render_view_flat`); here that
render, and every accessor over it, is held to the flat the numpy
split-back built before the view form existed (kept below, verbatim, as
the reference), and the engine-level outputs to the interpreter's.

CPU, small sizes, values only.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from spubench import manifest  # noqa: E402
from spubench.ragged import to_values  # noqa: E402

from fluvio_tpu.models import lookup  # noqa: E402
from fluvio_tpu.protocol.record import Record  # noqa: E402
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig  # noqa: E402
from fluvio_tpu.smartengine.tpu.buffer import (  # noqa: E402
    RecordBuffer,
    apply_postops_host,
    ragged_range_select,
    view_fold,
)
from fluvio_tpu.smartmodule import SmartModuleInput  # noqa: E402

NORTH_STAR = [
    '''
@smartmodule.filter(dsl=dsl.FilterProgram(
    predicate=dsl.RegexMatch(arg=dsl.Value(), pattern="fluvio")))
def f(record):
    import re
    return re.search(b"fluvio", record.value) is not None
''',
    '''
@smartmodule.map(dsl=dsl.MapProgram(
    value=dsl.Upper(arg=dsl.JsonGet(arg=dsl.Value(), key="name"))))
def m(record):
    return dsl.ascii_upper(dsl.json_get_bytes(record.value, "name"))
''',
]
LOWER_OF_UPPER = '''
@smartmodule.map(dsl=dsl.MapProgram(
    value=dsl.Lower(arg=dsl.Upper(arg=dsl.JsonGet(arg=dsl.Value(), key="name")))))
def m(record):
    return dsl.ascii_lower(dsl.ascii_upper(dsl.json_get_bytes(record.value, "name")))
'''
# the built-in module: its hook keeps the source record's key, as the
# fused explode does
EXPLODE = "array-map-json"
CHAINS = {
    "northstar": (NORTH_STAR, "gen_json"),
    "lower-of-upper": ([LOWER_OF_UPPER], "gen_json"),
    "explode": ([EXPLODE], "gen_arrays"),
    "filter-only": (NORTH_STAR[:1], "gen_json"),
}


def _parent_materialize_flat(in_flat, in_starts, src, st, ln, rows, postops):
    """The numpy split-back as it stood before the view form (the body
    of `executor._materialize_view_flat`, verbatim but for its
    arguments): (4-aligned flat, int32 starts over ``rows``)."""
    count = len(ln)
    ln64 = ln.astype(np.int64)
    l4 = (ln64 + 3) & ~3
    starts64 = np.cumsum(l4) - l4
    total = int(l4.sum()) if count else 0
    flat_out = np.zeros((total,), dtype=np.uint8)
    if count and total:
        if len(in_flat):
            base = in_starts.astype(np.int64)[src] + st
            fast = (
                base[0] >= 0
                and base[-1] + l4[-1] <= len(in_flat)
                and bool((base[1:] >= base[:-1] + l4[:-1]).all())
            )
            if fast:
                flat_out = ragged_range_select(in_flat, base, l4)
                pad = l4 - ln64
                if pad.any():
                    npad = int(pad.sum())
                    padbase = np.repeat(starts64 + ln64, pad)
                    within = np.arange(npad, dtype=np.int64) - np.repeat(
                        np.cumsum(pad) - pad, pad
                    )
                    flat_out[padbase + within] = 0
            else:
                pos = np.arange(total, dtype=np.int64) - np.repeat(
                    starts64, l4
                )
                idx = np.clip(
                    np.repeat(base, l4) + pos, 0, len(in_flat) - 1
                )
                keep = pos < np.repeat(ln64, l4)
                flat_out = np.where(keep, in_flat[idx], 0).astype(
                    np.uint8
                )
        flat_out = apply_postops_host(flat_out, postops)
    starts = np.zeros((rows,), dtype=np.int32)
    starts[:count] = starts64
    starts[count:] = total
    return flat_out, starts


def _input(values):
    records = [Record(value=v, offset_delta=i) for i, v in enumerate(values)]
    return RecordBuffer.from_records(records)


SPAN_CASES = {
    # exploded array elements: one comma apart, 4-aligned spans overlap
    "explode": ([b'["a12","b",3]', b'[1,22,"xyz1"]', b'["q"]'],
                [(0, 1, 5), (0, 7, 3), (0, 11, 1), (1, 1, 1), (1, 3, 2),
                 (1, 6, 6), (2, 1, 3)], ("upper",)),
    # JsonGet spans, disjoint once padded: the range-select path
    "json": ([b'{"name":"Fluvio-1","n":1}', b'{"name":"kafka_Z","n":22}'],
             [(0, 9, 8), (1, 9, 7)], ("upper", "lower")),
    "identity": ([b"ab", b"", b"fluvio!", b"XyZ"],
                 [(0, 0, 2), (2, 0, 7), (3, 0, 3)], ()),
    "empty-values": ([b"", b"", b""], [(0, 0, 0), (2, 0, 0)], ("lower",)),
}
ACCESSORS = ["ragged_values", "dense_values", "to_columns", "to_records"]


def _view_and_reference(case):
    values, spans, postops = SPAN_CASES[case]
    buf = _input(values)
    in_flat, in_starts = buf.ragged_values()
    src = np.array([s for s, _, _ in spans], np.int64)
    st = np.array([a for _, a, _ in spans], np.int64)
    ln = np.array([n for _, _, n in spans], np.int32)
    count, rows = len(spans), 16
    lengths = np.zeros(rows, np.int32)
    lengths[:count] = ln
    meta = dict(
        lengths=lengths, keys=np.zeros((rows, 1), np.uint8),
        key_lengths=np.full(rows, -1, np.int32),
        offset_deltas=np.arange(rows, dtype=np.int32),
        timestamp_deltas=np.zeros(rows, np.int64), count=count,
        _width=32, _rows=rows,
    )
    base = np.add(in_starts[src], st, dtype=np.int64)
    view = RecordBuffer(
        values=None, _view=(in_flat, base, view_fold(postops)), **meta
    )
    flat, starts = _parent_materialize_flat(
        in_flat, in_starts, src, st, ln, rows, postops
    )
    parent = RecordBuffer(values=None, _flat=flat, _starts=starts, **meta)
    return view, parent


def _same(a, b):
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    if isinstance(a, np.ndarray):
        return a.dtype == b.dtype and np.array_equal(a, b)
    return a == b


@pytest.mark.parametrize("accessor", ACCESSORS)
@pytest.mark.parametrize("case", list(SPAN_CASES))
def test_view_backed_accessors_equal_the_parent_flat(case, accessor):
    view, parent = _view_and_reference(case)
    assert view.values is None and view._flat is None
    assert view.width == parent.width == 32   # known without a render
    assert view._flat is None
    got, want = getattr(view, accessor)(), getattr(parent, accessor)()
    assert _same(got, want)
    # the render happened once, and left a flat-backed buffer behind
    assert view._view is None and view._flat is not None
    assert np.array_equal(view._flat, parent._flat)


def _engine_chain(sources, backend):
    b = SmartEngine(backend=backend).builder()
    for src in sources:
        b.add_smart_module(
            SmartModuleConfig(), src if "@smartmodule" in src else lookup(src)
        )
    return b.initialize()


def _corpus(generator, n=600):
    gen = manifest.load_plugin(BENCH, "corpora", generator)
    return to_values(*gen.generate(n, [20261015, 0]))


@pytest.mark.parametrize("chain", list(CHAINS))
def test_a_view_fetch_is_view_backed_and_renders_the_interpreter_bytes(chain):
    sources, generator = CHAINS[chain]
    values = _corpus(generator)
    ex = _engine_chain(sources, "tpu").tpu_chain
    assert ex._viewable and ex._result_compact
    buf = _input(values)
    _prev, header, packed, spec = ex.dispatch_buffer(buf)
    out = ex._fetch(buf, header, packed, dict(spec), defer=False)
    assert out.values is None and out._flat is None and out._ints is None
    in_flat, base, fold = out._view
    assert in_flat is buf.ragged_values()[0] and base.dtype == np.int64
    assert fold == view_fold(ex._view_postops)
    want = _engine_chain(sources, "python").process(
        SmartModuleInput.from_records(
            [Record(value=v, offset_delta=i) for i, v in enumerate(values)])
    ).successes
    assert out.count == len(want) > 0
    assert [r.value for r in out.to_records()] == [r.value for r in want]


@pytest.mark.parametrize("chain", list(CHAINS))
def test_engine_outputs_of_view_chains_equal_the_interpreter(chain):
    sources, generator = CHAINS[chain]
    values = _corpus(generator)

    def run(backend):
        records = [Record(value=v, key=b"k%d" % (i % 3) if i % 2 else None,
                          offset_delta=i, timestamp_delta=7 * i)
                   for i, v in enumerate(values)]
        out = _engine_chain(sources, backend).process(
            SmartModuleInput.from_records(records, 40, 1000)
        )
        assert out.error is None
        return [(r.value, r.key, r.offset_delta, r.timestamp_delta)
                for r in out.successes]

    tpu = run("tpu")
    assert tpu == run("python") and tpu
