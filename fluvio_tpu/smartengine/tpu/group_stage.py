"""The group stage of a fused chain: a keyed table that answers every
record (`dsl.GroupProgram`; NEXmark Q17 is the model case).

`GroupStage` is the LAST stage of its chain, as `window_stage.
WindowStage` is of a windowed one, and what the executor does for it is
`window_stage.WindowChainMixin`'s: the stream's table is a
`WindowStateBank` (one lane an accumulator column, no count), empty at
the stream's first dispatch, committed at the slice's FETCH, grown by
doubling and re-run against the untouched table when a slice counts more
groups than it holds, the learned capacity staying with the compiled
chain. There is no second bank discipline here; this module holds what
differs:

- the lowering: key, time and each DISTINCT int expression of the
  columns evaluated once a slice (`_Shared`), the device work
  `windows/kernels.py:update_group` under the scopes
  `stage<i>.group_merge` and `stage<i>.group_emit`,
- the way out: one answer row per input row, eight int64 columns for
  Q17. Each column crosses the down-link as its low 32-bit words, and
  its high words only where the header says some row needs them; the
  host keeps the columns as int64 and the native encoder renders the
  rows straight into the served records (`RowFormat`,
  `baseline_engine.cpp:RowValues`), so no Python loop renders a row.

It lives beside `executor.py` for `window_stage.py`'s reason: a line
added above the jit calls of `_dispatch_inner` costs the chains with a
Pallas kernel a cold set-up.
"""

from __future__ import annotations

import functools
import json
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fluvio_tpu.smartengine.tpu import kernels, window_stage
from fluvio_tpu.smartengine.tpu.buffer import RowFormat
from fluvio_tpu.smartengine.tpu.lower import (
    Unlowerable,
    infer_type,
    lower_expr,
)
from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.spans import stage_scope
from fluvio_tpu.windows.spec import EMPTY_ID, KEY_STRIDE, WindowSpec

# stage kinds a group stage cannot follow: fan-out rows have no record
# of their own to answer at, and a second stateful stage has no carry
# slot. Nothing follows one: its rows are the chain's output
_NO_GROUP_AFTER = ("array_map", "aggregate", "window", "group")
GROUP_LANES_MAX = 16
# the days of one slice the native encoder takes as a table of texts;
# a slice that spans more renders through `dense_values`
_BUCKET_TABLE_MAX = 1 << 16


class _Shared:
    """Lowerings of a program's expressions in which every DISTINCT int
    expression is evaluated once a slice: the ten columns of NEXmark
    Q17 read ``price`` seven times, and `lower.lower_expr` closes over
    its sub-expressions, so each use would parse the field again. Bool
    combinators (`Cmp`, `And`, `Or`, `Not`) are combined here over the
    shared ints; everything else is `lower_expr`'s."""

    def __init__(self) -> None:
        self._fns: Dict[str, Callable] = {}

    def _once(self, expr, build) -> Callable:
        """``fn(state, memo)`` that computes ``expr`` at most once a
        slice (``memo`` lives for one `apply`); ``build()`` gives the
        computing function, made once a program."""
        key = json.dumps(expr.to_json(), sort_keys=True)
        if key not in self._fns:
            self._fns[key] = build()
        compute = self._fns[key]

        def fn(s, memo):
            if key not in memo:
                memo[key] = compute(s, memo)
            return memo[key]

        return fn

    def _lowered(self, expr) -> Callable:
        def build():
            fn = lower_expr(expr)
            return lambda s, memo: fn(s)

        return self._once(expr, build)

    def present(self, expr) -> Callable:
        """Does the row have this int at all? A `ParseInt`'s bytes are
        not empty (a field that is missing parses as 0 otherwise)."""
        if not isinstance(expr, dsl.ParseInt):
            return lambda s, memo: True
        arg = self._lowered(expr.arg)
        return lambda s, memo: arg(s, memo)[1] > 0

    def int_fn(self, expr) -> Callable:
        if expr is None or infer_type(expr) != "int":
            raise Unlowerable("a group's key, time and contributions are ints")
        if not isinstance(expr, dsl.ParseInt):
            return self._lowered(expr)
        if isinstance(expr.arg, dsl.Const):
            const = jnp.int64(dsl.parse_int_prefix(expr.arg.data))
            return lambda s, memo: const
        arg = self._lowered(expr.arg)
        return self._once(
            expr, lambda: lambda s, memo: kernels.parse_int(*arg(s, memo))
        )

    def bool_fn(self, expr) -> Callable:
        if isinstance(expr, dsl.Cmp):
            op = {
                "eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less,
                "le": jnp.less_equal, "gt": jnp.greater,
                "ge": jnp.greater_equal,
            }[expr.cmp]
            left, right = self.int_fn(expr.left), self.int_fn(expr.right)
            return lambda s, memo: op(left(s, memo), right(s, memo))
        if isinstance(expr, (dsl.And, dsl.Or)):
            fns = [self.bool_fn(a) for a in expr.args]
            op = jnp.logical_and if isinstance(expr, dsl.And) else jnp.logical_or
            return lambda s, memo: functools.reduce(
                op, (f(s, memo) for f in fns)
            )
        if isinstance(expr, dsl.Not):
            inner = self.bool_fn(expr.arg)
            return lambda s, memo: ~inner(s, memo)
        if infer_type(expr) != "bool":
            raise Unlowerable("a group column's `where` is a bool")
        return self._lowered(expr)


@dataclass
class GroupStage:
    """A keyed running aggregate (`dsl.GroupProgram`): the last stage of
    its chain. Its carry is the stream's table (ids, one accumulator
    column a lane), its output one answer row per input row.
    ``capacity`` is the size the chain's streams have learned (shared by
    every stream of the compiled chain); ``emit`` is the mixin's second
    capacity, which a table has no use for."""

    program: dsl.GroupProgram
    key_fn: Callable
    time_fn: Callable
    present_fns: Tuple[Callable, ...]
    lane_fns: Tuple[Tuple[Callable, Callable], ...]
    ops: Tuple[str, ...]
    capacity: int = 0
    emit: int = 8

    kind = "group"
    preserves_rows = True
    rewrites_offsets = False

    @classmethod
    def lower(cls, prog: dsl.GroupProgram, stages: List) -> "GroupStage":
        lanes = dsl.group_accumulators(prog)
        names = [c.name for c in prog.columns]
        if not lanes or len(lanes) > GROUP_LANES_MAX:
            raise Unlowerable(f"a group has 1..{GROUP_LANES_MAX} accumulators")
        if len(set(names)) != len(names) or not all(names):
            raise Unlowerable("group columns are named, each once")
        if int(prog.bucket_ms) <= 0:
            raise Unlowerable("a group's time bucket is positive")
        if any(s.kind in _NO_GROUP_AFTER for s in stages):
            raise Unlowerable("group after array_map or a stateful stage")
        acc_names = {c.name for c in lanes}
        for c in prog.columns:
            if c.combine not in dsl.GROUP_COLUMN_COMBINES:
                raise Unlowerable(f"group combine {c.combine}")
            if c.combine == "div" and not {c.num, c.den} <= acc_names:
                raise Unlowerable("a derived column divides two accumulators")
        if prog.key is None or prog.event_time is None:
            raise Unlowerable("a group has a key and an event time")
        shared = _Shared()
        return cls(
            prog,
            shared.int_fn(prog.key),
            shared.int_fn(prog.event_time),
            (shared.present(prog.key), shared.present(prog.event_time)),
            tuple(
                (
                    shared.int_fn(c.contribution),
                    None if c.where is None else shared.bool_fn(c.where),
                )
                for c in lanes
            ),
            tuple(c.combine for c in lanes),
            capacity=window_stage.WINDOW_CAPACITY_START,
        )

    def bank_spec(self) -> WindowSpec:
        """The stream table's shape: one lane a column, no count, no
        watermark to speak of."""
        return WindowSpec(
            window_ms=int(self.program.bucket_ms), op=self.ops, keyed=True,
            lateness_ms=0, capacity=self.capacity, emit_capacity=self.emit,
            counted=False,
        )

    # -- the device side (traced) ---------------------------------------------

    def apply(self, state: Dict, carries, base_ts, ctx):
        from fluvio_tpu.windows import kernels as window_kernels
        from fluvio_tpu.windows.spec import OP_NEUTRAL

        p = self.program
        i = ctx.get("stage_index", 0)
        memo: Dict = {}
        key = self.key_fn(state, memo).astype(jnp.int64)
        t = self.time_fn(state, memo).astype(jnp.int64)
        keyed = (
            (key >= 0) & (key < KEY_STRIDE)
            & (t >= 0) & (t < dsl.GROUP_TIME_LIMIT_MS)
        )
        for present in self.present_fns:
            keyed = keyed & present(state, memo)
        ids = jnp.where(keyed, key * KEY_STRIDE + t // int(p.bucket_ms), EMPTY_ID)
        lanes = []
        for (contribution, where), op in zip(self.lane_fns, self.ops):
            x = jnp.broadcast_to(
                contribution(state, memo).astype(jnp.int64), ids.shape
            )
            if where is not None:
                x = jnp.where(where(state, memo), x, jnp.int64(OP_NEUTRAL[op]))
            lanes.append(x)
        emit_scope = stage_scope(i, "group_emit")
        header, bank, rows = window_kernels.update_group(
            self.ops, carries, ids, jnp.stack(lanes), state["valid"],
            merge_scope=stage_scope(i, "group_merge"), emit_scope=emit_scope,
        )
        with jax.named_scope(emit_scope):
            answered = state["valid"] & keyed
            # a column crosses the link as its low words; the header says
            # which columns have a row that needs the high words too
            wide = jnp.any(
                answered & (rows != rows.astype(jnp.int32).astype(jnp.int64)),
                axis=1,
            ).astype(jnp.int64)
            out = {
                "window_header": jnp.concatenate([header, wide]),
                "group_lo": rows.astype(jnp.uint32),
                "group_hi": (rows >> 32).astype(jnp.int32),
                "group_mask": kernels.pack_mask(answered),
            }
        return out, bank

    # -- the host side ---------------------------------------------------------

    def counts(self, hdr) -> Tuple[int, int, int]:
        """(bank entries the slice needs, emit rows it needs, watermark
        to commit) of a synced header."""
        return int(hdr[1]), 0, 0

    def emit_rows(self, packed) -> int:
        """A table answers in place: no emit column to outgrow."""
        return 0

    def raise_floor(self, rows: int) -> None:
        """A table may gain a key a row: it starts at half the slice's
        padded rows."""
        self.capacity = max(self.capacity, rows // 2)

    note_growth = staticmethod(TELEMETRY.add_group_grow)

    def fetch(self, ex, buf, hdr, packed, span):
        """A group slice's D2H after the header sync: ONE bucketed
        download of the answer columns' low words, and one of the high
        words of each column whose flag the header sets (NEXmark Q17:
        `sum_price` alone); the 1-bit answered mask only where a row
        went unanswered. Returns the split-back thunk."""
        n_rows, n_keys, n_invalid = (int(x) for x in hdr[:3])
        wide = [bool(x) for x in hdr[3:]]
        lo_dev, hi_dev = packed["group_lo"], packed["group_hi"]
        k, rows_dev = lo_dev.shape
        bucket = min(ex._bucket_bytes(max(buf.count, 1), 8), rows_dev)
        slices = [lax.slice(lo_dev, (0, 0), (k, bucket))]
        slices += [
            lax.slice(hi_dev, (j, 0), (j + 1, bucket))
            for j, w in enumerate(wide) if w
        ]
        holes = n_rows != buf.count
        if holes:
            slices.append(packed["group_mask"])
        host = ex._download(slices, span) if n_rows else []
        TELEMETRY.add_link_variant(
            "grp-int64" if all(wide) else "grp-mixed" if any(wide)
            else "grp-int32"
        )
        TELEMETRY.add_group_slice(n_rows, n_keys, n_invalid)
        return functools.partial(
            self._split_back, ex, buf, n_rows, host, wide, holes, k
        )

    def _split_back(self, ex, buf, count, host, wide, holes, k):
        """Downloaded words -> the int64 answer columns (one row a
        column, the answered rows only) in an int-backed `RecordBuffer`
        that the native encoder renders from."""
        src = np.arange(count, dtype=np.int64)
        if holes and count:
            src = ex._mask_to_src(host[-1], buf)[:count]
        cols = np.empty((k, count), dtype=np.int64)
        his = iter(host[1:])
        for j in range(k if count else 0):
            lo = host[0][j]
            if wide[j]:
                col = (next(his)[0].astype(np.int64) << 32) | lo.astype(np.int64)
            else:
                col = lo.view(np.int32)
            cols[j] = col[src] if holes else col[:count]
        rows = ex._bucket_bytes(max(count, 1), 8)
        out_keys, out_klens = ex._view_keys(buf, count, rows, src)
        return ex._assemble(
            buf, count, rows, None, None, out_keys, out_klens, src,
            ints=cols, render=self.render, row_format=self.row_format(cols),
        )

    def _slots(self) -> List[Tuple[bytes, Tuple[int, int, int]]]:
        """(literal before the slot, (kind, a, b)) for each value of a
        row, in `dsl.group_row_bytes`' order; answer column 0 is the
        composite id, column 1 + i the i-th accumulator."""
        p = self.program
        lane = {c.name: 1 + i
                for i, c in enumerate(dsl.group_accumulators(p))}
        shift = KEY_STRIDE.bit_length() - 1
        out = [
            (b'{"%s":' % p.key_field.encode(), (RowFormat.SHIFTED, 0, shift)),
            (b',"%s":"' % p.bucket_field.encode(), (RowFormat.TABLE, 0, shift)),
        ]
        lead = b'"'
        for c in p.columns:
            slot = (
                (RowFormat.DIV, lane[c.num], lane[c.den])
                if c.combine == "div" else (RowFormat.INT, lane[c.name], 0)
            )
            out.append((lead + b',"%s":' % c.name.encode(), slot))
            lead = b""
        return out

    def row_format(self, cols: np.ndarray):
        """How the native encoder renders this slice's rows; None where
        its time buckets are too many for a table of texts."""
        if not cols.shape[1]:
            return None
        buckets = cols[0] & (KEY_STRIDE - 1)
        first, last = int(buckets.min()), int(buckets.max())
        if last - first >= _BUCKET_TABLE_MAX:
            return None
        slots = self._slots()
        return RowFormat(
            pieces=[lit for lit, _ in slots] + [b"}"],
            slots=np.array([s for _, s in slots], dtype=np.int64),
            table=[dsl.group_bucket_text(self.program, b)
                   for b in range(first, last + 1)],
            table_base=first,
        )

    def render(self, cols: np.ndarray, rows: int, count: int):
        """The rows as a padded value matrix + lengths, through the ONE
        rendering (`dsl.group_row_bytes`): what `dense_values()` gives a
        consumer that is not the served encoder."""
        return rendered_matrix(
            [
                dsl.group_row_bytes(
                    self.program, row[0] // KEY_STRIDE, row[0] % KEY_STRIDE,
                    row[1:],
                )
                for row in cols.T.tolist()
            ],
            rows,
        )


def rendered_matrix(texts: List[bytes], rows: int):
    """Rendered rows as a padded value matrix (width bucketed as the
    executor's are: a power of two from 8) + lengths."""
    width = 8
    while width < max(map(len, texts), default=1):
        width <<= 1
    values = np.zeros((rows, width), dtype=np.uint8)
    lengths = np.zeros((rows,), dtype=np.int32)
    for i, text in enumerate(texts):
        values[i, :len(text)] = np.frombuffer(text, dtype=np.uint8)
        lengths[i] = len(text)
    return values, lengths
