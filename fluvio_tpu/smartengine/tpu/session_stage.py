"""The session stage of a fused chain: keyed event-time session windows
(`dsl.SessionProgram`; NEXmark Q11 is the model case).

`SessionStage` is the LAST stage of its chain, as `window_stage.
WindowStage` is of a windowed one, and what the executor does for it is
`window_stage.WindowChainMixin`'s: the stream's open sessions are a
`WindowStateBank` with three lanes (start, last, count; no count of its
own), empty at the stream's first dispatch, committed at the slice's
FETCH, grown by doubling and re-run against the untouched bank when a
slice counts more open sessions or closed rows than its shapes hold, the
learned capacities staying with the compiled chain. This module holds
what differs:

- the lowering: key and event time from the chain's own expression
  lowering, the device work `windows/kernels.py:update_session` under
  the scopes `stage<i>.session_merge` and `stage<i>.session_close`,
- the way out: the closed sessions' four int64 columns, ordered by
  (end, key) on the host (a few thousand rows a slice), rendered by the
  native writer straight into the served records (`RowFormat`), as a
  keyed table's rows are.

It lives beside `executor.py` for `window_stage.py`'s reason: a line
added above the jit calls of `_dispatch_inner` costs the chains with a
Pallas kernel a cold set-up.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List

import numpy as np
from jax import lax

from fluvio_tpu.smartengine.tpu import window_stage
from fluvio_tpu.smartengine.tpu.buffer import MIN_WIDTH, RecordBuffer, RowFormat
from fluvio_tpu.smartengine.tpu.lower import Unlowerable, infer_type, lower_expr
from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.spans import stage_scope
from fluvio_tpu.windows.spec import WindowSpec

# stage kinds a session stage cannot follow: fan-out rows carry no
# record of their own to take an event time from, and a second stateful
# stage has no carry slot
_NO_SESSION_AFTER = ("array_map", "aggregate") + window_stage.LAST_KINDS


@dataclass
class SessionStage:
    """Keyed event-time sessions (`dsl.SessionProgram`): the last stage
    of its chain. Its carry is the stream's open sessions (key; start,
    last, count; the watermark), its output the closed sessions' rows,
    at most ``ctx["fanout_cap"]`` of them (the emit capacity).
    ``capacity`` / ``emit`` are the sizes the chain's streams have
    learned (shared by every stream of the compiled chain)."""

    program: dsl.SessionProgram
    key_fn: Callable
    time_fn: Callable
    capacity: int = 0
    emit: int = 0

    kind = "session"
    preserves_rows = False
    rewrites_offsets = True

    @classmethod
    def lower(cls, prog: dsl.SessionProgram, stages: List) -> "SessionStage":
        if prog.gap_ms <= 0 or prog.lateness_ms < 0:
            raise Unlowerable("a session's gap is positive, its lateness >= 0")
        if any(s.kind in _NO_SESSION_AFTER for s in stages):
            raise Unlowerable("session after array_map or a stateful stage")
        fns = []
        for expr in (prog.key, prog.event_time):
            if expr is None or infer_type(expr) != "int":
                raise Unlowerable("a session's key and event time are ints")
            fns.append(lower_expr(expr))
        start = window_stage.WINDOW_CAPACITY_START
        return cls(prog, *fns, capacity=start, emit=start)

    def apply(self, state: Dict, carries, base_ts, ctx):
        import jax.numpy as jnp

        from fluvio_tpu.windows import kernels as window_kernels

        p = self.program
        i = ctx.get("stage_index", 0)
        header, bank, rows = window_kernels.update_session(
            p.gap_ms, p.lateness_ms, ctx["fanout_cap"], carries,
            self.key_fn(state).astype(jnp.int64),
            self.time_fn(state).astype(jnp.int64),
            state["valid"],
            merge_scope=stage_scope(i, "session_merge"),
            close_scope=stage_scope(i, "session_close"),
        )
        return {"window_header": header, "session_rows": rows}, bank

    # -- what `WindowChainMixin` asks of a banked stage -----------------------

    def bank_spec(self) -> WindowSpec:
        """The stream's open sessions: three lanes, no count."""
        from fluvio_tpu.windows.kernels import SESSION_OPS

        p = self.program
        return WindowSpec(
            window_ms=p.gap_ms, op=SESSION_OPS, keyed=True,
            lateness_ms=p.lateness_ms, capacity=self.capacity,
            emit_capacity=self.emit, counted=False,
        )

    def counts(self, hdr):
        """(bank entries the slice needs, emit rows it needs, the
        watermark to commit) of a synced header."""
        return int(hdr[1]), int(hdr[2]), int(hdr[4])

    def emit_rows(self, packed) -> int:
        """Closed rows the slice's program had room for."""
        return packed["session_rows"].shape[1]

    def raise_floor(self, rows: int) -> None:
        """Neither capacity starts under a thirty-second of the slice's
        padded rows: a session takes many rows (NEXmark Q11: about 22
        closed and 26 open a thousand bids), so a large slice's first
        program is not one that only reports 1,024 entries too few."""
        self.capacity = max(self.capacity, rows // 32)
        self.emit = max(self.emit, rows // 32)

    note_growth = staticmethod(TELEMETRY.add_session_grow)

    def fetch(self, ex, buf, hdr, packed, span):
        """A session slice's D2H after the header sync: ONE bucketed
        download of the closed sessions' columns (none when nothing
        closed). Returns the thunk that orders and renders them."""
        n_rows, _open, n_closed, n_late, _wm, n_invalid, n_bridged = (
            int(x) for x in hdr
        )
        rows = np.zeros((4, 0), dtype=np.int64)
        if n_rows:
            rows_dev = packed["session_rows"]
            bucket = min(ex._pad_slice(n_rows), rows_dev.shape[1])
            rows = ex._download(
                [lax.slice(rows_dev, (0, 0), (4, bucket))], span
            )[0][:, :n_rows]
        TELEMETRY.add_session_slice(n_closed, n_bridged, n_late, n_invalid)
        return functools.partial(self._split_back, ex, buf, rows)

    def _split_back(self, ex, buf, rows: np.ndarray) -> RecordBuffer:
        """(key, count, start, last) -> an int-backed `RecordBuffer` of
        (key, count, start, end) in (end, key) order: fresh records at
        the slice's base offset (delta 0, like a window's answer rows:
        never before the slice's first input, never past the record
        whose arrival closed the session)."""
        n = rows.shape[1]
        key, count, start, last = rows
        end = last + self.program.gap_ms
        order = np.lexsort((key, end))
        cols = np.stack([key, count, start, end])[:, order]
        padded = ex._bucket_bytes(max(n, 1), 8)
        return RecordBuffer(
            values=None,
            lengths=None,
            keys=np.zeros((padded, MIN_WIDTH), dtype=np.uint8),
            key_lengths=np.full((padded,), -1, dtype=np.int32),
            offset_deltas=np.zeros((padded,), dtype=np.int32),
            timestamp_deltas=np.zeros((padded,), dtype=np.int64),
            count=n,
            base_offset=buf.base_offset,
            base_timestamp=buf.base_timestamp,
            _rows=padded,
            _ints=cols,
            _render=self.render,
            _row_format=self.row_format() if n else None,
        )

    def row_format(self) -> RowFormat:
        """How the native encoder renders a row: four decimals, in
        `dsl.session_row_bytes`' order."""
        p = self.program
        return RowFormat(
            pieces=[b'{"%s":' % p.key_field.encode(),
                    b',"%s":' % p.count_field.encode(),
                    b',"starttime":', b',"endtime":', b"}"],
            slots=np.array([(RowFormat.INT, j, 0) for j in range(4)],
                           dtype=np.int64),
            table=[],
        )

    def render(self, cols: np.ndarray, rows: int, count: int):
        """The rows as a padded value matrix + lengths, through the ONE
        rendering (`dsl.session_row_bytes`): what `dense_values()` gives
        a consumer that is not the served encoder."""
        from fluvio_tpu.smartengine.tpu.group_stage import rendered_matrix

        return rendered_matrix(
            [dsl.session_row_bytes(self.program, *row) for row in cols.T.tolist()],
            rows,
        )
