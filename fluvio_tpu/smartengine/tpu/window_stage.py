"""The window stage of a fused chain, and what a chain that ends in one
does differently from the others.

A `dsl.WindowProgram` (keyed sliding event-time windows; NEXmark Q5 is
the model case) lowers to `WindowStage`, the LAST stage of its chain.
Key, event time and contribution come from the chain's own expression
lowering (`lower.lower_expr`); the device work is
`windows/kernels.py:update_top`, inside the chain's one jitted program
for a slice. There is no second windowed implementation here.

The rest of this module is `WindowChainMixin`, the methods
`executor.TpuChainExecutor` inherits for such a chain, and for one that
ends in a keyed table or in sessions (`group_stage`, `session_stage`):

- a stream's carry is a `WindowStateBank` (`StreamState.window_bank`):
  empty at the stream's first dispatch, on the device across the
  stream's slices, never shared between streams,
- the bank is COMMITTED in `_fetch_window`, after the slice's header
  read clean, not at dispatch: a slice that is retried, re-run under a
  larger shape, discarded or rolled back reads the bank it started from,
- bank and emit capacities grow by doubling when the header reports an
  overflow (`WindowOverflow` -> `_grow_window`, the slice re-run against
  the untouched bank); the learned sizes stay with the compiled chain's
  stage, so a later stream starts at them, as the fan-out chain's
  learned output capacity does.

It lives beside `executor.py` because a Pallas kernel's serialised
body holds the line numbers of its Python call sites inside the
compile-cache key (PERF.md section 6, PRs 33 and 37): `executor.py`
keeps its lines down to the jit calls of `_dispatch_inner`, and this
file down to `WindowStage.apply`, or such a chain pays a cold set-up.
"""

from __future__ import annotations

import functools
import weakref
from dataclasses import dataclass
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fluvio_tpu.smartengine.tpu.lower import (
    Unlowerable,
    infer_type,
    lower_expr,
)
from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.spans import stage_scope, timed

# a served stream's window bank and emit columns start here (or at a
# quarter of its first slice's rows, `_window_emit_cap`) and double when
# a slice's header reports an overflow (`_grow_window`); past the
# ceiling `WindowCapacityError` (2**24 entries are 400 MB of bank)
WINDOW_CAPACITY_START = 1024
WINDOW_CAPACITY_MAX = 1 << 24

# stage kinds a window stage cannot follow: fan-out rows carry no record
# of their own to take an event time from, and a second stateful stage
# has no carry slot
_NO_WINDOW_AFTER = ("array_map", "aggregate", "window")


class WindowOverflow(Exception):
    """A window slice's header reports more open entries than the
    stream's bank holds, or more closed rows than the emit columns."""

    def __init__(self, n_open: int, n_closed: int):
        super().__init__(
            f"window slice needs {n_open} bank entries, {n_closed} emit rows"
        )
        self.n_open = n_open
        self.n_closed = n_closed


@dataclass
class WindowStage:
    """Keyed sliding event-time windows (`dsl.WindowProgram`): the last
    stage of its chain. Its carry is the stream's window bank (ids,
    accs, counts, watermark), its output the closed windows' rows, at
    most ``ctx["fanout_cap"]`` of them (the emit capacity).
    ``capacity`` / ``emit`` are the sizes the chain's streams have
    learned (shared by every stream of the compiled chain)."""

    program: dsl.WindowProgram
    key_fn: Callable
    time_fn: Callable
    contribution_fn: Callable
    capacity: int = WINDOW_CAPACITY_START
    emit: int = WINDOW_CAPACITY_START

    kind = "window"
    preserves_rows = False
    rewrites_offsets = True

    @classmethod
    def lower(cls, prog, stages: List) -> "WindowStage":
        """`try_build`'s last branch: the stage of a `WindowProgram`
        that may follow ``stages``; any other program is one the fused
        path does not lower."""
        if not isinstance(prog, dsl.WindowProgram):
            raise Unlowerable(f"no fused stage for {type(prog).__name__}")
        slide = prog.slide_ms or prog.window_ms
        if prog.window_ms <= 0 or slide <= 0 or prog.window_ms % slide:
            raise Unlowerable("window slide must divide a positive window")
        if prog.lateness_ms < 0 or prog.emit not in dsl.WINDOW_EMITS:
            raise Unlowerable("window lateness is >= 0, emit top or all")
        if prog.combine not in dsl.AGGREGATE_COMBINES:
            raise Unlowerable(f"window combine {prog.combine}")
        if any(s.kind in _NO_WINDOW_AFTER for s in stages):
            raise Unlowerable("window after array_map or an aggregate")
        fns = []
        for expr in (prog.key, prog.event_time, prog.contribution):
            if expr is None or infer_type(expr) != "int":
                raise Unlowerable("window key, time, contribution are ints")
            fns.append(lower_expr(expr))
        return cls(prog, *fns)

    def apply(self, state: Dict, carries, base_ts, ctx):
        from fluvio_tpu.windows import kernels as window_kernels

        p = self.program
        i = ctx.get("stage_index", 0)
        header, bank, rows = window_kernels.update_top(
            p.window_ms,
            p.slide_ms or p.window_ms,
            p.lateness_ms,
            p.combine,
            ctx["fanout_cap"],
            p.emit == "all",
            carries,
            self.contribution_fn(state).astype(jnp.int64),
            self.key_fn(state).astype(jnp.int64),
            self.time_fn(state).astype(jnp.int64),
            state["valid"],
            merge_scope=stage_scope(i, "window_merge"),
            top_scope=stage_scope(i, "window_top"),
        )
        return {"window_header": header, "window_rows": rows}, bank

    # -- what `WindowChainMixin` asks of a banked stage -----------------------

    def bank_spec(self):
        from fluvio_tpu.windows.spec import WindowSpec

        p = self.program
        return WindowSpec(
            window_ms=p.window_ms, slide_ms=p.slide_ms, op=p.combine,
            keyed=True, lateness_ms=p.lateness_ms,
            capacity=self.capacity, emit_capacity=self.emit,
        )

    def counts(self, hdr):
        """(bank entries the slice needs, emit rows it needs, the
        watermark to commit) of a synced header."""
        return int(hdr[1]), int(hdr[2]), int(hdr[4])

    def emit_rows(self, packed) -> int:
        """Answer rows the slice's program had room for."""
        return packed["window_rows"].shape[0]

    def raise_floor(self, rows: int) -> None:
        """Neither capacity starts under a quarter of the slice's padded
        rows: a large slice's first program is then not one whose only
        use is to report that 1,024 entries were too few."""
        self.capacity = max(self.capacity, rows // 4)
        self.emit = max(self.emit, rows // 4)

    note_growth = staticmethod(TELEMETRY.add_window_grow)

    def fetch(self, ex, buf, hdr, packed, span):
        """A window slice's D2H after the header sync: ONE bucketed
        download of the answer rows (none when nothing closed).
        Returns the thunk that renders them."""
        n_rows, _n_open, n_closed, n_late, _wm, n_invalid = (
            int(x) for x in hdr
        )
        rows_dev = packed["window_rows"]
        rows = np.zeros((0, 3), dtype=np.int64)
        if n_rows:
            bucket = min(ex._pad_slice(n_rows), rows_dev.shape[0])
            rows = ex._download(
                [lax.slice(rows_dev, (0, 0), (bucket, 3))], span
            )[0][:n_rows]
        TELEMETRY.add_link_variant("win-top")
        TELEMETRY.add_window_slice(n_closed, n_late, n_invalid)
        return functools.partial(self.render_rows, buf, rows)

    def render_rows(self, buf, rows: np.ndarray):
        """Answer rows (window end, key, aggregate) as fresh output
        records at the slice's base offset (delta 0, like a fan-out's:
        never before the slice's first input, never past the record
        whose arrival closed the window). A few rows a slice: the
        general record form does."""
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

        return RecordBuffer.from_records(
            [Record(value=dsl.window_row_bytes(self.program, *r))
             for r in rows.tolist()],
            base_offset=buf.base_offset,
            base_timestamp=buf.base_timestamp,
        )


# the kinds of a BANKED stage: the last of its chain, its carry a
# stream's `WindowStateBank`, served through `WindowChainMixin`
LAST_KINDS = ("window", "group", "session")


def lower_banked(prog, stages: List):
    """`try_build`'s last branch: the banked stage of a `WindowProgram`,
    a `GroupProgram` or a `SessionProgram` that may follow ``stages``;
    any other program is one the fused path does not lower."""
    if isinstance(prog, dsl.GroupProgram):
        from fluvio_tpu.smartengine.tpu.group_stage import GroupStage

        return GroupStage.lower(prog, stages)
    if isinstance(prog, dsl.SessionProgram):
        from fluvio_tpu.smartengine.tpu.session_stage import SessionStage

        return SessionStage.lower(prog, stages)
    return WindowStage.lower(prog, stages)


def chain_outputs(state: Dict, bank):
    """A banked chain's (header, packed, carries): the stage's own
    header and outputs ARE the result; the new bank rides in ``packed``
    because the fetch commits it, not the dispatch."""
    packed = {k: v for k, v in state.items()
              if k.startswith(("window_rows", "group_", "session_"))}
    packed["window_bank"] = bank
    return state["window_header"], packed, ()


class WindowChainMixin:
    """What `TpuChainExecutor` does for a chain that ends in a
    `WindowStage` (module docstring). ``self`` is the executor, or one
    stream of it: ``self.state`` is the stream's own, ``self.stages``
    the compiled chain's."""

    @property
    def _window(self):
        """The chain's banked stage (`WindowStage`, `GroupStage`,
        `SessionStage`), or None."""
        stages = self.stages
        if stages and stages[-1].kind in LAST_KINDS:
            return stages[-1]
        return None

    @property
    def _window_bank(self):
        return self.state.window_bank

    @_window_bank.setter
    def _window_bank(self, bank) -> None:
        self.state.window_bank = bank

    @property
    def stateful(self) -> bool:
        """Does a stream of this chain carry state from slice to slice
        (aggregate carries, a window bank)? Such a chain is served one
        chunk a slice, never cut by ``max_bytes``, and every consumer
        stream gets `open_stream` of it."""
        return bool(self.agg_configs) or self._window is not None

    def _bank_sig(self, args) -> str:
        """A window program's compile-event signature names its bank's
        capacity too (the emit capacity is its ``fanout_cap``);
        ``args`` are the ragged jit's positionals, the carries ninth."""
        if self._window is None or len(args) < 9:
            return ""
        return f" bank={args[8][0].shape[0]}"

    def _stream_bank(self):
        """This stream's window bank at the chain's learned capacity:
        made empty at the stream's first dispatch, padded when another
        stream of the chain (or this one's last slice) has learned a
        larger capacity, so every stream runs the one compiled shape."""
        stage = self._window
        bank = self._window_bank
        if bank is None:
            from fluvio_tpu.telemetry import memory as memory_mod
            from fluvio_tpu.windows.state import WindowStateBank

            bank = self._window_bank = WindowStateBank(stage.bank_spec())
            # a stream's bank dies with the stream: its ledger entry too
            weakref.finalize(bank, memory_mod.release_window_bank, id(bank))
        bank.grow(stage.capacity)
        return bank

    def _window_emit_cap(self, buf) -> int:
        """The slice program's emit capacity (its ``fanout_cap``), after
        the stage raised its capacities to the floor this slice's rows
        set (`raise_floor`)."""
        stage = self._window
        stage.raise_floor(buf.rows)
        return stage.emit

    def _grow_window(self, o: WindowOverflow) -> None:
        """Double the bank and emit capacities until they hold what the
        slice's header counted (both counts are exact whatever the
        shape was, so ONE re-run fits)."""
        from fluvio_tpu.windows.spec import WindowCapacityError

        stage = self._window
        capacity = self._pad_slice(o.n_open, stage.capacity)
        emit = self._pad_slice(o.n_closed, stage.emit)
        if max(capacity, emit) > WINDOW_CAPACITY_MAX:
            TELEMETRY.add_decline("window-capacity")
            raise WindowCapacityError(
                f"{o.n_open} open entries / {o.n_closed} closed rows in one "
                f"slice exceed the ceiling of {WINDOW_CAPACITY_MAX}"
            )
        stage.note_growth(
            f"bank {stage.capacity}->{capacity} emit {stage.emit}->{emit}"
        )
        stage.capacity, stage.emit = capacity, emit

    def _refetch_grown(self, buf, o: WindowOverflow, spec, defer):
        """A slice whose header reported an overflow: the bank was not
        committed, so grow, and re-run the slice against the bank it
        started from under the larger shape."""
        self._grow_window(o)
        span = (spec or {}).get("span")
        header, packed = self._dispatch(
            buf, fanout_cap=self._fanout_cap(buf), span=span
        )
        if span is not None:
            span.mark_dispatched()
        return self._fetch_inner(
            buf, header, packed, {"span": span} if span else None, defer
        )

    def _fetch_window(self, buf, header, packed, span, defer):
        """A banked slice's D2H: the header sync, then what the stage
        downloads of its answer (`WindowStage.fetch`: the closed
        windows' rows; `GroupStage.fetch`: a row per record;
        `SessionStage.fetch`: the closed sessions' rows). The
        stream's bank is committed here, after the header read clean and
        the download ended: an overflow, a fault or a discard before
        this point leaves the bank the slice started from."""
        with timed(span, "wait"):
            hdr = jax.device_get(header)
        if span is not None:
            span.mark_device_ready()
        stage = self._window
        n_open, n_closed, watermark = stage.counts(hdr)
        bank = packed["window_bank"]
        if n_open > bank[0].shape[0] or n_closed > stage.emit_rows(packed):
            raise WindowOverflow(n_open, n_closed)
        thunk = stage.fetch(self, buf, hdr, packed, span)
        self._window_bank.commit(*bank, n_open, watermark)
        for inst in self._instances[-1:]:
            inst.window_source = self._window_bank  # stale until it loads
        return thunk if defer else thunk()

    def _restore_window(self, instance) -> None:
        """`sync_state_from`: the interpreter's window state becomes
        the stream's bank."""
        stage = self._window
        entries, watermark = instance.window_state()
        stage.capacity = self._pad_slice(len(entries), stage.capacity)
        self._stream_bank().restore(entries, watermark)
