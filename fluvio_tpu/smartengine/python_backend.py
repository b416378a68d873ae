"""Python (per-record) engine backend — the semantics reference.

This is the architectural slot of the reference's wasmtime engine: each
module instance processes one `SmartModuleInput` at a time, record by
record, with the exact per-kind semantics of the generated WASM guest loops
(fluvio-smartmodule-derive/src/generator/{filter,map,filter_map,array_map,
aggregate}.rs):

- filter:      keep the record unchanged when the predicate holds
- map:         mutate value (and key, when provided) in place; preamble
               (offset/timestamp deltas) preserved
- filter_map:  None drops; otherwise as map
- array_map:   emits fresh records (zero deltas) per output element
- aggregate:   acc = f(acc, record); the output record's value is the new
               accumulator (running value emitted per input record)
- any user exception -> SmartModuleTransformRuntimeError at that record,
  stop, return successes so far (partial output)

DSL programs (modules without Python hooks) are interpreted here with the
same per-record loop via `fluvio_tpu.smartmodule.dsl.eval_expr`, which
pins the byte-level semantics the TPU backend must reproduce.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.smartmodule.sdk import SmartModuleDef
from fluvio_tpu.smartmodule.types import (
    SmartModuleInput,
    SmartModuleKind,
    SmartModuleLookbackError,
    SmartModuleOutput,
    SmartModuleRecord,
    SmartModuleTransformRuntimeError,
)
from fluvio_tpu.smartengine.config import SmartModuleConfig
from fluvio_tpu.smartengine.metrics import SmartModuleChainMetrics
from fluvio_tpu.telemetry import TELEMETRY


# the associative monoids of `dsl.AGGREGATE_COMBINES`, over Python ints
_COMBINE = {"add": lambda a, x: a + x, "max": max, "min": min}


def _normalize_map_result(result, record: Record) -> Tuple[Optional[bytes], bytes]:
    """User map result -> (key, value). Bare bytes preserves the input key."""
    if isinstance(result, tuple):
        key, value = result
        key = key if key is None else bytes(key)
        return key, bytes(value)
    return record.key, bytes(result)


class PythonInstance:
    """One module instance: config + hooks + per-instance aggregate state."""

    def __init__(self, module: SmartModuleDef, config: SmartModuleConfig):
        self.module = module
        self.config = config
        self.kind = module.transform_kind()
        self.accumulator: bytes = config.initial_data
        self._dsl_programs = {
            k: dsl.resolve_params(p, config.params) for k, p in module.dsl.items()
        }
        # windowed aggregate state
        self._window_start: Optional[int] = None
        # `dsl.WindowProgram` state: open windows {window index: {key:
        # [aggregate, count]}} and the largest event time seen (the
        # watermark is that less the program's lateness). A fused chain
        # that served slices since leaves its device bank in
        # ``window_source`` instead: loaded on the next use
        self._window_open: dict = {}
        self._window_max_ts = dsl.INT64_MIN + 1
        self.window_source = None
        # `dsl.GroupProgram` state: the table {key * WINDOW_KEY_LIMIT +
        # time bucket: [accumulator per lane]}; ``window_source`` is a
        # fused chain's device table the same way
        self._group_table: dict = {}

    # -- init / look_back ---------------------------------------------------

    def call_init(self) -> None:
        hook = self.module.hook(SmartModuleKind.INIT)
        if hook is not None:
            hook(dict(self.config.params))

    def call_look_back(self, records: List[SmartModuleRecord]) -> None:
        hook = self.module.hook(SmartModuleKind.LOOK_BACK)
        if hook is None:
            return
        for rec in records:
            try:
                hook(rec)
            except Exception as e:  # noqa: BLE001 — user code boundary
                raise SmartModuleLookbackError(str(e), rec.offset) from e

    # -- state the engine snapshots around a spill rerun ----------------------

    def state_snapshot(self) -> tuple:
        self._load_window_source()
        return (
            self.accumulator, self._window_start, self._window_max_ts,
            {w: {k: list(v) for k, v in row.items()}
             for w, row in self._window_open.items()},
            {g: list(v) for g, v in self._group_table.items()},
        )

    def state_restore(self, snapshot: tuple) -> None:
        (self.accumulator, self._window_start, self._window_max_ts,
         window_open, group_table) = snapshot
        self._window_open = {
            w: {k: list(v) for k, v in row.items()}
            for w, row in window_open.items()
        }
        self._group_table = {g: list(v) for g, v in group_table.items()}

    def _keeps_a_table(self) -> bool:
        return isinstance(self._dsl_programs.get(self.kind), dsl.GroupProgram)

    def _load_window_source(self) -> None:
        """Take over the device bank a fused chain left behind."""
        bank, self.window_source = self.window_source, None
        if bank is None:
            return
        entries, self._window_max_ts = bank.snapshot()
        if self._keeps_a_table():
            self._group_table = {g: list(accs) for g, accs, _ in entries}
            return
        self._window_open = {}
        for composite, acc, cnt in entries:
            key, w = divmod(composite, dsl.WINDOW_KEY_LIMIT)
            self._window_open.setdefault(w, {})[key] = [acc, cnt]

    def window_state(self) -> tuple:
        """(open entries [(key * WINDOW_KEY_LIMIT + window index,
        aggregate, count)] in that order, largest event time): what a
        device bank is restored from."""
        self._load_window_source()
        if self._keeps_a_table():
            return sorted(
                (g, tuple(accs), None) for g, accs in self._group_table.items()
            ), self._window_max_ts
        entries = sorted(
            (key * dsl.WINDOW_KEY_LIMIT + w, acc, cnt)
            for w, row in self._window_open.items()
            for key, (acc, cnt) in row.items()
        )
        return entries, self._window_max_ts

    # -- transform ----------------------------------------------------------

    def process(
        self, inp: SmartModuleInput, metrics: Optional[SmartModuleChainMetrics] = None
    ) -> SmartModuleOutput:
        records = inp.into_records(self.config.version)
        if inp.records is not None:
            # inputs built via from_records alias caller objects; map-family
            # transforms below rewrite record fields in place, and the
            # reference's guest-copy ABI (input.rs:83 raw_bytes) makes such
            # mutation impossible — work on copies for the same contract
            records = [dataclasses.replace(r) for r in records]
        sm_records = [
            SmartModuleRecord(r, inp.base_offset, inp.base_timestamp) for r in records
        ]
        hook = self.module.hook(self.kind)
        # one clock pair per instance per batch: interpreter cost stays
        # comparable against the fused path's phase spans. NOT gated on
        # TELEMETRY.enabled — event counters stay on when span/histogram
        # capture is off (the documented contract)
        t0 = time.perf_counter()
        if hook is not None:
            out = self._run_hook(hook, sm_records, inp)
        else:
            out = self._run_dsl(sm_records, inp)
        TELEMETRY.add_interp_instance(time.perf_counter() - t0, len(sm_records))
        if metrics is not None:
            metrics.add_fuel_used(len(sm_records))
        return out

    def _error(
        self, exc: Exception, rec: SmartModuleRecord
    ) -> SmartModuleTransformRuntimeError:
        return SmartModuleTransformRuntimeError(
            hint=str(exc),
            offset=rec.offset,
            kind=self.kind,
            record_key=rec.key,
            record_value=rec.value,
        )

    def _run_hook(
        self,
        hook: Callable,
        sm_records: List[SmartModuleRecord],
        inp: SmartModuleInput,
    ) -> SmartModuleOutput:
        out = SmartModuleOutput()
        kind = self.kind
        if kind == SmartModuleKind.FILTER:
            for rec in sm_records:
                try:
                    keep = hook(rec)
                except Exception as e:  # noqa: BLE001
                    out.error = self._error(e, rec)
                    break
                if keep:
                    out.successes.append(rec.record)
        elif kind == SmartModuleKind.MAP:
            for rec in sm_records:
                try:
                    key, value = _normalize_map_result(hook(rec), rec.record)
                except Exception as e:  # noqa: BLE001
                    out.error = self._error(e, rec)
                    break
                rec.record.key = key
                rec.record.value = value
                out.successes.append(rec.record)
        elif kind == SmartModuleKind.FILTER_MAP:
            for rec in sm_records:
                try:
                    result = hook(rec)
                except Exception as e:  # noqa: BLE001
                    out.error = self._error(e, rec)
                    break
                if result is None:
                    continue
                key, value = _normalize_map_result(result, rec.record)
                rec.record.key = key
                rec.record.value = value
                out.successes.append(rec.record)
        elif kind == SmartModuleKind.ARRAY_MAP:
            for rec in sm_records:
                try:
                    results = hook(rec)
                except Exception as e:  # noqa: BLE001
                    out.error = self._error(e, rec)
                    break
                for item in results:
                    if isinstance(item, tuple):
                        k, v = item
                        k = k if k is None else bytes(k)
                    else:
                        k, v = None, item
                    out.successes.append(Record(value=bytes(v), key=k))
        elif kind == SmartModuleKind.AGGREGATE:
            acc = self.accumulator
            for rec in sm_records:
                try:
                    acc = bytes(hook(acc, rec))
                except Exception as e:  # noqa: BLE001
                    out.error = self._error(e, rec)
                    break
                rec.record.value = acc
                out.successes.append(rec.record)
            self.accumulator = acc
        else:  # pragma: no cover
            raise TypeError(f"not a transform kind: {kind}")
        return out

    # -- DSL interpretation --------------------------------------------------

    def _run_dsl(
        self, sm_records: List[SmartModuleRecord], inp: SmartModuleInput
    ) -> SmartModuleOutput:
        program = self._dsl_programs[self.kind]
        out = SmartModuleOutput()
        ev = dsl.eval_expr
        if isinstance(program, dsl.FilterProgram):
            for rec in sm_records:
                if ev(program.predicate, rec.value, rec.key):
                    out.successes.append(rec.record)
        elif isinstance(program, dsl.MapProgram):
            for rec in sm_records:
                value = ev(program.value, rec.value, rec.key)
                if program.key is not None:
                    rec.record.key = ev(program.key, rec.value, rec.key)
                rec.record.value = value
                out.successes.append(rec.record)
        elif isinstance(program, dsl.FilterMapProgram):
            for rec in sm_records:
                if not ev(program.predicate, rec.value, rec.key):
                    continue
                value = ev(program.value, rec.value, rec.key)
                if program.key is not None:
                    rec.record.key = ev(program.key, rec.value, rec.key)
                rec.record.value = value
                out.successes.append(rec.record)
        elif isinstance(program, dsl.ArrayMapProgram):
            for rec in sm_records:
                if program.mode == "json_array":
                    elements = dsl.json_array_elements(rec.value)
                    if elements is None:
                        out.error = self._error(
                            ValueError("input record is not a JSON array"), rec
                        )
                        break
                else:  # split
                    elements = [s for s in rec.value.split(program.sep) if s]
                for el in elements:
                    out.successes.append(Record(value=el, key=rec.key))
        elif isinstance(program, dsl.AggregateProgram):
            self._run_dsl_aggregate(program, sm_records, out)
        elif isinstance(program, dsl.WindowProgram):
            self._run_dsl_window(program, sm_records, out)
        elif isinstance(program, dsl.GroupProgram):
            self._run_dsl_group(program, sm_records, out)
        else:
            raise TypeError(f"unknown DSL program {type(program).__name__}")
        return out

    def _run_dsl_aggregate(
        self,
        program: dsl.AggregateProgram,
        sm_records: List[SmartModuleRecord],
        out: SmartModuleOutput,
    ) -> None:
        kind = program.kind

        if program.contribution is not None:
            combine = program.combine
            if combine not in dsl.AGGREGATE_COMBINES:
                raise ValueError(f"unknown aggregate combine {combine!r}")
            neutral = dsl.AGGREGATE_COMBINE_NEUTRAL[combine]
            comb = _COMBINE[combine]

            def init_acc() -> int:
                return neutral

            def step(acc: int, rec: SmartModuleRecord) -> int:
                x = dsl.eval_expr(program.contribution, rec.value, rec.key)
                return comb(acc, int(x))

        else:

            def init_acc() -> int:
                if kind == "max_int":
                    return -(2**63)
                if kind == "min_int":
                    return 2**63 - 1
                return 0

            def step(acc: int, rec: SmartModuleRecord) -> int:
                if kind == "sum_int":
                    return acc + dsl.parse_int_prefix(rec.value)
                if kind == "count":
                    return acc + 1
                if kind == "word_count":
                    return acc + dsl.count_words(rec.value)
                if kind == "max_int":
                    return max(acc, dsl.parse_int_prefix(rec.value))
                if kind == "min_int":
                    return min(acc, dsl.parse_int_prefix(rec.value))
                raise ValueError(f"unknown aggregate kind {kind!r}")

        acc = dsl.parse_int_prefix(self.accumulator) if self.accumulator else init_acc()
        for rec in sm_records:
            if program.window_ms:
                ts = rec.timestamp
                window = 0 if ts < 0 else ts - (ts % program.window_ms)
                if self._window_start is None or window != self._window_start:
                    self._window_start = window
                    acc = init_acc()
                acc = step(acc, rec)
                rec.record.key = str(window).encode("ascii")
            else:
                acc = step(acc, rec)
            rec.record.value = str(acc).encode("ascii")
            out.successes.append(rec.record)
        self.accumulator = str(acc).encode("ascii")

    def _run_dsl_window(
        self,
        program: dsl.WindowProgram,
        sm_records: List[SmartModuleRecord],
        out: SmartModuleOutput,
    ) -> None:
        """`dsl.WindowProgram` record by record: fold the record into
        the windows that hold its event time, then emit every window
        the watermark has reached, in order of its end. A contribution
        to a window already emitted is late: dropped and counted."""
        if program.combine not in dsl.AGGREGATE_COMBINES:
            raise ValueError(f"unknown window combine {program.combine!r}")
        if program.emit not in dsl.WINDOW_EMITS:
            raise ValueError(f"unknown window emit {program.emit!r}")
        comb = _COMBINE[program.combine]
        neutral = dsl.AGGREGATE_COMBINE_NEUTRAL[program.combine]
        window = program.window_ms
        slide = program.slide_ms or window
        reach = window + program.lateness_ms  # start -> emitted
        self._load_window_source()
        open_ = self._window_open
        ev = dsl.eval_expr
        closed = late = invalid = 0
        for rec in sm_records:
            key = int(ev(program.key, rec.value, rec.key))
            t = int(ev(program.event_time, rec.value, rec.key))
            x = int(ev(program.contribution, rec.value, rec.key))
            if not 0 <= key < dsl.WINDOW_KEY_LIMIT:
                invalid += 1
                continue
            for w in range(t // slide, t // slide - window // slide, -1):
                if w < 0:
                    break
                if w * slide + reach <= self._window_max_ts:
                    late += 1
                    continue
                cell = open_.setdefault(w, {}).setdefault(key, [neutral, 0])
                cell[0] = comb(cell[0], x)
                cell[1] += 1
            if t <= self._window_max_ts:
                continue
            self._window_max_ts = t
            for w in sorted(w for w in open_ if w * slide + reach <= t):
                row = open_.pop(w)
                closed += len(row)
                top = max(acc for acc, _ in row.values())
                for k in sorted(row):
                    if program.emit == "all" or row[k][0] == top:
                        out.successes.append(Record(value=dsl.window_row_bytes(
                            program, w * slide + window, k, row[k][0]
                        )))
        TELEMETRY.add_window_slice(closed, late, invalid)

    def _run_dsl_group(
        self,
        program: dsl.GroupProgram,
        sm_records: List[SmartModuleRecord],
        out: SmartModuleOutput,
    ) -> None:
        """`dsl.GroupProgram` record by record: fold the record into its
        (key, time bucket) group; the group's row after the fold takes
        the record's place. A record without a key is dropped and
        counted invalid."""
        lanes = dsl.group_accumulators(program)
        for c in lanes:
            if c.combine not in dsl.AGGREGATE_COMBINES:
                raise ValueError(f"unknown group combine {c.combine!r}")
        self._load_window_source()
        table = self._group_table
        ev = dsl.eval_expr
        invalid = 0
        for rec in sm_records:
            group = dsl.group_key(program, rec.value, rec.key)
            if group is None:
                invalid += 1
                continue
            gid = group[0] * dsl.WINDOW_KEY_LIMIT + group[1]
            accs = table.get(gid)
            if accs is None:
                accs = table[gid] = [
                    dsl.AGGREGATE_COMBINE_NEUTRAL[c.combine] for c in lanes
                ]
            for i, c in enumerate(lanes):
                if c.where is None or ev(c.where, rec.value, rec.key):
                    x = int(ev(c.contribution, rec.value, rec.key))
                    accs[i] = _COMBINE[c.combine](accs[i], x)
            rec.record.value = dsl.group_row_bytes(program, *group, accs)
            out.successes.append(rec.record)
        TELEMETRY.add_group_slice(
            len(sm_records) - invalid, len(table), invalid
        )
