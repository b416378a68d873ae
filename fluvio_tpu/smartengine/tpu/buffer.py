"""RecordBuffer — the HBM-resident batched-record layout.

The TPU-native replacement for the reference's per-record WASM ABI round
trip (fluvio-smartengine .../instance.rs:164-191): instead of
encode -> guest alloc -> memcpy -> call -> decode per module per batch,
records are staged once into padded columnar arrays and every transform in
the chain operates on those arrays in place on device.

Shape discipline: widths and row counts are bucketed to powers of two so
XLA compiles one program per bucket, not per batch.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartmodule.types import SmartModuleInput
from fluvio_tpu.types import NO_TIMESTAMP

MIN_ROWS = 8
MIN_WIDTH = 32
# widest record the NARROW (one row per record) device layout stages;
# wider records stage as striped segments (smartengine/tpu/stripes.py)
# up to the hard staging ceiling below
MAX_WIDTH = 1 << 16
MAX_RECORD_WIDTH = 1 << 20
# int32 addressing ceiling for one staged batch: every flat byte
# offset downstream of here is i32 — host `starts`, the device cumsum
# of aligned lengths (`ragged_repad_words`, `striped_repad_words`) and
# the block and shift arithmetic on it (`kernels.rows_from_word_starts`),
# and the packed-payload destination indices. A batch past this must
# be refused loudly (shard it / smaller slices), never wrapped; the
# valueflow analyzer's FLV302/FLV303 noqas at those sites cite THIS
# guard as the reason the device arithmetic cannot overflow.
FLAT_ADDRESS_MAX = 2**31 - 1


class FlatAddressingError(ValueError):
    """The batch's byte extent exceeds int32 addressing — split the
    batch before staging (the typed decline, same contract as the
    MAX_RECORD_WIDTH raise: loud at the seam, impossible on-chip)."""


def check_flat_addressing(lengths, count: Optional[int] = None) -> int:
    """Total 4-aligned flat bytes of the live rows; raises
    :class:`FlatAddressingError` past ``FLAT_ADDRESS_MAX``. Computed on
    an int64 host mirror, so the check itself cannot overflow."""
    lengths64 = np.asarray(lengths, dtype=np.int64)
    if count is not None:
        lengths64 = lengths64[:count]
    total = int(((lengths64 + 3) & ~3).sum())
    if total > FLAT_ADDRESS_MAX:
        raise FlatAddressingError(
            f"4-aligned flat of {total} bytes exceeds int32 addressing "
            f"({FLAT_ADDRESS_MAX}); split the batch before staging"
        )
    return total


def _check_matrix_addressing(rows: int, width: int) -> None:
    """``rows x width`` is the ceiling of every per-batch flat/payload
    extent (lengths are <= the bucketed width): bounding the dense
    matrix under int32 bounds them all. O(1), checked BEFORE any
    allocation."""
    if rows * width > FLAT_ADDRESS_MAX:
        raise FlatAddressingError(
            f"staged matrix {rows} x {width} = {rows * width} bytes "
            f"exceeds int32 addressing ({FLAT_ADDRESS_MAX}); split the "
            "batch before staging"
        )


def apply_postops_host(values: np.ndarray, postops) -> np.ndarray:
    """Host mirror of `lower.apply_postops`: static byte-wise case folds
    applied after view-mode materialization (case folds flip bit 5 of
    ASCII letters; padding zeros are outside both letter ranges)."""
    for op in postops:
        lo, hi = (0x61, 0x7A) if op == "upper" else (0x41, 0x5A)
        fold = (values >= lo) & (values <= hi)
        values = np.where(fold, values ^ 0x20, values).astype(np.uint8)
    return values


def ragged_range_select(
    flat: np.ndarray, starts: np.ndarray, lengths: np.ndarray
) -> np.ndarray:
    """Extract ascending, DISJOINT byte ranges [starts[i], +lengths[i])
    from ``flat`` with one diff-mark + cumsum boolean select — a few
    sequential passes, no large fancy-index temporaries (the fat-record
    split-back hot path). Callers own the precondition: ranges must be
    ascending and non-overlapping (the running sum then stays in
    {0, 1}, which is what makes the int8 cumsum safe) and end within
    ``flat``."""
    marks = np.zeros(len(flat) + 1, dtype=np.int8)
    np.add.at(marks, starts, 1)
    np.add.at(marks, starts + lengths, -1)
    keep = np.cumsum(marks[:-1], dtype=np.int8).view(np.bool_)
    return flat[keep]


# the postops each `view_fold` byte stands for; the native record writer
# applies the byte itself (`baseline_engine.cpp:FlatValues`)
_FOLD_OPS = ((), ("upper",), ("lower",))


def view_fold(postops) -> int:
    """The one fold (0 none, 1 upper, 2 lower) a sequence of case folds
    comes to: each maps every letter to one case and passes every other
    byte, so the last one decides (`upper` then `lower` is `lower`)."""
    if not postops:
        return 0
    return 1 if postops[-1] == "upper" else 2


def render_view_flat(
    in_flat: np.ndarray, base: np.ndarray, ln: np.ndarray, rows: int,
    fold: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """A view-backed buffer's values as a flat of its own: the 4-aligned
    ragged form with int32 starts over ``rows`` (padding rows start at
    the end), built from the live rows' spans ``[base[i], +ln[i])`` of
    the input's flat, then case-folded. O(sum of lengths), no padded
    matrix.

    Fast path: when the source ranges, each padded to 4 bytes, are
    ascending and disjoint (whole-record survivors, JsonGet spans that
    end 3 bytes or more before the next one starts), ONE boolean
    range-select (diff-mark + cumsum over the input flat) extracts the
    payload — a few sequential passes instead of the fancy-index
    gather's int64 temporaries. Otherwise the exact gather: array
    elements are one comma apart, so an exploded element padded to 4
    bytes overlaps the next one and explode outputs always take it."""
    count = len(ln)
    ln64 = ln.astype(np.int64)
    l4 = (ln64 + 3) & ~3
    starts64 = np.cumsum(l4) - l4
    total = int(l4.sum()) if count else 0
    flat_out = np.zeros((total,), dtype=np.uint8)
    if count and total:
        if len(in_flat):
            fast = (
                base[0] >= 0
                and base[-1] + l4[-1] <= len(in_flat)
                and bool((base[1:] >= base[:-1] + l4[:-1]).all())
            )
            if fast:
                flat_out = ragged_range_select(in_flat, base, l4)
                # zero the alignment-pad tail bytes (<= 3/record)
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
        flat_out = apply_postops_host(flat_out, _FOLD_OPS[fold])
    starts = np.zeros((rows,), dtype=np.int32)
    starts[:count] = starts64
    starts[count:] = total
    return flat_out, starts


def _next_pow2(n: int, floor: int) -> int:
    v = floor
    while v < n:
        v <<= 1
    return v


def bucket_width(max_v: int) -> int:
    """Value-matrix width bucket.

    Pure pow2 up to 128; above that, pow2/8-granular steps (multiples of
    32, so sublane tiling stays aligned). Every per-byte kernel is a
    sequential `lax.scan` over width columns, so padding IS compute: a
    300-byte corpus runs 320 scan steps instead of 512 (-37%), which the
    wide-record bench config measures directly (review round 4 weak #3).
    Bounded shapes: <=8 buckets per size decade, persisted by the XLA
    compile cache like every other shape bucket."""
    v = _next_pow2(max(max_v, 1), MIN_WIDTH)
    if v <= 128:
        return v
    step = max(32, v >> 3)
    return ((max_v + step - 1) // step) * step


@dataclass
class RowFormat:
    """How the native record writer renders one value per row from
    int64 columns (`baseline_engine.cpp:RowValues`): ``pieces`` are the
    literal bytes around the slots (one more than slots), ``slots`` is
    int64[k, 3] of (kind, a, b) over the columns of a ``[columns,
    rows]`` matrix: the decimal of column ``a`` (`INT`), of ``a >> b``
    (`SHIFTED`), of ``a // b``-th columns' floor quotient, 0 where the
    divisor is 0 (`DIV`), or text ``(a & (2**b - 1)) - table_base`` of
    ``table`` (`TABLE`)."""

    INT, SHIFTED, DIV, TABLE = range(4)

    pieces: list
    slots: np.ndarray
    table: list
    table_base: int = 0


@dataclass
class RecordBuffer:
    """Padded columnar record batch (numpy on host; device puts are cheap).

    - ``values``: uint8 [N, L]; row i holds record i's value bytes, zero-pad
    - ``lengths``: int32 [N]
    - ``keys``: uint8 [N, LK]; ``key_lengths`` int32 [N], -1 = null key
    - ``offset_deltas``: int32 [N]; ``timestamp_deltas``: int64 [N]
    - ``count``: live rows (rows >= count are padding)
    """

    values: Optional[np.ndarray]
    lengths: np.ndarray
    keys: np.ndarray
    key_lengths: np.ndarray
    offset_deltas: np.ndarray
    timestamp_deltas: np.ndarray
    count: int
    base_offset: int = 0
    base_timestamp: int = NO_TIMESTAMP
    # fan-out (array_map) outputs are "fresh" relative to their source
    # record's batch: these host-side columns hold the per-record batch
    # rebase deltas the broker's coalescer computed (None = zeros, the
    # single-input engine surface)
    fresh_offset_deltas: Optional[np.ndarray] = None
    fresh_timestamp_deltas: Optional[np.ndarray] = None
    # cached ragged (flat) form of `values` for transfer-thin H2D staging.
    # A FLAT-BACKED buffer (`values is None`, `from_flat`) holds ONLY this
    # form — the upload path never builds the padded matrix at all, and
    # `_width`/`_rows` carry the bucketed shape the matrix would have.
    _flat: Optional[np.ndarray] = None
    _starts: Optional[np.ndarray] = None
    _width: int = 0
    _rows: int = 0
    # An INT-BACKED buffer (`values is None`, `_flat is None`) holds the
    # live rows' values as ONE int64 column: an int-output fetch keeps
    # what crossed the link and renders nothing. The served encode
    # writes the decimals straight into the wire records
    # (`encode_into`); every other consumer gets the padded matrix AND
    # `lengths` (None until then) from `dense_values()`, rendered
    # through ``_render(ints, rows, count) -> (values, lengths)``.
    # ``_ints`` may also be a ``[columns, rows]`` matrix with the
    # ``_row_format`` that says how one value is rendered from a row's
    # ints (a keyed table's answer rows); without a format (a slice the
    # encoder's table of texts cannot cover) `encode_into` renders first.
    _ints: Optional[np.ndarray] = None
    _render: Optional[Callable] = None
    _row_format: Optional[RowFormat] = None
    # A VIEW-BACKED buffer (`values is None`, `_flat is None`) holds its
    # live rows' values as spans of its INPUT's 4-aligned flat: (that
    # flat, int64 first byte a live row, `view_fold` byte), with
    # `lengths`, `_width` and `_rows` set. A view-mode fetch copies no
    # byte: the served encode writes the spans straight into the wire
    # records (`encode_into`); every other consumer gets the flat of its
    # own, rendered once by `render_view_flat` (`_unview`).
    _view: Optional[Tuple[np.ndarray, np.ndarray, int]] = None

    def _unview(self) -> None:
        """A view-backed buffer becomes flat-backed (else nothing)."""
        if self._view is not None:
            in_flat, base, fold = self._view
            self._flat, self._starts = render_view_flat(
                in_flat, base, self.lengths[: self.count], self._rows, fold
            )
            self._view = None

    @property
    def width(self) -> int:
        """Bucketed value-matrix width (valid in every backing mode)."""
        if self.values is None and self._flat is None and self._view is None:
            return self.dense_values().shape[1]
        return self.values.shape[1] if self.values is not None else self._width

    @property
    def rows(self) -> int:
        return self.values.shape[0] if self.values is not None else self._rows

    def dense_values(self) -> np.ndarray:
        """The padded matrix; materialized on demand for flat-backed
        and int-backed buffers (slow-path consumers only — the TPU hot
        path never calls this)."""
        self._unview()
        if self.values is None and self._flat is None:
            self.values, self.lengths = self._render(
                self._ints, self._rows, self.count
            )
        if self.values is None:
            rows, width = self._rows, self._width
            values = np.zeros((rows, width), dtype=np.uint8)
            flat, starts = self._flat, self._starts
            if len(flat):  # all-empty values (tombstones): zeros already
                mask = (
                    np.arange(width, dtype=np.int32)[None, :]
                    < self.lengths[:, None]
                )
                idx = (
                    starts.astype(np.int64)[:, None]
                    + np.arange(width, dtype=np.int64)[None, :]
                )
                values[mask] = flat[np.clip(idx, 0, len(flat) - 1)][mask]
            self.values = values
        return self.values

    def ragged_values(self) -> Tuple[np.ndarray, np.ndarray]:
        """(flat, starts): concatenated live bytes + per-row start index.

        The host link is the consume path's bottleneck; shipping the flat
        form (sum of lengths) instead of the padded matrix (rows x width)
        cuts H2D bytes by the padding ratio. Each record's span is padded
        to a 4-byte boundary (~6% overhead on short records) so the
        device re-pad can gather whole i32 words — a 4x cheaper gather
        than per-byte on TPU. The device derives the starts from a cumsum
        of the aligned lengths; they are returned here for host-side
        consumers. Cached: stream benches reuse the same buffer, and
        flat-backed buffers are BORN in this form (the native decoder
        emits the 4-aligned flat directly).
        """
        self._unview()
        if self._flat is None:
            values = self.values
            if values is None:  # int-backed: render first
                values = self.dense_values()
            width = values.shape[1]
            check_flat_addressing(self.lengths)
            lengths4 = (self.lengths.astype(np.int64) + 3) & ~3
            # rows' padding bytes are already zero in `values`
            mask = np.arange(width, dtype=np.int64)[None, :] < lengths4[:, None]
            self._flat = np.ascontiguousarray(values[mask])
            starts = np.zeros(len(self.lengths), dtype=np.int64)
            starts[1:] = np.cumsum(lengths4[:-1])
            # check_flat_addressing above: every start fits i32
            self._starts = starts.astype(np.int32)  # noqa: FLV302
        return self._flat, self._starts

    def has_keys(self) -> bool:
        return bool((self.key_lengths[: self.count] >= 0).any())

    # -- construction -------------------------------------------------------

    @classmethod
    def from_records(
        cls,
        records: List[Record],
        base_offset: int = 0,
        base_timestamp: int = NO_TIMESTAMP,
    ) -> "RecordBuffer":
        n = len(records)
        rows = _next_pow2(max(n, 1), MIN_ROWS)
        max_v = max((len(r.value) for r in records), default=0)
        max_k = max((len(r.key) for r in records if r.key is not None), default=0)
        width = bucket_width(max_v)
        kwidth = _next_pow2(max_k, MIN_WIDTH) if max_k else MIN_WIDTH
        if width > MAX_RECORD_WIDTH:
            raise ValueError(
                f"record value of {max_v} bytes exceeds {MAX_RECORD_WIDTH}"
            )
        _check_matrix_addressing(rows, width)

        values = np.zeros((rows, width), dtype=np.uint8)
        lengths = np.zeros(rows, dtype=np.int32)
        keys = np.zeros((rows, kwidth), dtype=np.uint8)
        key_lengths = np.full(rows, -1, dtype=np.int32)
        offset_deltas = np.zeros(rows, dtype=np.int32)
        timestamp_deltas = np.zeros(rows, dtype=np.int64)
        for i, rec in enumerate(records):
            v = rec.value
            values[i, : len(v)] = np.frombuffer(v, dtype=np.uint8)
            lengths[i] = len(v)
            if rec.key is not None:
                k = rec.key
                keys[i, : len(k)] = np.frombuffer(k, dtype=np.uint8)
                key_lengths[i] = len(k)
            offset_deltas[i] = rec.offset_delta
            timestamp_deltas[i] = rec.timestamp_delta
        return cls(
            values=values,
            lengths=lengths,
            keys=keys,
            key_lengths=key_lengths,
            offset_deltas=offset_deltas,
            timestamp_deltas=timestamp_deltas,
            count=n,
            base_offset=base_offset,
            base_timestamp=base_timestamp,
        )

    @classmethod
    def from_smartmodule_input(cls, inp: SmartModuleInput) -> "RecordBuffer":
        return cls.from_records(
            inp.into_records(),
            base_offset=inp.base_offset,
            base_timestamp=inp.base_timestamp,
        )

    @classmethod
    def from_arrays(
        cls,
        values: np.ndarray,
        lengths: np.ndarray,
        count: Optional[int] = None,
        keys: Optional[np.ndarray] = None,
        key_lengths: Optional[np.ndarray] = None,
        offset_deltas: Optional[np.ndarray] = None,
        timestamp_deltas: Optional[np.ndarray] = None,
        base_offset: int = 0,
        base_timestamp: int = NO_TIMESTAMP,
    ) -> "RecordBuffer":
        """Adopt pre-staged arrays (bench/broker fast path). Rows must
        already be bucketed; ``count`` defaults to all rows."""
        rows = values.shape[0]
        _check_matrix_addressing(rows, values.shape[1])
        n = rows if count is None else count
        if keys is None:
            keys = np.zeros((rows, MIN_WIDTH), dtype=np.uint8)
            key_lengths = np.full(rows, -1, dtype=np.int32)
        if offset_deltas is None:
            offset_deltas = np.arange(rows, dtype=np.int32)
        if timestamp_deltas is None:
            timestamp_deltas = np.zeros(rows, dtype=np.int64)
        return cls(
            values=values,
            lengths=lengths.astype(np.int32),
            keys=keys,
            key_lengths=key_lengths.astype(np.int32),
            offset_deltas=offset_deltas,
            timestamp_deltas=timestamp_deltas,
            count=n,
            base_offset=base_offset,
            base_timestamp=base_timestamp,
        )

    @classmethod
    def _stage_meta_columns(cls, cols: dict, rows: int, n: int):
        """Shared key/offset/timestamp staging for the two native-decode
        constructors (one implementation: a key-handling fix cannot land
        in one and miss the other)."""
        key_present = cols["key_present"].astype(bool)
        key_lengths = np.full(rows, -1, dtype=np.int32)
        if n and key_present.any():
            key_off = cols["key_off"]
            klive = (key_off[1:] - key_off[:-1]).astype(np.int32)
            key_lengths[:n] = np.where(key_present, klive, -1)
            kwidth = _next_pow2(max(int(klive.max()), 1), MIN_WIDTH)
            keys = np.zeros((rows, kwidth), dtype=np.uint8)
            kmask = (
                np.arange(kwidth, dtype=np.int32)[None, :]
                < np.maximum(key_lengths, 0)[:, None]
            )
            keys[kmask] = cols["key_flat"]
        else:
            keys = np.zeros((rows, MIN_WIDTH), dtype=np.uint8)
        offset_deltas = np.zeros(rows, dtype=np.int32)
        offset_deltas[:n] = cols["off_delta"].astype(np.int32)
        timestamp_deltas = np.zeros(rows, dtype=np.int64)
        timestamp_deltas[:n] = cols["ts_delta"]
        return keys, key_lengths, offset_deltas, timestamp_deltas

    @classmethod
    def from_columns(
        cls,
        cols: dict,
        base_offset: int = 0,
        base_timestamp: int = NO_TIMESTAMP,
    ) -> "RecordBuffer":
        """Adopt native-decoded columnar arrays (broker fast path).

        ``cols`` is the dict produced by
        `native_backend.decode_record_columns`: flat byte runs + offsets,
        re-padded here with one vectorized mask assignment — no
        per-record Python objects anywhere on the path.
        """
        n = cols["count"]
        rows = _next_pow2(max(n, 1), MIN_ROWS)
        val_off = cols["val_off"]
        lengths_live = (val_off[1:] - val_off[:-1]).astype(np.int32)
        max_v = int(lengths_live.max()) if n else 0
        width = bucket_width(max_v)
        _check_matrix_addressing(rows, width)
        if width > MAX_RECORD_WIDTH:
            raise ValueError(
                f"record value of {max_v} bytes exceeds {MAX_RECORD_WIDTH}"
            )
        lengths = np.zeros(rows, dtype=np.int32)
        lengths[:n] = lengths_live
        values = np.zeros((rows, width), dtype=np.uint8)
        mask = np.arange(width, dtype=np.int32)[None, :] < lengths[:, None]
        values[mask] = cols["val_flat"]

        keys, key_lengths, offset_deltas, timestamp_deltas = (
            cls._stage_meta_columns(cols, rows, n)
        )
        return cls(
            values=values,
            lengths=lengths,
            keys=keys,
            key_lengths=key_lengths,
            offset_deltas=offset_deltas,
            timestamp_deltas=timestamp_deltas,
            count=n,
            base_offset=base_offset,
            base_timestamp=base_timestamp,
        )

    @classmethod
    def from_flat(
        cls,
        cols: dict,
        base_offset: int = 0,
        base_timestamp: int = NO_TIMESTAMP,
    ) -> "RecordBuffer":
        """Adopt the aligned-decode columns (broker fast path, zero-copy
        staging).

        ``cols`` is the dict from
        `native_backend.decode_record_columns_aligned`: the value flat is
        already in the engine's 4-aligned ragged upload form, so this
        buffer is flat-backed — the padded matrix is never built unless a
        slow-path consumer asks (`dense_values`).
        """
        n = cols["count"]
        rows = _next_pow2(max(n, 1), MIN_ROWS)
        val_len = cols["val_len"]
        max_v = int(val_len.max()) if n else 0
        width = bucket_width(max_v)
        if width > MAX_RECORD_WIDTH:
            raise ValueError(
                f"record value of {max_v} bytes exceeds {MAX_RECORD_WIDTH}"
            )
        _check_matrix_addressing(rows, width)
        if n and int(cols["val_off"][-1]) > FLAT_ADDRESS_MAX:
            raise FlatAddressingError(
                f"decoded flat of {int(cols['val_off'][-1])} bytes "
                f"exceeds int32 addressing ({FLAT_ADDRESS_MAX}); split "
                "the batch before staging"
            )
        lengths = np.zeros(rows, dtype=np.int32)
        lengths[:n] = val_len.astype(np.int32)
        starts = np.zeros(rows, dtype=np.int32)
        starts[:n] = cols["val_off"][:-1].astype(np.int32)
        # padding rows "start" at the end of the flat with length 0
        starts[n:] = np.int32(cols["val_off"][-1]) if n else 0

        keys, key_lengths, offset_deltas, timestamp_deltas = (
            cls._stage_meta_columns(cols, rows, n)
        )
        return cls(
            values=None,
            lengths=lengths,
            keys=keys,
            key_lengths=key_lengths,
            offset_deltas=offset_deltas,
            timestamp_deltas=timestamp_deltas,
            count=n,
            base_offset=base_offset,
            base_timestamp=base_timestamp,
            _flat=np.asarray(cols["val_flat"], dtype=np.uint8),
            _starts=starts,
            _width=width,
            _rows=rows,
        )

    def to_columns(self) -> dict:
        """Exact (unaligned) columnar form of the live rows — the input
        shape of `native_backend.encode_record_columns`.

        Flat-backed buffers (device-side result compaction: the fetch
        adopted the packed payload, or the view split-back built the
        4-aligned flat directly) convert with ONE ragged gather over the
        flat — the padded matrix (and the masked re-extraction it would
        cost on top) never exists. This is the broker split-back's input
        form, so a fused slice goes packed-payload -> wire bytes without
        ever densifying."""
        n = self.count
        self._unview()
        if self._flat is None:
            self.dense_values()  # an int-backed buffer renders here
        lengths = self.lengths[:n].astype(np.int64)
        val_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lengths, out=val_off[1:])
        if self.values is None:
            val_flat = self._flat_unaligned(lengths, val_off)
        else:
            values = self.values
            width = values.shape[1]
            mask = np.arange(width, dtype=np.int32)[None, :] < lengths[:, None]
            val_flat = values[:n][mask]
        key_present = (self.key_lengths[:n] >= 0).astype(np.uint8)
        klens = np.maximum(self.key_lengths[:n], 0).astype(np.int64)
        key_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(klens, out=key_off[1:])
        kwidth = self.keys.shape[1]
        kmask = np.arange(kwidth, dtype=np.int32)[None, :] < klens[:, None]
        key_flat = self.keys[:n][kmask]
        return {
            "count": n,
            "val_flat": val_flat,
            "val_off": val_off,
            "key_flat": key_flat,
            "key_off": key_off,
            "key_present": key_present,
            "off_delta": self.offset_deltas[:n].astype(np.int64),
            "ts_delta": self.timestamp_deltas[:n].astype(np.int64),
        }

    def _flat_unaligned(self, lengths: np.ndarray, val_off: np.ndarray):
        """Exact-packed live bytes from the 4-aligned flat.

        The live byte ranges [start, start+len) are ascending and
        disjoint by construction (starts are a cumsum of the aligned
        lengths), so ONE boolean range-select extracts them — a few
        sequential passes over the flat, no big fancy-index
        temporaries (this is the broker split-back's hot path for fat
        records)."""
        n = len(lengths)
        total = int(val_off[-1])
        if not n or not total:
            return np.zeros(0, dtype=np.uint8)
        flat = self._flat
        if not len(flat):  # all-empty values
            return np.zeros(total, dtype=np.uint8)
        # live ranges are ascending and disjoint by construction
        # (starts are a cumsum of the aligned lengths)
        return ragged_range_select(
            flat, self._starts[:n].astype(np.int64), lengths
        )

    def encode_into(self, slab, first: int = 0) -> Tuple[int, str]:
        """Append live rows [first, count) to a response slab
        (`native_backend.RecordSlab`) in ONE native pass that reads the
        form this buffer already holds: the spans of the input's flat,
        the int64 column, the 4-aligned flat, or (a dense matrix) the
        exact-packed columns of `to_columns`. Returns (rows kept — fewer
        than asked for is the slab's ``max_bytes`` cut —, the encode
        form taken)."""
        n = self.count
        meta = (self.keys, self.key_lengths, self.offset_deltas,
                self.timestamp_deltas, first, n)
        if self._view is not None:
            in_flat, base, fold = self._view
            return slab.append_view(
                in_flat, base, self.lengths, fold, *meta
            ), "enc-direct-view"
        if self._row_format is not None:
            return slab.append_rows(
                self._ints, self._row_format, *meta
            ), "enc-direct-rows"
        if self._flat is None and self._ints is not None and self._ints.ndim > 1:
            self.dense_values()
        if self.values is not None:
            c = self.to_columns()
            return slab.append_columns(
                c["val_flat"], c["val_off"], c["key_flat"], c["key_off"],
                c["key_present"], c["off_delta"], c["ts_delta"], first,
            ), "enc-columns"
        if self._flat is not None:
            return slab.append_flat(
                self._flat, self._starts, self.lengths, *meta
            ), "enc-direct-bytes"
        return slab.append_ints(self._ints, *meta), "enc-direct-int"

    # -- materialization ----------------------------------------------------

    def to_records(self) -> List[Record]:
        out: List[Record] = []
        keys = self.keys
        self._unview()
        if self._flat is None:
            self.dense_values()  # an int-backed buffer renders here
        if self.values is None:
            # flat-backed: slice each record straight out of the flat
            flat, starts = self._flat, self._starts
            values_row = lambda i, vlen: flat[  # noqa: E731
                int(starts[i]) : int(starts[i]) + vlen
            ]
        else:
            values = self.values
            values_row = lambda i, vlen: values[i, :vlen]  # noqa: E731
        for i in range(self.count):
            vlen = int(self.lengths[i])
            klen = int(self.key_lengths[i])
            out.append(
                Record(
                    value=values_row(i, vlen).tobytes(),
                    key=None if klen < 0 else keys[i, :klen].tobytes(),
                    offset_delta=int(self.offset_deltas[i]),
                    timestamp_delta=int(self.timestamp_deltas[i]),
                )
            )
        return out

    def shape_key(self) -> Tuple[int, int, int]:
        """(rows, value width, key width) — the jit-cache bucket."""
        return (self.rows, self.width, self.keys.shape[1])
