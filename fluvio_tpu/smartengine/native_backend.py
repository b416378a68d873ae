"""Native (C++) chain backend: lowering + ctypes bridge.

Capability parity: the reference's wasmtime engine executes *compiled*
per-record transform code on the host CPU; this backend is that
execution model for our artifact format — DSL programs lower to a
compact postfix spec interpreted by ``fluvio_tpu/native/baseline_engine.cpp``
(compiled on demand with g++, cached by source hash). It is both the
fast host path (``backend="native"``) and the honest wasmtime-proxy
denominator for bench.py.

State parity: aggregate accumulators round-trip to the Python instances
after every call (like the TPU executor's attach/sync), so lookback and
`--aggregate-initial` behave identically across backends.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path
from typing import List, Optional, Tuple

import numpy as np

from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartmodule import dsl
from fluvio_tpu.smartmodule.types import (
    SmartModuleInput,
    SmartModuleKind,
    SmartModuleOutput,
    SmartModuleTransformRuntimeError,
)

from fluvio_tpu.analysis.lockwatch import make_lock

logger = logging.getLogger(__name__)

_SOURCE = Path(__file__).resolve().parents[1] / "native" / "baseline_engine.cpp"
_BUILD_DIR = Path(
    os.environ.get("FLUVIO_TPU_NATIVE_BUILD", str(_SOURCE.parent / "_build"))
)
_lock = make_lock("native_backend.build")
_lib = None
_lib_failed = False


class RecordColumns(ctypes.Structure):
    _fields_ = [
        ("count", ctypes.c_int64),
        ("parsed", ctypes.c_int64),  # bytes consumed; != raw len => malformed
        ("val_flat", ctypes.POINTER(ctypes.c_uint8)),
        ("val_off", ctypes.POINTER(ctypes.c_int64)),
        ("key_flat", ctypes.POINTER(ctypes.c_uint8)),
        ("key_off", ctypes.POINTER(ctypes.c_int64)),
        ("key_present", ctypes.POINTER(ctypes.c_uint8)),
        ("off_delta", ctypes.POINTER(ctypes.c_int64)),
        ("ts_delta", ctypes.POINTER(ctypes.c_int64)),
    ]


class RecordColumnsV2(ctypes.Structure):
    _fields_ = [
        ("base", RecordColumns),
        ("val_len", ctypes.POINTER(ctypes.c_int64)),  # exact lengths
    ]


class EncodedRecords(ctypes.Structure):
    _fields_ = [
        ("data", ctypes.POINTER(ctypes.c_uint8)),
        ("len", ctypes.c_int64),
    ]


class NativeResult(ctypes.Structure):
    _fields_ = [
        ("count", ctypes.c_int64),
        ("error_src", ctypes.c_int64),
        ("val_flat", ctypes.POINTER(ctypes.c_uint8)),
        ("val_off", ctypes.POINTER(ctypes.c_int64)),
        ("key_flat", ctypes.POINTER(ctypes.c_uint8)),
        ("key_off", ctypes.POINTER(ctypes.c_int64)),
        ("key_present", ctypes.POINTER(ctypes.c_uint8)),
        ("src_idx", ctypes.POINTER(ctypes.c_int64)),
        ("fresh", ctypes.POINTER(ctypes.c_uint8)),
        ("out_off_delta", ctypes.POINTER(ctypes.c_int64)),
        ("out_ts_delta", ctypes.POINTER(ctypes.c_int64)),
        ("acc_out", ctypes.POINTER(ctypes.c_int64)),
        ("acc_count", ctypes.c_int64),
    ]


def _compile_library() -> Path:
    source = _SOURCE.read_bytes()
    digest = hashlib.sha256(source).hexdigest()[:16]
    out = _BUILD_DIR / f"baseline_engine-{digest}.so"
    if out.exists():
        return out
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    tmp = out.with_suffix(f".so.tmp.{os.getpid()}")
    cmd = [
        "g++",
        "-O2",
        "-std=c++17",
        "-shared",
        "-fPIC",
        str(_SOURCE),
        "-o",
        str(tmp),
    ]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, out)
    return out


def load_library():
    """Build-once, load-once; None when no toolchain is available."""
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            path = _compile_library()
            lib = ctypes.CDLL(str(path))
        except (OSError, subprocess.CalledProcessError) as e:
            logger.warning("native engine unavailable: %s", e)
            _lib_failed = True
            return None
        lib.chain_create.restype = ctypes.c_void_p
        lib.chain_create.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.chain_destroy.argtypes = [ctypes.c_void_p]
        lib.chain_set_accumulator.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64,
        ]
        lib.chain_run.restype = ctypes.POINTER(NativeResult)
        lib.chain_run.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
        ]
        lib.chain_run_encoded.restype = ctypes.POINTER(NativeResult)
        lib.chain_run_encoded.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.result_free.argtypes = [ctypes.POINTER(NativeResult)]
        lib.decode_record_columns.restype = ctypes.POINTER(RecordColumns)
        lib.decode_record_columns.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.record_columns_free.argtypes = [ctypes.POINTER(RecordColumns)]
        lib.decode_record_columns_v2.restype = ctypes.POINTER(RecordColumnsV2)
        lib.decode_record_columns_v2.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.record_columns_v2_free.argtypes = [ctypes.POINTER(RecordColumnsV2)]
        lib.encoded_records_new.restype = ctypes.POINTER(EncodedRecords)
        lib.encoded_records_new.argtypes = []
        # numpy arrays go in as they are: ctypes checks dtype and layout
        slab = ctypes.POINTER(EncodedRecords)
        p8, p32, p64 = (
            np.ctypeslib.ndpointer(dtype=t, flags="C_CONTIGUOUS")
            for t in (np.uint8, np.int32, np.int64)
        )
        i64 = ctypes.c_int64
        lib.encode_append_columns.restype = i64
        lib.encode_append_columns.argtypes = [
            slab, p8, p64, p8, p64, p8, p64, p64, i64, i64, i64,
        ]
        lib.encode_append_flat.restype = i64
        lib.encode_append_flat.argtypes = [
            slab, p8, i64, p32, p32, p8, i64, p32, p32, p64, i64, i64, i64,
        ]
        lib.encode_append_ints.restype = i64
        lib.encode_append_ints.argtypes = [
            slab, p64, p8, i64, p32, p32, p64, i64, i64, i64,
        ]
        lib.encode_append_rows.restype = i64
        lib.encode_append_rows.argtypes = [
            slab, p64, i64, i64, p8, p64, p64, i64, p8, p64, i64, i64,
            p8, i64, p32, p32, p64, i64, i64, i64,
        ]
        lib.encoded_records_free.argtypes = [ctypes.POINTER(EncodedRecords)]
        _lib = lib
        return _lib


def _ptr_array(ptr, n, dtype):
    if n <= 0:
        return np.zeros(0, dtype=dtype)
    return np.ctypeslib.as_array(ptr, shape=(n,)).astype(dtype, copy=True)


def decode_record_columns(raw: bytes):
    """Record slab -> columnar numpy arrays via the native parser.

    Returns ``None`` when the native library is unavailable (callers fall
    back to the per-record Python decode). Layout mirrors the wire format
    parsed by `protocol.record.Record.decode`. ``parsed`` is the number of
    slab bytes consumed by whole well-formed records — callers must treat
    ``parsed != len(raw)`` as a malformed slab and fall back rather than
    silently dropping the tail.
    """
    lib = load_library()
    if lib is None:
        return None
    c = lib.decode_record_columns(raw, len(raw))
    try:
        cc = c.contents
        n = int(cc.count)
        val_off = _ptr_array(cc.val_off, n + 1, np.int64)
        key_off = _ptr_array(cc.key_off, n + 1, np.int64)
        return {
            "count": n,
            "parsed": int(cc.parsed),
            "val_off": val_off,
            "val_flat": _ptr_array(cc.val_flat, int(val_off[-1]) if n else 0, np.uint8),
            "key_off": key_off,
            "key_flat": _ptr_array(cc.key_flat, int(key_off[-1]) if n else 0, np.uint8),
            "key_present": _ptr_array(cc.key_present, n, np.uint8),
            "off_delta": _ptr_array(cc.off_delta, n, np.int64),
            "ts_delta": _ptr_array(cc.ts_delta, n, np.int64),
        }
    finally:
        lib.record_columns_free(c)


def decode_record_columns_aligned(raw: bytes):
    """Slab -> columns with the value flat written at 4-aligned offsets —
    exactly the TPU engine's ragged upload form, so staging needs no
    re-pad/re-flatten pass. ``val_off`` holds aligned starts (count + 1,
    last = total aligned bytes, zero gap bytes) and ``val_len`` the exact
    lengths. The alignment is fixed at 4: `RecordBuffer.from_flat` and
    the device's cumsum-of-aligned-lengths starts both assume it. Same
    malformed-slab contract as `decode_record_columns` (check
    ``parsed``)."""
    lib = load_library()
    if lib is None:
        return None
    c2 = lib.decode_record_columns_v2(raw, len(raw), 4)
    try:
        cc = c2.contents.base
        n = int(cc.count)
        val_off = _ptr_array(cc.val_off, n + 1, np.int64)
        key_off = _ptr_array(cc.key_off, n + 1, np.int64)
        return {
            "count": n,
            "parsed": int(cc.parsed),
            "val_off": val_off,
            "val_len": _ptr_array(c2.contents.val_len, n, np.int64),
            "val_flat": _ptr_array(
                cc.val_flat, int(val_off[-1]) if n else 0, np.uint8
            ),
            "key_off": key_off,
            "key_flat": _ptr_array(
                cc.key_flat, int(key_off[-1]) if n else 0, np.uint8
            ),
            "key_present": _ptr_array(cc.key_present, n, np.uint8),
            "off_delta": _ptr_array(cc.off_delta, n, np.int64),
            "ts_delta": _ptr_array(cc.ts_delta, n, np.int64),
        }
    finally:
        lib.record_columns_v2_free(c2)


class RecordSlab:
    """One response's wire-format record slab, appended to chunk after
    chunk by the native record writer (`baseline_engine.cpp:
    append_records`): one sizing-and-writing pass per call, reading the
    caller's arrays as they are.

    ``max_bytes`` > 0 cuts the slab at the longest record prefix that
    fits, the slab's own length carrying the budget across calls: a row
    is kept while the bytes before it are under ``max_bytes`` (numpy's
    ``searchsorted(cumsum(sizes), max_bytes, side="left") + 1``, so at
    least one record). Every ``append_*`` returns the rows it kept of
    [first, end); fewer than asked for is the cut.
    """

    def __init__(self, lib, max_bytes: int = 0):
        self._lib = lib
        self._slab = lib.encoded_records_new()
        self._max_bytes = int(max_bytes)

    @staticmethod
    def _arg(a, dtype):
        """The array as the native entry takes it (no copy when it
        already is); an empty one is padded so its pointer is real."""
        a = np.ascontiguousarray(a, dtype=dtype)
        return a if len(a) else np.zeros(1, dtype)

    @staticmethod
    def _check_rows(first: int, end: int, rows: int) -> None:
        if not 0 <= first <= end <= rows:
            raise ValueError(f"rows [{first}, {end}) outside 0..{rows}")

    @staticmethod
    def _kept(kept: int) -> int:
        if kept == -1:
            raise MemoryError("record slab: out of memory")
        if kept < 0:
            raise ValueError("record slab: a row lies outside its column")
        return int(kept)

    def _key_matrix(self, keys, key_lengths, end: int):
        keys = np.ascontiguousarray(keys, dtype=np.uint8)
        if keys.ndim != 2 or len(keys) < end or len(key_lengths) < end:
            raise ValueError("key matrix shorter than the rows to encode")
        return (self._arg(keys.reshape(-1), np.uint8), keys.shape[1],
                self._arg(key_lengths, np.int32))

    def append_columns(
        self, val_flat, val_off, key_flat, key_off, key_present,
        off_delta, ts_delta, first: int = 0,
    ) -> int:
        """Exact-packed columns (`RecordBuffer.to_columns`): the general
        form."""
        end = len(val_off) - 1
        rows = min(end, len(key_off) - 1, len(key_present),
                   len(off_delta), len(ts_delta))
        self._check_rows(first, end, rows)
        arg = self._arg
        return self._kept(self._lib.encode_append_columns(
            self._slab, arg(val_flat, np.uint8), arg(val_off, np.int64),
            arg(key_flat, np.uint8), arg(key_off, np.int64),
            arg(key_present, np.uint8),
            arg(off_delta, np.int64), arg(ts_delta, np.int64),
            first, end, self._max_bytes,
        ))

    def append_flat(
        self, flat, starts, lengths, keys, key_lengths, off_delta, ts_delta,
        first: int, end: int,
    ) -> int:
        """A flat-backed buffer as its fetch left it: the 4-aligned
        value flat with per-row starts and lengths, the key matrix with
        per-row lengths (-1 = null), int32 offset deltas."""
        rows = min(len(starts), len(lengths), len(off_delta), len(ts_delta))
        self._check_rows(first, end, rows)
        arg = self._arg
        return self._kept(self._lib.encode_append_flat(
            self._slab, arg(flat, np.uint8), len(flat),
            arg(starts, np.int32), arg(lengths, np.int32),
            *self._key_matrix(keys, key_lengths, end),
            arg(off_delta, np.int32), arg(ts_delta, np.int64),
            first, end, self._max_bytes,
        ))

    def append_ints(
        self, ints, keys, key_lengths, off_delta, ts_delta,
        first: int, end: int,
    ) -> int:
        """An int-backed buffer: each int64 rendered as its decimal
        (byte-equal to `kernels.int_to_ascii`) straight into its
        record."""
        rows = min(len(ints), len(off_delta), len(ts_delta))
        self._check_rows(first, end, rows)
        arg = self._arg
        return self._kept(self._lib.encode_append_ints(
            self._slab, arg(ints, np.int64),
            *self._key_matrix(keys, key_lengths, end),
            arg(off_delta, np.int32), arg(ts_delta, np.int64),
            first, end, self._max_bytes,
        ))

    def append_rows(
        self, ints, fmt, keys, key_lengths, off_delta, ts_delta,
        first: int, end: int,
    ) -> int:
        """An int-backed buffer of ``[columns, rows]`` int64 with a
        `buffer.RowFormat`: each row's value rendered from its ints
        (literal pieces, decimals, a table of texts) straight into its
        record."""
        ints = np.ascontiguousarray(ints, dtype=np.int64)
        columns, stride = ints.shape
        self._check_rows(first, end, min(stride, len(off_delta), len(ts_delta)))
        arg = self._arg

        def packed(texts):
            off = np.zeros(len(texts) + 1, dtype=np.int64)
            np.cumsum([len(t) for t in texts], out=off[1:])
            return arg(np.frombuffer(b"".join(texts), np.uint8), np.uint8), off

        slots = np.ascontiguousarray(fmt.slots, dtype=np.int64).reshape(-1, 3)
        if len(fmt.pieces) != len(slots) + 1:
            raise ValueError("a row format has one more piece than slots")
        lit, lit_off = packed(fmt.pieces)
        tab, tab_off = packed(fmt.table)
        return self._kept(self._lib.encode_append_rows(
            self._slab, arg(ints.reshape(-1), np.int64), stride, columns,
            lit, lit_off, arg(slots.reshape(-1), np.int64), len(slots),
            tab, tab_off, len(fmt.table), int(fmt.table_base),
            *self._key_matrix(keys, key_lengths, end),
            arg(off_delta, np.int32), arg(ts_delta, np.int64),
            first, end, self._max_bytes,
        ))

    def take(self) -> bytes:
        """The slab's bytes; the slab is freed and may not be used
        again."""
        slab, self._slab = self._slab, None
        try:
            n = int(slab.contents.len)
            return ctypes.string_at(slab.contents.data, n) if n else b""
        finally:
            self._lib.encoded_records_free(slab)

    def __del__(self):
        slab = getattr(self, "_slab", None)  # unset if the constructor failed
        if slab is not None:
            self._slab = None
            self._lib.encoded_records_free(slab)


def record_slab(max_bytes: int = 0) -> "RecordSlab | None":
    """An empty `RecordSlab`; ``None`` when the native library is
    unavailable."""
    lib = load_library()
    return None if lib is None else RecordSlab(lib, max_bytes)


def encode_record_columns(
    val_flat: np.ndarray,
    val_off: np.ndarray,
    key_flat: np.ndarray,
    key_off: np.ndarray,
    key_present: np.ndarray,
    off_delta: np.ndarray,
    ts_delta: np.ndarray,
) -> "bytes | None":
    """Columnar arrays -> wire-format record slab via the native encoder.

    Returns ``None`` when the native library is unavailable.
    """
    slab = record_slab()
    if slab is None:
        return None
    slab.append_columns(
        val_flat, val_off, key_flat, key_off, key_present, off_delta, ts_delta
    )
    return slab.take()


# ---------------------------------------------------------------------------
# DSL -> postfix spec lowering
# ---------------------------------------------------------------------------


class LoweringError(Exception):
    pass


def _hex(data: bytes) -> str:
    return data.hex() or "00"[:0] or ""


def _lower_expr(expr: dsl.Expr, out: List[str]) -> None:
    e = _lower_expr
    if isinstance(expr, dsl.Value):
        out.append("VALUE")
    elif isinstance(expr, dsl.Key):
        out.append("KEY")
    elif isinstance(expr, dsl.Const):
        out.append(f"CONST {expr.data.hex()}")
    elif isinstance(expr, dsl.Upper):
        e(expr.arg, out)
        out.append("UPPER")
    elif isinstance(expr, dsl.Lower):
        e(expr.arg, out)
        out.append("LOWER")
    elif isinstance(expr, dsl.Concat):
        for a in expr.args:
            e(a, out)
        out.append(f"CONCAT {len(expr.args)}")
    elif isinstance(expr, dsl.JsonGet):
        e(expr.arg, out)
        out.append(f"JSONGET {expr.key.encode('utf-8').hex()}")
    elif isinstance(expr, dsl.RegexMatch):
        e(expr.arg, out)
        out.append(f"REGEX {expr.pattern.encode('utf-8').hex()}")
    elif isinstance(expr, dsl.Contains):
        e(expr.arg, out)
        out.append(f"CONTAINS {expr.literal.hex()}")
    elif isinstance(expr, dsl.StartsWith):
        e(expr.arg, out)
        out.append(f"STARTSWITH {expr.literal.hex()}")
    elif isinstance(expr, dsl.EndsWith):
        e(expr.arg, out)
        out.append(f"ENDSWITH {expr.literal.hex()}")
    elif isinstance(expr, dsl.Len):
        e(expr.arg, out)
        out.append("LEN")
    elif isinstance(expr, dsl.ParseInt):
        e(expr.arg, out)
        out.append("PARSEINT")
    elif isinstance(expr, dsl.IntToBytes):
        e(expr.arg, out)
        out.append("INT2BYTES")
    elif isinstance(expr, dsl.Cmp):
        e(expr.left, out)
        e(expr.right, out)
        out.append(f"CMP {expr.cmp}")
    elif isinstance(expr, dsl.And):
        for a in expr.args:
            e(a, out)
        out.append(f"AND {len(expr.args)}")
    elif isinstance(expr, dsl.Or):
        for a in expr.args:
            e(a, out)
        out.append(f"OR {len(expr.args)}")
    elif isinstance(expr, dsl.Not):
        e(expr.arg, out)
        out.append("NOT")
    else:
        raise LoweringError(f"cannot lower {type(expr).__name__} natively")


def lower_chain(entries: List[Tuple]) -> str:
    """[(module, config)] -> native spec text; raises LoweringError."""
    lines: List[str] = []
    for module, config in entries:
        kind = module.transform_kind()
        program = module.dsl_program(kind)
        if program is None:
            raise LoweringError(f"module {module.name!r} has no DSL program")
        program = dsl.resolve_params(program, config.params)
        if isinstance(program, dsl.FilterProgram):
            pred: List[str] = []
            _lower_expr(program.predicate, pred)
            lines.append(f"STEP FILTER {len(pred)} 0 0")
            lines.extend(pred)
        elif isinstance(program, dsl.MapProgram):
            val: List[str] = []
            _lower_expr(program.value, val)
            key: List[str] = []
            if program.key is not None:
                _lower_expr(program.key, key)
            lines.append(f"STEP MAP 0 {len(val)} {len(key)}")
            lines.extend(val)
            lines.extend(key)
        elif isinstance(program, dsl.FilterMapProgram):
            pred, val, key = [], [], []
            _lower_expr(program.predicate, pred)
            _lower_expr(program.value, val)
            if program.key is not None:
                _lower_expr(program.key, key)
            lines.append(f"STEP FILTER_MAP {len(pred)} {len(val)} {len(key)}")
            lines.extend(pred)
            lines.extend(val)
            lines.extend(key)
        elif isinstance(program, dsl.ArrayMapProgram):
            lines.append(
                f"STEP ARRAY_MAP {program.mode} {program.sep.hex() or '0a'}"
            )
        elif isinstance(program, dsl.AggregateProgram):
            window = program.window_ms if program.window_ms else -1
            seed = (config.initial_data or b"").hex()
            if program.contribution is not None:
                if program.combine not in dsl.AGGREGATE_COMBINES:
                    raise LoweringError(
                        f"aggregate combine {program.combine!r}"
                    )
                contrib: List[str] = []
                _lower_expr(program.contribution, contrib)
                lines.append(
                    f"STEP AGGREGATE_EXPR {program.combine} {window} "
                    f"{seed or '-'} {len(contrib)}"
                )
                lines.extend(contrib)
            else:
                lines.append(
                    f"STEP AGGREGATE {program.kind} {window} {seed or '00'[:0]}"
                )
        else:
            raise LoweringError(
                f"cannot lower program {type(program).__name__} natively"
            )
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


def _as_ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


class NativeChainExecutor:
    """Compiled-chain executor with the TPU executor's interface shape."""

    def __init__(self, handle, lib, entries):
        self._handle = handle
        self._lib = lib
        self._entries = entries
        self._instances: List = []
        self.agg_kinds = [
            module.dsl_program(module.transform_kind()).kind
            for module, _ in entries
            if isinstance(
                module.dsl_program(module.transform_kind()), dsl.AggregateProgram
            )
        ]

    @classmethod
    def try_build(cls, entries: List[Tuple]) -> Optional["NativeChainExecutor"]:
        lib = load_library()
        if lib is None:
            return None
        try:
            spec = lower_chain(entries)
        except LoweringError as e:
            logger.debug("native lowering unavailable: %s", e)
            return None
        err = ctypes.create_string_buffer(512)
        handle = lib.chain_create(spec.encode(), err, len(err))
        if not handle:
            logger.warning(
                "native chain rejected: %s", err.value.decode("utf-8", "replace")
            )
            return None
        return cls(handle, lib, entries)

    def attach(self, instances: List) -> None:
        self._instances = instances

    def sync_state_from(self, instances: List) -> None:
        """Host aggregate state becomes authoritative (post-lookback)."""
        slot = 0
        for inst in instances:
            if inst.kind != SmartModuleKind.AGGREGATE:
                continue
            acc = inst.accumulator or b""
            buf = (ctypes.c_uint8 * max(1, len(acc))).from_buffer_copy(
                acc or b"\x00"
            )
            self._lib.chain_set_accumulator(self._handle, slot, buf, len(acc))
            slot += 1

    def _sync_instances(self, accs: List[int]) -> None:
        slot = 0
        for inst in self._instances:
            if inst.kind != SmartModuleKind.AGGREGATE:
                continue
            if slot < len(accs):
                inst.accumulator = str(accs[slot]).encode("ascii")
            slot += 1

    def process(self, inp: SmartModuleInput, metrics=None) -> SmartModuleOutput:
        if inp.raw_bytes is not None and inp.records is None:
            # wire-encoded slab: decode + transform entirely in native code
            # (the wasmtime-guest execution model)
            result = self._lib.chain_run_encoded(
                self._handle,
                inp.raw_bytes,
                len(inp.raw_bytes),
                inp.base_timestamp,
            )
            return self._collect(result, inp, records=None)
        records = inp.into_records()
        n = len(records)
        base_ts = inp.base_timestamp

        val_off = np.zeros(n + 1, dtype=np.int64)
        key_off = np.zeros(n + 1, dtype=np.int64)
        key_present = np.zeros(max(n, 1), dtype=np.uint8)
        timestamps = np.full(max(n, 1), -1, dtype=np.int64)
        val_parts, key_parts = [], []
        vo = ko = 0
        for i, rec in enumerate(records):
            val_parts.append(rec.value)
            vo += len(rec.value)
            val_off[i + 1] = vo
            if rec.key is not None:
                key_present[i] = 1
                key_parts.append(rec.key)
                ko += len(rec.key)
            key_off[i + 1] = ko
            if base_ts >= 0:
                timestamps[i] = base_ts + rec.timestamp_delta
        flat = np.frombuffer(b"".join(val_parts), dtype=np.uint8) if vo else np.zeros(1, np.uint8)
        kflat = np.frombuffer(b"".join(key_parts), dtype=np.uint8) if ko else np.zeros(1, np.uint8)

        result = self._lib.chain_run(
            self._handle,
            _as_ptr(flat, ctypes.c_uint8),
            _as_ptr(val_off, ctypes.c_int64),
            _as_ptr(kflat, ctypes.c_uint8),
            _as_ptr(key_off, ctypes.c_int64),
            _as_ptr(key_present, ctypes.c_uint8),
            _as_ptr(timestamps, ctypes.c_int64),
            n,
        )
        return self._collect(result, inp, records)

    def _collect(
        self, result, inp: SmartModuleInput, records: Optional[List[Record]]
    ) -> SmartModuleOutput:
        """Rebuild output Records from the flat native result.

        With ``records`` (the flat input path) deltas come from the source
        Python records; without (the encoded path) they come from the
        native decoder's per-output delta arrays.
        """
        try:
            res = result.contents
            count = res.count
            out = SmartModuleOutput()
            vflat = bytes(
                np.ctypeslib.as_array(res.val_flat, shape=(max(1, res.val_off[count]),))
            ) if count else b""
            kflat_out = bytes(
                np.ctypeslib.as_array(res.key_flat, shape=(max(1, res.key_off[count]),))
            ) if count else b""
            for i in range(count):
                value = vflat[res.val_off[i] : res.val_off[i + 1]]
                key = (
                    kflat_out[res.key_off[i] : res.key_off[i + 1]]
                    if res.key_present[i]
                    else None
                )
                fresh = bool(res.fresh[i])  # fan-out records reset deltas
                if records is not None:
                    src = records[res.src_idx[i]]
                    ts_delta = 0 if fresh else src.timestamp_delta
                    off_delta = 0 if fresh else src.offset_delta
                else:
                    ts_delta = res.out_ts_delta[i]
                    off_delta = res.out_off_delta[i]
                out.successes.append(
                    Record(
                        value=value,
                        key=key,
                        timestamp_delta=ts_delta,
                        offset_delta=off_delta,
                    )
                )
            if res.error_src >= 0:
                failing = (records or inp.into_records())[res.error_src]
                out.error = SmartModuleTransformRuntimeError(
                    hint="input record is not a JSON array",
                    offset=inp.base_offset + failing.offset_delta,
                    kind=SmartModuleKind.ARRAY_MAP,
                    record_key=failing.key,
                )
            accs = [res.acc_out[i] for i in range(res.acc_count)]
        finally:
            self._lib.result_free(result)
        self._sync_instances(accs)
        return out

    def __del__(self):
        try:
            if self._handle and self._lib is not None:
                self._lib.chain_destroy(self._handle)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
