"""ctypes loader for fluvio_tpu/native/codecs.cpp (lz4-frame + snappy).

Same compile-on-demand pattern as smartengine/native_backend.py: the
shared library builds once per source hash with the baked-in g++ and
loads via ctypes. When no toolchain is available the loader returns
None and protocol/compression.py falls back to the bundled pure-Python
codecs (with an operator-visible warning — the fallbacks are 20-100x
slower; `bench.py`'s codec table measures both).

Parity: fluvio-compression/src/lib.rs links the native lz4/snappy
libraries; this is the equivalent native path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path

from fluvio_tpu.analysis.lockwatch import make_lock

logger = logging.getLogger(__name__)

_SOURCE = Path(__file__).resolve().parents[1] / "native" / "codecs.cpp"
_BUILD_DIR = Path(
    os.environ.get("FLUVIO_TPU_NATIVE_BUILD", str(_SOURCE.parent / "_build"))
)
_lock = make_lock("native_codecs.build")
_lib = None
_lib_failed = False


class _CodecBuf(ctypes.Structure):
    _fields_ = [("data", ctypes.POINTER(ctypes.c_uint8)), ("len", ctypes.c_int64)]


def _load():
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            source = _SOURCE.read_bytes()
            digest = hashlib.sha256(source).hexdigest()[:16]
            out = _BUILD_DIR / f"codecs-{digest}.so"
            if not out.exists():
                _BUILD_DIR.mkdir(parents=True, exist_ok=True)
                tmp = out.with_suffix(f".so.tmp.{os.getpid()}")
                subprocess.run(
                    ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                     str(_SOURCE), "-o", str(tmp)],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, out)
            lib = ctypes.CDLL(str(out))
        except (OSError, subprocess.CalledProcessError) as e:
            logger.warning("native codecs unavailable: %s", e)
            _lib_failed = True
            return None
        for fn in ("lz4_frame_compress", "lz4_frame_decompress",
                   "snappy_compress", "snappy_decompress"):
            getattr(lib, fn).restype = _CodecBuf
            getattr(lib, fn).argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ]
        lib.codec_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        lib.codec_free.restype = None
        _lib = lib
        return _lib


def _call(fn_name: str, data: bytes, error_cls) -> bytes:
    lib = _load()
    buf = (ctypes.c_uint8 * max(len(data), 1)).from_buffer_copy(
        data if data else b"\x00"
    )
    res = getattr(lib, fn_name)(
        ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8)), len(data)
    )
    if res.len < 0:
        raise error_cls(f"{fn_name}: malformed input")
    try:
        return ctypes.string_at(res.data, res.len)
    finally:
        lib.codec_free(res.data)


class _Lz4Native:
    """Drop-in for the lz4.frame module surface compression.py uses."""

    @staticmethod
    def compress(data: bytes) -> bytes:
        from fluvio_tpu.protocol.lz4_py import Lz4Error

        return _call("lz4_frame_compress", data, Lz4Error)

    @staticmethod
    def decompress(data: bytes) -> bytes:
        from fluvio_tpu.protocol.lz4_py import Lz4Error

        return _call("lz4_frame_decompress", data, Lz4Error)


class _SnappyNative:
    @staticmethod
    def compress(data: bytes) -> bytes:
        from fluvio_tpu.protocol.snappy_py import SnappyError

        return _call("snappy_compress", data, SnappyError)

    @staticmethod
    def decompress(data: bytes) -> bytes:
        from fluvio_tpu.protocol.snappy_py import SnappyError

        return _call("snappy_decompress", data, SnappyError)


def lz4_module():
    """The native lz4 codec, or None without a toolchain."""
    return _Lz4Native if _load() is not None else None


def snappy_module():
    """The native snappy codec, or None without a toolchain."""
    return _SnappyNative if _load() is not None else None
