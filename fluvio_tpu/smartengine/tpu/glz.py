"""glz: the down-link's result codec — device encoder, host decoders.

Result streams (descriptor blocks, packed payloads) may cross the D2H
link COMPRESSED: the chain's own jit program encodes them on the device
(`encode_result`, armed by ``FLUVIO_RESULT_COMPRESS``) and the fetch
inflates them on the host (`decode_result_host`: the native
`glz_decompress` of native/glz.cpp, which validates what the device
sent, else the numpy mirror `decompress_numpy`). The format is a list
of LZ4-shaped sequences (literal run + match) whose matches never
overlap their own output and whose match-chain depth is capped at
``MAX_DEPTH``, so a decode is a fixed number of vectorized gather
rounds (`decompress_numpy` is that algorithm's executable spec):
  1. per-sequence dst offsets = exclusive cumsum of lit_len+match_len;
     literal-stream offsets = exclusive cumsum of lit_len
  2. sequence id per output byte = scatter(1 at dst offsets) + cumsum
  3. bytes inside the literal part: one gather from the literal stream
  4. match bytes: `depth` rounds of out = out[src_idx] — round k
     resolves every depth-k byte because its sources (depth < k)
     resolved in earlier rounds

The up-link carries no glz: the staged flat ships raw. On the v5e the
device-side inflate ran at about 9 MB/s (283 ms per 2.6 MB flat)
against 1.06 ms to ship it raw (PERF.md §6, PRs 27 and 33).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path
from typing import NamedTuple

import numpy as np

from fluvio_tpu.analysis.lockwatch import make_lock
from fluvio_tpu.analysis.envreg import env_int

logger = logging.getLogger(__name__)

_SOURCE = Path(__file__).resolve().parents[2] / "native" / "glz.cpp"
_BUILD_DIR = Path(
    os.environ.get("FLUVIO_TPU_NATIVE_BUILD", str(_SOURCE.parent / "_build"))
)
_lock = make_lock("glz.build")
_lib = None
_lib_failed = False

MAX_DEPTH = 6       # gather rounds a decode runs at most
# streams encode in independent CHUNKS of this many output bytes:
# every match source stays inside its own chunk (the device encoder's
# per-chunk hash tables); sources are absolute, so the host decoders
# read the merged stream
GLZ_CHUNK = 256 * 1024


def chunk_bytes() -> int:
    """Configured encode-chunk size (``FLUVIO_GLZ_CHUNK``); must stay a
    multiple of 1024 so chunk starts stay word- and group-aligned."""
    c = int(env_int("FLUVIO_GLZ_CHUNK"))
    if c < 4096 or c % 1024:
        raise ValueError(f"FLUVIO_GLZ_CHUNK={c}: need a multiple of 1024 >= 4096")
    return c


def _load():
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            source = _SOURCE.read_bytes()
            digest = hashlib.sha256(source).hexdigest()[:16]
            out = _BUILD_DIR / f"glz-{digest}.so"
            if not out.exists():
                _BUILD_DIR.mkdir(parents=True, exist_ok=True)
                # per-process tmp name: concurrent builders must not
                # write through the same inode the winner renames
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
            logger.warning("native glz decoder unavailable: %s", e)
            _lib_failed = True
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.glz_decompress.restype = ctypes.c_int32
        lib.glz_decompress.argtypes = [
            u8p, u8p, i32p, ctypes.c_int64,
            u8p, ctypes.c_int64, u8p, ctypes.c_int64,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class Compressed(NamedTuple):
    lit_lens: np.ndarray    # uint8[n_seqs]
    match_lens: np.ndarray  # uint8[n_seqs]
    srcs: np.ndarray        # int32[n_seqs]
    lits: np.ndarray        # uint8[n_lits]
    depth: int              # gather rounds needed (<= MAX_DEPTH)
    out_len: int            # decompressed size == len(raw)


def decompress_host(comp: Compressed) -> np.ndarray:
    """Native decoder; fails closed (``ValueError``) on a stream that
    overruns its output or literals, reads forward or overlapping
    sources, decodes to another length, or carries an empty sequence."""
    lib = _load()
    assert lib is not None
    out = np.empty(comp.out_len, dtype=np.uint8)
    ll = np.ascontiguousarray(comp.lit_lens, dtype=np.uint8)
    ml = np.ascontiguousarray(comp.match_lens, dtype=np.uint8)
    srcs = np.ascontiguousarray(comp.srcs, dtype=np.int32)
    lits = np.ascontiguousarray(comp.lits, dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    rc = lib.glz_decompress(
        ll.ctypes.data_as(u8p), ml.ctypes.data_as(u8p),
        srcs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), ll.size,
        lits.ctypes.data_as(u8p), lits.size,
        out.ctypes.data_as(u8p), out.size,
    )
    if rc != 0:
        raise ValueError(f"corrupt glz stream (rc={rc})")
    return out


def decompress_numpy(comp: Compressed) -> np.ndarray:
    """Pure-numpy gather-round decode (the module docstring's
    algorithm), for hosts without a toolchain: literal (and pad) bytes
    carry ``midx == their own index``, so ``out = out[midx]`` is the
    decode's fixpoint iteration with no literal mask.
    """
    out_len = comp.out_len
    ll = comp.lit_lens.astype(np.int64)
    ml = comp.match_lens.astype(np.int64)
    total = ll + ml
    dst_start = np.cumsum(total) - total
    lit_start = np.cumsum(ll) - ll
    marks = np.zeros(out_len, dtype=np.int64)
    valid = (dst_start < out_len) & (total > 0)
    np.add.at(marks, dst_start[valid], 1)
    seq_id = np.cumsum(marks) - 1
    idx = np.arange(out_len, dtype=np.int64)
    within = idx - dst_start[seq_id]
    in_lit = within < ll[seq_id]
    nlit = max(comp.lits.size, 1)
    lit_idx = np.clip(lit_start[seq_id] + within, 0, nlit - 1)
    lits = comp.lits if comp.lits.size else np.zeros(1, np.uint8)
    out = np.where(in_lit, lits[lit_idx], 0).astype(np.uint8)
    midx = np.where(
        in_lit,
        idx,
        np.clip(
            comp.srcs.astype(np.int64)[seq_id] + (within - ll[seq_id]),
            0, out_len - 1,
        ),
    )
    for _ in range(comp.depth):
        out = out[midx]
    return out


# ---------------------------------------------------------------------------
# Device-side result ENCODER
# ---------------------------------------------------------------------------
#
# Result streams compress ON DEVICE before they ever
# cross the link and inflate host-side with the existing decoders
# (`decompress_host` native, `decompress_numpy` fallback). The wire
# format: chunk-local matches, absolute sources, lit/match lens <= 255,
# depth <= MAX_DEPTH.
#
# A TPU cannot run a serial greedy parse, so the device encoder is a
# data-parallel formulation over aligned 8-byte GROUPS:
#
#   1. match detection — a group matches an EARLIER group of its own
#      chunk with identical bytes: a scatter-built per-chunk
#      first-occurrence hash table finds the source. It only ever emits
#      depth-1 sources (targets are literal groups by construction), so
#      streams stay wire-legal.
#   2. constant runs (v[g] == v[g-1], e.g. zero tails of bucketed
#      payloads) get a closed-form source ladder: doubling pieces up to
#      32 groups, then 31-group pieces reading the run head — depth <=
#      6 == MAX_DEPTH, and every piece's sources are CONSECUTIVE so the
#      coalescer below folds each into one 6-byte sequence.
#   3. sequence formation — runs of literal groups and source-
#      consecutive match runs coalesce into (lit_len, match_len, src)
#      sequences, capped at ENC_MAX_RUN groups per half (248 <= u8),
#      split at chunk boundaries; one scatter packs the literal stream.
#
# The stream is VALID, not canonical (the differential tests pin
# round-trip equality, not byte-identical tokens).

ENC_GROUP = 8        # bytes per match group (a sequence is 6 B: shorter
                     # matches don't pay)
ENC_MAX_RUN = 31     # groups per sequence half: 248 bytes <= the u8 field
ENC_TABLE = 1 << 15  # first-occurrence hash slots per chunk

# down-link decline-reason vocabulary (telemetry counter keys)
DECLINE_ENC_RATIO = "glz-enc-ratio"
DECLINE_ENC_WIDE = "glz-enc-wide"


def _enc_roll1(x, fill=0):
    import jax.numpy as jnp

    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def enc_group_words(raw):
    """(w0, w1) int32 words per aligned 8-byte group of ``raw`` (uint8,
    length % 8 == 0). Group equality == both words equal."""
    import jax.numpy as jnp
    from jax import lax

    words = lax.bitcast_convert_type(raw.reshape(-1, 4), jnp.int32)
    w = words.reshape(-1, 2)
    return w[:, 0], w[:, 1]


def enc_const_runs(w0, w1, chunk_groups: int):
    """Constant-run detection + closed-form legal sources.

    Returns (const_m bool[G], csrc int32[G]): group g in a run of
    identical groups (broken at chunk starts) matches ``csrc[g]`` with
    chain depth <= 5 relative to the run head; heads themselves may be
    hash/window-matched (depth 1), so the stream depth bound is 6."""
    import jax.numpy as jnp
    from jax import lax

    G = w0.shape[0]
    gidx = jnp.arange(G, dtype=jnp.int32)
    eq_prev = (w0 == _enc_roll1(w0)) & (w1 == _enc_roll1(w1))
    eq_prev = eq_prev & (gidx % chunk_groups != 0)
    run_start = lax.cummax(jnp.where(~eq_prev, gidx, -1))
    k = gidx - run_start
    # doubling pieces for k < 32 (src offset k - 2^floor(log2 k)), then
    # 31-group pieces replaying the run head; each piece's sources are
    # consecutive, so coalescing falls out of the generic ext rule
    hp = jnp.ones_like(k)
    for b in (2, 4, 8, 16):
        hp = jnp.where(k >= b, jnp.int32(b), hp)
    csrc = jnp.where(
        k < 32, run_start + (k - hp), run_start + ((k - 32) % 31)
    )
    return eq_prev, csrc


def enc_match_xla(raw, chunk: int):
    """XLA match-detection rung: (is_match, src_g, depth) per group.

    First-occurrence hash table per chunk (scatter-min), verified by
    exact group-word compare — a candidate is always the first
    non-const occurrence of its key in the chunk, hence a literal, so
    hash matches are depth 1. One extension pass lets a match run
    continue past its root recycling when the continuation target is a
    literal (still depth 1). Constant runs override (depth <= 6).
    """
    import jax.numpy as jnp

    w0, w1 = enc_group_words(raw)
    G = w0.shape[0]
    chunk_groups = chunk // ENC_GROUP
    n_chunks = max(1, (G + chunk_groups - 1) // chunk_groups)
    gidx = jnp.arange(G, dtype=jnp.int32)
    chunk_id = gidx // jnp.int32(chunk_groups)

    const_m, csrc = enc_const_runs(w0, w1, chunk_groups)

    h = (w0 * jnp.int32(-1640531527)) ^ (w1 * jnp.int32(40503))
    h = (h ^ (h >> 15)) & jnp.int32(ENC_TABLE - 1)
    # const-matched groups stay out of the table so candidates (and the
    # extension targets below) can never chain through a const source
    entry = jnp.where(const_m, jnp.int32(G), gidx)
    table = jnp.full((n_chunks, ENC_TABLE), G, jnp.int32)
    table = table.at[chunk_id, h].min(entry, mode="drop")
    cand = table[chunk_id, h]
    hm = (
        (cand < gidx)
        & (jnp.take(w0, cand, mode="clip") == w0)
        & (jnp.take(w1, cand, mode="clip") == w1)
        & ~const_m
    )
    src0 = jnp.where(hm, cand, gidx)
    # extension pass: group g continues the previous group's match when
    # its bytes equal the next source group AND that target is a
    # literal under the pre-extension flags (depth stays 1)
    m0 = const_m | hm
    prev_m = _enc_roll1(m0, fill=False)
    prev_src = _enc_roll1(jnp.where(const_m, csrc, src0))
    tgt = prev_src + 1
    ext = (
        ~m0
        & prev_m
        & (chunk_id == _enc_roll1(chunk_id))
        & (tgt < gidx)
        & (jnp.take(chunk_id, tgt, mode="clip") == chunk_id)
        & (jnp.take(w0, tgt, mode="clip") == w0)
        & (jnp.take(w1, tgt, mode="clip") == w1)
        & ~jnp.take(m0, tgt, mode="clip")
    )
    is_match = m0 | ext
    src_g = jnp.where(
        const_m, csrc, jnp.where(hm, cand, jnp.where(ext, tgt, gidx))
    )
    depth = jnp.where(jnp.any(const_m), jnp.int32(MAX_DEPTH), jnp.int32(1))
    return is_match, src_g, depth


def enc_sequences(raw, is_match, src_g, chunk: int):
    """Shared sequence formation: group match plan -> token arrays.

    Returns (lit_lens u8[G], match_lens u8[G], srcs i32[G],
    lits u8[G*8], n_seq i32, n_lit i32) — seg arrays are G-capacity;
    callers slice to ``n_seq`` / ``n_lit`` (the fetch downloads bucketed
    slices; the scalars ride the header sync).
    """
    import jax.numpy as jnp
    from jax import lax

    G = is_match.shape[0]
    chunk_groups = chunk // ENC_GROUP
    gidx = jnp.arange(G, dtype=jnp.int32)
    at_cb = (gidx % chunk_groups) == 0
    prev_m = _enc_roll1(is_match, fill=False)
    prev_src = _enc_roll1(src_g)
    ext_run = is_match & prev_m & (src_g == prev_src + 1) & ~at_cb
    run_change = at_cb | (is_match != prev_m) | (is_match & ~ext_run)
    runpos = gidx - lax.cummax(jnp.where(run_change, gidx, -1))
    cap_break = (runpos > 0) & (runpos % ENC_MAX_RUN == 0)
    piece_change = run_change | cap_break
    # a match piece directly after a literal group joins that literal
    # piece's sequence (lits-then-match); every other piece starts one
    seg_start = piece_change & ~(is_match & ~prev_m & ~at_cb)
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    n_seq = seg_id[-1] + 1
    litg = ~is_match
    lit_cnt = jnp.zeros((G,), jnp.int32).at[seg_id].add(
        litg.astype(jnp.int32), mode="drop"
    )
    mat_cnt = jnp.zeros((G,), jnp.int32).at[seg_id].add(
        is_match.astype(jnp.int32), mode="drop"
    )
    lit_lens = (lit_cnt * 8).astype(jnp.uint8)
    match_lens = (mat_cnt * 8).astype(jnp.uint8)
    first_m = is_match & (~prev_m | seg_start)
    srcs = jnp.zeros((G,), jnp.int32).at[
        jnp.where(first_m, seg_id, jnp.int32(G))
    ].set(src_g * 8, mode="drop")
    lit_pos = jnp.cumsum(litg.astype(jnp.int32)) - litg.astype(jnp.int32)
    n_lit = (jnp.sum(litg.astype(jnp.int32))) * 8
    dst = (
        jnp.where(litg, lit_pos, jnp.int32(G))[:, None] * 8
        + jnp.arange(8, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    lits = jnp.zeros((G * 8,), jnp.uint8).at[dst].set(raw, mode="drop")
    return lit_lens, match_lens, srcs, lits, n_seq, n_lit


def encode_result(raw, chunk: int):
    """The device half of the result-ENCODE path.

    ``raw`` is a traced uint8 buffer whose static length is a multiple
    of 8 (callers pad; bucketed result payloads already are). Raw ship
    is the fallback and lives on the fetch side: the raw columns are
    still in ``packed``, so it costs a bigger download, never a
    re-dispatch.
    Returns (lit_lens, match_lens, srcs, lits, n_seq, n_lit, depth).
    """
    # single-window streams (most descriptor blocks are well under one
    # link chunk) clamp the window to the stream's own lane-rounded
    # size so the hash table tracks the real stream instead of padding
    # up to a full 256 KiB chunk of zeros. Multi-window streams keep
    # the configured chunk. 128 groups = 1024 bytes.
    G = raw.shape[0] // ENC_GROUP
    if G <= chunk // ENC_GROUP:
        chunk = max(128, ((G + 127) // 128) * 128) * ENC_GROUP
    is_match, src_g, depth = enc_match_xla(raw, chunk)
    ll, ml, srcs, lits, n_seq, n_lit = enc_sequences(
        raw, is_match, src_g, chunk
    )
    return ll, ml, srcs, lits, n_seq, n_lit, depth


def decode_result_host(
    ll: np.ndarray,
    ml: np.ndarray,
    srcs: np.ndarray,
    lits: np.ndarray,
    n_seq: int,
    n_lit: int,
    out_len: int,
    depth: int = MAX_DEPTH,
) -> np.ndarray:
    """Host half of the result-encode fetch: token slices (bucketed —
    may carry zero padding past the real counts) -> raw bytes. Uses the
    native reference decoder when available, else the numpy mirror of
    the device algorithm."""
    comp = Compressed(
        lit_lens=np.ascontiguousarray(ll[:n_seq], dtype=np.uint8),
        match_lens=np.ascontiguousarray(ml[:n_seq], dtype=np.uint8),
        srcs=np.ascontiguousarray(srcs[:n_seq], dtype=np.int32),
        lits=np.ascontiguousarray(lits[:n_lit], dtype=np.uint8),
        depth=max(int(depth), 1),
        out_len=out_len,
    )
    if available():
        return decompress_host(comp)
    return decompress_numpy(comp)
