"""The re-pad rebuild (`kernels.rows_from_word_starts`) and its callers
against a numpy reference that shares no code with them.

The reference walks the records one by one: ``out[r] = flat_bytes[
start[r] : start[r] + len[r]]``, zero-padded to the row width. The
flat it is given carries JUNK wherever the staging contract says
nobody may look: each record's 0-3 alignment bytes, the bucket padding
after the last record, and the whole flat under rows past ``count``
(length 0). A rebuild that lets one such byte through fails here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fluvio_tpu.parallel import RECORD_AXIS, make_record_mesh
from fluvio_tpu.smartengine.tpu import kernels, stripes
from fluvio_tpu.smartengine.tpu.executor import ragged_repad_words

EDGE_LENGTHS = (0, 1, 3, 4, 5)


def _lengths(rng, rows, count, width):
    """Every edge length (0, 1, 3, 4, 5, width - 1, width) first, random
    after; zero past ``count``."""
    lens = rng.integers(0, width + 1, size=rows).astype(np.int32)
    edge = [e for e in EDGE_LENGTHS if e <= width] + [width - 1, width]
    lens[:len(edge)] = edge[:rows]
    lens[count:] = 0
    return lens


def _stage(rng, records, slack_words):
    """The 4-aligned ragged flat of ``records`` as int32 words, junk in
    every pad byte and in ``slack_words`` words after the last record."""
    total = sum((len(r) + 3) & ~3 for r in records)
    raw = rng.integers(1, 256, size=total + 4 * slack_words, dtype=np.uint8)
    at = 0
    for r in records:
        raw[at:at + len(r)] = np.frombuffer(r, np.uint8)
        at += (len(r) + 3) & ~3
    return raw.view("<i4").astype(np.int32)


def _records(rng, lens):
    # no zero byte inside a record: a leaked pad byte (junk is 1..255)
    # and a dropped record byte are both seen
    return [rng.integers(1, 256, size=int(n), dtype=np.uint8).tobytes() for n in lens]


def _reference(records, rows, width):
    out = np.zeros((rows, width), np.uint8)
    for r, rec in enumerate(records):
        out[r, :len(rec)] = np.frombuffer(rec, np.uint8)
    return out


def _run_repad(flat, lens, width):
    fn = jax.jit(lambda f, l: ragged_repad_words(f, l, width))
    values, lengths = fn(jnp.asarray(flat), jnp.asarray(lens))
    assert values.dtype == jnp.uint8 and lengths.dtype == jnp.int32
    return np.asarray(values), np.asarray(lengths)


# ---------------------------------------------------------------------------
# the helper: words in, words out
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rows,wwidth,n_words",
    [
        (8, 8, 64), (8, 16, 1000), (24, 32, 128), (13, 40, 777),
        (8, 256, 4096), (24, 1024, 8192), (8, 2048, 2048 * 3 + 5),
        (65536, 16, 655360), (5, 8, 1), (8, 16, 127), (8, 16, 129),
    ],
)
def test_rows_are_the_consecutive_words_at_their_starts(rows, wwidth, n_words):
    """`out[r, j] = flat[start[r] + j]` wherever that word is inside the
    flat, for starts in ANY order (the helper promises no more to its
    callers than the per-word gather did): sorted, unsorted, at word 0,
    on the last word, past the end and negative."""
    rng = np.random.default_rng(rows * 31 + wwidth)
    flat = rng.integers(-2**31, 2**31 - 1, size=n_words).astype(np.int32)
    starts = rng.integers(0, n_words, size=rows).astype(np.int32)
    starts[0], starts[1], starts[2], starts[3] = 0, n_words - 1, n_words + 70000, -5
    starts[4] = max(0, n_words - wwidth)  # ends on the flat's last word
    got = np.asarray(
        jax.jit(lambda f, s: kernels.rows_from_word_starts(f, s, wwidth))(
            jnp.asarray(flat), jnp.asarray(starts)
        )
    )
    assert got.shape == (rows, wwidth) and got.dtype == np.int32
    clipped = np.clip(starts, 0, n_words - 1)
    for r in range(0, rows, max(1, rows // 64)):
        inside = min(wwidth, n_words - clipped[r])
        assert (got[r, :inside] == flat[clipped[r]:clipped[r] + inside]).all(), r


# ---------------------------------------------------------------------------
# the narrow caller: `ragged_repad_words`
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [32, 64, 128, 160, 1024, 4096])
@pytest.mark.parametrize("rows,count", [(8, 8), (24, 19), (13, 13)])
def test_ragged_repad_is_the_zero_padded_records(width, rows, count):
    """Rows of 8 and 24 (the buckets' small end), one count that no
    grouping factor divides; all seven edge lengths; junk in the pad
    bytes, after the last record and under the rows past ``count``."""
    rng = np.random.default_rng(width * 7 + rows)
    lens = _lengths(rng, rows, count, width)
    records = _records(rng, lens)
    flat = _stage(rng, records, slack_words=37)
    values, lengths = _run_repad(flat, lens, width)
    assert (lengths == lens).all()
    assert (values == _reference(records, rows, width)).all()


@pytest.mark.parametrize("width", [32, 64, 128, 160, 1024])
def test_last_row_may_end_on_the_flats_last_word(width):
    """No slack at all: the last record (full width) ends where the flat
    ends, and every shorter row before it reads past its own bytes into
    its neighbours'."""
    rng = np.random.default_rng(width)
    lens = _lengths(rng, 16, 16, width)
    lens[-1] = width
    records = _records(rng, lens)
    flat = _stage(rng, records, slack_words=0)
    values, _ = _run_repad(flat, lens, width)
    assert (values == _reference(records, 16, width)).all()


@pytest.mark.parametrize("width,n_words", [(32, 1), (64, 8), (4096, 256)])
def test_all_empty_records_read_nothing(width, n_words):
    rng = np.random.default_rng(3)
    flat = rng.integers(1, 2**31 - 1, size=n_words).astype(np.int32)
    values, lengths = _run_repad(flat, np.zeros(8, np.int32), width)
    assert not values.any() and not lengths.any()


def test_a_chunk_sized_bucket():
    """65,536 rows of 64 B from a 655,360-word flat: `ns-drain`'s chunk."""
    rng = np.random.default_rng(11)
    rows, width = 65536, 64
    lens = rng.integers(20, 53, size=rows).astype(np.int32)
    lens[60000:] = 0
    l4 = (lens + 3) & ~3
    starts = np.cumsum(l4) - l4
    raw = rng.integers(1, 256, size=655360 * 4, dtype=np.uint8)
    flat = raw.view("<i4").astype(np.int32)
    values, _ = _run_repad(flat, lens, width)
    col = np.arange(width)[None, :]
    want = np.where(col < lens[:, None], raw[starts[:, None] + col], 0)
    assert (values == want).all()


# ---------------------------------------------------------------------------
# the striped caller: overlapping stripe rows of one wide record
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,v", [(64, 16), (256, 128), (1024, 128)])
def test_striped_rows_overlap_and_stop_at_the_record(s, v):
    """Stripe row k of a record is its bytes ``[k * (s - v), k * (s - v)
    + s)``, cut at the record's end: consecutive rows share ``v`` bytes,
    the last is short, rows past the live stripes are empty."""
    rng = np.random.default_rng(s + v)
    step = s - v
    lens = np.array([3 * s + 5, 1, 0, step, s, s + 1, 7 * step + v, 0], np.int32)
    count = 7
    records = _records(rng, lens)
    flat = _stage(rng, records, slack_words=9)
    want = []
    for rec in records[:count]:
        k = max(1, -(-max(len(rec) - v, 0) // step))
        want += [rec[i * step:i * step + s] for i in range(k)]
    srows = len(want) + 5
    assert stripes.plan_rows(lens, count, s, v) == len(want)

    def fn(f, l):
        live = jnp.arange(l.shape[0], dtype=jnp.int32) < count
        plan = stripes.plan_device(l, live, srows, s, v)
        return stripes.striped_repad_words(f, l, plan, s)

    got = np.asarray(jax.jit(fn)(jnp.asarray(flat), jnp.asarray(lens)))
    assert got.dtype == np.uint8
    assert (got == _reference(want, srows, s)).all()


# ---------------------------------------------------------------------------
# the sharded caller: each shard rebuilds its own rows from its own flat
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [64, 160])
def test_each_shard_rebuilds_from_its_own_flat(width):
    """`parallel/sharded.py:_local_step_ragged` calls the same function
    under `shard_map`: a shard's word starts count from the head of ITS
    flat, whatever lies in its neighbours'."""
    n_dev, rows_local, words_local = 4, 8, 8 * (width // 4) + 11
    if len(jax.devices()) < n_dev:
        pytest.skip(f"needs {n_dev} virtual devices")
    mesh = make_record_mesh(n_dev)
    rng = np.random.default_rng(width)
    flats, lens_all, want = [], [], []
    for d in range(n_dev):
        lens = _lengths(rng, rows_local, rows_local - d, width)
        records = _records(rng, lens)
        flat = _stage(rng, records, slack_words=0)
        slack = rng.integers(1, 2**31 - 1, size=words_local - flat.shape[0])
        flats.append(np.concatenate([flat, slack.astype(np.int32)]))
        lens_all.append(lens)
        want.append(_reference(records, rows_local, width))
    step = jax.jit(
        jax.shard_map(
            lambda f, l: ragged_repad_words(f, l, width)[0],
            mesh=mesh, in_specs=(P(RECORD_AXIS), P(RECORD_AXIS)),
            out_specs=P(RECORD_AXIS),
        )
    )
    got = np.asarray(
        step(jnp.asarray(np.concatenate(flats)), jnp.asarray(np.concatenate(lens_all)))
    )
    assert (got == np.concatenate(want)).all()
