"""Stable compaction without a search loop.

`kernels.compaction_sources` replaced `searchsorted(rank, 1..cap)` in
`windows/kernels.py:compact_front` with one int32 permutation scatter:
on the chip the search was a `while` of log2(n) dependent gathers, the
largest device operation of `q5-drain`. The fan-out's placement
(`kernels.fanout_place`, and `kernels.fanout_scatter` on it) does
without the scatter too: a select-sum inside each record's row and
shifted selects of the flat table. The old search form is kept HERE as
the oracle, beside `np.flatnonzero`; every live slot must equal both,
every dead slot must be zero. The structural guards hold the search
loop, and for the fan-out every sort, scatter and gather, out of the
lowered programs.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluvio_tpu.smartengine.tpu import kernels
from fluvio_tpu.windows.kernels import compact_front, prefix_sum

MASKS = ("empty", "full", "single", "alternating", "d0.01", "d0.5", "d0.99")
LENGTHS = (1, 129, 1000)
# cap under the mask's count, at its length, over its length
CAPS = ("third", "len", "over")


def _mask(kind: str, n: int, rng) -> np.ndarray:
    if kind == "empty":
        return np.zeros(n, bool)
    if kind == "full":
        return np.ones(n, bool)
    if kind == "single":
        m = np.zeros(n, bool)
        m[rng.integers(n)] = True
        return m
    if kind == "alternating":
        return np.arange(n) % 2 == 1
    return rng.random(n) < float(kind[1:])


def _cap(rule: str, n: int) -> int:
    return {"third": n // 3 + 1, "len": n, "over": n + 77}[rule]


def _search_sources(rank, cap: int):
    """The replaced form: slot k binary-searches the row of rank k + 1."""
    slot = jnp.arange(cap, dtype=jnp.int32)
    return jnp.minimum(
        jnp.searchsorted(rank, slot + 1, side="left"), rank.shape[0] - 1
    )


def _packed(rows: np.ndarray, n_slots: int, col: np.ndarray) -> np.ndarray:
    """``col`` at ``rows`` in the first slots, zeros after (last axis)."""
    out = np.zeros(col.shape[:-1] + (n_slots,), col.dtype)
    k = min(len(rows), n_slots)
    out[..., :k] = col[..., rows[:k]]
    return out


def _old_fanout(flag, start_g, len_g, fflag, fstart, flen, contributing, cap):
    """`fanout_scatter` as it stood before PR 40."""
    n, width = flag.shape
    flag = flag & contributing[:, None]
    fflag = fflag & contributing
    allflag = jnp.concatenate([flag, fflag[:, None]], axis=1).reshape(-1)
    allstart = jnp.concatenate([start_g, fstart[:, None]], axis=1).reshape(-1)
    alllen = jnp.concatenate([len_g, flen[:, None]], axis=1).reshape(-1)
    cum = jnp.cumsum(allflag.astype(jnp.int32))
    total = cum[-1]
    pos = jnp.clip(_search_sources(cum, cap), 0, allflag.shape[0] - 1)
    live = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(total, cap)
    out_row = jnp.where(live, pos // jnp.int32(width + 1), 0)
    out_start = jnp.where(live, jnp.take(allstart, pos), 0)
    out_len = jnp.where(live, jnp.take(alllen, pos), 0)
    return total, out_row, out_start, out_len


def _check_helper(mask):
    rank = jnp.cumsum(jnp.asarray(mask).astype(jnp.int32))
    count, src = jax.jit(kernels.compaction_sources)(mask, rank)
    src = np.asarray(src)
    assert int(count) == int(mask.sum())
    # a stable partition: the kept rows in order, then the others in order
    np.testing.assert_array_equal(
        src, np.concatenate([np.flatnonzero(mask), np.flatnonzero(~mask)])
    )
    k = int(mask.sum())
    np.testing.assert_array_equal(
        src[:k], np.asarray(_search_sources(rank, max(k, 1)))[:k]
    )


def _check_compact_front(mask, cap, rng):
    n = mask.shape[0]
    col = rng.integers(-(2**62), 2**62, n, dtype=np.int64)
    stack = rng.integers(-(2**62), 2**62, (3, n), dtype=np.int64)
    count, (got_col, got_stack) = jax.jit(
        lambda m, a, b: compact_front(m, cap, a, b)
    )(mask, col, stack)
    slots = min(cap, n)
    rows = np.flatnonzero(mask)
    assert int(count) == len(rows)  # exact, may exceed cap
    np.testing.assert_array_equal(np.asarray(got_col), _packed(rows, slots, col))
    np.testing.assert_array_equal(
        np.asarray(got_stack), _packed(rows, slots, stack)
    )
    # the oracle: the same slots through the replaced search
    old = np.asarray(
        _search_sources(prefix_sum(jnp.asarray(mask, jnp.int32)), slots)
    )
    live = np.arange(slots) < len(rows)
    np.testing.assert_array_equal(
        np.asarray(got_col), np.where(live, col[old], 0)
    )


def _check_fanout(mask, cap, rng):
    # the flattened emission order of n records x (width grid slots + 1
    # final slot); pad the mask to whole records
    width = 3
    n = -(-mask.shape[0] // (width + 1))
    allflag = np.zeros(n * (width + 1), bool)
    allflag[: mask.shape[0]] = mask
    grid = allflag.reshape(n, width + 1)
    contributing = np.ones(n, bool)
    if n > 2:
        contributing[rng.integers(n)] = False
    starts = rng.integers(0, 1 << 20, (n, width + 1)).astype(np.int32)
    lens = rng.integers(0, 1 << 10, (n, width + 1)).astype(np.int32)
    args = (
        grid[:, :width], starts[:, :width], lens[:, :width],
        grid[:, width], starts[:, width], lens[:, width], contributing,
    )
    got = jax.jit(lambda *a: kernels.fanout_scatter(*a, cap))(*args)
    old = jax.jit(lambda *a: _old_fanout(*a, cap))(*args)
    for g, o in zip(got, old):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(o))
    kept = (grid & contributing[:, None]).reshape(-1)
    rows = np.flatnonzero(kept)
    assert int(got[0]) == len(rows)
    np.testing.assert_array_equal(
        np.asarray(got[1]), _packed(rows, cap, np.arange(kept.size) // (width + 1))
    )
    np.testing.assert_array_equal(
        np.asarray(got[2]), _packed(rows, cap, starts.reshape(-1))
    )
    np.testing.assert_array_equal(
        np.asarray(got[3]), _packed(rows, cap, lens.reshape(-1))
    )


# `fanout_place` at a bound of 8 elements a record (the explode corpus
# has six): each case is a grid of records x (width + 1) slots
BOUND = 8
BOUND_CASES = ("empty", "full", "at_bound", "over_bound", "holes", "cap_under")


def _bound_case(case: str, rng):
    """(grid, contributing, cap, bound) for one case. "full" fills every
    column of every record and places them uncut (a bound of the
    columns, the `fanout_scatter` form)."""
    n, cols = 40, 12
    grid = np.zeros((n, cols), bool)
    bound = BOUND
    if case == "full":
        grid[:] = True
        bound = cols
    elif case != "empty":
        for r in range(n):
            k = int(rng.integers(0, 7))  # up to six elements a record
            grid[r, rng.choice(cols, k, replace=False)] = True
    if case == "at_bound":
        grid[3] = False
        grid[3, rng.choice(cols, BOUND, replace=False)] = True
    if case == "over_bound":
        grid[5] = False
        grid[5, rng.choice(cols, BOUND + 3, replace=False)] = True
    contributing = np.ones(n, bool)
    if case == "holes":
        contributing[rng.choice(n, 9, replace=False)] = False
    total = int((grid & contributing[:, None]).sum())
    cap = total // 2 + 1 if case == "cap_under" else total + 17
    return grid, contributing, cap, bound


def _check_fanout_place(case: str, rng):
    grid, contributing, cap, bound = _bound_case(case, rng)
    n, cols = grid.shape
    starts = rng.integers(0, 1 << 20, grid.shape).astype(np.int32)
    lens = rng.integers(0, 1 << 10, grid.shape).astype(np.int32)
    view = rng.integers(0, 1 << 24, n).astype(np.int32)
    args = (
        grid[:, :-1], starts[:, :-1], lens[:, :-1],
        grid[:, -1], starts[:, -1], lens[:, -1], contributing,
    )
    total, largest, st, ln, vs, row = jax.jit(
        lambda *a: kernels.fanout_place(
            *a, cap, bound, (jnp.asarray(view), jnp.arange(n, dtype=jnp.int32))
        )
    )(*args)
    kept = grid & contributing[:, None]
    counts = kept.sum(axis=1)
    assert int(total) == int(counts.sum())  # exact, past the cap and bound
    assert int(largest) == int(counts.max())
    # the oracle: each record's first `bound` elements, in column order
    placed = np.zeros_like(kept)
    for r in range(n):
        placed[r, np.flatnonzero(kept[r])[:bound]] = True
    rows = np.flatnonzero(placed.reshape(-1))
    record = np.arange(kept.size) // cols
    for got, col in (
        (st, starts.reshape(-1)), (ln, lens.reshape(-1)),
        (row, record), (vs, view[record]),
    ):
        np.testing.assert_array_equal(np.asarray(got), _packed(rows, cap, col))
    if counts.max() > bound:
        # flagged: the records over the bound lost their elements past it
        assert case == "over_bound" and len(rows) < int(counts.sum())
        return
    # within the bound the placement is the one before it, slot for slot
    old = jax.jit(lambda *a: _old_fanout(*a, cap))(*args)
    for got, want in zip((total, row, st, ln), old):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", BOUND_CASES)
def test_fanout_place_cuts_records_at_the_bound(case):
    _check_fanout_place(case, np.random.default_rng(zlib.crc32(case.encode())))


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize(
    "fn, cap_rule",
    [("helper", "len")]  # the helper takes no cap
    + [(f, c) for f in ("compact_front", "fanout_scatter") for c in CAPS],
)
def test_compaction_matches_flatnonzero_and_search(fn, cap_rule, kind, n):
    rng = np.random.default_rng(zlib.crc32(f"{fn}{kind}{n}{cap_rule}".encode()))
    mask = _mask(kind, n, rng)
    cap = _cap(cap_rule, n)
    if fn == "helper":
        _check_helper(mask)
    elif fn == "compact_front":
        _check_compact_front(mask, cap, rng)
    else:
        _check_fanout(mask, cap, rng)


def _lowered(fn: str) -> str:
    n = 1000
    if fn == "compact_front":
        return jax.jit(lambda m, x: compact_front(m, 300, x)).lower(
            jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int64)
        ).as_text()
    w = 5
    b, i = jnp.zeros((n, w), bool), jnp.zeros((n, w), jnp.int32)
    return jax.jit(lambda *a: kernels.fanout_scatter(*a, 4000)).lower(
        b, i, i, b[:, 0], i[:, 0], i[:, 0], b[:, 0]
    ).as_text()


@pytest.mark.parametrize("fn", ("compact_front", "fanout_scatter"))
def test_compaction_lowers_to_one_unique_scatter_and_no_loop(fn):
    """The search was a `while` on the chip; `compact_front`'s inverse
    is ONE scatter of distinct in-range indices (`unique_indices =
    true`), the form that does not lower to sort-based loops. The
    fan-out places without one: no scatter, sort or gather at all (the
    described-v5e compile in `test_chip_compile.py` holds its prefix
    sums, which lower to reduce-windows only on the chip, to its
    records)."""
    text = _lowered(fn)
    assert "stablehlo.while" not in text
    scatters = [ln for ln in text.splitlines() if '"stablehlo.scatter"(' in ln]
    if fn == "compact_front":
        assert len(scatters) == 1, scatters
        assert "unique_indices = true" in scatters[0]
        return
    assert not scatters
    for op in ("sort", "gather", "dynamic_gather"):
        assert f'"stablehlo.{op}"(' not in text and f"stablehlo.{op} " not in text
