"""Jitted update kernels for the windowed-state engine.

The per-batch update is ONE fused device program: window assignment
(tumbling or sliding replication), optional per-key segmentation, a
sort-based segmented merge of the batch's contributions into the
HBM-resident state bank, watermark advance, window closing, and
delta-row compaction — everything up to (but not including) the tiny
delta D2H. The inter-batch carry is the bank itself: ``capacity``
(id, acc, count) rows plus one watermark scalar, the same constant-size
inter-chunk state shape as the partition carry bank (SSM chunked-scan
argument), never re-uploaded between batches.

Merge strategy: concat (bank entries ++ replicated batch rows), one
argsort over the composite int64 segment id (key * KEY_STRIDE +
window_index; empties sort last), segment heads where the id changes,
then the SAME `segmented_scan` primitives the aggregate engine uses —
bit-exact for the integer monoids, and associative, which is what makes
the bank mergeable across striped/sharded ingest (``merge`` below is
the shard-combine).
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

from fluvio_tpu.windows.spec import EMPTY_ID, INT64_MIN, KEY_STRIDE, WindowSpec


def _jnp():
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# Keyed record parsing
# ---------------------------------------------------------------------------


def parse_two_ints(values, lengths) -> Tuple:
    """Per-record ``"<key> <value>"`` parse: the leading ASCII int and
    the int after the first space (0 when absent). Reuses the engine's
    `parse_int` scan twice over a shifted view instead of growing a
    second two-field state machine."""
    import jax.numpy as jnp

    from fluvio_tpu.smartengine.tpu.kernels import parse_int

    n, width = values.shape
    lengths = lengths.astype(jnp.int32)
    first = parse_int(values, lengths)
    col = jnp.arange(width, dtype=jnp.int32)
    is_sp = (values == 32) & (col[None, :] < lengths[:, None])
    has_sp = jnp.any(is_sp, axis=1)
    sp = jnp.argmax(is_sp, axis=1).astype(jnp.int32)
    idx = jnp.clip(sp[:, None] + 1 + col[None, :], 0, width - 1)
    shifted = jnp.take_along_axis(values, idx, axis=1)
    rest = jnp.where(has_sp, lengths - sp - 1, 0)
    second = parse_int(shifted, rest)
    return first, second


# ---------------------------------------------------------------------------
# Segmented merge (the bank combine)
# ---------------------------------------------------------------------------


_SCAN_LANE = 128


def prefix_sum(x):
    """Inclusive prefix sum of a vector, exact (integer adds), as a
    blocked two-level scan: rows of 128 are scanned by a reduce-window
    no wider than a row, the rows' totals by the same scan one level
    up. It is the tree the chip's compiler makes of `jnp.cumsum`'s one
    long reduce-window itself, stated here so that its operations keep
    the caller's `jax.named_scope`: the compiler's rewritten ones carry
    no `op_name`, and `jnp.cumsum`'s lowering on a TPU drops the name
    stack (22 ms a million bids of the merge's device time booked to no
    scope; PERF.md section 6, PR 35)."""
    import jax.numpy as jnp
    from jax import lax

    def row_scan(rows):
        w = rows.shape[1]
        return lax.reduce_window(
            rows, np.zeros((), rows.dtype), lax.add,
            (1, w), (1, 1), [(0, 0), (w - 1, 0)],
        )

    n = x.shape[0]
    if n <= _SCAN_LANE:
        return row_scan(x[None, :])[0] if n else x
    rows = jnp.pad(x, (0, -n % _SCAN_LANE)).reshape(-1, _SCAN_LANE)
    inner = row_scan(rows)
    through = prefix_sum(inner[:, -1])
    before = jnp.concatenate([jnp.zeros((1,), x.dtype), through[:-1]])
    return (inner + before[:, None]).reshape(-1)[:n]


def compact_front(mask, cap: int, *arrays):
    """The first ``cap`` rows of ``arrays`` where ``mask`` holds, in
    order: (rows kept by the mask — MAY exceed ``cap`` —, the packed
    columns, zeros past the count; a 2-D array is a stack of columns,
    packed along its last axis). By GATHER: a row's rank is a prefix
    sum of the mask, output slot k binary-searches the row of rank k + 1,
    so the cost is ``cap`` lookups and not one scattered write a row
    (`smartengine.tpu.kernels.compact_rows`: on the chip a scatter of
    1.4 M int64 rows took 82 ms, eight of them three quarters of a
    window slice; PERF.md section 6, PR 35)."""
    import jax.numpy as jnp

    cap = min(cap, mask.shape[0])
    rank = prefix_sum(mask.astype(jnp.int32))
    slot = jnp.arange(cap, dtype=jnp.int32)
    src = jnp.minimum(
        jnp.searchsorted(rank, slot + 1, side="left"), mask.shape[0] - 1
    )
    live = slot < rank[-1]
    return rank[-1], tuple(
        jnp.where(
            live,
            # `src` is in bounds already; the default fill mode lowers
            # through a function that drops the caller's scope
            jnp.take(arr, src, axis=arr.ndim - 1, mode="clip"),
            jnp.zeros((), arr.dtype),
        )
        for arr in arrays
    )


def _sort_segments(cols, num_keys: int = 1):
    """The ONE sort of a banked stage: by the first column (composite
    ids), carrying the others along. Returns (sorted ids, the other
    columns sorted, ``change``: row i + 1 starts another id). The
    monoids commute, so a merge that reads only segment totals needs no
    order among ties; a stage that answers every ROW in arrival order
    makes its position column the second key (a stable sort by another
    name: positions are distinct; it compiles in two thirds of the
    time)."""
    from jax import lax

    sid, *rest = lax.sort(cols, num_keys=num_keys, is_stable=False)
    return sid, rest, sid[1:] != sid[:-1]


_SCAN_BLOCK = 1024


def segment_scans(head, lanes, ops):
    """Inclusive scans along the rows of ``lanes`` (int64[lanes, n], one
    monoid of ``ops`` each) that restart at every position where
    ``head`` holds, as a BLOCKED two-level associative scan of (flag,
    the add lanes, the max lanes, the min lanes): within blocks of
    1,024, across the blocks' last values, then each block's carry
    folded into its positions ahead of the block's first head. No
    gather (on the chip a gathered word costs what forty scanned ones
    do), and no int64 reduce-window or `jnp.cumsum` (either compiles
    for two minutes at a slice's size on the chip's compiler, one long
    associative scan of a [lanes, n] matrix for ten; the blocked form
    for half a minute)."""
    import jax.numpy as jnp
    from jax import lax

    from fluvio_tpu.windows.spec import OP_NEUTRAL

    fns = {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum}
    groups = [(op, [i for i, o in enumerate(ops) if o == op]) for op in fns]
    groups = [(fns[op], OP_NEUTRAL[op], rows) for op, rows in groups if rows]

    def combine(a, b):
        return (a[0] | b[0],) + tuple(
            jnp.where(b[0], y, fn(x, y))
            for (fn, _, _), x, y in zip(groups, a[1:], b[1:])
        )

    n = head.shape[0]
    block = min(_SCAN_BLOCK, n)
    pad = -n % block
    # padding starts segments of its own: it carries nothing forward
    flags = jnp.pad(head, (0, pad), constant_values=True)
    flags = flags.reshape(1, -1, block)
    blocks = tuple(
        jnp.pad(
            jnp.stack([lanes[i] for i in rows]), ((0, 0), (0, pad))
        ).reshape(len(rows), -1, block)
        for _, _, rows in groups
    )
    seen, *inner = lax.associative_scan(combine, (flags,) + blocks, axis=2)
    _, *through = lax.associative_scan(
        combine, (seen[:, :, -1],) + tuple(b[:, :, -1] for b in inner), axis=1
    )
    out = [None] * len(ops)
    for (fn, neutral, rows), part, ends in zip(groups, inner, through):
        carry = jnp.concatenate(
            [jnp.full((len(rows), 1), neutral, ends.dtype), ends[:, :-1]],
            axis=1,
        )
        whole = jnp.where(seen, part, fn(carry[:, :, None], part))
        whole = whole.reshape(len(rows), -1)[:, :n]
        for j, i in enumerate(rows):
            out[i] = whole[j]
    return jnp.stack(out)


def _segment_merge(ids, accs, cnts, touched, op: str, entry_cap: int):
    """Combine rows sharing a composite id: ONE sort that carries the
    columns along, prefix sums, and the segments' totals read at their
    last rows. Returns (sorted ids and the is-entry mask over all rows,
    for exact counts; then the first ``entry_cap`` entries, compacted to
    the front: ids — empty slots re-marked EMPTY_ID —, accs, counts,
    touched-or-None, live mask). A caller whose merge may hold more
    entries than ``entry_cap`` reads the overflow from the counts.
    ``touched`` None (nobody reads which entries this batch touched:
    the bank merge, the served top-of-window update) drops that column."""
    import jax.numpy as jnp

    from fluvio_tpu.smartengine.tpu.kernels import segmented_scan

    cols = [ids, accs, cnts] + ([touched] if touched is not None else [])
    sid, (sacc, *counted), change = _sort_segments(cols)
    tail = jnp.concatenate([change, jnp.ones((1,), bool)])
    is_entry = tail & (sid != EMPTY_ID)
    if op == "add":
        acc_col = prefix_sum(sacc)
    else:
        head = jnp.concatenate([jnp.ones((1,), bool), change])
        acc_col = segmented_scan(sacc, head, op)
    n_kept, (e_ids, e_accs, *e_sums) = compact_front(
        is_entry, entry_cap, sid, acc_col, *map(prefix_sum, counted)
    )
    live = jnp.arange(e_ids.shape[0], dtype=jnp.int32) < n_kept
    # a zero id is a REAL composite id (key 0, window 0): dead slots
    # must be re-marked
    e_ids = jnp.where(live, e_ids, EMPTY_ID)

    def total(prefix):
        # a segment's total is the prefix sum at its last row less the
        # one at the entry before (empties sort last: no entry follows one)
        return prefix - jnp.concatenate(
            [jnp.zeros((1,), prefix.dtype), prefix[:-1]]
        )

    if op == "add":
        e_accs = total(e_accs)
    e_cnts, *e_tb = map(total, e_sums)
    return sid, is_entry, e_ids, e_accs, e_cnts, (e_tb or [None])[0], live


def _assign_windows(
    window_ms: int, slide_ms: int, fanout: int, lateness_ms: int,
    neutral: int, watermark, contribs, keys, ts, valid,
):
    """Window assignment of one batch: every record replicated over the
    ``fanout`` window phases that hold its event time (tumbling is
    fanout == 1). Returns (ids, accs, counts — the flat ``n * fanout``
    rows the merge folds into the bank —, the batch's largest valid
    event time, late rows, invalid rows)."""
    import jax.numpy as jnp

    # composite-id packing only holds for keys in [0, KEY_STRIDE): an
    # out-of-range key would silently alias into another key's window-id
    # space (or overflow int64). Such rows are invalid — counted in the
    # header and dropped entirely (no fold, no watermark advance), the
    # same drop-not-corrupt rule as late rows; reference.py mirrors it.
    key_ok = (keys >= 0) & (keys < KEY_STRIDE)
    invalid = valid & ~key_ok
    valid = valid & key_ok
    base_idx = jnp.where(valid, ts // slide_ms, 0)
    j = jnp.arange(fanout, dtype=jnp.int64)
    win_idx = base_idx[:, None] - j[None, :]
    rep_valid = valid[:, None] & (win_idx >= 0)
    win_end = win_idx * slide_ms + window_ms
    # late vs the PRE-batch watermark: the window already closed in an
    # earlier batch, so folding this row in would re-open it — count
    # and drop instead (the host reference applies the same rule)
    late = rep_valid & (win_end + lateness_ms <= watermark)
    rep_valid = rep_valid & ~late
    ids = jnp.where(
        rep_valid, keys[:, None] * KEY_STRIDE + win_idx, EMPTY_ID
    )
    rep_acc = jnp.where(rep_valid, contribs[:, None], neutral)
    batch_max = jnp.max(
        jnp.where(valid, ts, jnp.int64(INT64_MIN + 1)), initial=INT64_MIN + 1
    )
    return (
        ids.reshape(-1), rep_acc.reshape(-1),
        rep_valid.reshape(-1).astype(jnp.int64), batch_max,
        jnp.sum(late).astype(jnp.int64), jnp.sum(invalid).astype(jnp.int64),
    )


def _merge_and_close(
    window_ms: int, slide_ms: int, lateness_ms: int, op: str,
    track_touched: bool, entry_cap: int, bank_ids, bank_accs, bank_cnts,
    watermark, ids, accs, cnts, batch_max,
):
    """Fold a batch's assigned rows into the bank, advance the
    watermark, and say which entries close. Returns (entry ids, accs,
    counts, touched-or-None, closed mask, open mask — over the first
    ``entry_cap`` entries —, new watermark, open entries, closed
    entries: both counted over ALL rows, so exact whatever
    ``entry_cap`` kept). A row's count doubles as its touched flag."""
    import jax.numpy as jnp

    touched = None
    if track_touched:
        touched = jnp.concatenate(
            [jnp.zeros(bank_ids.shape, dtype=jnp.int64), cnts]
        )
    sid, is_entry, e_ids, e_accs, e_cnts, e_tb, live = _segment_merge(
        jnp.concatenate([bank_ids, ids]),
        jnp.concatenate([bank_accs, accs]),
        jnp.concatenate([bank_cnts, cnts]),
        touched,
        op,
        entry_cap,
    )
    new_wm = jnp.maximum(watermark, batch_max)

    def closes(entry_ids, entry):
        win_end = jnp.where(entry, entry_ids % KEY_STRIDE, 0) * slide_ms + window_ms
        return entry & (win_end + lateness_ms <= new_wm)

    closed_rows = closes(sid, is_entry)
    n_closed = jnp.sum(closed_rows).astype(jnp.int64)
    n_open = jnp.sum(is_entry).astype(jnp.int64) - n_closed
    closed = closes(e_ids, live)
    return (e_ids, e_accs, e_cnts, e_tb, closed, live & ~closed, new_wm,
            n_open, n_closed)


def _new_bank(open_m, e_ids, e_accs, e_cnts, capacity: int, neutral):
    """The open entries, compacted to ``capacity`` bank rows (ids,
    accs, counts). ``e_accs`` is one column with its ``neutral``, or a
    keyed table's int64[lanes, entries] with a neutral a lane;
    ``e_cnts`` may be None (a table keeps no count)."""
    import jax.numpy as jnp

    cols = [e_accs] + ([] if e_cnts is None else [e_cnts])
    n_open, (o_ids, *o_cols) = compact_front(open_m, capacity, e_ids, *cols)
    pad = capacity - o_ids.shape[0]  # fewer entries than bank rows
    if pad:
        o_ids, *o_cols = (
            jnp.concatenate(
                [c, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1
            )
            for c in (o_ids, *o_cols)
        )
    in_bank = jnp.arange(capacity, dtype=jnp.int32) < n_open
    fill = (jnp.int64(neutral) if np.ndim(neutral) == 0
            else jnp.asarray(neutral, dtype=jnp.int64)[:, None])
    return (
        jnp.where(in_bank, o_ids, EMPTY_ID),
        jnp.where(in_bank, o_cols[0], fill),
        None if e_cnts is None else jnp.where(in_bank, o_cols[1], jnp.int64(0)),
    )


def _update_core(
    window_ms: int,
    slide_ms: int,
    fanout: int,
    lateness_ms: int,
    op: str,
    neutral: int,
    capacity: int,
    emit_cap: int,
    delta_only: bool,
    bank_ids,
    bank_accs,
    bank_cnts,
    watermark,
    contribs,
    keys,
    ts,
    valid,
):
    """One batch's full window-state transition. Pure function of
    (bank, batch): the bank inputs are NOT donated, so a faulted batch
    retries against the identical carry — exactness under chaos comes
    for free instead of from an undo path."""
    import jax.numpy as jnp

    ids, accs, cnts, batch_max, n_late, n_invalid = _assign_windows(
        window_ms, slide_ms, fanout, lateness_ms, neutral, watermark,
        contribs, keys, ts, valid,
    )
    # more entries than bank and emit rows together is an overflow of
    # one of them, which the header reports from the exact counts
    (e_ids, e_accs, e_cnts, e_tb, closed, open_m, new_wm, n_open,
     n_closed) = _merge_and_close(
        window_ms, slide_ms, lateness_ms, op, True, capacity + emit_cap,
        bank_ids, bank_accs, bank_cnts, watermark, ids, accs, cnts, batch_max,
    )
    # -- delta emission: closed windows always ship; open entries ship
    # only when this batch touched them (delta_only off = full state).
    # Closed rows compact FIRST (the two-block concat keeps them ahead
    # of the open upserts): a close evicts its entry from the bank, so
    # the emit-overflow resync path must still be able to fetch the
    # batch's closes as a bounded prefix of the emit columns — open
    # rows it can recover from the bank image, final aggregates of
    # closed windows live nowhere else.
    emit_open = (open_m & (e_tb > 0)) if delta_only else open_m
    m = e_ids.shape[0]
    e_slice = min(emit_cap, 2 * m)
    n_emit, (em_ids, em_accs, em_cnts, em_closed) = compact_front(
        jnp.concatenate([closed, emit_open]),
        e_slice,
        jnp.concatenate([e_ids, e_ids]),
        jnp.concatenate([e_accs, e_accs]),
        jnp.concatenate([e_cnts, e_cnts]),
        jnp.concatenate(
            [jnp.ones((m,), dtype=jnp.int32), jnp.zeros((m,), dtype=jnp.int32)]
        ),
    )
    # -- new bank: open entries only, compacted to capacity ------------------
    nb_ids, nb_accs, nb_cnts = _new_bank(
        open_m, e_ids, e_accs, e_cnts, capacity, neutral
    )
    header = jnp.stack(
        [
            n_emit.astype(jnp.int64),
            n_open,
            n_closed,
            n_late,
            new_wm,
            (n_open > capacity).astype(jnp.int64),
            (n_emit > e_slice).astype(jnp.int64),
            n_invalid,
        ]
    )
    return (
        header,
        nb_ids,
        nb_accs,
        nb_cnts,
        em_ids,
        em_accs,
        em_cnts,
        em_closed,
    )


# header slots of `update_top` (all int64)
TOP_HEADER = (
    "n_rows", "n_open", "n_closed", "n_late", "watermark", "n_invalid",
)


def update_top(
    window_ms: int,
    slide_ms: int,
    lateness_ms: int,
    op: str,
    emit_cap: int,
    emit_all: bool,
    bank,
    contribs,
    keys,
    ts,
    valid,
    merge_scope: str = "window_merge",
    top_scope: str = "window_top",
):
    """The SERVED window update (traced inside a chain's one program
    for a slice; `smartengine/tpu/executor.py:_WindowStage`): the same
    assignment, fold and close as `_update_core` (the assignment under
    the caller's scope, the rest under ``merge_scope``), and in place of the materialized
    view's delta the ANSWER of the closed windows, so that what crosses
    the down-link is rows of (window end, key, aggregate), not every
    (key, window) aggregate.

    ``bank`` is (ids, accs, counts, watermark); its capacity is the
    arrays' length. The slice's closed rows (at most ``emit_cap``; more
    is the caller's overflow: it re-runs the slice under a larger
    shape) are re-sorted by (window, key); with ``emit_all`` off only
    the key(s) whose aggregate equals their window's maximum stay.
    Returns (header [`TOP_HEADER`], new bank arrays, rows i64[emit_cap,
    3] ordered by (window end, key), the first ``n_rows`` live). The
    caller compares ``n_open`` with the capacity and ``n_closed`` with
    ``emit_cap``; past either the other outputs are not to be read."""
    import jax
    import jax.numpy as jnp

    from fluvio_tpu.smartengine.tpu.kernels import segmented_scan
    from fluvio_tpu.windows.spec import OP_NEUTRAL

    bank_ids, bank_accs, bank_cnts, watermark = bank
    capacity = bank_ids.shape[0]
    neutral = OP_NEUTRAL[op]
    ids, accs, cnts, batch_max, n_late, n_invalid = _assign_windows(
        window_ms, slide_ms, window_ms // slide_ms, lateness_ms, neutral,
        watermark, contribs, keys, ts, valid,
    )
    with jax.named_scope(merge_scope):
        (e_ids, e_accs, e_cnts, _tb, closed, open_m, new_wm, n_open,
         n_closed) = _merge_and_close(
            window_ms, slide_ms, lateness_ms, op, False, capacity + emit_cap,
            bank_ids, bank_accs, bank_cnts, watermark,
            ids, accs, cnts, batch_max,
        )
        nb_ids, nb_accs, nb_cnts = _new_bank(
            open_m, e_ids, e_accs, e_cnts, capacity, neutral
        )
        _n, (c_ids, c_accs) = compact_front(closed, emit_cap, e_ids, e_accs)
        cap = c_ids.shape[0]
    with jax.named_scope(top_scope):
        # (key, window) order -> (window, key) order: the closed rows
        # only, so this sort is over the emit capacity, not the merge's
        c_live = jnp.arange(cap, dtype=jnp.int32) < n_closed
        by_win = jnp.where(
            c_live,
            (c_ids % KEY_STRIDE) * KEY_STRIDE + c_ids // KEY_STRIDE,
            EMPTY_ID,
        )
        order = jnp.argsort(by_win)
        s_id = jnp.take(by_win, order, mode="clip")
        s_acc = jnp.take(c_accs, order, mode="clip")
        keep = s_id != EMPTY_ID
        if not emit_all:
            s_win = s_id // KEY_STRIDE
            change = s_win[1:] != s_win[:-1]
            head = jnp.concatenate([jnp.ones((1,), bool), change])
            tail = jnp.concatenate([change, jnp.ones((1,), bool)])
            # a window's maximum: the running maximum at its last row,
            # carried back over the window by the same scan reversed
            run = segmented_scan(
                jnp.where(keep, s_acc, jnp.int64(INT64_MIN)), head, "max"
            )
            win_max = segmented_scan(run[::-1], tail[::-1], "max")[::-1]
            keep = keep & (s_acc == win_max)
        n_rows, (t_id, t_acc) = compact_front(keep, cap, s_id, s_acc)
        rows = jnp.stack(
            [
                (t_id // KEY_STRIDE) * slide_ms + window_ms,
                t_id % KEY_STRIDE,
                t_acc,
            ],
            axis=1,
        )
    header = jnp.stack(
        [
            n_rows.astype(jnp.int64),
            n_open,
            n_closed,
            n_late,
            new_wm,
            n_invalid,
        ]
    )
    return header, (nb_ids, nb_accs, nb_cnts, new_wm), rows


# header slots of `update_group` (all int64)
GROUP_HEADER = ("n_rows", "n_keys", "n_invalid")


def update_group(ops, bank, ids, lanes, valid,
                 merge_scope: str = "group_merge",
                 emit_scope: str = "group_emit"):
    """The SERVED update of a keyed table (`dsl.GroupProgram`; traced
    inside a chain's one program for a slice, `smartengine/tpu/
    group_stage.py`): every row is answered by its group's accumulators
    as they stand after it, and the table takes the slice's groups in.

    ``bank`` is (ids, accs int64[lanes, capacity], None, watermark:
    carried, not read); ``ids`` the rows' composite ids (EMPTY_ID where
    a ``valid`` row has no key), ``lanes`` int64[lanes, rows] their
    contributions. Under ``merge_scope``: the table's entries ahead of
    the rows, ONE sort by (id, position) (`_sort_segments`: an
    entry then leads its group's rows, which keep their order; nine
    carried int64 columns compile for twelve minutes on the chip's
    compiler, so the lanes follow by one gather of the stacked matrix),
    the segmented scans (`segment_scans`: the scan at a row IS its
    answer, the entry's totals included), and the new table from each
    group's last row (`_new_bank`). Under ``emit_scope``: the rows back
    in arrival order, by the inverse of the sort's permutation (an
    int32 scatter of positions) and one gather of the stacked answers.
    Returns (header [`GROUP_HEADER`], the new bank's arrays, the
    answers int64[1 + lanes, rows]: id, then a row a lane). The caller
    compares ``n_keys`` with the capacity; past it the other outputs
    are not to be read."""
    import jax
    import jax.numpy as jnp

    from fluvio_tpu.windows.spec import OP_NEUTRAL

    bank_ids, bank_accs, _no_counts, watermark = bank
    capacity = bank_ids.shape[0]
    neutral = tuple(OP_NEUTRAL[op] for op in ops)
    keyed = valid & (ids != EMPTY_ID)
    with jax.named_scope(merge_scope):
        ids = jnp.where(keyed, ids, EMPTY_ID)
        pos = jnp.arange(capacity + ids.shape[0], dtype=jnp.int32)
        sid, (spos,), change = _sort_segments(
            [jnp.concatenate([bank_ids, ids]), pos], num_keys=2
        )
        fill = jnp.asarray(neutral, dtype=jnp.int64)[:, None]
        merged = jnp.concatenate(
            [bank_accs, jnp.where(keyed, lanes, fill)], axis=1
        )
        edge = jnp.ones((1,), bool)
        scans = segment_scans(
            jnp.concatenate([edge, change]),
            jnp.take(merged, spos, axis=1, mode="clip"),
            ops,
        )
        is_entry = jnp.concatenate([change, edge]) & (sid != EMPTY_ID)
        nb_ids, nb_accs, _ = _new_bank(
            is_entry, sid, scans, None, capacity, neutral
        )
    with jax.named_scope(emit_scope):
        at = (
            jnp.zeros(pos.shape, jnp.int32)
            .at[spos].set(pos, unique_indices=True)[capacity:]
        )
        rows = jnp.take(
            jnp.concatenate([sid[None, :], scans]), at, axis=1, mode="clip"
        )
    header = jnp.stack([
        jnp.sum(keyed).astype(jnp.int64),
        jnp.sum(is_entry).astype(jnp.int64),
        jnp.sum(valid & ~keyed).astype(jnp.int64),
    ])
    return header, (nb_ids, nb_accs, None, watermark), rows


def _merge_core(op: str, neutral: int, capacity: int, a, b):
    """Associative bank combine for striped/sharded ingest: two banks'
    entries merge into one (watermark = max). No closing and no
    emission here — those happen at the next `update` against the
    merged bank, so split ingest stays bit-equal to serial ingest."""
    import jax.numpy as jnp

    a_ids, a_accs, a_cnts, a_wm = a
    b_ids, b_accs, b_cnts, b_wm = b
    _sid, is_entry, e_ids, e_accs, e_cnts, _tb, live = _segment_merge(
        jnp.concatenate([a_ids, b_ids]),
        jnp.concatenate([a_accs, b_accs]),
        jnp.concatenate([a_cnts, b_cnts]),
        None,
        op,
        a_ids.shape[0] + b_ids.shape[0],
    )
    n_open = jnp.sum(is_entry)
    nb_ids, nb_accs, nb_cnts = _new_bank(
        live, e_ids, e_accs, e_cnts, capacity, neutral
    )
    header = jnp.stack(
        [
            n_open.astype(jnp.int64),
            jnp.maximum(a_wm, b_wm),
            (n_open > capacity).astype(jnp.int64),
        ]
    )
    return header, nb_ids, nb_accs, nb_cnts


# ---------------------------------------------------------------------------
# Jit construction (instrumented like the executor's chain jits)
# ---------------------------------------------------------------------------


def _spec_statics(spec: WindowSpec) -> tuple:
    return (
        spec.window_ms,
        spec.slide_ms,
        spec.fanout,
        spec.lateness_ms,
        spec.op,
        spec.neutral,
        spec.capacity,
        spec.emit_capacity,
        spec.delta_only,
    )


def _from_values(statics, keyed, bank_ids, bank_accs, bank_cnts, watermark,
                 values, lengths, ts, valid):
    import jax.numpy as jnp

    from fluvio_tpu.smartengine.tpu.kernels import parse_int

    if keyed:
        keys, contribs = parse_two_ints(values, lengths)
    else:
        keys = jnp.zeros(values.shape[:1], dtype=jnp.int64)
        contribs = parse_int(values, lengths)
    return _update_core(
        *statics, bank_ids, bank_accs, bank_cnts, watermark,
        contribs, keys, ts, valid,
    )


def _from_arrays(statics, bank_ids, bank_accs, bank_cnts, watermark,
                 contribs, keys, ts, valid):
    return _update_core(
        *statics, bank_ids, bank_accs, bank_cnts, watermark,
        contribs, keys, ts, valid,
    )


class WindowJits:
    """The compiled surface for one `WindowSpec`: the value-parsing
    update (single-device RecordBuffer path), the pre-parsed-array
    update (the seam striped/sharded split-backs feed), and the bank
    merge (the shard combine). Shared across engines of the same spec
    so partitioned runtimes compile once, and instrumented like every
    other engine entry point so compiles land on the telemetry ladder
    and the jaxpr-lint AOT work list."""

    def __init__(self, spec: WindowSpec):
        import jax

        from fluvio_tpu.telemetry.compiles import instrument_jit

        self.spec = spec
        statics = _spec_statics(spec)
        sig = spec.describe()

        def describe_values(*args, **kwargs):
            return f"{sig} rows={args[4].shape[0]}x{args[4].shape[1]}"

        def describe_arrays(*args, **kwargs):
            return f"{sig} rows={args[4].shape[0]}"

        self.update_values = instrument_jit(
            jax.jit(
                functools.partial(_from_values, statics, spec.keyed)
            ),
            "window",
            describe_values,
        )
        self.update_arrays = instrument_jit(
            jax.jit(functools.partial(_from_arrays, statics)),
            "window",
            describe_arrays,
        )
        self.merge = instrument_jit(
            jax.jit(
                functools.partial(
                    _merge_core, spec.op, spec.neutral, spec.capacity
                )
            ),
            "window",
            lambda *a, **k: f"{sig} merge",
        )


def trace_update(spec: WindowSpec, rows: int = 8, width: int = 32):
    """Abstract-trace the windowed update for the jaxpr lint / AOT
    work list (mirrors `jaxpr_lint.scan_function` call shape)."""
    import jax.numpy as jnp

    from fluvio_tpu.analysis.jaxpr_lint import scan_function

    statics = _spec_statics(spec)
    k = spec.capacity
    return scan_function(
        functools.partial(_from_values, statics, spec.keyed),
        jnp.full((k,), EMPTY_ID, dtype=jnp.int64),
        jnp.full((k,), spec.neutral, dtype=jnp.int64),
        jnp.zeros((k,), dtype=jnp.int64),
        jnp.int64(INT64_MIN + 1),
        jnp.asarray(np.zeros((rows, width), dtype=np.uint8)),
        jnp.zeros((rows,), dtype=jnp.int32),
        jnp.zeros((rows,), dtype=jnp.int64),
        jnp.ones((rows,), dtype=bool),
    )
