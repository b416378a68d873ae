"""Paced: a tailing consumer while a generator appends at a fixed rate.

Open loop. The consumer stream-fetches from the log's end with the
configuration's chain and never closes. The generator shares the SPU's
event loop (`leader.write_record_set` is a coroutine of that loop, as a
produce request's handler is) and appends one stored batch of about
``batch_bytes`` of records every ``1 / rate_batches_per_s`` seconds, each
stamped with the time it was DUE. A batch's age is the arrival at the
consumer of the response whose ``next_filter_offset`` passes the batch's
last offset, minus its due time, so a stall is charged to the batches
behind it; how late the generator itself ran is reported beside it.

Warm-up walks the slice shapes the rate produces. The broker coalesces
whatever has arrived into one slice, and a slice's program is keyed by
its row bucket and by the buckets of its compressed link form, which
depend on the bytes: so the warm-up appends ``warm_single_batches``
single batches (the slice a sustained rate mostly produces, in the few
variants its data gives) and then bursts of 2, 3, ... ``warm_max_batches``
batches, each burst one record set, hence one slice, and waits for each.
Its output is decoded in full and compared with the host reference; in
the window each response is held to the reference's count and order.
"""

from __future__ import annotations

import asyncio
import math
import time

from spubench import check, window
from spubench.broker import encode_batches
from spubench.stats import percentile

WARM_WAIT_S = 900.0     # one burst's first compile on the chip is slow


def _records_per_batch(flat, off, batch_bytes: int) -> int:
    """How many corpus records fill ``batch_bytes`` of a stored batch's
    record slab, from the encoded size of a 1024-record sample."""
    sample = min(1024, len(off) - 1)
    raw = encode_batches(flat, off, 0, sample, sample)[0].raw_records
    return max(1, int(batch_bytes // math.ceil(len(raw) / sample)))


async def run(s) -> dict:
    tr = s.traffic
    rate = float(tr["rate_batches_per_s"])
    interval = 1.0 / rate
    warm_singles = int(tr["warm_single_batches"])
    warm_max = int(tr["warm_max_batches"])
    grace_s = float(tr["grace_s"])
    n0 = int(s.config["backlog_records"])

    flat, off = s.generate(n0)
    await s.write_backlog(flat, off)
    per = _records_per_batch(flat, off, int(tr["batch_bytes"]))
    del flat, off

    bursts = [1] * warm_singles + list(range(2, warm_max + 1))
    n_warm = sum(bursts)
    n_win = max(1, math.ceil(rate * s.seconds))
    flat, off = s.generate((n_warm + n_win) * per, stream=1)
    ref = s.reference(flat, off, n0)
    batches = encode_batches(flat, off, 0, len(off) - 1, per)
    del flat, off
    faults = []

    seen = []            # (t, next_offset, ok) per response
    kept = []            # warm-up batches, for the full compare
    state = {"cur": n0, "keep": True}

    async def consume():
        async with s.broker.stream(n0, int(tr["max_bytes"])) as stream:
            while True:
                r = await stream.next()
                cur = state["cur"]
                ok = (
                    r.next_offset > cur
                    and r.records_out == ref.count(cur, r.next_offset)
                    and check.headers_in_order(r.batches, cur, r.next_offset)
                )
                if state["keep"]:
                    kept.extend(r.batches)
                seen.append((r.t, r.next_offset, ok))
                state["cur"] = max(cur, r.next_offset)

    async def caught_up(target: int, timeout: float) -> bool:
        t_end = time.perf_counter() + timeout
        while state["cur"] < target:
            if consumer.done() or time.perf_counter() > t_end:
                return False
            await asyncio.sleep(0.001)
        return True

    consumer = asyncio.ensure_future(consume())
    try:
        k = 0
        for j in bursts:
            end = await s.broker.write(batches[k:k + j])
            k += j
            if not await caught_up(end, WARM_WAIT_S):
                raise RuntimeError(f"warm-up burst of {j} batches never arrived")
        warm_end = n0 + n_warm * per
        faults += [f"warm-up: {f}" for f in
                   check.compare(ref, n0, warm_end, kept)]
        kept.clear()
        state["keep"] = False
        n_warm_responses = len(seen)
        s.note(f"warm-up: {len(bursts)} bursts served and compared")
        await s.settle()

        # the window
        t_open = s.open_window()
        due, late, last = [], [], []
        lag_mid = None
        for i in range(n_win):
            t_due = t_open + i * interval
            wait = t_due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            t_w = time.perf_counter()
            end = await s.broker.write([batches[n_warm + i]])
            due.append(t_due)
            late.append(t_w - t_due)
            last.append(end - 1)
            if lag_mid is None and i >= n_win // 2:
                lag_mid = end - state["cur"]
        t_gen_end = time.perf_counter()
        lag_end = s.broker.log_end() - state["cur"]
        await caught_up(s.broker.log_end(), grace_s)
        t_close = time.perf_counter()
        s.tracer.finish()
    finally:
        consumer.cancel()
        await asyncio.gather(consumer, return_exceptions=True)
    if consumer.done() and not consumer.cancelled() and consumer.exception():
        raise consumer.exception()
    c_close = window.snapshot(s.broker)

    # one age sample per written batch: the first response past its end
    ages, failed = [], 0
    win_seen = seen[n_warm_responses:]
    j = 0
    for t_due, last_off in zip(due, last):
        while j < len(win_seen) and win_seen[j][1] <= last_off:
            j += 1
        if j == len(win_seen) or not win_seen[j][2]:
            failed += 1
            continue
        ages.append(win_seen[j][0] - t_due)
    if failed:
        faults.append(f"{failed} written batch(es) did not arrive within "
                      f"{grace_s} s of the window's end, or arrived wrong")
    offs = [n0 + n_warm * per] + [o for _, o, _ in win_seen]
    return {
        "counts": {
            "lag_mid_records": lag_mid,
            "lag_end_records": lag_end,
            "gen_late_p95_ms": percentile(late, 0.95) * 1000.0,
            "records_per_batch": per,
            "max_slice_batches": max(
                (-(-(b - a) // per) for a, b in zip(offs, offs[1:])), default=0),
        },
        "attempted": n_win,
        "failed": failed,
        "records_in": state["cur"] - warm_end,
        "responses": len(win_seen),
        "ages_s": ages,
        "late_s": late,
        "window_s": t_gen_end - t_open,
        "t_open": t_open,
        "t_close": t_close,
        "delta": window.delta(s.c_open, c_close),
        "faults": faults,
        "c_close": c_close,
    }
