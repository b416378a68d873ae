"""Drain of a topic of several partitions: one catch-up consumer a
partition, all on the run's one event loop over the client's socket.

Partition p's backlog is an independent draw of the configuration's
corpus (`Session.generate(n_p, stream=p)`; ``--seed`` orders its stored
batches as in `drain`) of the size ``partition_records`` states, and has
a host reference of its own. Set-up writes every partition's backlog,
makes each consumer's first pass and compares it byte for byte with its
partition's reference, then the configuration's further ``warm_passes``
(default 1 in all) with every consumer at once, and `Session.settle`, as
`drain` does. The first passes run one partition after another: the SPU
shares one compiled chain among the streams of one chain (its chain
cache), and streams that all open before it holds one each build a chain
of their own, of which the cache keeps the last; that chain would then
meet the other partitions' chips first inside the window, and compile
there.

One window opens for all the consumers. Each then makes passes over its
own partition from offset 0, each pass a new stream fetch, and stops at
its first response boundary at or after ``--seconds``. ``records_in`` is
the sum of every consumer's input progress and ``window_s`` runs from
the open to the LAST consumer's close, so `records_in_per_s` keeps its
meaning: input records whose results reached their consumer, per second
of window. Each response's count and batch headers are checked against
its own partition's reference, and no compile may fall in the window.
"""

from __future__ import annotations

import asyncio

from spubench import check, manifest, window
from spubench.broker import TOPIC


async def _one_pass(s, p: int, n: int, on_response) -> None:
    cur = 0
    async with s.broker.stream(0, int(s.traffic["max_bytes"]), p) as stream:
        while cur < n:
            r = await stream.next()
            if r.next_offset <= cur and r.records_out == 0:
                raise RuntimeError(
                    f"partition {p}: the stream made no progress at offset {cur}")
            stop = on_response(cur, r)
            cur = r.next_offset
            if stop:
                return


async def _each(coros) -> None:
    """Run the coroutines (one a partition) at once; the first failure
    cancels the others."""
    async with asyncio.TaskGroup() as tg:
        for c in coros:
            tg.create_task(c)


def _groups(s, n: int) -> list:
    """The device group the program's partition gate placed each
    partition on; None a partition where no gate is armed."""
    from fluvio_tpu.partition import partition_key

    gate = s.broker.gate
    plan = gate.plan.assignments if gate is not None else {}
    return [plan.get(partition_key(TOPIC, p)) for p in range(n)]


async def run(s) -> dict:
    counts = manifest.partition_records(s.config)
    parts = range(len(counts))
    refs = []
    for p in parts:
        flat, off = s.generate(counts[p], stream=p)
        refs.append(s.reference(flat, off, 0))
        await s.write_backlog(flat, off, p)
        del flat, off
    s.note(f"{len(counts)} partitions of {counts} records")
    faults = []

    for p in parts:
        got = []
        await _one_pass(s, p, counts[p], lambda cur, r: got.extend(r.batches))
        faults += [f"partition {p} warm-up pass: {f}"
                   for f in check.compare(refs[p], 0, counts[p], got)]
        del got
        s.note(f"partition {p}: warm-up pass 1 received and compared")
    for _ in range(int(s.config.get("warm_passes", 1)) - 1):
        await _each(_one_pass(s, p, counts[p], lambda cur, r: False)
                    for p in parts)
    await s.settle()

    obs = {
        "records_out": 0, "bytes_out": 0, "responses": 0,
        "attempted": 0, "failed": 0,
    }
    records_in = [0] * len(counts)
    closes = [None] * len(counts)
    t_open = s.open_window()

    async def consume(p):
        ref = refs[p]
        pass_bad = False

        def count(cur, r):
            nonlocal pass_bad
            if (r.records_out != ref.count(cur, r.next_offset)
                    or not check.headers_in_order(r.batches, cur, r.next_offset)):
                pass_bad = True
            records_in[p] += r.next_offset - cur
            obs["records_out"] += r.records_out
            obs["bytes_out"] += r.bytes_out
            obs["responses"] += 1
            if r.t - t_open >= s.seconds:
                closes[p] = r.t
                return True
            return False

        while closes[p] is None:
            pass_bad = False
            obs["attempted"] += 1
            await _one_pass(s, p, counts[p], count)
            obs["failed"] += pass_bad

    await _each(consume(p) for p in parts)
    s.tracer.finish()
    c_close = window.snapshot(s.broker)
    t_close = max(closes)
    if obs["failed"]:
        faults.append(f"{obs['failed']} pass(es) in the window delivered other "
                      "counts or offsets than their partition's reference states")
    d = window.delta(s.c_open, c_close)
    if d["compiles"]:
        faults.append(f"{d['compiles']} compile(s) inside the window")
    obs |= {
        "records_in": sum(records_in),
        "window_s": t_close - t_open,
        "t_open": t_open,
        "t_close": t_close,
        "delta": d,
        "faults": faults,
        "c_close": c_close,
        "counts": {"records_in_by_partition": records_in,
                   "partition_groups": _groups(s, len(counts))},
    }
    return obs
