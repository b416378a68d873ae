"""Drain: a catch-up consumer reads the whole backlog, again and again.

Each pass is a new stream fetch from the beginning of the log. Set-up
writes the backlog and makes the configuration's ``warm_passes`` warm-up
passes (default 1), the first decoded in full and compared with the host
reference. The window opens when the first
measured pass asks for its stream and closes at the first response
boundary after ``--seconds``; stream re-opens at the end of the backlog
fall inside it, as a restarted consumer pays them. Progress is counted in
INPUT offsets (each response's ``next_filter_offset``), so a filter and a
fan-out count alike. Inside the window the consumer counts records, bytes
and batch headers and builds no object per record.
"""

from __future__ import annotations

from spubench import check, window


async def _one_pass(s, ref, n, on_response) -> None:
    cur = 0
    async with s.broker.stream(0, int(s.traffic["max_bytes"])) as stream:
        while cur < n:
            r = await stream.next()
            if r.next_offset <= cur and r.records_out == 0:
                raise RuntimeError(f"the stream made no progress at offset {cur}")
            stop = on_response(cur, r)
            cur = r.next_offset
            if stop:
                return


async def run(s) -> dict:
    n = int(s.config["backlog_records"])
    flat, off = s.generate(n)
    s.note(f"corpus of {n} records")
    ref = s.reference(flat, off, 0)
    await s.write_backlog(flat, off)
    del flat, off
    faults = []

    warm = []

    def keep(cur, r):
        warm.extend(r.batches)

    await _one_pass(s, ref, n, keep)
    s.note("warm-up pass 1 received")
    faults += [f"warm-up pass: {f}" for f in check.compare(ref, 0, n, warm)]
    del warm
    s.note("warm-up pass 1 compared")
    # a chain whose programs settle only after the first pass (a fan-out
    # chain relearns its output capacity once) states how many warm-up
    # passes it needs; the passes after the first are not decoded
    for _ in range(int(s.config.get("warm_passes", 1)) - 1):
        await _one_pass(s, ref, n, lambda cur, r: False)
    await s.settle()

    obs = {
        "records_in": 0, "records_out": 0, "bytes_out": 0, "responses": 0,
        "attempted": 0, "failed": 0,
    }
    t_open = s.open_window()
    t_close = None
    pass_bad = False

    def count(cur, r):
        nonlocal t_close, pass_bad
        if (r.records_out != ref.count(cur, r.next_offset)
                or not check.headers_in_order(r.batches, cur, r.next_offset)):
            pass_bad = True
        obs["records_in"] += r.next_offset - cur
        obs["records_out"] += r.records_out
        obs["bytes_out"] += r.bytes_out
        obs["responses"] += 1
        if r.t - t_open >= s.seconds:
            t_close = r.t
            return True
        return False

    while t_close is None:
        pass_bad = False
        obs["attempted"] += 1
        await _one_pass(s, ref, n, count)
        obs["failed"] += pass_bad
    s.tracer.finish()
    c_close = window.snapshot(s.broker)
    if obs["failed"]:
        faults.append(f"{obs['failed']} pass(es) in the window delivered other "
                      "counts or offsets than the reference states")
    d = window.delta(s.c_open, c_close)
    if d["compiles"]:
        faults.append(f"{d['compiles']} compile(s) inside the window")
    obs |= {
        "window_s": t_close - t_open,
        "t_open": t_open,
        "t_close": t_close,
        "delta": d,
        "faults": faults,
        "c_close": c_close,
    }
    return obs
