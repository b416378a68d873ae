"""Host reference of NEXmark Query 5, "hot items": for every sliding
event-time window, the auction(s) with the most bids.

`json`, `dict` and Python `int` only; calls no engine code. The query
as the field runs it (`nexmark/nexmark` `queries/q5.sql`): bids counted
per auction over HOP(dateTime, slide 2 s, size 10 s), and per window
the auction(s) whose count is the window's maximum; the bid table's
watermark is ``dateTime - 4 s``. Record by record, as Flink does it:

- a bid at event time ``t`` counts in every window ``[k*slide, k*slide +
  window)``, ``k >= 0``, that holds ``t``;
- after folding bid ``i`` the watermark is ``max(dateTime seen so far) -
  lateness``; every window with ``end <= watermark`` that was not yet
  emitted is emitted now, with source index ``i``, windows in order of
  their end, within a window the auctions with the maximum count in
  ascending id;
- a window is emitted once: a bid's contribution to a window already
  emitted is LATE, dropped and counted (`fold`'s third result);
- windows still open at the end of the log are not emitted.

The program under test closes windows once a SLICE (late against the
watermark before the slice, close against the one after it), not once
a record. Both give the same rows, attributed to the same slices, while
disorder stays below the watermark's delay: a bid at ``t`` then arrives
while ``max seen <= t + disorder < t + lateness``, so no window that
holds ``t`` (their ends are ``> t``) has been emitted under either rule
and every bid is counted in all its windows under both; a window's rows
are complete when it closes under both, and it closes in the slice that
holds the record whose arrival took the watermark past its end, which is
the record this reference names as the rows' source. With disorder over
the delay the rules differ for a late bid that shares a slice with the
record that closed its window; `tests/test_stream_window.py::
test_late_bid_is_dropped_and_counted_alike` puts its late bid in a
later slice, where they agree again.

Outputs are fresh records (offset delta 0): they read as the base
offset of the response batch that carries them, so a pass is compared as
a value stream in order, with non-decreasing offsets that never pass
their source record.
"""

import json

import numpy as np

OFFSETS = "nondecreasing"


def fold(values, window_ms=10000, slide_ms=2000, lateness_ms=4000):
    """-> (source input index of each output, output values as a list of
    bytes, late contributions dropped)."""
    bids = json.loads(b"[" + b",".join(values) + b"]")
    if len(bids) != len(values):
        raise ValueError("a record holds more than one JSON value")
    windows = {}            # window index k -> {auction: bids}
    emitted_to = -1         # every window with index <= this was emitted
    max_t = None
    late = 0
    src, out = [], []
    for i, bid in enumerate(bids):
        t, auction = int(bid["dateTime"]), int(bid["auction"])
        for k in range(t // slide_ms, t // slide_ms - window_ms // slide_ms, -1):
            if k < 0:
                break
            if k <= emitted_to:
                late += 1
                continue
            row = windows.setdefault(k, {})
            row[auction] = row.get(auction, 0) + 1
        if max_t is not None and t <= max_t:
            continue
        max_t = t
        # windows with k*slide + window <= max_t - lateness
        reach = (max_t - lateness_ms - window_ms) // slide_ms
        for k in sorted(k for k in windows if k <= reach):
            row = windows.pop(k)
            most = max(row.values())
            for auction in sorted(row):
                if row[auction] == most:
                    src.append(i)
                    out.append(b'{"window_end":%d,"auction":%d,"num":%d}' % (
                        k * slide_ms + window_ms, auction, most))
        emitted_to = max(emitted_to, reach)
    return np.array(src, dtype=np.int64), out, late


def expect(values, **params):
    src, out, _late = fold(values, **params)
    return src, out
