"""Host reference of NEXmark Query 11, "user sessions": per bidder, the
number of bids in each session of bids no more than 10 s apart.

`json`, `dict` and Python `int` only; calls no engine code. The query as
the Flink suite states it (`nexmark/nexmark` `queries/q11.sql`; Apache
Beam's `Query11` / `UserSessions` is the same, with the same gap):

    SELECT B.bidder, count(*) AS bid_count,
           SESSION_START(B.dateTime, INTERVAL '10' SECOND) AS starttime,
           SESSION_END(B.dateTime, INTERVAL '10' SECOND) AS endtime
    FROM bid B GROUP BY B.bidder, SESSION(B.dateTime, INTERVAL '10' SECOND)

with the bid table's watermark ``dateTime - 4 s``. Record by record, as
Flink's event-time session windows run it:

- bid ``i`` of bidder ``b`` at ``dateTime`` ``t`` opens the window
  ``[t, t + gap)``; it merges with every open session of ``b`` that it
  intersects, ends included (``start <= other.end and end >=
  other.start``, Flink's `TimeWindow.intersects`: two bids exactly
  ``gap`` apart share a session), and a bid between two sessions merges
  all three;
- a session's ``starttime`` is its least ``t``, its ``endtime`` its
  largest ``t`` plus ``gap``, ``bid_count`` its bids;
- after bid ``i`` the watermark is ``max(dateTime seen so far) -
  lateness``; every open session with ``endtime <= watermark`` is emitted
  now, with source index ``i``, in order of (``endtime``, ``bidder``), and
  leaves the state;
- a bid whose own window ``[t, t + gap)`` already ends at or below the
  watermark is LATE: dropped and counted; a bidder outside [0, 2**31) is
  dropped and counted invalid;
- sessions still open at the end of the log are not emitted.

The program under test merges and closes once a SLICE (late against the
watermark before the slice, close against the one after it), not once a
record. Both give the same rows, attributed to the same slices, while
disorder stays below the watermark's delay: a bid at ``t`` then arrives
while ``max seen <= t + disorder < t + lateness``, so its own window
(whose end is ``t + gap``) is late under neither rule; and no session it
touches has been emitted under either (an emitted session ends at or
below ``max seen - lateness < t``), so under both rules it joins the same
open sessions, and a slice's sessions are the same connected unions of
windows; a session closes in the slice that holds the record whose
arrival took the watermark past its end, the record this reference names
as its rows' source, and within one record's rows (end, bidder) order is
also the slice's order (a later record closes only sessions that end
later). With disorder over the delay the rules differ: a late bid that
shares a slice with the record that closed its session is dropped by one
and not the other.

Outputs are fresh records (offset delta 0): they read as the base offset
of the response batch that carries them, so a pass is compared as a
value stream in order, with non-decreasing offsets that never pass their
source record.
"""

import json

import numpy as np

OFFSETS = "nondecreasing"

KEY_LIMIT = 1 << 31

ROW = b'{"bidder":%d,"bid_count":%d,"starttime":%d,"endtime":%d}'


def fold(values, gap_ms=10000, lateness_ms=4000, window_ms=10000,
         slide_ms=10000):
    """-> (source input index of each output, output values as a list of
    bytes, bids dropped late, bids dropped for a bidder out of range).
    ``window_ms`` = ``slide_ms`` is what the harness's event-time mode
    reads of every configuration (one window a record)."""
    if slide_ms != window_ms:
        raise ValueError("a session is one window a record: slide_ms == window_ms")
    bids = json.loads(b"[" + b",".join(values) + b"]")
    if len(bids) != len(values):
        raise ValueError("a record holds more than one JSON value")
    sessions = {}       # bidder -> {start: [start, last, count]}
    due = {}            # endtime -> {(bidder, start): None}
    swept = None        # every session that ends below it was emitted
    max_t = None
    late = invalid = 0
    src, out = [], []
    for i, bid in enumerate(bids):
        bidder, t = int(bid["bidder"]), int(bid["dateTime"])
        if not 0 <= bidder < KEY_LIMIT:
            invalid += 1
            continue
        if max_t is not None and t + gap_ms + lateness_ms <= max_t:
            late += 1
            continue
        mine = sessions.setdefault(bidder, {})
        start, last, count = t, t, 1
        for s in list(mine.values()):
            if s[0] <= t + gap_ms and t <= s[1] + gap_ms:
                del mine[s[0]]
                del due[s[1] + gap_ms][bidder, s[0]]
                start, last, count = min(start, s[0]), max(last, s[1]), count + s[2]
        mine[start] = [start, last, count]
        due.setdefault(last + gap_ms, {})[bidder, start] = None
        if max_t is not None and t <= max_t:
            continue
        max_t = t
        reach = max_t - lateness_ms
        if swept is None:
            swept = reach + 1
        # the ends the watermark passed: one by one while few, else those held
        ends = (range(swept, reach + 1) if reach + 1 - swept <= len(due)
                else sorted(e for e in due if e <= reach))
        closed = []
        for end in ends:
            for bidder_, start_ in due.pop(end, ()):
                s = sessions[bidder_].pop(start_)
                closed.append((end, bidder_, s[2], s[0]))
        swept = max(swept, reach + 1)
        for end, bidder_, n, start_ in sorted(closed):
            src.append(i)
            out.append(ROW % (bidder_, n, start_, end))
    return np.array(src, dtype=np.int64), out, late, invalid


def expect(values, **params):
    src, out, _late, _invalid = fold(values, **params)
    return src, out
