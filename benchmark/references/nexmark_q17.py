"""Host reference of NEXmark Query 17, "auction statistics report": per
(auction, day) the running count, three counts by price rank, the
minimum, maximum, average and sum of the price, answered bid by bid.

`json`, `dict`, Python `int` and `datetime` only; calls no engine code.
The query as the Flink suite states it (`nexmark/nexmark`
`queries/q17.sql`; "How many bids on an auction made a day and what is
the price? Illustrates an unbounded group aggregation."):

    SELECT auction, DATE_FORMAT(dateTime, 'yyyy-MM-dd') AS `day`,
           count(*) AS total_bids,
           count(*) filter (where price < 10000) AS rank1_bids,
           count(*) filter (where price >= 10000 and price < 1000000) AS rank2_bids,
           count(*) filter (where price >= 1000000) AS rank3_bids,
           min(price) AS min_price, max(price) AS max_price,
           avg(price) AS avg_price, sum(price) AS sum_price
    FROM bid GROUP BY auction, DATE_FORMAT(dateTime, 'yyyy-MM-dd');

Flink answers an unbounded GROUP BY with a changelog: every bid yields
the updated row of its group. So, record by record, in the order of the
log given:

- bid ``i`` of auction ``a`` at ``dateTime`` ``t`` belongs to the group
  ``(a, t // window_ms)`` (``window_ms`` is the day's length: 86,400,000;
  the harness's event-time mode reads it and ``slide_ms`` of every
  configuration it runs, and the day is this query's tumbling bucket);
- it is folded into its group, and ONE output takes its place, at its
  own offset (source index ``i``): the group's row after the fold,
  ``{"auction":<id>,"day":"yyyy-MM-dd","total_bids":<n>,"rank1_bids":<n>,
  "rank2_bids":<n>,"rank3_bids":<n>,"min_price":<n>,"max_price":<n>,
  "avg_price":<n>,"sum_price":<n>}``;
- the table is empty at the first record and never closes a group.

Departures from the query as Flink runs it, each stated: the day is the
UTC day of the epoch-ms ``dateTime`` (Flink formats in the session time
zone); ``avg_price`` is ``sum_price // total_bids`` (Flink's AVG over a
BIGINT is a BIGINT; prices are positive, so floor and truncation agree);
a retraction (-U) row is not emitted, only the upsert (+I / +U) row (a
Fluvio consumer folds rows by key: `rfc/materialize_view.md`); emission
is per record (Flink without mini-batch). A bid whose key cannot be
formed (``auction`` or ``dateTime`` missing, auction outside [0, 2**31),
time outside [0, year 10000)) yields no row.
"""

import datetime
import json

import numpy as np

OFFSETS = "exact"

KEY_LIMIT = 1 << 31
TIME_LIMIT_MS = 253_402_300_800_000    # 10000-01-01T00:00:00Z
RANK1_BELOW, RANK3_FROM = 10_000, 1_000_000
_EPOCH = datetime.datetime(1970, 1, 1)

ROW = (b'{"auction":%d,"day":"%s","total_bids":%d,"rank1_bids":%d,'
       b'"rank2_bids":%d,"rank3_bids":%d,"min_price":%d,"max_price":%d,'
       b'"avg_price":%d,"sum_price":%d}')


def _int(bid, name):
    v = bid.get(name) if isinstance(bid, dict) else None
    return v if isinstance(v, int) and not isinstance(v, bool) else None


def fold(values, window_ms=86_400_000, slide_ms=86_400_000):
    """-> (source input index of each output, output values as a list of
    bytes, records dropped for want of a key)."""
    if slide_ms != window_ms:
        raise ValueError("a day is a tumbling bucket: slide_ms == window_ms")
    bids = json.loads(b"[" + b",".join(values) + b"]")
    if len(bids) != len(values):
        raise ValueError("a record holds more than one JSON value")
    table = {}       # (auction, day) -> [total, r1, r2, r3, min, max, sum]
    day_text = {}
    invalid = 0
    src, out = [], []
    for i, bid in enumerate(bids):
        auction, t = _int(bid, "auction"), _int(bid, "dateTime")
        if (auction is None or t is None or not 0 <= auction < KEY_LIMIT
                or not 0 <= t < TIME_LIMIT_MS):
            invalid += 1
            continue
        price = _int(bid, "price") or 0
        day = t // window_ms
        row = table.get((auction, day))
        if row is None:
            row = table[auction, day] = [0, 0, 0, 0, None, None, 0]
        row[0] += 1
        if price < RANK1_BELOW:
            row[1] += 1
        elif price < RANK3_FROM:
            row[2] += 1
        else:
            row[3] += 1
        row[4] = price if row[4] is None else min(row[4], price)
        row[5] = price if row[5] is None else max(row[5], price)
        row[6] += price
        text = day_text.get(day)
        if text is None:
            text = day_text[day] = (
                _EPOCH + datetime.timedelta(milliseconds=day * window_ms)
            ).strftime("%Y-%m-%d").encode("ascii")
        src.append(i)
        out.append(ROW % (auction, text, row[0], row[1], row[2], row[3],
                          row[4], row[5], row[6] // row[0], row[6]))
    return np.array(src, dtype=np.int64), out, invalid


def expect(values, **params):
    src, out, _invalid = fold(values, **params)
    return src, out
