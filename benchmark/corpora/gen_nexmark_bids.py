"""The bids of a NEXmark event stream, as JSON records on a topic of
their own:
`{"auction":<id>,"bidder":<id>,"price":<cents>,"dateTime":<epoch ms>,"extra":"<pad>"}`.

Written from memory of the generator that Apache Beam
(`sdks/java/testing/nexmark`: `GeneratorConfig`, `BidGenerator`,
`AuctionGenerator`, `PriceGenerator`) and the Flink suite
(`nexmark/nexmark`) share, at its defaults, in vectorised form. Bids
only, but event ids, timestamps and the ids a bid may name progress as
in the full stream: of every 50 events 1 is a person, 3 are auctions and
46 are bids (the bids are events 4..49 of their group of 50), event k
happens at ``base_time_ms + k * 1000 // first_event_rate``, half of all
bids (``hot_auction_ratio`` 2) go to the hot auction ``(last auction id
// 100) * 100``, the others uniformly to the ``num_in_flight_auctions``
newest auctions and the 10 ids ahead of them; three bids in four
(``hot_bidders_ratio`` 4) come from the hot bidder; the price is
``round(10 ** (6u) * 100)``. Set here, not the generator's: ``extra`` is
random lower-case letters that pad the RECORD AS RENDERED HERE to a size
drawn uniformly from ``avg_bid_byte_size`` less a fifth to plus a fifth
(80..120 B at 100; the generator pads its in-memory size estimate), so a
record is never wider than 128 B.
"""

import numpy as np

PERSONS, AUCTIONS, BIDS = 1, 3, 46          # of every GROUP events
GROUP = PERSONS + AUCTIONS + BIDS
FIRST_AUCTION_ID = FIRST_PERSON_ID = 1000
HOT_ID_STEP = 100         # HOT_AUCTION_RATIO / HOT_BIDDER_RATIO, the constants
ID_LEAD = 10              # AUCTION_ID_LEAD / PERSON_ID_LEAD
ACTIVE_PEOPLE = 1000      # NUM_ACTIVE_PEOPLE

FIELDS = (b'{"auction":', b',"bidder":', b',"price":', b',"dateTime":',
          b',"extra":"', b'"}')


def draws(n: int, seed, first_event_rate=10000, hot_auction_ratio=2,
          hot_bidders_ratio=4, num_in_flight_auctions=100,
          avg_bid_byte_size=100, base_time_ms=1436918400000) -> dict:
    """The columns of ``n`` bids: ids, price, time (and their decimal
    texts, ``digits``), pad length and the pad letters (one row of
    letters a bid, the first ``extra_len`` used). `generate` renders
    them; the tests render them record by record."""
    rng = np.random.default_rng(seed)
    b = np.arange(n, dtype=np.int64)
    group = b // BIDS
    event_id = group * GROUP + PERSONS + AUCTIONS + b % BIDS
    last_auction = group * AUCTIONS + AUCTIONS - 1   # newest auction, base 0
    last_person = group                              # newest person, base 0
    hot_a = rng.integers(0, hot_auction_ratio, size=n) > 0
    min_a = np.maximum(last_auction - num_in_flight_auctions, 0)
    cold_a = min_a + (rng.random(n) * (last_auction - min_a + 1 + ID_LEAD)
                      ).astype(np.int64)
    hot_b = rng.integers(0, hot_bidders_ratio, size=n) > 0
    active = np.minimum(last_person + 1, ACTIVE_PEOPLE)
    cold_b = last_person + 1 - active + (
        rng.random(n) * (active + ID_LEAD)).astype(np.int64)
    fifth = avg_bid_byte_size // 5
    cols = {
        "auction": FIRST_AUCTION_ID + np.where(
            hot_a, last_auction // HOT_ID_STEP * HOT_ID_STEP, cold_a),
        "bidder": FIRST_PERSON_ID + np.where(
            hot_b, last_person // HOT_ID_STEP * HOT_ID_STEP + 1, cold_b),
        "price": np.rint(10.0 ** (rng.random(n) * 6.0) * 100.0).astype(np.int64),
        "dateTime": base_time_ms + event_id * 1000 // first_event_rate,
    }
    size = rng.integers(avg_bid_byte_size - fifth,
                        avg_bid_byte_size + fifth + 1, size=n)
    cols["digits"] = {name: _digits(c) for name, c in cols.items()}
    bare = sum(map(len, FIELDS)) + sum(d[1] for d in cols["digits"].values())
    cols["extra_len"] = np.maximum(size - bare, 0)
    cols["letters"] = rng.integers(
        0x61, 0x7B, size=(n, int(cols["extra_len"].max(initial=0)) or 1),
        dtype=np.uint8)
    return cols


def _digits(vals: np.ndarray):
    """(table[n, w] uint8, lens[n]): the decimal text of each value, one
    row a value (`spubench.ragged.digit_table` lists a RANGE of values)."""
    width = len(str(int(vals.max(initial=0))))
    lens = np.ones(len(vals), dtype=np.int64)
    for p in range(1, width):
        lens += vals >= 10 ** p
    table = np.zeros((len(vals), width), dtype=np.uint8)
    for j in range(width):
        table[:, j] = (vals // 10 ** np.maximum(lens - 1 - j, 0)) % 10 + 0x30
    return table, lens


def generate(n: int, seed, **params):
    """-> (flat uint8, offsets int64[n+1]). Every field is written at a
    fixed column of a padded row (its widest text), with a mask of the
    bytes that are text; one boolean select packs the rows."""
    c = draws(n, seed, **params)
    blocks = []
    for name, text in zip(("auction", "bidder", "price", "dateTime"), FIELDS):
        blocks += [text, c["digits"][name]]
    blocks += [FIELDS[4], (c["letters"], c["extra_len"]), FIELDS[5]]
    width = sum(len(b) if isinstance(b, bytes) else b[0].shape[1]
                for b in blocks)
    rows = np.zeros((n, width), dtype=np.uint8)
    text = np.zeros((n, width), dtype=bool)
    at = 0
    for b in blocks:
        if isinstance(b, bytes):
            rows[:, at:at + len(b)] = np.frombuffer(b, dtype=np.uint8)
            text[:, at:at + len(b)] = True
            at += len(b)
        else:
            table, lens = b
            w = table.shape[1]
            rows[:, at:at + w] = table
            text[:, at:at + w] = np.arange(w) < lens[:, None]
            at += w
    off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(text.sum(axis=1), out=off[1:])
    return rows[text], off
