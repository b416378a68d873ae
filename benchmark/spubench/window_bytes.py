"""Bytes the merge of a served window stage must move for one
dispatched slice, from its shapes (`shapes.py`'s staging rule: rows pad
to a power of two from 8), and the busy time a reduced trace books under
the stage's scopes.

The least the merge can do: every replicated row (a record counts in
``window_ms // slide_ms`` window phases, the configuration's own: 5 for
NEXmark Q5's HOP of 10 s by 2 s, 1 for a tumbling window) has its
composite id, its accumulator and its count, three int64, read once and
written once: 48 bytes. The
stream's bank rows, the sort's passes and the scans' intermediates are
not counted: a roofline share over these bytes says how far the merge is
from touching its rows once each way at the HBM's peak.
"""

from __future__ import annotations

import re

from spubench.shapes import MIN_ROWS, _next_pow2

ROW_BYTES = 2 * 3 * 8
_WINDOW_SCOPE = re.compile(r"^stage\d+\.window(_merge|_top)?$")
_MERGE_SCOPE = re.compile(r"^stage\d+\.window_merge$")


def merge_bytes(records: int, replicas: int) -> int:
    return _next_pow2(max(records, 1), MIN_ROWS) * replicas * ROW_BYTES


def _seconds(reduced: dict, pattern) -> float:
    return sum(s for scope, s in reduced["scope_s"].items() if pattern.match(scope))


def window_scope_seconds(reduced: dict) -> float:
    """`stage<i>.window` (field spans, parses, window assignment),
    `.window_merge` (concat with the bank, the one sort that carries
    the columns, prefix sums, compaction, close, new bank) and `.window_top` (the per-window
    maximum)."""
    return _seconds(reduced, _WINDOW_SCOPE)


def merge_scope_seconds(reduced: dict) -> float:
    return _seconds(reduced, _MERGE_SCOPE)
