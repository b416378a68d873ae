"""Bytes the group stage of a served keyed table must move for one
dispatched slice, from its shapes (`shapes.py`'s staging rule: rows pad
to a power of two from 8), the busy time a reduced trace books under
the stage's scopes, and the stage's instant events inside a window.

The least the stage can do for NEXmark Q17: every padded row has its
composite key and its seven contributions, eight int64, read once, and
its eight answer columns (the key and seven running aggregates) written
once: 128 bytes. The table's rows, the sort's passes and the scans'
intermediates are not counted: a roofline share over these bytes says
how far the merge and the emission are from touching each row once each
way at the HBM's peak.
"""

from __future__ import annotations

import re

from spubench.shapes import MIN_ROWS, _next_pow2
from spubench.window_events import in_window

COLUMNS = 8                      # the key and seven accumulators
ROW_BYTES = 2 * COLUMNS * 8
_GROUP_SCOPE = re.compile(r"^stage\d+\.group(_merge|_emit)?$")
_WORK_SCOPE = re.compile(r"^stage\d+\.group_(merge|emit)$")


def group_bytes(records: int) -> int:
    return _next_pow2(max(records, 1), MIN_ROWS) * ROW_BYTES


def _seconds(reduced: dict, pattern) -> float:
    return sum(s for scope, s in reduced["scope_s"].items() if pattern.match(scope))


def group_scope_seconds(reduced: dict) -> float:
    """`stage<i>.group` (field spans, parses, the key), `.group_merge`
    (concat with the table, the stable sort, the segmented scans, the
    new table) and `.group_emit` (rows back in offset order, the output
    columns)."""
    return _seconds(reduced, _GROUP_SCOPE)


def work_scope_seconds(reduced: dict) -> float:
    return _seconds(reduced, _WORK_SCOPE)


def events_in_window(obs, kind: str):
    """The stage's events of ``kind`` (`group-grow`, `group-drop`)
    stamped inside the window; None where the program books none (a
    parent commit), or where its event ring overwrote part of the
    window (`window_events.in_window`)."""
    from fluvio_tpu.telemetry import TELEMETRY

    if not hasattr(TELEMETRY, "add_group_grow"):
        return None
    return in_window(obs, kind)
