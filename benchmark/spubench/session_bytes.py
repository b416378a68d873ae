"""Bytes the session stage of served session windows must move for one
dispatched slice, from its shapes (`shapes.py`'s staging rule: rows pad
to a power of two from 8), the busy time a reduced trace books under the
stage's scopes, and the stage's instant events inside a window.

The least the stage can do for NEXmark Q11: every padded row's
(key, start, last, count), four int64, read once, and written once as
the row's entry of a session (its totals): 64 bytes. The bank's
sessions, the sort's passes and the scans' intermediates are not
counted: a roofline share over these bytes says how far the merge and
the close are from touching each row once each way at the HBM's peak.
"""

from __future__ import annotations

import re

from spubench.shapes import MIN_ROWS, _next_pow2
from spubench.window_events import in_window

COLUMNS = 4                      # key, start, last, count
ROW_BYTES = 2 * COLUMNS * 8
_SESSION_SCOPE = re.compile(r"^stage\d+\.session(_merge|_close)?$")
_WORK_SCOPE = re.compile(r"^stage\d+\.session_(merge|close)$")


def session_bytes(records: int) -> int:
    return _next_pow2(max(records, 1), MIN_ROWS) * ROW_BYTES


def _seconds(reduced: dict, pattern) -> float:
    return sum(s for scope, s in reduced["scope_s"].items() if pattern.match(scope))


def session_scope_seconds(reduced: dict) -> float:
    """`stage<i>.session` (field spans, parses, the key), `.session_merge`
    (concat with the bank, the sort, the gather, the two segmented
    scans) and `.session_close` (the close, the closed rows and the new
    bank compacted)."""
    return _seconds(reduced, _SESSION_SCOPE)


def work_scope_seconds(reduced: dict) -> float:
    return _seconds(reduced, _WORK_SCOPE)


def events_in_window(obs, kind: str):
    """The stage's events of ``kind`` (`session-grow`, `session-drop`)
    stamped inside the window; None where the program books none (a
    parent commit), or where its event ring overwrote part of the
    window (`window_events.in_window`)."""
    from fluvio_tpu.telemetry import TELEMETRY

    if not hasattr(TELEMETRY, "add_session_grow"):
        return None
    return in_window(obs, kind)
