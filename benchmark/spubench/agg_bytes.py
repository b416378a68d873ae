"""Bytes the aggregate stage of a chain must move for one dispatched
slice, from its shapes (`shapes.py`'s staging rules: rows pad to a
power of two from 8, the value width to its bucket).

The least the stage can do: read every staged row once (the padded
value matrix plus its 4-byte length, which the field extraction scans)
and write one 8-byte accumulator a row (the int64 column the int-output
mode ships). The scan's own intermediates, the carry and the survivor
mask are not counted: a roofline share over these bytes says how far
the stage is from touching its input and output once at the HBM's peak.
"""

from __future__ import annotations

import re

from spubench.shapes import MIN_ROWS, _next_pow2, bucket_width

ACCUMULATOR_BYTES = 8
_AGG_SCOPE = re.compile(r"^stage\d+\.aggregate")


def agg_stage_bytes(records: int, shape: dict) -> int:
    """``shape``: max_in_len of the configuration's corpus."""
    rows = _next_pow2(max(records, 1), MIN_ROWS)
    return rows * (bucket_width(shape["max_in_len"]) + 4) + rows * ACCUMULATOR_BYTES


def agg_scope_seconds(reduced: dict) -> float:
    """Busy seconds of a reduced trace under the aggregate stages'
    scopes: `stage<i>.aggregate` (the contribution) and
    `stage<i>.aggregate_scan` (the carry chain and the scan)."""
    return sum(
        s for scope, s in reduced["scope_s"].items() if _AGG_SCOPE.match(scope)
    )
