"""``correct``: every label the window served against the reference.

The numbers compared, each with its limit (see PERF.md for the readings
the limits were set from):

* ``wrong_labels``: served labels that differ from the plain
  reference's label for the same feature row. Exact comparison: 0.
* ``unanswered``: requests of the window that never got a label
  (rejected, failed, or not done a minute after the close): 0.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

LIMITS = {"wrong_labels": 0, "unanswered": 0}


def compare(served, ref_labels: np.ndarray) -> Dict[str, int]:
    """Readings of one run: the two counts above and how many labels
    were compared."""
    ok = np.repeat(served.answered, served.rows)
    want = ref_labels[served.pool_rows()[ok]]
    return {"wrong_labels": int(np.count_nonzero(served.labels[ok] != want)),
            "unanswered": int(served.n - served.answered.sum()),
            "compared_labels": int(want.size)}


def verdict(readings: Dict[str, int]) -> bool:
    return (readings["compared_labels"] > 0
            and all(readings[k] <= v for k, v in LIMITS.items()))


def table(readings: Dict[str, int]) -> Dict[str, dict]:
    """The result line's ``checks`` entry: each number with its limit."""
    return {k: {"value": readings[k], "limit": v} for k, v in LIMITS.items()}
