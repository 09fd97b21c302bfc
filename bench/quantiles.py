"""Percentile arithmetic, copied from the program's ``repro.obs.stats.pct``
so that the benchmark's statistics cannot move with the program."""

from __future__ import annotations

import numpy as np


def pct(xs, q: float) -> float:
    """``float(np.percentile(xs, q))``, NaN on an empty input."""
    arr = np.asarray(xs, np.float64)
    if arr.size == 0:
        return float("nan")
    return float(np.percentile(arr, q))
