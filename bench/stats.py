"""Arithmetic of the end-to-end metrics."""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it. Infinite values (failed queries) rank last
    and can be the answer."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no values")
    return float(v[max(0, math.ceil(q / 100.0 * v.size) - 1)])


def teps(window) -> float | None:
    """Reached out-edges of every completed query over the window's time."""
    span = window.end - window.start
    if not window.batches or span <= 0:
        return None
    return sum(b.reached_edges for b in window.batches) / span
