"""Arithmetic of the metrics: percentiles, the traversed-edge count, the
table of peaks and the local fixpoint's byte model."""
from __future__ import annotations

import json
import math
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


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


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind`` (``peaks.json``);
    an error for a kind the table does not hold."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise LookupError(f"device kind {device_kind!r} is not in "
                          f"bench/peaks.json; have {sorted(table)}")
    return table[device_kind]


def local_floor_bytes(relaxations: int, lanes: int) -> float:
    """The least HBM bytes that ``relaxations`` relaxations of the local
    fixpoint move in a batch of ``lanes`` lanes.

    Each relaxation reads its tail's float32 distance and min-writes its
    head's, 8 bytes in its own lane, and reads the edge (int32 tail, int32
    head, float32 weight), 12 bytes that the batch's lanes share: 8 + 12 /
    ``lanes`` bytes. It counts the relaxations the program performed, not
    the edge slots of its shapes, so a kernel that performs the same
    relaxations in fewer bytes reads a higher share, and no kernel that
    reads every edge it relaxes once per sweep can read more than 100%.
    """
    return relaxations * (8.0 + 12.0 / lanes)
