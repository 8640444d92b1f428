"""Plain single-source shortest paths and the comparison that decides a
run's ``correct``.

The reference is SciPy's Dijkstra in float64 over :class:`SimpleGraph`
(:func:`distances`). The control is label-correcting Bellman-Ford in NumPy
(:func:`shortest_paths`), one source at a time, whose precision is a
parameter: in float64 it gives the reference's rows bit for bit; in
bfloat16 (every sum rounded to bfloat16, as a float32 program computed in
the next precision down would) it must come out not correct. Neither
shares code with the program.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np
import scipy.sparse
from scipy.sparse import csgraph

from bench.graphs import SimpleGraph

PRECISIONS = ("float64", "bfloat16")


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "bfloat16":
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    return x


def distances(g: SimpleGraph, sources) -> np.ndarray:
    """float64 distances ``[len(sources), n]`` from each source, ``inf``
    where unreached: the reference."""
    a = scipy.sparse.csr_matrix((g.w.astype(np.float64), g.dst, g.indptr),
                                shape=(g.n, g.n))
    return csgraph.dijkstra(a, indices=np.asarray(sources, np.int64))


def shortest_paths(g: SimpleGraph, source: int,
                   precision: str = "float64") -> np.ndarray:
    """Distances from ``source`` to every vertex, ``inf`` where unreached,
    as float64."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}: {PRECISIONS}")
    acc = np.float64 if precision == "float64" else np.float32
    w = _round(g.w.astype(acc), precision)
    d = np.full(g.n, np.inf, acc)
    d[source] = 0.0
    frontier = np.array([source], np.int64)
    while frontier.size:
        starts = g.indptr[frontier]
        counts = g.indptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        # edge ids of every frontier vertex's out-edges, run by run
        eid = np.repeat(starts - np.cumsum(counts) + counts, counts)
        eid += np.arange(total)
        tails = np.repeat(frontier, counts)
        heads = g.dst[eid]
        cand = _round(d[tails] + w[eid], precision)
        before = d[heads]
        np.minimum.at(d, heads, cand)
        frontier = np.unique(heads[d[heads] < before])
    return d.astype(np.float64)


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared, beside its limit: it passes at or under it."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def compare(program_rows: np.ndarray, reference_rows: np.ndarray,
            unconverged: int, limits: dict) -> list[Check]:
    """The numbers that decide ``correct``.

    - ``unconverged``: queries in the window that the program did not
      certify as converged, or that never got an answer (limit 0);
    - ``reach_mismatch``: vertices of the sampled rows whose reachability
      differs from the reference's (limit 0);
    - ``max_rel_gap``: the widest relative gap ``|d - ref| / ref`` over the
      vertices both reach, the source excluded.
    """
    prog = np.asarray(program_rows, np.float64)
    ref = np.asarray(reference_rows, np.float64)
    if prog.shape != ref.shape:
        raise ValueError(f"rows {prog.shape} vs reference {ref.shape}")
    reach_p, reach_r = np.isfinite(prog), np.isfinite(ref)
    both = reach_p & reach_r & (ref > 0)
    gap = np.abs(prog[both] - ref[both]) / ref[both]
    return [
        Check("unconverged", float(unconverged), float(limits["unconverged"])),
        Check("reach_mismatch", float(np.count_nonzero(reach_p != reach_r)),
              float(limits["reach_mismatch"])),
        Check("max_rel_gap", float(gap.max()) if gap.size else 0.0,
              float(limits["max_rel_gap"])),
    ]
