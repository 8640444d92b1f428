"""The benchmark's graphs: the lookup of a configuration's generator, and
the plain graph form the reference and the metrics read.

A generator is a file of its own, ``bench/generators/<generator>.py``,
found by the name a configuration file gives under ``generator``: a new
graph family needs a new file and no edit here. Each is a copy, not an
import, of the program's own generator, so that a later change to the
program cannot change the graphs the benchmark measures on.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import load_module


def generator(name: str):
    """The module ``bench/generators/<name>.py``: its ``generate(seed,
    **graph) -> (n_vertices, chunks)``, and its ``tiny(graph) -> graph``,
    the graph cut to at most 2**10 vertices for a CPU rehearsal."""
    return load_module("generators", name)


def generate(config: dict, seed: int):
    """``(n_vertices, chunks)`` of a configuration's graph for ``seed``:
    each chunk ``(src, dst, w)``, every edge listed in the directions it
    goes."""
    return generator(config["generator"]).generate(seed, **config["graph"])


@dataclasses.dataclass(frozen=True)
class SimpleGraph:
    """Directed graph in CSR order: no self-loops, one (lightest) edge per
    ordered pair. ``dst[indptr[u]:indptr[u + 1]]`` are u's heads."""

    indptr: np.ndarray   # [n + 1] int64
    dst: np.ndarray      # [m] int64
    w: np.ndarray        # [m] float32

    @property
    def n(self) -> int:
        return len(self.indptr) - 1

    @property
    def m(self) -> int:
        return len(self.dst)

    @property
    def out_degree(self) -> np.ndarray:
        return np.diff(self.indptr)


def simple_graph(n: int, chunks) -> SimpleGraph:
    src = np.concatenate([c[0] for c in chunks])
    dst = np.concatenate([c[1] for c in chunks])
    w = np.concatenate([c[2] for c in chunks])
    keep = src != dst
    key = src[keep] * n + dst[keep]
    order = np.argsort(key)
    key, w = key[order], w[keep][order]
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    key, w = key[starts], np.minimum.reduceat(w, starts)
    src = key // n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return SimpleGraph(indptr=indptr, dst=key % n, w=w)
