"""The benchmark's own graph generators and the plain graph form the
reference and the metrics read.

A copy, not an import, of the program's R-MAT stream: a later change to the
program cannot change the graphs the benchmark measures on. Generators are
found by the name a configuration file gives under ``generator``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

CHUNK_EDGES = 1 << 20


def rmat(seed: int, scale: int, edge_factor: int | None = None,
         edges: int | None = None, vertices: int | None = None,
         a: float = 0.57, b: float = 0.19, c: float = 0.19,
         weight_low: float = 1.0, weight_high: float = 20.0,
         topology_seed: int = 0):
    """R-MAT edges as ``(n_vertices, [(src, dst, w), ...])``.

    ``edges`` undirected edges (default ``edge_factor * 2**scale``) are drawn
    over ``2**scale`` ids with quadrant probabilities a, b, c and
    d = 1 - a - b - c, the ids are permuted, and, where ``vertices`` is under
    ``2**scale``, folded onto ``[0, vertices)`` by ``id % vertices``.
    Self-loops are dropped; each edge gets one U[weight_low, weight_high)
    float32 weight and goes both ways. Duplicates are left in (the shard
    build and :func:`simple_graph` keep the lightest).

    The edge set is drawn from ``topology_seed``, the deployment's own, and
    the weights from ``seed``: every seed gives a graph of the same shape,
    so the program's shards, and its compiled programs, have the same
    shapes on every seed. Each chunk draws from its own counter-keyed
    streams.
    """
    side = 1 << scale
    n = side if vertices is None else int(vertices)
    if not 0 < n <= side:
        raise ValueError(f"vertices={n} must lie in (0, 2**scale={side}]")
    m = int(edges) if edges is not None else int(edge_factor) * side
    perm = np.random.default_rng([0, topology_seed]).permutation(side)
    chunks = []
    for i, start in enumerate(range(0, m, CHUNK_EDGES)):
        cm = min(CHUNK_EDGES, m - start)
        rng = np.random.default_rng([1 + i, topology_seed])
        src = np.zeros(cm, np.int64)
        dst = np.zeros(cm, np.int64)
        for level in range(scale):
            r = rng.random(cm, dtype=np.float32)
            bit = np.int64(1) << (scale - 1 - level)
            src |= np.where(r >= a + b, bit, 0)
            dst |= np.where(((r >= a) & (r < a + b)) | (r >= a + b + c), bit, 0)
        src, dst = perm[src] % n, perm[dst] % n
        keep = src != dst
        src, dst = src[keep], dst[keep]
        w = np.random.default_rng([1 + i, 1 << 32, seed % (1 << 64)]).uniform(
            weight_low, weight_high, len(src)).astype(np.float32)
        chunks.append((np.concatenate([src, dst]), np.concatenate([dst, src]),
                       np.concatenate([w, w])))
    return n, chunks


GENERATORS = {"rmat": rmat}


def generate(config: dict, seed: int):
    """``(n_vertices, chunks)`` of a configuration's graph for ``seed``."""
    name = config["generator"]
    if name not in GENERATORS:
        raise KeyError(f"unknown generator {name!r}: have {sorted(GENERATORS)}")
    return GENERATORS[name](seed, **config["graph"])


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
