"""R-MAT graphs: Graph500's generator, and the SP-Async paper's ParMat
graphs, which are R-MAT too.

A copy, not an import, of the program's R-MAT stream: a later change to the
program cannot change the graphs the benchmark measures on.
"""
from __future__ import annotations

import numpy as np

CHUNK_EDGES = 1 << 20


def generate(seed: int, scale: int, edge_factor: int | None = None,
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
    build and :func:`bench.graphs.simple_graph` keep the lightest).

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


def tiny(graph: dict) -> dict:
    """``graph`` cut to at most 2**10 vertices for a CPU rehearsal: SCALE
    10, and a folded vertex count kept under 3/4 of the side, with three
    edges a vertex."""
    g = dict(graph, scale=min(graph["scale"], 10))
    if "vertices" in g:
        g["vertices"] = min(g["vertices"], 3 << (g["scale"] - 2))
        g["edges"] = min(g["edges"], 3 * g["vertices"])
    return g
