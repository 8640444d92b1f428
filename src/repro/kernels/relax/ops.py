"""jit'd wrappers + host-side dst-tiled layout builder for the relax kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.relax.relax import (
    relax_dst_ragged_fixpoint_batch, relax_dst_tiled,
    relax_dst_tiled_fixpoint, relax_dst_tiled_fixpoint_batch,
    relax_dst_tiled_masked,
)


def build_dst_tiled_layout(src, dst, w, n_vertices: int, *, vb: int = 128,
                           eb: int = 512, with_eid: bool = False):
    """One-time host preprocessing: edges -> [n_vtiles, n_chunks, EB] layout.

    Padding entries use src = block_pad - 1 (gather stays in range; the
    padded distance slot is +inf) and w = +inf so they never win the min.

    With ``with_eid=True`` also returns eid_t: the position of each tiled
    slot in the ORIGINAL edge list (sentinel = len(src) for padding), so
    runtime per-edge state (the Trishla pruned mask) can be gathered into
    tiled order without rebuilding the layout.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float32)
    n_edges = len(src)
    eid = np.arange(n_edges, dtype=np.int64)
    keep = np.isfinite(w)
    src, dst, w, eid = src[keep], dst[keep], w[keep], eid[keep]

    n_vtiles = max(-(-n_vertices // vb), 1)
    block_pad = n_vtiles * vb
    order = np.argsort(dst, kind="stable")
    src, dst, w, eid = src[order], dst[order], w[order], eid[order]
    tile_of = dst // vb
    counts = np.bincount(tile_of, minlength=n_vtiles)
    n_chunks = max(int(-(-counts.max() // eb)) if counts.size else 1, 1)

    src_t = np.full((n_vtiles, n_chunks * eb), block_pad - 1, np.int64)
    w_t = np.full((n_vtiles, n_chunks * eb), np.inf, np.float32)
    dstrel_t = np.zeros((n_vtiles, n_chunks * eb), np.int64)
    eid_t = np.full((n_vtiles, n_chunks * eb), n_edges, np.int64)
    starts = np.zeros(n_vtiles + 1, np.int64)
    starts[1:] = np.cumsum(counts)
    for t in range(n_vtiles):
        lo, hi = starts[t], starts[t + 1]
        k = hi - lo
        src_t[t, :k] = src[lo:hi]
        w_t[t, :k] = w[lo:hi]
        dstrel_t[t, :k] = dst[lo:hi] - t * vb
        eid_t[t, :k] = eid[lo:hi]

    shape3 = (n_vtiles, n_chunks, eb)
    out = (jnp.asarray(src_t.reshape(shape3), jnp.int32),
           jnp.asarray(w_t.reshape(shape3), jnp.float32),
           jnp.asarray(dstrel_t.reshape(shape3), jnp.int32))
    if with_eid:
        return out + (jnp.asarray(eid_t.reshape(shape3), jnp.int32), block_pad)
    return out + (block_pad,)


def build_dst_ragged_layout(src, dst, w, n_vertices: int, *, vb: int = 128,
                            eb: int = 512, with_eid: bool = False):
    """CSR-chunked (ragged) dst layout: edges -> [total_chunks, EB] rows
    plus a [total_chunks] chunk→tile map.

    Same stable dst-sort and per-tile EB split as ``build_dst_tiled_layout``
    — chunk CONTENTS are identical; only the worst-case padding chunks of
    under-full tiles are dropped, so ``total_chunks = sum_t ceil(count_t /
    EB)`` instead of ``n_vtiles * max_t ceil(count_t / EB)``. Built
    directly (never materializes the dense array), so a skewed 10M-edge
    tile histogram costs O(edges), not O(worst case × tiles).

    Returns (src_r, w_r, dstrel_r[, eid_r], ctile, block_pad). Padding
    entries inside a partly-filled chunk mirror the dense builder (src =
    block_pad - 1, w = +inf, eid sentinel = len(src)).
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float32)
    n_edges = len(src)
    eid = np.arange(n_edges, dtype=np.int64)
    keep = np.isfinite(w)
    src, dst, w, eid = src[keep], dst[keep], w[keep], eid[keep]

    n_vtiles = max(-(-n_vertices // vb), 1)
    block_pad = n_vtiles * vb
    order = np.argsort(dst, kind="stable")
    src, dst, w, eid = src[order], dst[order], w[order], eid[order]
    tile_of = dst // vb
    counts = np.bincount(tile_of, minlength=n_vtiles)
    chunks_per_tile = -(-counts // eb)                 # ceil, 0 for empty tiles
    total_chunks = max(int(chunks_per_tile.sum()), 1)

    src_r = np.full((total_chunks, eb), block_pad - 1, np.int64)
    w_r = np.full((total_chunks, eb), np.inf, np.float32)
    dstrel_r = np.zeros((total_chunks, eb), np.int64)
    eid_r = np.full((total_chunks, eb), n_edges, np.int64)
    ctile = np.full(total_chunks, n_vtiles, np.int64)  # sentinel: inert chunk
    starts = np.zeros(n_vtiles + 1, np.int64)
    starts[1:] = np.cumsum(counts)
    row = 0
    for t in range(n_vtiles):
        lo, hi = starts[t], starts[t + 1]
        for off in range(lo, hi, eb):
            k = min(eb, hi - off)
            src_r[row, :k] = src[off:off + k]
            w_r[row, :k] = w[off:off + k]
            dstrel_r[row, :k] = dst[off:off + k] - t * vb
            eid_r[row, :k] = eid[off:off + k]
            ctile[row] = t
            row += 1

    out = (jnp.asarray(src_r, jnp.int32),
           jnp.asarray(w_r, jnp.float32),
           jnp.asarray(dstrel_r, jnp.int32))
    if with_eid:
        out = out + (jnp.asarray(eid_r, jnp.int32),)
    return out + (jnp.asarray(ctile, jnp.int32), block_pad)


@partial(jax.jit, static_argnames=("vb", "eb", "interpret"))
def relax_pallas(dist_pad, src_t, w_t, dstrel_t, *, vb: int = 128,
                 eb: int = 512, interpret: bool | None = None):
    return relax_dst_tiled(dist_pad, src_t, w_t, dstrel_t, vb=vb, eb=eb,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("vb", "eb", "interpret"))
def relax_masked_pallas(dist_pad, front_pad, src_t, w_t, dstrel_t, pruned_t,
                        *, vb: int = 128, eb: int = 512,
                        interpret: bool | None = None):
    """One frontier-masked sweep. Returns (new_dist, n_relax scalar)."""
    new, nrel = relax_dst_tiled_masked(dist_pad, front_pad, src_t, w_t,
                                       dstrel_t, pruned_t, vb=vb, eb=eb,
                                       interpret=interpret)
    return new, nrel[0]


@partial(jax.jit, static_argnames=("vb", "eb", "n_sweeps", "interpret"))
def relax_fixpoint_pallas(dist_pad, front_pad, src_t, w_t, dstrel_t, pruned_t,
                          *, vb: int = 128, eb: int = 512, n_sweeps: int = 8,
                          interpret: bool | None = None):
    """Fused multi-sweep solve. Returns (new_dist, residual_frontier, n_relax)."""
    new, resid, nrel = relax_dst_tiled_fixpoint(
        dist_pad, front_pad, src_t, w_t, dstrel_t, pruned_t, vb=vb, eb=eb,
        n_sweeps=n_sweeps, interpret=interpret)
    return new, resid, nrel[0]


@partial(jax.jit, static_argnames=("vb", "eb", "n_sweeps", "interpret"))
def relax_fixpoint_batch_pallas(dist_pad, front_pad, src_t, w_t, dstrel_t,
                                pruned_t, *, vb: int = 128, eb: int = 512,
                                n_sweeps: int = 8, interpret: bool | None = None):
    """Batched fused solve over a leading query axis K (shared edge layout).

    dist_pad/front_pad: [K, block_pad]. Returns (new_dist [K, block_pad],
    residual_frontier [K, block_pad], n_relax [K])."""
    return relax_dst_tiled_fixpoint_batch(
        dist_pad, front_pad, src_t, w_t, dstrel_t, pruned_t, vb=vb, eb=eb,
        n_sweeps=n_sweeps, interpret=interpret)


@partial(jax.jit, static_argnames=("vb", "eb", "n_sweeps", "interpret"))
def relax_fixpoint_batch_ragged_pallas(dist_pad, front_pad, ctile, src_r, w_r,
                                       dstrel_r, pruned_r, *, vb: int = 128,
                                       eb: int = 512, n_sweeps: int = 8,
                                       interpret: bool | None = None):
    """Ragged-grid batched fused solve (CSR-chunked layout + chunk→tile map).

    Same contract as ``relax_fixpoint_batch_pallas`` with the flat
    [total_chunks, EB] layout from ``build_dst_ragged_layout``."""
    return relax_dst_ragged_fixpoint_batch(
        dist_pad, front_pad, ctile, src_r, w_r, dstrel_r, pruned_r, vb=vb,
        eb=eb, n_sweeps=n_sweeps, interpret=interpret)


@jax.jit
def relax_jnp(dist, src, dst, w):
    """XLA fallback (same as ref but jit'd for benchmarking)."""
    d_src = jnp.take(dist, src, mode="fill", fill_value=float("inf"))
    return dist.at[dst].min(d_src + w, mode="drop")
