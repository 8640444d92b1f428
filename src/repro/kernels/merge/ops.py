"""jit'd wrappers + host-side msg-tiled layout builder for the merge kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.merge.merge import merge_scatter_ragged, merge_scatter_tiled

INF = float("inf")


def build_msg_ragged_layout(recv_idx, block: int, *, vb: int = 128,
                            eb: int = 512):
    """Ragged (CSR-chunked) msg routing layout: the static receive table ->
    flat [total_chunks, EB] position rows + [total_chunks] chunk→tile map
    (sentinel ``n_vtiles`` for inert padding chunks; their valid plane is
    0). Same stable sort and per-tile EB split as the dense builder.

    Returns (pos_r, dstrel_r, valid_r, ctile, block_pad)."""
    ridx = np.asarray(recv_idx, np.int64).reshape(-1)
    pos = np.arange(ridx.shape[0], dtype=np.int64)
    keep = ridx < block
    ridx, pos = ridx[keep], pos[keep]

    n_vtiles = max(-(-block // vb), 1)
    block_pad = n_vtiles * vb
    order = np.argsort(ridx, kind="stable")
    ridx, pos = ridx[order], pos[order]
    tile_of = ridx // vb
    counts = np.bincount(tile_of, minlength=n_vtiles)
    chunks_per_tile = -(-counts // eb)
    total_chunks = max(int(chunks_per_tile.sum()), 1)

    pos_r = np.zeros((total_chunks, eb), np.int64)
    dstrel_r = np.zeros((total_chunks, eb), np.int64)
    valid_r = np.zeros((total_chunks, eb), np.int64)
    ctile = np.full(total_chunks, n_vtiles, np.int64)
    starts = np.zeros(n_vtiles + 1, np.int64)
    starts[1:] = np.cumsum(counts)
    row = 0
    for t in range(n_vtiles):
        lo, hi = starts[t], starts[t + 1]
        for off in range(lo, hi, eb):
            k = min(eb, hi - off)
            pos_r[row, :k] = pos[off:off + k]
            dstrel_r[row, :k] = ridx[off:off + k] - t * vb
            valid_r[row, :k] = 1
            ctile[row] = t
            row += 1

    return (jnp.asarray(pos_r, jnp.int32),
            jnp.asarray(dstrel_r, jnp.int32),
            jnp.asarray(valid_r, jnp.int32),
            jnp.asarray(ctile, jnp.int32),
            block_pad)


def build_msg_tiled_layout(recv_idx, block: int, *, vb: int = 128,
                           eb: int = 512):
    """One-time host preprocessing: the static receive routing table
    ``recv_idx`` [P, C] (local vertex addressed by (sender, bucket pos);
    sentinel >= block = no message) -> flat message positions grouped by
    destination vertex tile.

    Returns (pos_t, dstrel_t, valid_t, block_pad), each layout array
    [n_vtiles, n_chunks, EB]: ``pos_t`` indexes the FLATTENED [P*C]
    incoming buffer, ``dstrel_t`` is the destination slot within its tile,
    ``valid_t`` masks padding (no weight plane exists to carry +inf here,
    unlike the edge layouts)."""
    ridx = np.asarray(recv_idx, np.int64).reshape(-1)
    pos = np.arange(ridx.shape[0], dtype=np.int64)
    keep = ridx < block
    ridx, pos = ridx[keep], pos[keep]

    n_vtiles = max(-(-block // vb), 1)
    block_pad = n_vtiles * vb
    order = np.argsort(ridx, kind="stable")
    ridx, pos = ridx[order], pos[order]
    tile_of = ridx // vb
    counts = np.bincount(tile_of, minlength=n_vtiles)
    n_chunks = max(int(-(-counts.max() // eb)) if counts.size else 1, 1)

    pos_t = np.zeros((n_vtiles, n_chunks * eb), np.int64)
    dstrel_t = np.zeros((n_vtiles, n_chunks * eb), np.int64)
    valid_t = np.zeros((n_vtiles, n_chunks * eb), np.int64)
    starts = np.zeros(n_vtiles + 1, np.int64)
    starts[1:] = np.cumsum(counts)
    for t in range(n_vtiles):
        lo, hi = starts[t], starts[t + 1]
        k = hi - lo
        pos_t[t, :k] = pos[lo:hi]
        dstrel_t[t, :k] = ridx[lo:hi] - t * vb
        valid_t[t, :k] = 1

    shape3 = (n_vtiles, n_chunks, eb)
    return (jnp.asarray(pos_t.reshape(shape3), jnp.int32),
            jnp.asarray(dstrel_t.reshape(shape3), jnp.int32),
            jnp.asarray(valid_t.reshape(shape3), jnp.int32),
            block_pad)


@partial(jax.jit, static_argnames=("vb", "eb", "interpret"))
def merge_scatter_pallas(dist, incoming_flat, pos_t, dstrel_t, valid_t,
                         ctile=None, *, vb: int = 128, eb: int = 512,
                         interpret: bool | None = None):
    """Solver-facing wrapper: pads to kernel tile shapes, slices back.

    dist: [K, block]; incoming_flat: [K, M] flattened bucketed messages.
    With ``ctile`` given, the layout arrays are the flat ragged rows from
    ``build_msg_ragged_layout``. Returns (new_dist [K, block],
    new_active [K, block] bool, recvs [K] i32)."""
    nq, block = dist.shape
    n_vtiles = pos_t.shape[0] if ctile is None else max(-(-block // vb), 1)
    bp = n_vtiles * vb
    dist_pad = jnp.full((nq, bp), INF).at[:, :block].set(dist)
    if ctile is None:
        new, front, recvs = merge_scatter_tiled(
            dist_pad, incoming_flat, pos_t, dstrel_t, valid_t, vb=vb, eb=eb,
            interpret=interpret)
    else:
        new, front, recvs = merge_scatter_ragged(
            dist_pad, incoming_flat, ctile, pos_t, dstrel_t, valid_t, vb=vb,
            eb=eb, interpret=interpret)
    return new[:, :block], front[:, :block] > 0, recvs
