"""Pallas TPU kernel: merge-phase scatter-min of incoming boundary messages.

The merge phase scatters each round's incoming ``[K, P, C]`` bucketed
messages into the local distance block (``dist.at[recv_idx].min``), marks
improved vertices as the next frontier, and counts receives. Like the
relax scatter before it, ``at[].min`` has no efficient TPU lowering.

TPU adaptation, third instance of the dst-tiled pattern: the receive
routing table ``recv_idx`` is STATIC (built at partition time), so the
flat message positions ``[0, P*C)`` are pre-grouped by destination vertex
tile (host-side, one-time) into ``[n_vtiles, n_chunks, EB]`` arrays and
each grid step min-reduces one VB-wide vertex tile with the one-hot
reduce. The value gather pulls from the VMEM-resident flattened incoming
row. Unlike the edge layouts there is no weight to carry the padding mask,
so an explicit ``valid`` plane rides along (positions whose ``recv_idx``
is the sentinel never enter the layout; padding is valid = 0).

Grid ``(n_vtiles, n_chunks)`` — NO query axis. Each position chunk is
fetched once and every query in the batch reduces against it in-register
via ``tile_min_batch``, so layout tile loads per merge are ``n_tiles``
rather than ``n_tiles × K``. All chunks of tile ``i`` are complete at
``j == n_chunks - 1``, so the new-frontier plane (``new < dist``) is
emitted in-kernel at tile finalization; receive counts accumulate in
per-query SMEM counters.

VMEM working set per step:
  dist / new rows            8 * K * block_pad
  frontier plane             4 * K * block_pad
  incoming rows              4 * K * P * C
  position chunk (pos, dstrel, valid)  ~12 * EB
  one-hot expansion          4 * K * EB * VB   (dominant; batched reduce)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tile_reduce import tile_min_batch
from repro.runtime import pallas_interpret

INF = float("inf")


def _merge_scatter_kernel(dist_ref, in_ref, pos_ref, dstrel_ref, valid_ref,
                          out_ref, front_ref, recv_ref, count_ref, *, vb: int,
                          n_vtiles: int, n_chunks: int, n_queries: int):
    """Grid (vertex tile i, position chunk j) — whole query batch per step."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    first = (i == 0) & (j == 0)
    last = (i == n_vtiles - 1) & (j == n_chunks - 1)
    tile = pl.dslice(i * vb, vb)

    @pl.when(first)
    def _init_counts():
        for k in range(n_queries):
            count_ref[k] = 0

    @pl.when(j == 0)
    def _init_tile():
        out_ref[:, tile] = dist_ref[:, tile]

    pos = pos_ref[0, 0, :]                    # [EB] int32 (padding = 0)
    dstrel = dstrel_ref[0, 0, :]              # [EB] int32 in [0, vb)
    valid = valid_ref[0, 0, :] > 0            # [EB]
    v = jnp.take(in_ref[...], pos, axis=1)    # [K, EB]
    cand = jnp.where(valid[None, :], v, INF)
    sums = jnp.sum(valid[None, :] & (v < INF), axis=1).astype(jnp.int32)
    for k in range(n_queries):
        count_ref[k] = count_ref[k] + sums[k]
    mins = tile_min_batch(cand, dstrel, width=vb)     # [K, vb]
    out_ref[:, tile] = jnp.minimum(out_ref[:, tile], mins)

    # tile i complete: improved vertices form the next frontier
    @pl.when(j == n_chunks - 1)
    def _finalize_tile():
        front_ref[:, tile] = (
            out_ref[:, tile] < dist_ref[:, tile]
        ).astype(jnp.float32)

    @pl.when(last)
    def _fin():
        for k in range(n_queries):
            recv_ref[k] = count_ref[k]


def merge_scatter_tiled(dist_pad, incoming_flat, pos_t, dstrel_t, valid_t, *,
                        vb: int, eb: int, interpret: bool | None = None):
    """dist_pad: [K, block_pad] f32 (block_pad = n_vtiles * vb);
    incoming_flat: [K, M] f32 flattened messages; pos_t/dstrel_t/valid_t:
    [n_vtiles, n_chunks, EB] msg-tiled routing layout (query-invariant).
    Returns (new_dist [K, block_pad], new_frontier [K, block_pad] f32 0/1,
    recvs [K] i32 — finite incoming messages seen)."""
    n_vtiles, n_chunks, eb_l = pos_t.shape
    nq, bp = dist_pad.shape
    assert eb_l == eb and bp == n_vtiles * vb

    grid = (n_vtiles, n_chunks)
    dist_spec = pl.BlockSpec((nq, bp), lambda i, j: (0, 0))
    in_spec = pl.BlockSpec(incoming_flat.shape, lambda i, j: (0, 0))
    pos_spec = pl.BlockSpec((1, 1, eb), lambda i, j: (i, j, 0))
    kernel = functools.partial(_merge_scatter_kernel, vb=vb,
                               n_vtiles=n_vtiles, n_chunks=n_chunks,
                               n_queries=nq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[dist_spec, in_spec, pos_spec, pos_spec, pos_spec],
        out_specs=[
            dist_spec,                                     # merged distances
            dist_spec,                                     # new frontier
            pl.BlockSpec((nq,), lambda i, j: (0,)),        # per-query recvs
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, bp), dist_pad.dtype),
            jax.ShapeDtypeStruct((nq, bp), jnp.float32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((nq,), jnp.int32)],
        interpret=pallas_interpret(interpret),
    )(dist_pad, incoming_flat, pos_t, dstrel_t, valid_t)


def _merge_scatter_ragged_kernel(ctile_ref, dist_ref, in_ref, pos_ref,
                                 dstrel_ref, valid_ref, out_ref, front_ref,
                                 recv_ref, count_ref, *, vb: int,
                                 n_vtiles: int, total_chunks: int,
                                 n_queries: int):
    """Ragged grid ``(total_chunks,)`` with the scalar-prefetched chunk→tile
    map. Tile init/finalize move to GLOBAL (whole [K, block_pad] at the
    first/last chunk): the accumulate never reads the frontier plane, so the
    result is bit-identical, and zero-chunk tiles — skipped by the ragged
    grid entirely — still get ``out = dist`` / frontier 0."""
    c = pl.program_id(0)
    t = jnp.minimum(ctile_ref[c], n_vtiles - 1)
    tile = pl.dslice(t * vb, vb)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = dist_ref[...]
        for k in range(n_queries):
            count_ref[k] = 0

    pos = pos_ref[0, :]                       # [EB] int32 (padding = 0)
    dstrel = dstrel_ref[0, :]                 # [EB] int32 in [0, vb)
    valid = valid_ref[0, :] > 0               # [EB]
    v = jnp.take(in_ref[...], pos, axis=1)    # [K, EB]
    cand = jnp.where(valid[None, :], v, INF)
    sums = jnp.sum(valid[None, :] & (v < INF), axis=1).astype(jnp.int32)
    for k in range(n_queries):
        count_ref[k] = count_ref[k] + sums[k]
    mins = tile_min_batch(cand, dstrel, width=vb)     # [K, vb]
    out_ref[:, tile] = jnp.minimum(out_ref[:, tile], mins)

    @pl.when(c == total_chunks - 1)
    def _fin():
        front_ref[...] = (out_ref[...] < dist_ref[...]).astype(jnp.float32)
        for k in range(n_queries):
            recv_ref[k] = count_ref[k]


def merge_scatter_ragged(dist_pad, incoming_flat, ctile, pos_r, dstrel_r,
                         valid_r, *, vb: int, eb: int,
                         interpret: bool | None = None):
    """Ragged counterpart of ``merge_scatter_tiled``: pos_r/dstrel_r/valid_r
    are flat [total_chunks, EB] rows, ``ctile`` the [total_chunks] chunk→
    tile map (sentinel ``n_vtiles`` for inert padding chunks). Same
    returns."""
    total_chunks, eb_l = pos_r.shape
    nq, bp = dist_pad.shape
    assert eb_l == eb and bp % vb == 0
    n_vtiles = bp // vb

    grid = (total_chunks,)
    dist_spec = pl.BlockSpec((nq, bp), lambda c, ctile: (0, 0))
    in_spec = pl.BlockSpec(incoming_flat.shape, lambda c, ctile: (0, 0))
    pos_spec = pl.BlockSpec((1, eb), lambda c, ctile: (c, 0))
    kernel = functools.partial(_merge_scatter_ragged_kernel, vb=vb,
                               n_vtiles=n_vtiles, total_chunks=total_chunks,
                               n_queries=nq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[dist_spec, in_spec, pos_spec, pos_spec, pos_spec],
        out_specs=[
            dist_spec,                                     # merged distances
            dist_spec,                                     # new frontier
            pl.BlockSpec((nq,), lambda c, ctile: (0,)),
        ],
        scratch_shapes=[pltpu.SMEM((nq,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, bp), dist_pad.dtype),
            jax.ShapeDtypeStruct((nq, bp), jnp.float32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
        ],
        interpret=pallas_interpret(interpret),
    )(ctile, dist_pad, incoming_flat, pos_r, dstrel_r, valid_r)
