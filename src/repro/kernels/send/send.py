"""Pallas TPU kernel: send-phase segment-min pack (SP-Async boundary send).

The send phase reduces every shard's cut-edge candidates ``dist[src] + w``
to ONE value per message slot (a slot = a unique boundary pair
``(dst_owner, dst_local)``), masks the result against ``last_sent`` so only
improvements transmit, and counts the sends. The XLA backend does the
per-slot min without a scatter, by log-step doubling over the slot-sorted
cut edges (``core/sssp.py:_slot_min``); this kernel is the tiled
alternative, which the v5e compiler refuses.

TPU adaptation, following ``kernels/relax``'s dst-tiled pattern with the
SLOT axis in the destination role: cut edges are pre-grouped by slot tile
(host-side, one-time — the grouping is as static as the message routing
itself) into ``[n_stiles, n_chunks, EB]`` arrays, and each grid step
produces one SB-wide slot tile via the one-hot masked min-reduce (pure VPU
work). The source-distance gather is the same 1-D dynamic gather from the
VMEM-resident distance row the relax kernel uses.

Grid ``(n_stiles, n_chunks)`` — NO query axis. Each edge chunk is fetched
exactly once and all K queries reduce against it in-register via the
batched one-hot reduce (``tile_min_batch``), the same layout-amortization
the batched relax kernel proves: layout tile loads per round are
``n_tiles``, not ``n_tiles × K``. Because the grid iterates chunks within
a tile, all chunks of tile ``i`` are complete at ``j == n_chunks - 1``, so
the improvement mask against ``last_sent``, the ``last_sent`` update, and
the per-query send counts all happen in-kernel at tile finalization — the
kernel emits exactly what the solver's send phase needs, not a partial
reduction.

VMEM working set per step:
  dist rows                 4 * K * block_pad
  last_sent / send_val / new_last rows   12 * K * S_pad
  edge chunk (src, w, segrel, pruned)    ~16 * EB
  one-hot expansion         4 * K * EB * SB   (dominant; batched reduce)
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


def _send_pack_kernel(dist_ref, last_ref, valid_ref, src_ref, w_ref,
                      segrel_ref, pruned_ref, val_ref, newlast_ref, sends_ref,
                      count_ref, *, sb: int, n_stiles: int, n_chunks: int,
                      n_queries: int):
    """Grid (slot tile i, edge chunk j) — whole query batch per step.

    ``val_ref`` accumulates raw per-slot minima for ALL K queries while
    tile ``i`` streams its chunks; at the tile's last chunk it is rewritten
    in place as the masked send value (INF where no improvement) and
    ``newlast_ref`` / ``count_ref`` are updated. SMEM ``count_ref`` holds
    the per-query send counters."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    first = (i == 0) & (j == 0)
    last = (i == n_stiles - 1) & (j == n_chunks - 1)
    tile = pl.dslice(i * sb, sb)

    @pl.when(first)
    def _init_counts():
        for k in range(n_queries):
            count_ref[k] = 0

    @pl.when(j == 0)
    def _init_tile():
        val_ref[:, tile] = jnp.full((n_queries, sb), INF, jnp.float32)

    # accumulate this chunk's candidates into the slot tile, all queries
    src = src_ref[0, 0, :]                    # [EB] int32 (padding = 0)
    w = jnp.where(pruned_ref[0, 0, :] > 0, INF, w_ref[0, 0, :])
    segrel = segrel_ref[0, 0, :]              # [EB] int32 in [0, sb)
    d_src = jnp.take(dist_ref[...], src, axis=1)      # [K, EB]
    cand = d_src + w[None, :]
    mins = tile_min_batch(cand, segrel, width=sb)     # [K, sb]
    val_ref[:, tile] = jnp.minimum(val_ref[:, tile], mins)

    # tile i complete: improvement mask + last_sent update + counts
    @pl.when(j == n_chunks - 1)
    def _finalize_tile():
        val = val_ref[:, tile]                        # [K, sb]
        prev = last_ref[:, tile]
        valid = valid_ref[tile][None, :] > 0
        improved = valid & (val < prev)
        val_ref[:, tile] = jnp.where(improved, val, INF)
        newlast_ref[:, tile] = jnp.where(improved, val, prev)
        sums = jnp.sum(improved, axis=1).astype(jnp.int32)
        for k in range(n_queries):
            count_ref[k] = count_ref[k] + sums[k]

    @pl.when(last)
    def _fin():
        for k in range(n_queries):
            sends_ref[k] = count_ref[k]


def send_pack_tiled(dist_pad, last_pad, valid_pad, src_t, w_t, segrel_t,
                    pruned_t, *, sb: int, eb: int, interpret: bool | None = None):
    """dist_pad: [K, block_pad] f32; last_pad/valid_pad: [K, S_pad] /
    [S_pad] with S_pad = n_stiles * sb; src_t/w_t/segrel_t/pruned_t:
    [n_stiles, n_chunks, EB] slot-tiled cut-edge layout (shared by all K
    queries). Returns (send_val [K, S_pad] — INF where not improved,
    new_last [K, S_pad], sends [K] i32)."""
    n_stiles, n_chunks, eb_l = src_t.shape
    nq, bp = dist_pad.shape
    sp = n_stiles * sb
    assert eb_l == eb and last_pad.shape == (nq, sp)
    assert valid_pad.shape == (sp,)

    grid = (n_stiles, n_chunks)
    dist_spec = pl.BlockSpec((nq, bp), lambda i, j: (0, 0))
    slot_spec = pl.BlockSpec((nq, sp), lambda i, j: (0, 0))
    edge_spec = pl.BlockSpec((1, 1, eb), lambda i, j: (i, j, 0))
    kernel = functools.partial(_send_pack_kernel, sb=sb, n_stiles=n_stiles,
                               n_chunks=n_chunks, n_queries=nq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            dist_spec,
            slot_spec,
            pl.BlockSpec((sp,), lambda i, j: (0,)),
            edge_spec, edge_spec, edge_spec, edge_spec,
        ],
        out_specs=[
            slot_spec,                                     # masked send values
            slot_spec,                                     # updated last_sent
            pl.BlockSpec((nq,), lambda i, j: (0,)),        # per-query sends
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((nq,), jnp.int32)],
        interpret=pallas_interpret(interpret),
    )(dist_pad, last_pad, valid_pad, src_t, w_t, segrel_t, pruned_t)


def _send_pack_ragged_kernel(ctile_ref, dist_ref, last_ref, valid_ref,
                             src_ref, w_ref, segrel_ref, pruned_ref, val_ref,
                             newlast_ref, sends_ref, *, sb: int,
                             n_stiles: int, total_chunks: int,
                             n_queries: int):
    """Ragged grid ``(total_chunks,)``: each flat chunk carries its slot
    tile in the scalar-prefetched ``ctile`` map. Init/finalize move from
    per-tile to GLOBAL (whole [K, S_pad] at the first/last chunk): the
    accumulate step never reads the improvement mask, so finalizing every
    tile at once — after all its chunks necessarily streamed — produces
    bit-identical send values, and zero-chunk tiles (absent from the ragged
    chunk list entirely) still get their INF/no-improvement finalization."""
    c = pl.program_id(0)
    t = jnp.minimum(ctile_ref[c], n_stiles - 1)
    tile = pl.dslice(t * sb, sb)

    @pl.when(c == 0)
    def _init():
        val_ref[...] = jnp.full(val_ref.shape, INF, jnp.float32)

    src = src_ref[0, :]                       # [EB] int32 (padding = 0)
    w = jnp.where(pruned_ref[0, :] > 0, INF, w_ref[0, :])
    segrel = segrel_ref[0, :]                 # [EB] int32 in [0, sb)
    d_src = jnp.take(dist_ref[...], src, axis=1)      # [K, EB]
    cand = d_src + w[None, :]
    mins = tile_min_batch(cand, segrel, width=sb)     # [K, sb]
    val_ref[:, tile] = jnp.minimum(val_ref[:, tile], mins)

    @pl.when(c == total_chunks - 1)
    def _fin():
        val = val_ref[...]                            # [K, S_pad]
        prev = last_ref[...]
        valid = valid_ref[...][None, :] > 0
        improved = valid & (val < prev)
        val_ref[...] = jnp.where(improved, val, INF)
        newlast_ref[...] = jnp.where(improved, val, prev)
        sums = jnp.sum(improved, axis=1).astype(jnp.int32)
        for k in range(n_queries):
            sends_ref[k] = sums[k]


def send_pack_ragged(dist_pad, last_pad, valid_pad, ctile, src_r, w_r,
                     segrel_r, pruned_r, *, sb: int, eb: int,
                     interpret: bool | None = None):
    """Ragged counterpart of ``send_pack_tiled``: the slot-tiled layout is
    flat [total_chunks, EB] rows plus the [total_chunks] chunk→tile map
    (sentinel ``n_stiles`` marks inert padding chunks, clamped in-kernel).
    ``S_pad`` comes from ``last_pad`` since the layout no longer encodes the
    tile count. Same returns as the dense kernel."""
    total_chunks, eb_l = src_r.shape
    nq, bp = dist_pad.shape
    sp = last_pad.shape[1]
    assert eb_l == eb and sp % sb == 0
    assert valid_pad.shape == (sp,)
    n_stiles = sp // sb

    grid = (total_chunks,)
    dist_spec = pl.BlockSpec((nq, bp), lambda c, ctile: (0, 0))
    slot_spec = pl.BlockSpec((nq, sp), lambda c, ctile: (0, 0))
    edge_spec = pl.BlockSpec((1, eb), lambda c, ctile: (c, 0))
    kernel = functools.partial(_send_pack_ragged_kernel, sb=sb,
                               n_stiles=n_stiles, total_chunks=total_chunks,
                               n_queries=nq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            dist_spec,
            slot_spec,
            pl.BlockSpec((sp,), lambda c, ctile: (0,)),
            edge_spec, edge_spec, edge_spec, edge_spec,
        ],
        out_specs=[
            slot_spec,                                     # masked send values
            slot_spec,                                     # updated last_sent
            pl.BlockSpec((nq,), lambda c, ctile: (0,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
        ],
        interpret=pallas_interpret(interpret),
    )(ctile, dist_pad, last_pad, valid_pad, src_r, w_r, segrel_r, pruned_r)
