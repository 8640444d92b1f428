"""Pallas TPU kernels: blocked min-plus edge relaxation (SP-Async hot loop).

TPU adaptation (vs. the CUDA-style atomicMin scatter a GPU port would use):
scatter has no efficient TPU lowering, so edges are *pre-tiled by
destination* (host-side, one-time — the layout is as static as the CSR
itself) and each grid step produces one VB-wide vertex tile with a one-hot
masked min-reduce, which is pure VPU work over an [EB, VB] tile held in
VMEM. The source-distance gather is a 1-D dynamic gather from the
VMEM-resident distance vector. The v5e compiler refuses that gather
("Only 2D gather is supported") and the ``(1, EB)`` edge-chunk blocks
(last two block dimensions must be divisible by 8 and 128), so these
kernels run only in interpret mode on the CPU today
(``tests/test_tpu_compile.py`` records the refusal).

Four entry points, in increasing integration with the solver:

- ``relax_dst_tiled``: one unmasked sweep (the original micro-benchmark
  kernel). Grid ``(n_vtiles, n_chunks)``.
- ``relax_dst_tiled_masked``: one sweep with the local solver's full
  contract — frontier masking (only edges whose source improved last sweep
  relax), per-edge Trishla pruned masks, and relaxation counting (the TEPS
  numerator). Grid ``(n_vtiles, n_chunks)`` + an SMEM count accumulator.
- ``relax_dst_tiled_fixpoint``: the fused local solve — the whole
  frontier-chased fixpoint runs inside ONE ``pallas_call`` with grid
  ``(n_sweeps, n_vtiles, n_chunks)`` instead of re-entering XLA per sweep.
- ``relax_dst_tiled_fixpoint_batch``: the fixpoint over a leading query
  axis ``K`` (multi-source SSSP). Grid ``(n_sweeps, n_vtiles, n_chunks,
  K)`` with the query axis INNERMOST: the edge-chunk block index map
  depends only on ``(i, j)``, so one fetched chunk is reused by all K
  queries before the next chunk streams in — the dst-tiled layout is
  amortized across the whole batch. Distances/frontiers are per-query
  ``[K, block_pad]`` rows; the SMEM early-out flag and the relaxation
  counter become per-query ``[K]`` vectors, so a converged query degrades
  to predicated no-op grid steps while stragglers keep relaxing.
  Distances update in place (Gauss–Seidel within a sweep: tiles later in
  the grid see earlier tiles' improvements, which only accelerates
  convergence of the monotone min-plus operator). The frontier for sweep
  ``s`` is recomputed at sweep start as ``dist < prev`` (vertices improved
  during sweep ``s-1``); an SMEM ``changed`` flag early-outs the remaining
  sweeps once a sweep makes no improvement, so a converged call costs only
  predicated no-op grid steps. Returns the residual frontier (vertices
  improved in the final sweep) so a thin outer loop can re-invoke the
  kernel until empty when ``n_sweeps`` did not suffice.

The chunk axis streams over a tile's edge list in EB-sized pieces,
revisiting the same output block (reduction pattern; initialized at chunk 0
/ sweep 0).

VMEM working set per step:
  dist (full block)            4 * block_pad
  prev + frontier (fixpoint)   8 * block_pad
  edge chunk (src, w, dstrel, pruned) ~16 * EB
  one-hot tile                 4 * EB * VB   (dominant; 512*128*4 = 256 KiB)
The batched variant multiplies the dist/prev/frontier terms by K (the
in/out distance and scratch buffers are [K, block_pad] and resident for
the whole call); the edge chunk and one-hot terms are unchanged — that is
the VMEM price of reusing one edge stream for K queries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tile_reduce import tile_min
from repro.runtime import pallas_interpret

INF = float("inf")


def _relax_kernel(dist_ref, src_ref, w_ref, dstrel_ref, out_ref, *, vb: int):
    i = pl.program_id(0)   # vertex tile
    j = pl.program_id(1)   # edge chunk within the tile

    # initialize the output tile from the current distances on first visit
    @pl.when(j == 0)
    def _init():
        out_ref[...] = dist_ref[pl.dslice(i * vb, vb)]

    src = src_ref[0, 0, :]                 # [EB] int32 (sentinel = block_pad-1)
    w = w_ref[0, 0, :]                     # [EB] f32 (+inf padding)
    dstrel = dstrel_ref[0, 0, :]           # [EB] int32 in [0, vb)

    d_src = jnp.take(dist_ref[...], src)   # 1-D dynamic gather from VMEM
    cand = d_src + w                       # [EB]
    mins = _tile_min(cand, dstrel, vb=vb)
    out_ref[...] = jnp.minimum(out_ref[...], mins)


def relax_dst_tiled(dist_pad, src_t, w_t, dstrel_t, *, vb: int, eb: int,
                    interpret: bool | None = None):
    """dist_pad: [block_pad] f32 (block_pad % vb == 0).
    src_t/w_t/dstrel_t: [n_vtiles, n_chunks, EB] dst-tiled edge layout.
    Returns new distances [block_pad]."""
    n_vtiles, n_chunks, eb_l = src_t.shape
    assert eb_l == eb and dist_pad.shape[0] == n_vtiles * vb

    grid = (n_vtiles, n_chunks)
    kernel = functools.partial(_relax_kernel, vb=vb)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(dist_pad.shape, lambda i, j: (0,)),          # full dist
            pl.BlockSpec((1, 1, eb), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, eb), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, eb), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((vb,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_vtiles * vb,), dist_pad.dtype),
        interpret=pallas_interpret(interpret),
    )(dist_pad, src_t, w_t, dstrel_t)


def _edge_chunk(src_ref, w_ref, dstrel_ref, pruned_ref):
    """Load one [EB] edge chunk with the Trishla mask folded into w."""
    src = src_ref[0, 0, :]
    w = jnp.where(pruned_ref[0, 0, :] > 0, INF, w_ref[0, 0, :])
    dstrel = dstrel_ref[0, 0, :]
    return src, w, dstrel


def _tile_min(cand, dstrel, *, vb: int):
    """[EB] candidates -> [VB] per-destination minima (shared one-hot
    reduce from ``kernels/tile_reduce``)."""
    return tile_min(cand, dstrel, width=vb)


def _relax_masked_kernel(dist_ref, front_ref, src_ref, w_ref, dstrel_ref,
                         pruned_ref, out_ref, nrel_ref, acc_ref, *, vb: int,
                         n_vtiles: int, n_chunks: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_acc():
        acc_ref[0] = 0

    @pl.when(j == 0)
    def _init():
        out_ref[...] = dist_ref[pl.dslice(i * vb, vb)]

    src, w, dstrel = _edge_chunk(src_ref, w_ref, dstrel_ref, pruned_ref)
    f_src = jnp.take(front_ref[...], src) > 0
    d_src = jnp.take(dist_ref[...], src)
    cand = jnp.where(f_src, d_src + w, INF)
    acc_ref[0] = acc_ref[0] + jnp.sum(f_src & (w < INF)).astype(jnp.int32)
    mins = _tile_min(cand, dstrel, vb=vb)
    out_ref[...] = jnp.minimum(out_ref[...], mins)

    @pl.when((i == n_vtiles - 1) & (j == n_chunks - 1))
    def _fin():
        nrel_ref[0] = acc_ref[0]


def relax_dst_tiled_masked(dist_pad, front_pad, src_t, w_t, dstrel_t,
                           pruned_t, *, vb: int, eb: int,
                           interpret: bool | None = None):
    """One frontier-masked, Trishla-pruned sweep with relaxation counting.

    front_pad: [block_pad] f32 0/1; pruned_t: [n_vtiles, n_chunks, EB] int32
    0/1 in tiled edge order. Returns (new_dist [block_pad], n_relax [1])."""
    n_vtiles, n_chunks, eb_l = src_t.shape
    assert eb_l == eb and dist_pad.shape[0] == n_vtiles * vb

    bp = dist_pad.shape[0]
    grid = (n_vtiles, n_chunks)
    edge_spec = pl.BlockSpec((1, 1, eb), lambda i, j: (i, j, 0))
    kernel = functools.partial(_relax_masked_kernel, vb=vb,
                               n_vtiles=n_vtiles, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bp,), lambda i, j: (0,)),
            pl.BlockSpec((bp,), lambda i, j: (0,)),
            edge_spec, edge_spec, edge_spec, edge_spec,
        ],
        out_specs=[
            pl.BlockSpec((vb,), lambda i, j: (i,)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp,), dist_pad.dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=pallas_interpret(interpret),
    )(dist_pad, front_pad, src_t, w_t, dstrel_t, pruned_t)


def _relax_fixpoint_kernel(dist_ref, front_ref, src_ref, w_ref, dstrel_ref,
                           pruned_ref, out_ref, resid_ref, nrel_ref,
                           prev_ref, fcur_ref, flags_ref, *, vb: int,
                           n_vtiles: int, n_chunks: int, n_sweeps: int):
    """Whole local fixpoint in one grid: (sweep, vertex tile, edge chunk).

    SMEM flags: [0] = sweep-active (early-out once a sweep changes
    nothing), [1] = relaxation count accumulator."""
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    first = (s == 0) & (i == 0) & (j == 0)
    sweep_start = (i == 0) & (j == 0)
    last = (s == n_sweeps - 1) & (i == n_vtiles - 1) & (j == n_chunks - 1)

    @pl.when(first)
    def _init():
        out_ref[...] = dist_ref[...]
        prev_ref[...] = dist_ref[...]
        fcur_ref[...] = front_ref[...]
        flags_ref[0] = jnp.any(front_ref[...] > 0).astype(jnp.int32)
        flags_ref[1] = 0

    @pl.when(sweep_start & (s > 0) & (flags_ref[0] > 0))
    def _advance_frontier():
        newf = (out_ref[...] < prev_ref[...]).astype(jnp.float32)
        fcur_ref[...] = newf
        flags_ref[0] = jnp.any(newf > 0).astype(jnp.int32)
        prev_ref[...] = out_ref[...]

    @pl.when(flags_ref[0] > 0)
    def _relax():
        src, w, dstrel = _edge_chunk(src_ref, w_ref, dstrel_ref, pruned_ref)
        f_src = jnp.take(fcur_ref[...], src) > 0
        # Gauss–Seidel: gather from the live distances, not a sweep snapshot
        d_src = jnp.take(out_ref[...], src)
        cand = jnp.where(f_src, d_src + w, INF)
        flags_ref[1] = flags_ref[1] + jnp.sum(f_src & (w < INF)).astype(jnp.int32)
        mins = _tile_min(cand, dstrel, vb=vb)
        cur = out_ref[pl.dslice(i * vb, vb)]
        out_ref[pl.dslice(i * vb, vb)] = jnp.minimum(cur, mins)

    @pl.when(last)
    def _fin():
        resid_ref[...] = (out_ref[...] < prev_ref[...]).astype(jnp.float32)
        nrel_ref[0] = flags_ref[1]


def relax_dst_tiled_fixpoint(dist_pad, front_pad, src_t, w_t, dstrel_t,
                             pruned_t, *, vb: int, eb: int, n_sweeps: int,
                             interpret: bool | None = None):
    """Fused multi-sweep local solve: up to ``n_sweeps`` frontier-chased
    relaxation sweeps inside one ``pallas_call``.

    Returns (new_dist [block_pad], residual_frontier [block_pad] f32 0/1,
    n_relax [1] i32). The residual frontier is empty iff the fixpoint was
    reached within ``n_sweeps`` — callers loop on it."""
    n_vtiles, n_chunks, eb_l = src_t.shape
    assert eb_l == eb and dist_pad.shape[0] == n_vtiles * vb

    bp = dist_pad.shape[0]
    grid = (n_sweeps, n_vtiles, n_chunks)
    full_spec = pl.BlockSpec((bp,), lambda s, i, j: (0,))
    edge_spec = pl.BlockSpec((1, 1, eb), lambda s, i, j: (i, j, 0))
    kernel = functools.partial(_relax_fixpoint_kernel, vb=vb,
                               n_vtiles=n_vtiles, n_chunks=n_chunks,
                               n_sweeps=n_sweeps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[full_spec, full_spec,
                  edge_spec, edge_spec, edge_spec, edge_spec],
        out_specs=[
            full_spec,                                   # live distances
            full_spec,                                   # residual frontier
            pl.BlockSpec((1,), lambda s, i, j: (0,)),    # relaxation count
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp,), dist_pad.dtype),
            jax.ShapeDtypeStruct((bp,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bp,), jnp.float32),              # prev-sweep snapshot
            pltpu.VMEM((bp,), jnp.float32),              # current frontier
            pltpu.SMEM((2,), jnp.int32),                 # active flag, count
        ],
        interpret=pallas_interpret(interpret),
    )(dist_pad, front_pad, src_t, w_t, dstrel_t, pruned_t)


def _relax_fixpoint_batch_kernel(dist_ref, front_ref, src_ref, w_ref,
                                 dstrel_ref, pruned_ref, out_ref, resid_ref,
                                 nrel_ref, prev_ref, fcur_ref, active_ref,
                                 count_ref, *, vb: int, n_vtiles: int,
                                 n_chunks: int, n_sweeps: int):
    """Fixpoint kernel with a query axis. Grid (sweep, vtile, chunk, query);
    the query axis is innermost so the edge chunk loaded for (vtile, chunk)
    is reused by every query before the next chunk streams in.

    Per-query SMEM state: ``active_ref[q]`` (early-out once query q's sweep
    changes nothing) and ``count_ref[q]`` (relaxation accumulator).
    ``prev_ref``/``fcur_ref`` are [K, block_pad] VMEM scratch rows."""
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    q = pl.program_id(3)
    first = (s == 0) & (i == 0) & (j == 0)
    sweep_start = (i == 0) & (j == 0)
    last = (s == n_sweeps - 1) & (i == n_vtiles - 1) & (j == n_chunks - 1)
    qrow = pl.dslice(q, 1)

    @pl.when(first)
    def _init():
        out_ref[qrow, :] = dist_ref[qrow, :]
        prev_ref[qrow, :] = dist_ref[qrow, :]
        fcur_ref[qrow, :] = front_ref[qrow, :]
        active_ref[q] = jnp.any(front_ref[qrow, :] > 0).astype(jnp.int32)
        count_ref[q] = 0

    @pl.when(sweep_start & (s > 0) & (active_ref[q] > 0))
    def _advance_frontier():
        newf = (out_ref[qrow, :] < prev_ref[qrow, :]).astype(jnp.float32)
        fcur_ref[qrow, :] = newf
        active_ref[q] = jnp.any(newf > 0).astype(jnp.int32)
        prev_ref[qrow, :] = out_ref[qrow, :]

    @pl.when(active_ref[q] > 0)
    def _relax():
        src, w, dstrel = _edge_chunk(src_ref, w_ref, dstrel_ref, pruned_ref)
        f_src = jnp.take(fcur_ref[qrow, :][0], src) > 0
        # Gauss–Seidel: gather from query q's live distances
        d_src = jnp.take(out_ref[qrow, :][0], src)
        cand = jnp.where(f_src, d_src + w, INF)
        count_ref[q] = count_ref[q] + jnp.sum(f_src & (w < INF)).astype(jnp.int32)
        mins = _tile_min(cand, dstrel, vb=vb)
        cur = out_ref[qrow, pl.dslice(i * vb, vb)]
        out_ref[qrow, pl.dslice(i * vb, vb)] = jnp.minimum(cur, mins)

    @pl.when(last)
    def _fin():
        resid_ref[qrow, :] = (out_ref[qrow, :] < prev_ref[qrow, :]).astype(
            jnp.float32)
        nrel_ref[q] = count_ref[q]


def relax_dst_tiled_fixpoint_batch(dist_pad, front_pad, src_t, w_t, dstrel_t,
                                   pruned_t, *, vb: int, eb: int,
                                   n_sweeps: int, interpret: bool | None = None):
    """Batched multi-query fixpoint: ``dist_pad``/``front_pad`` are
    [K, block_pad]; the dst-tiled edge layout (and the Trishla pruned mask)
    is SHARED by all K queries — built/gathered once, streamed once per
    (vtile, chunk) grid step and reused K times.

    Returns (new_dist [K, block_pad], residual_frontier [K, block_pad] f32
    0/1, n_relax [K] i32). A query's residual row is empty iff its fixpoint
    was reached within ``n_sweeps``."""
    n_vtiles, n_chunks, eb_l = src_t.shape
    nq, bp = dist_pad.shape
    assert eb_l == eb and bp == n_vtiles * vb

    grid = (n_sweeps, n_vtiles, n_chunks, nq)
    # Every dist-shaped buffer uses a CONSTANT full-array block: the live
    # distances are read back on every revisit (Gauss–Seidel gather + min
    # accumulate), and a revisited out block is only guaranteed to keep its
    # data — and to not be flushed to HBM once per grid step — when its
    # block index never changes between steps (same argument as the
    # single-query kernel's constant out spec). The kernel addresses query
    # rows with pl.dslice(q, 1).
    full_spec = pl.BlockSpec((nq, bp), lambda s, i, j, q: (0, 0))
    edge_spec = pl.BlockSpec((1, 1, eb), lambda s, i, j, q: (i, j, 0))
    kernel = functools.partial(_relax_fixpoint_batch_kernel, vb=vb,
                               n_vtiles=n_vtiles, n_chunks=n_chunks,
                               n_sweeps=n_sweeps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[full_spec, full_spec,
                  edge_spec, edge_spec, edge_spec, edge_spec],
        out_specs=[
            full_spec,                                    # live distances
            full_spec,                                    # residual frontiers
            pl.BlockSpec((nq,), lambda s, i, j, q: (0,)), # per-query counts
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, bp), dist_pad.dtype),
            jax.ShapeDtypeStruct((nq, bp), jnp.float32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nq, bp), jnp.float32),           # prev-sweep snapshots
            pltpu.VMEM((nq, bp), jnp.float32),           # current frontiers
            pltpu.SMEM((nq,), jnp.int32),                # per-query active
            pltpu.SMEM((nq,), jnp.int32),                # per-query count
        ],
        interpret=pallas_interpret(interpret),
    )(dist_pad, front_pad, src_t, w_t, dstrel_t, pruned_t)


def _edge_chunk_ragged(src_ref, w_ref, dstrel_ref, pruned_ref):
    """Load one [EB] chunk row of a ragged (flat-chunk) layout."""
    src = src_ref[0, :]
    w = jnp.where(pruned_ref[0, :] > 0, INF, w_ref[0, :])
    dstrel = dstrel_ref[0, :]
    return src, w, dstrel


def _relax_ragged_fixpoint_batch_kernel(ctile_ref, dist_ref, front_ref,
                                        src_ref, w_ref, dstrel_ref,
                                        pruned_ref, out_ref, resid_ref,
                                        nrel_ref, prev_ref, fcur_ref,
                                        active_ref, count_ref, *, vb: int,
                                        n_vtiles: int, total_chunks: int,
                                        n_sweeps: int):
    """Ragged-grid batched fixpoint. Grid (sweep, chunk, query): the vertex
    tile axis of the dense kernel is gone — each flat chunk carries its
    destination tile in the scalar-prefetched ``ctile`` map, so padding
    chunks of under-full tiles are never scheduled. Inert padding chunks
    (stacking shards to a common chunk count) carry w=+inf and the
    out-of-range tile sentinel ``n_vtiles``, clamped here to a valid tile:
    their min-accumulation is a no-op, preserving bit-identity with the
    dense schedule (same stable dst-sorted chunk sequence, minus no-ops)."""
    s = pl.program_id(0)
    c = pl.program_id(1)
    q = pl.program_id(2)
    t = jnp.minimum(ctile_ref[c], n_vtiles - 1)
    first = (s == 0) & (c == 0)
    sweep_start = (c == 0)
    last = (s == n_sweeps - 1) & (c == total_chunks - 1)
    qrow = pl.dslice(q, 1)

    @pl.when(first)
    def _init():
        out_ref[qrow, :] = dist_ref[qrow, :]
        prev_ref[qrow, :] = dist_ref[qrow, :]
        fcur_ref[qrow, :] = front_ref[qrow, :]
        active_ref[q] = jnp.any(front_ref[qrow, :] > 0).astype(jnp.int32)
        count_ref[q] = 0

    @pl.when(sweep_start & (s > 0) & (active_ref[q] > 0))
    def _advance_frontier():
        newf = (out_ref[qrow, :] < prev_ref[qrow, :]).astype(jnp.float32)
        fcur_ref[qrow, :] = newf
        active_ref[q] = jnp.any(newf > 0).astype(jnp.int32)
        prev_ref[qrow, :] = out_ref[qrow, :]

    @pl.when(active_ref[q] > 0)
    def _relax():
        src, w, dstrel = _edge_chunk_ragged(src_ref, w_ref, dstrel_ref,
                                            pruned_ref)
        f_src = jnp.take(fcur_ref[qrow, :][0], src) > 0
        d_src = jnp.take(out_ref[qrow, :][0], src)
        cand = jnp.where(f_src, d_src + w, INF)
        count_ref[q] = count_ref[q] + jnp.sum(f_src & (w < INF)).astype(jnp.int32)
        mins = _tile_min(cand, dstrel, vb=vb)
        cur = out_ref[qrow, pl.dslice(t * vb, vb)]
        out_ref[qrow, pl.dslice(t * vb, vb)] = jnp.minimum(cur, mins)

    @pl.when(last)
    def _fin():
        resid_ref[qrow, :] = (out_ref[qrow, :] < prev_ref[qrow, :]).astype(
            jnp.float32)
        nrel_ref[q] = count_ref[q]


def relax_dst_ragged_fixpoint_batch(dist_pad, front_pad, ctile, src_r, w_r,
                                    dstrel_r, pruned_r, *, vb: int, eb: int,
                                    n_sweeps: int, interpret: bool | None = None):
    """Ragged counterpart of ``relax_dst_tiled_fixpoint_batch``.

    ``src_r``/``w_r``/``dstrel_r``/``pruned_r`` are [total_chunks, EB] flat
    CSR-chunked rows; ``ctile`` is the [total_chunks] int32 chunk→tile map
    (sentinel ``n_vtiles`` marks inert padding chunks). The grid has
    ``total_chunks = sum_t ceil(count_t / EB)`` steps per sweep instead of
    the dense ``n_vtiles * max_t ceil(count_t / EB)`` — on skewed
    (power-law) tiles that is the whole memory/compute win."""
    total_chunks, eb_l = src_r.shape
    nq, bp = dist_pad.shape
    assert eb_l == eb and bp % vb == 0
    n_vtiles = bp // vb

    grid = (n_sweeps, total_chunks, nq)
    full_spec = pl.BlockSpec((nq, bp), lambda s, c, q, ctile: (0, 0))
    edge_spec = pl.BlockSpec((1, eb), lambda s, c, q, ctile: (c, 0))
    kernel = functools.partial(_relax_ragged_fixpoint_batch_kernel, vb=vb,
                               n_vtiles=n_vtiles, total_chunks=total_chunks,
                               n_sweeps=n_sweeps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[full_spec, full_spec,
                  edge_spec, edge_spec, edge_spec, edge_spec],
        out_specs=[
            full_spec,                                       # live distances
            full_spec,                                       # residual frontiers
            pl.BlockSpec((nq,), lambda s, c, q, ctile: (0,)),
        ],
        scratch_shapes=[
            pltpu.VMEM((nq, bp), jnp.float32),
            pltpu.VMEM((nq, bp), jnp.float32),
            pltpu.SMEM((nq,), jnp.int32),
            pltpu.SMEM((nq,), jnp.int32),
        ],
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
    )(ctile, dist_pad, front_pad, src_r, w_r, dstrel_r, pruned_r)
