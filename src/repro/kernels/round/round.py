"""Pallas TPU megakernel: one SP-Async round in a single ``pallas_call``.

At today's graph scales every phase of the round (merge the previous
exchange's messages, chase the local frontier to a fixpoint, pack the
boundary sends) costs microseconds of compute — the round time IS the
per-phase dispatch overhead. All three phases already share the dst-tiled
tiling and the one-hot masked min-reduce, and all three read or write the
same [K, block_pad] distance rows, so they compose into ONE kernel whose
grid walks three stages over a shared VMEM-resident distance buffer:

  stage s = 0            merge: scatter-min the delivered messages into
                         the distance rows and derive the round's frontier
                         ``((merged < dist) & live) | injected``
  stage s in [1, S]      S Gauss–Seidel relaxation sweeps with the SMEM
                         early-out flag from ``relax_dst_tiled_fixpoint``
                         (a sweep with an empty global frontier is a
                         predicated no-op grid step)
  stage s = S + 1        send-pack: slot-tile segment-min of
                         ``dist[src] + w`` masked against ``last_sent``

Grid ``(S + 2, T, C)`` with ``T = max(tiles per stage)`` and ``C =
max(chunks per stage)`` — NO query axis; the [K] batch lives in-register
per tile via ``tile_min_batch`` exactly as in the batched per-phase
kernels, so layout tile loads per round stay ``n_tiles``, not
``n_tiles x K``. Each stage's layout refs use stage-aware index maps that
pin to block (0, 0, 0) while the stage is inactive (no refetch churn) and
clamp to valid tiles while active; validity predicates
``(i < n_xtiles) & (j < x_chunks)`` keep the clamped excess steps inert.

Like the per-phase kernels the distance buffer uses a CONSTANT full-array
BlockSpec: merged-then-relaxed-then-read-by-send values must survive
every revisit, which is only guaranteed when the block index never
changes between grid steps.

The kernel emits the residual frontier of the final sweep; when it is
non-empty (``n_sweeps`` did not reach the fixpoint) the in-kernel send
outputs were computed from unconverged distances and the caller runs the
``ops.fused_round_rescue`` continuation instead.

VMEM working set per step (bucket exchange):
  dist / prev / frontier rows   12 * K * block_pad
  incoming message rows          4 * K * P * C
  send val / last / new_last    12 * K * S_pad
  active stage's chunk          ~16 * EB
  one-hot expansion              4 * K * EB * width   (dominant)

The kernel is exchange-agnostic: the ``incoming`` operand is whatever
delivery the round hands it. Under the synchronous exchanges that is the
previous round's collective output held in ``carry.incoming``; under the
DEFERRED exchanges (``exchange="async*"``) it is a delivery that left its
sender one or more rounds earlier — the solver issues the collective for
the in-flight buffer at the top of the round, so nothing in this kernel's
dataflow depends on it and XLA is free to run the collective concurrently
with the whole grid. The scatter-min merge of stage 0 is monotone and
idempotent, which is exactly why merge lag is a round-count effect, never
a correctness one.
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


def _fused_round_kernel(*refs, dense: bool, vb: int, sb: int, n_vtiles: int,
                        n_stiles: int, n_mtiles: int, rx_chunks: int,
                        tx_chunks: int, mx_chunks: int, n_sweeps: int,
                        n_queries: int, grid_t: int, grid_c: int):
    """Grid (stage s, tile i, chunk j) — whole query batch per step."""
    if dense:
        (dist_ref, front_ref, live_ref, inc_ref, last_ref, svalid_ref,
         rxsrc_ref, rxw_ref, rxdst_ref, rxprn_ref,
         txsrc_ref, txw_ref, txseg_ref, txprn_ref,
         out_ref, resid_ref, val_ref, newlast_ref, nrel_ref, sends_ref,
         prev_ref, fcur_ref, flag_ref, rcount_ref, scount_ref) = refs
        mxpos_ref = mxdst_ref = mxval_ref = None
    else:
        (dist_ref, front_ref, live_ref, inc_ref, last_ref, svalid_ref,
         mxpos_ref, mxdst_ref, mxval_ref,
         rxsrc_ref, rxw_ref, rxdst_ref, rxprn_ref,
         txsrc_ref, txw_ref, txseg_ref, txprn_ref,
         out_ref, resid_ref, val_ref, newlast_ref, nrel_ref, sends_ref,
         prev_ref, fcur_ref, flag_ref, rcount_ref, scount_ref) = refs

    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    S = n_sweeps
    first = (s == 0) & (i == 0) & (j == 0)
    last = (s == S + 1) & (i == grid_t - 1) & (j == grid_c - 1)
    vtile = pl.dslice(i * vb, vb)
    stile = pl.dslice(i * sb, sb)
    live_col = live_ref[...][:, None] > 0             # [K, 1]

    @pl.when(first)
    def _init_counts():
        for k in range(n_queries):
            rcount_ref[k] = 0
            scount_ref[k] = 0

    # ---- stage 0: merge delivered messages, derive the frontier ----
    if dense:
        @pl.when(first)
        def _merge_dense():
            merged = jnp.minimum(dist_ref[...], inc_ref[...])
            out_ref[...] = merged
            newf = (merged < dist_ref[...]) & live_col
            fcur_ref[...] = jnp.maximum(newf.astype(jnp.float32),
                                        front_ref[...])
    else:
        m_ok = (s == 0) & (i < n_mtiles) & (j < mx_chunks)

        @pl.when(m_ok & (j == 0))
        def _init_mtile():
            out_ref[:, vtile] = dist_ref[:, vtile]

        @pl.when(m_ok)
        def _merge_chunk():
            pos = mxpos_ref[0, 0, :]              # [EB] int32 (padding = 0)
            dstrel = mxdst_ref[0, 0, :]           # [EB] int32 in [0, vb)
            valid = mxval_ref[0, 0, :] > 0
            v = jnp.take(inc_ref[...], pos, axis=1)       # [K, EB]
            cand = jnp.where(valid[None, :], v, INF)
            mins = tile_min_batch(cand, dstrel, width=vb)
            out_ref[:, vtile] = jnp.minimum(out_ref[:, vtile], mins)

        @pl.when(m_ok & (j == mx_chunks - 1))
        def _finalize_mtile():
            newf = (out_ref[:, vtile] < dist_ref[:, vtile]) & live_col
            fcur_ref[:, vtile] = jnp.maximum(newf.astype(jnp.float32),
                                             front_ref[:, vtile])

    # stage-end bookkeeping (ordered after the tile finalizers above)
    @pl.when((s == 0) & (i == grid_t - 1) & (j == grid_c - 1))
    def _merge_done():
        prev_ref[...] = out_ref[...]
        flag_ref[0] = jnp.any(fcur_ref[...] > 0).astype(jnp.int32)

    # ---- stages 1..S: frontier-chased relaxation sweeps ----
    r_stage = (s >= 1) & (s <= S)

    @pl.when(r_stage & (s > 1) & (i == 0) & (j == 0) & (flag_ref[0] > 0))
    def _advance_sweep():
        newf = (out_ref[...] < prev_ref[...]).astype(jnp.float32)
        fcur_ref[...] = newf
        flag_ref[0] = jnp.any(newf > 0).astype(jnp.int32)
        prev_ref[...] = out_ref[...]

    @pl.when(r_stage & (i < n_vtiles) & (j < rx_chunks) & (flag_ref[0] > 0))
    def _relax_chunk():
        src = rxsrc_ref[0, 0, :]                  # [EB] (padding = bp - 1)
        w = jnp.where(rxprn_ref[0, 0, :] > 0, INF, rxw_ref[0, 0, :])
        dstrel = rxdst_ref[0, 0, :]
        f_src = jnp.take(fcur_ref[...], src, axis=1) > 0  # [K, EB]
        d_src = jnp.take(out_ref[...], src, axis=1)       # Gauss–Seidel
        cand = jnp.where(f_src, d_src + w[None, :], INF)
        sums = jnp.sum(f_src & (w < INF)[None, :], axis=1).astype(jnp.int32)
        for k in range(n_queries):
            rcount_ref[k] = rcount_ref[k] + sums[k]
        mins = tile_min_batch(cand, dstrel, width=vb)
        out_ref[:, vtile] = jnp.minimum(out_ref[:, vtile], mins)

    # ---- stage S + 1: send-pack against last_sent ----
    s_ok = (s == S + 1) & (i < n_stiles) & (j < tx_chunks)

    @pl.when(s_ok & (j == 0))
    def _init_stile():
        val_ref[:, stile] = jnp.full((n_queries, sb), INF, jnp.float32)

    @pl.when(s_ok)
    def _send_chunk():
        src = txsrc_ref[0, 0, :]                  # [EB] (padding = 0)
        w = jnp.where(txprn_ref[0, 0, :] > 0, INF, txw_ref[0, 0, :])
        segrel = txseg_ref[0, 0, :]
        d_src = jnp.take(out_ref[...], src, axis=1)
        cand = d_src + w[None, :]
        mins = tile_min_batch(cand, segrel, width=sb)
        val_ref[:, stile] = jnp.minimum(val_ref[:, stile], mins)

    @pl.when(s_ok & (j == tx_chunks - 1))
    def _finalize_stile():
        val = val_ref[:, stile]
        prevl = last_ref[:, stile]
        valid = svalid_ref[stile][None, :] > 0
        improved = valid & (val < prevl)
        val_ref[:, stile] = jnp.where(improved, val, INF)
        newlast_ref[:, stile] = jnp.where(improved, val, prevl)
        sums = jnp.sum(improved, axis=1).astype(jnp.int32)
        for k in range(n_queries):
            scount_ref[k] = scount_ref[k] + sums[k]

    @pl.when(last)
    def _fin():
        resid_ref[...] = (out_ref[...] < prev_ref[...]).astype(jnp.float32)
        for k in range(n_queries):
            nrel_ref[k] = rcount_ref[k]
            sends_ref[k] = scount_ref[k]


def _stage_map(lo: int, hi: int, nt: int, nc: int):
    """Index map for a stage's layout refs: clamp to valid tiles while the
    stage is active, pin to block (0, 0, 0) otherwise (no refetch churn
    while other stages run)."""
    def m(s, i, j):
        ok = (s >= lo) & (s <= hi)
        ii = jnp.where(ok, jnp.minimum(i, nt - 1), 0)
        jj = jnp.where(ok, jnp.minimum(j, nc - 1), 0)
        return ii, jj, 0
    return m


def fused_round_tiled(dist_pad, front_pad, live, incoming, last_pad,
                      valid_pad, mx_layout, rx_layout, tx_layout, *, vb: int,
                      sb: int, n_sweeps: int, dense: bool,
                      interpret: bool | None = None):
    """One fused round. dist_pad/front_pad: [K, block_pad]; live: [K] f32
    0/1; incoming: [K, M] flat messages (bucket) or [K, block_pad] remote
    minima (dense); last_pad/valid_pad: [K, S_pad] / [S_pad].
    mx_layout = (pos_t, dstrel_t, valid_t) or None when dense;
    rx_layout = (src_t, w_t, dstrel_t, pruned_t);
    tx_layout = (src_t, w_t, segrel_t, pruned_t).

    Returns (new_dist [K, block_pad], resid [K, block_pad] f32 0/1,
    send_val [K, S_pad] — INF where not improved, new_last [K, S_pad],
    nrel [K] i32, sends [K] i32)."""
    rx_src, rx_w, rx_dst, rx_prn = rx_layout
    tx_src, tx_w, tx_seg, tx_prn = tx_layout
    n_vtiles, rx_chunks, rx_eb = rx_src.shape
    n_stiles, tx_chunks, tx_eb = tx_src.shape
    nq, bp = dist_pad.shape
    sp = n_stiles * sb
    assert bp == n_vtiles * vb and last_pad.shape == (nq, sp)
    S = n_sweeps

    if dense:
        assert incoming.shape == (nq, bp)
        n_mtiles, mx_chunks = 1, 1
    else:
        mx_pos, mx_dst, mx_val = mx_layout
        n_mtiles, mx_chunks, mx_eb = mx_pos.shape
        assert n_mtiles * vb == bp

    grid_t = max(n_vtiles, n_stiles, n_mtiles if not dense else 1)
    grid_c = max(rx_chunks, tx_chunks, mx_chunks if not dense else 1)
    grid = (S + 2, grid_t, grid_c)

    dist_spec = pl.BlockSpec((nq, bp), lambda s, i, j: (0, 0))
    slot_spec = pl.BlockSpec((nq, sp), lambda s, i, j: (0, 0))
    q_spec = pl.BlockSpec((nq,), lambda s, i, j: (0,))
    rx_spec = pl.BlockSpec((1, 1, rx_eb), _stage_map(1, S, n_vtiles,
                                                     rx_chunks))
    tx_spec = pl.BlockSpec((1, 1, tx_eb), _stage_map(S + 1, S + 1, n_stiles,
                                                     tx_chunks))

    in_specs = [dist_spec, dist_spec, q_spec]
    operands = [dist_pad, front_pad, live]
    if dense:
        in_specs += [dist_spec]
        operands += [incoming]
    else:
        inc_spec = pl.BlockSpec(incoming.shape, lambda s, i, j: (0, 0))
        mx_spec = pl.BlockSpec((1, 1, mx_eb), _stage_map(0, 0, n_mtiles,
                                                         mx_chunks))
        in_specs += [inc_spec]
        operands += [incoming]
    in_specs += [slot_spec, pl.BlockSpec((sp,), lambda s, i, j: (0,))]
    operands += [last_pad, valid_pad]
    if not dense:
        in_specs += [mx_spec, mx_spec, mx_spec]
        operands += [mx_pos, mx_dst, mx_val]
    in_specs += [rx_spec] * 4 + [tx_spec] * 4
    operands += [rx_src, rx_w, rx_dst, rx_prn, tx_src, tx_w, tx_seg, tx_prn]

    kernel = functools.partial(
        _fused_round_kernel, dense=dense, vb=vb, sb=sb, n_vtiles=n_vtiles,
        n_stiles=n_stiles, n_mtiles=n_mtiles, rx_chunks=rx_chunks,
        tx_chunks=tx_chunks, mx_chunks=mx_chunks, n_sweeps=S, n_queries=nq,
        grid_t=grid_t, grid_c=grid_c)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            dist_spec,            # merged + relaxed distances
            dist_spec,            # residual frontier of the final sweep
            slot_spec,            # masked send values
            slot_spec,            # updated last_sent
            q_spec,               # per-query relaxations
            q_spec,               # per-query sends
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, bp), dist_pad.dtype),
            jax.ShapeDtypeStruct((nq, bp), jnp.float32),
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nq, bp), jnp.float32),    # prev (sweep snapshot)
            pltpu.VMEM((nq, bp), jnp.float32),    # current frontier
            pltpu.SMEM((1,), jnp.int32),          # global early-out flag
            pltpu.SMEM((nq,), jnp.int32),         # relaxation counters
            pltpu.SMEM((nq,), jnp.int32),         # send counters
        ],
        interpret=pallas_interpret(interpret),
    )(*operands)


def _fused_round_ragged_kernel(*refs, dense: bool, vb: int, sb: int,
                               n_vtiles: int, n_stiles: int,
                               rx_chunks: int, tx_chunks: int, mx_chunks: int,
                               n_sweeps: int, n_queries: int, grid_c: int):
    """Ragged fused round: grid (stage s, flat chunk c) — the tile axis is
    folded into the scalar-prefetched per-stage chunk→tile maps, so the
    grid walks ``sum_t chunks_t`` steps per stage instead of ``max_t
    chunks_t × n_tiles``. Per-tile init/finalize become GLOBAL (first/last
    chunk of the stage): no accumulate step reads a finalizer's output, so
    the values are bit-identical to the dense schedule, and zero-chunk
    tiles — which the ragged chunk lists skip entirely — still get their
    identity init/finalize."""
    if dense:
        (rxct_ref, txct_ref,
         dist_ref, front_ref, live_ref, inc_ref, last_ref, svalid_ref,
         rxsrc_ref, rxw_ref, rxdst_ref, rxprn_ref,
         txsrc_ref, txw_ref, txseg_ref, txprn_ref,
         out_ref, resid_ref, val_ref, newlast_ref, nrel_ref, sends_ref,
         prev_ref, fcur_ref, flag_ref, rcount_ref) = refs
        mxct_ref = mxpos_ref = mxdst_ref = mxval_ref = None
    else:
        (mxct_ref, rxct_ref, txct_ref,
         dist_ref, front_ref, live_ref, inc_ref, last_ref, svalid_ref,
         mxpos_ref, mxdst_ref, mxval_ref,
         rxsrc_ref, rxw_ref, rxdst_ref, rxprn_ref,
         txsrc_ref, txw_ref, txseg_ref, txprn_ref,
         out_ref, resid_ref, val_ref, newlast_ref, nrel_ref, sends_ref,
         prev_ref, fcur_ref, flag_ref, rcount_ref) = refs

    s = pl.program_id(0)
    c = pl.program_id(1)
    S = n_sweeps
    first = (s == 0) & (c == 0)
    last = (s == S + 1) & (c == grid_c - 1)
    live_col = live_ref[...][:, None] > 0             # [K, 1]

    @pl.when(first)
    def _init():
        for k in range(n_queries):
            rcount_ref[k] = 0

    # ---- stage 0: merge delivered messages, derive the frontier ----
    if dense:
        @pl.when(first)
        def _merge_dense():
            out_ref[...] = jnp.minimum(dist_ref[...], inc_ref[...])
    else:
        @pl.when(first)
        def _init_merge():
            out_ref[...] = dist_ref[...]

        @pl.when((s == 0) & (c < mx_chunks))
        def _merge_chunk():
            t = jnp.minimum(mxct_ref[c], n_vtiles - 1)
            vtile = pl.dslice(t * vb, vb)
            pos = mxpos_ref[0, :]                 # [EB] int32 (padding = 0)
            dstrel = mxdst_ref[0, :]              # [EB] int32 in [0, vb)
            valid = mxval_ref[0, :] > 0
            v = jnp.take(inc_ref[...], pos, axis=1)       # [K, EB]
            cand = jnp.where(valid[None, :], v, INF)
            mins = tile_min_batch(cand, dstrel, width=vb)
            out_ref[:, vtile] = jnp.minimum(out_ref[:, vtile], mins)

    # stage-end bookkeeping: global frontier + sweep snapshot
    @pl.when((s == 0) & (c == grid_c - 1))
    def _merge_done():
        newf = (out_ref[...] < dist_ref[...]) & live_col
        fcur_ref[...] = jnp.maximum(newf.astype(jnp.float32), front_ref[...])
        prev_ref[...] = out_ref[...]
        flag_ref[0] = jnp.any(fcur_ref[...] > 0).astype(jnp.int32)

    # ---- stages 1..S: frontier-chased relaxation sweeps ----
    r_stage = (s >= 1) & (s <= S)

    @pl.when(r_stage & (s > 1) & (c == 0) & (flag_ref[0] > 0))
    def _advance_sweep():
        newf = (out_ref[...] < prev_ref[...]).astype(jnp.float32)
        fcur_ref[...] = newf
        flag_ref[0] = jnp.any(newf > 0).astype(jnp.int32)
        prev_ref[...] = out_ref[...]

    @pl.when(r_stage & (c < rx_chunks) & (flag_ref[0] > 0))
    def _relax_chunk():
        t = jnp.minimum(rxct_ref[c], n_vtiles - 1)
        vtile = pl.dslice(t * vb, vb)
        src = rxsrc_ref[0, :]                     # [EB] (padding = bp - 1)
        w = jnp.where(rxprn_ref[0, :] > 0, INF, rxw_ref[0, :])
        dstrel = rxdst_ref[0, :]
        f_src = jnp.take(fcur_ref[...], src, axis=1) > 0  # [K, EB]
        d_src = jnp.take(out_ref[...], src, axis=1)       # Gauss–Seidel
        cand = jnp.where(f_src, d_src + w[None, :], INF)
        sums = jnp.sum(f_src & (w < INF)[None, :], axis=1).astype(jnp.int32)
        for k in range(n_queries):
            rcount_ref[k] = rcount_ref[k] + sums[k]
        mins = tile_min_batch(cand, dstrel, width=vb)
        out_ref[:, vtile] = jnp.minimum(out_ref[:, vtile], mins)

    # ---- stage S + 1: send-pack against last_sent ----
    @pl.when((s == S + 1) & (c == 0))
    def _init_send():
        val_ref[...] = jnp.full(val_ref.shape, INF, jnp.float32)

    @pl.when((s == S + 1) & (c < tx_chunks))
    def _send_chunk():
        t = jnp.minimum(txct_ref[c], n_stiles - 1)
        stile = pl.dslice(t * sb, sb)
        src = txsrc_ref[0, :]                     # [EB] (padding = 0)
        w = jnp.where(txprn_ref[0, :] > 0, INF, txw_ref[0, :])
        segrel = txseg_ref[0, :]
        d_src = jnp.take(out_ref[...], src, axis=1)
        cand = d_src + w[None, :]
        mins = tile_min_batch(cand, segrel, width=sb)
        val_ref[:, stile] = jnp.minimum(val_ref[:, stile], mins)

    @pl.when(last)
    def _fin():
        val = val_ref[...]                        # [K, S_pad]
        prevl = last_ref[...]
        valid = svalid_ref[...][None, :] > 0
        improved = valid & (val < prevl)
        val_ref[...] = jnp.where(improved, val, INF)
        newlast_ref[...] = jnp.where(improved, val, prevl)
        ssums = jnp.sum(improved, axis=1).astype(jnp.int32)
        resid_ref[...] = (out_ref[...] < prev_ref[...]).astype(jnp.float32)
        for k in range(n_queries):
            nrel_ref[k] = rcount_ref[k]
            sends_ref[k] = ssums[k]


def _stage_map_ragged(lo: int, hi: int, nc: int):
    """Ragged stage index map: clamp the flat chunk while the stage is
    active, pin to block (0, 0) otherwise. Scalar-prefetch refs arrive as
    trailing args and are unused here — the CHUNK index is the block index;
    the tile lives in the kernel-side map."""
    def m(s, c, *_):
        ok = (s >= lo) & (s <= hi)
        return jnp.where(ok, jnp.minimum(c, nc - 1), 0), 0
    return m


def fused_round_ragged(dist_pad, front_pad, live, incoming, last_pad,
                       valid_pad, mx_layout, rx_layout, tx_layout, *,
                       vb: int, sb: int, n_sweeps: int, dense: bool,
                       interpret: bool | None = None):
    """One fused round over ragged CSR-chunked layouts.

    Same contract as ``fused_round_tiled`` except each layout tuple gains
    its chunk→tile map: rx/tx_layout = (src_r, w_r, *, pruned_r, ctile)
    with flat [total_chunks, EB] rows; mx_layout = (pos_r, dstrel_r,
    valid_r, ctile) or None when dense."""
    rx_src, rx_w, rx_dst, rx_prn, rx_ct = rx_layout
    tx_src, tx_w, tx_seg, tx_prn, tx_ct = tx_layout
    rx_chunks, rx_eb = rx_src.shape
    tx_chunks, tx_eb = tx_src.shape
    nq, bp = dist_pad.shape
    sp = last_pad.shape[1]
    assert bp % vb == 0 and sp % sb == 0 and last_pad.shape == (nq, sp)
    n_vtiles = bp // vb
    n_stiles = sp // sb
    S = n_sweeps

    if dense:
        assert incoming.shape == (nq, bp)
        mx_chunks = 1
        scalars = (rx_ct, tx_ct)
    else:
        mx_pos, mx_dst, mx_val, mx_ct = mx_layout
        mx_chunks, mx_eb = mx_pos.shape
        scalars = (mx_ct, rx_ct, tx_ct)

    grid_c = max(rx_chunks, tx_chunks, mx_chunks if not dense else 1)
    grid = (S + 2, grid_c)

    dist_spec = pl.BlockSpec((nq, bp), lambda s, c, *_: (0, 0))
    slot_spec = pl.BlockSpec((nq, sp), lambda s, c, *_: (0, 0))
    q_spec = pl.BlockSpec((nq,), lambda s, c, *_: (0,))
    rx_spec = pl.BlockSpec((1, rx_eb), _stage_map_ragged(1, S, rx_chunks))
    tx_spec = pl.BlockSpec((1, tx_eb), _stage_map_ragged(S + 1, S + 1,
                                                         tx_chunks))

    in_specs = [dist_spec, dist_spec, q_spec]
    operands = [dist_pad, front_pad, live]
    if dense:
        in_specs += [dist_spec]
    else:
        in_specs += [pl.BlockSpec(incoming.shape, lambda s, c, *_: (0, 0))]
    operands += [incoming]
    in_specs += [slot_spec, pl.BlockSpec((sp,), lambda s, c, *_: (0,))]
    operands += [last_pad, valid_pad]
    if not dense:
        mx_spec = pl.BlockSpec((1, mx_eb), _stage_map_ragged(0, 0, mx_chunks))
        in_specs += [mx_spec, mx_spec, mx_spec]
        operands += [mx_pos, mx_dst, mx_val]
    in_specs += [rx_spec] * 4 + [tx_spec] * 4
    operands += [rx_src, rx_w, rx_dst, rx_prn, tx_src, tx_w, tx_seg, tx_prn]

    kernel = functools.partial(
        _fused_round_ragged_kernel, dense=dense, vb=vb, sb=sb,
        n_vtiles=n_vtiles, n_stiles=n_stiles, rx_chunks=rx_chunks, tx_chunks=tx_chunks, mx_chunks=mx_chunks,
        n_sweeps=S, n_queries=nq, grid_c=grid_c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            dist_spec,            # merged + relaxed distances
            dist_spec,            # residual frontier of the final sweep
            slot_spec,            # masked send values
            slot_spec,            # updated last_sent
            q_spec,               # per-query relaxations
            q_spec,               # per-query sends
        ],
        scratch_shapes=[
            pltpu.VMEM((nq, bp), jnp.float32),    # prev (sweep snapshot)
            pltpu.VMEM((nq, bp), jnp.float32),    # current frontier
            pltpu.SMEM((1,), jnp.int32),          # global early-out flag
            pltpu.SMEM((nq,), jnp.int32),         # relaxation counters
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, bp), dist_pad.dtype),
            jax.ShapeDtypeStruct((nq, bp), jnp.float32),
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq, sp), jnp.float32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
            jax.ShapeDtypeStruct((nq,), jnp.int32),
        ],
        interpret=pallas_interpret(interpret),
    )(*scalars, *operands)
