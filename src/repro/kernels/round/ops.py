"""jit'd wrappers for the fused round megakernel.

``fused_round_pallas`` pads the solver-facing state into tile-aligned
buffers, gathers the Trishla pruned mask into both tiled edge orders, and
runs the megakernel. It deliberately does NOT resolve the residual
frontier: the caller inspects ``resid`` and — only when some query's
fixpoint escaped ``n_sweeps`` in-kernel sweeps — runs
``fused_round_rescue``, which finishes the relaxation with the batched
relax kernel and re-packs the sends against the ORIGINAL ``last_sent``
(the megakernel's send outputs were computed from unconverged distances
and are discarded wholesale). Keeping the rescue outside lets the solver
wrap it in a ``lax.cond`` whose predicate is reduced over the whole shard
stack, so the common all-converged round never pays for it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.relax import (
    relax_fixpoint_batch_pallas, relax_fixpoint_batch_ragged_pallas,
)
from repro.kernels.round.round import fused_round_ragged, fused_round_tiled
from repro.kernels.send.send import send_pack_ragged, send_pack_tiled

INF = float("inf")


def _pad_state(dist, front_in, live, last_sent, slot_valid, *, bp, sp):
    nq, block = dist.shape
    n_slots = last_sent.shape[1]
    dist_pad = jnp.full((nq, bp), INF, jnp.float32).at[:, :block].set(dist)
    front_pad = (jnp.zeros((nq, bp), jnp.float32)
                 .at[:, :block].set(front_in.astype(jnp.float32)))
    last_pad = (jnp.full((nq, sp), INF, jnp.float32)
                .at[:, :n_slots].set(last_sent))
    valid_pad = (jnp.zeros((sp,), jnp.int32)
                 .at[:n_slots].set(slot_valid.astype(jnp.int32)))
    return dist_pad, front_pad, live.astype(jnp.float32), last_pad, valid_pad


def _gather_pruned(pruned, eid_t):
    return jnp.take(pruned.astype(jnp.int32), eid_t, mode="fill",
                    fill_value=0)


@partial(jax.jit, static_argnames=("vb", "sb", "n_sweeps", "dense",
                                   "interpret"))
def fused_round_pallas(dist, front_in, live, incoming, last_sent, slot_valid,
                       relax_layout, send_layout, merge_layout, pruned_loc,
                       pruned_cut, *, vb: int = 128, sb: int = 128,
                       n_sweeps: int = 8, dense: bool = False,
                       interpret: bool | None = None):
    """One fused merge + local-fixpoint + send-pack round on one shard.

    dist/front_in: [K, block]; live: [K] bool; incoming: [K, M] flattened
    bucket messages or [K, block] dense remote minima; last_sent/slot_valid:
    [K, S] / [S]; relax_layout/send_layout: the shard's 4-tuple tiled edge
    layouts (src, w, rel, eid); merge_layout: (pos, dstrel, valid) msg-tiled
    layout (ignored when dense); pruned_loc/pruned_cut: [e_loc] / [e_cut]
    Trishla masks in original edge order.

    Returns (new_dist [K, block], send_val [K, S], new_last [K, S],
    nrel [K], sends [K], resid [K, block] f32 — non-empty rows mean the
    in-kernel sweeps did not converge and the caller must rescue).

    Ragged (CSR-chunked) shards pass 5-tuple relax/send layouts (flat
    chunk rows + chunk→tile map) and a 4-tuple merge layout; the tuple
    arity selects the ragged megakernel."""
    ragged = len(relax_layout) == 5
    if ragged:
        rx_src, rx_w, rx_dst, rx_eid, rx_ct = relax_layout
        tx_src, tx_w, tx_seg, tx_eid, tx_ct = send_layout
    else:
        rx_src, rx_w, rx_dst, rx_eid = relax_layout
        tx_src, tx_w, tx_seg, tx_eid = send_layout
    nq, block = dist.shape
    n_slots = last_sent.shape[1]
    if ragged:
        bp = max(-(-block // vb), 1) * vb
        sp = max(-(-n_slots // sb), 1) * sb
    else:
        bp = rx_src.shape[0] * vb
        sp = tx_src.shape[0] * sb

    dist_pad, front_pad, live_f, last_pad, valid_pad = _pad_state(
        dist, front_in, live, last_sent, slot_valid, bp=bp, sp=sp)
    rx = (rx_src, rx_w, rx_dst, _gather_pruned(pruned_loc, rx_eid))
    tx = (tx_src, tx_w, tx_seg, _gather_pruned(pruned_cut, tx_eid))
    if ragged:
        rx = rx + (rx_ct,)
        tx = tx + (tx_ct,)
    if dense:
        inc = jnp.full((nq, bp), INF, jnp.float32).at[:, :block].set(incoming)
        mx = None
    else:
        inc = incoming
        mx = merge_layout

    round_fn = fused_round_ragged if ragged else fused_round_tiled
    out, resid, sval, nlast, nrel, sends = round_fn(
        dist_pad, front_pad, live_f, inc, last_pad, valid_pad, mx, rx, tx,
        vb=vb, sb=sb, n_sweeps=n_sweeps, dense=dense, interpret=interpret)
    return (out[:, :block], sval[:, :n_slots], nlast[:, :n_slots], nrel,
            sends, resid[:, :block])


@partial(jax.jit, static_argnames=("vb", "sb", "n_sweeps", "max_iters",
                                   "interpret"))
def fused_round_rescue(dist, resid, last_sent, slot_valid, relax_layout,
                       send_layout, pruned_loc, pruned_cut, *, vb: int = 128,
                       sb: int = 128, n_sweeps: int = 8,
                       max_iters: int = 10_000, interpret: bool | None = None):
    """Finish a round whose in-kernel sweeps left a residual frontier.

    ``dist``/``resid`` are the megakernel's merged-and-partially-relaxed
    distances and its final-sweep residual. Continues the fixpoint with the
    batched relax kernel (iteration budget starts at ``n_sweeps``, exactly
    like the staged pipeline's outer loop) and re-packs the sends against
    the original ``last_sent``. Returns (new_dist [K, block],
    send_val [K, S], new_last [K, S], nrel_extra [K], sends [K])."""
    ragged = len(relax_layout) == 5
    if ragged:
        rx_src, rx_w, rx_dst, rx_eid, rx_ct = relax_layout
        tx_src, tx_w, tx_seg, tx_eid, tx_ct = send_layout
    else:
        rx_src, rx_w, rx_dst, rx_eid = relax_layout
        tx_src, tx_w, tx_seg, tx_eid = send_layout
    rx_eb = rx_src.shape[-1]
    tx_eb = tx_src.shape[-1]
    nq, block = dist.shape
    n_slots = last_sent.shape[1]
    if ragged:
        bp = max(-(-block // vb), 1) * vb
        sp = max(-(-n_slots // sb), 1) * sb
    else:
        bp = rx_src.shape[0] * vb
        sp = tx_src.shape[0] * sb

    dist_pad, front_pad, _, last_pad, valid_pad = _pad_state(
        dist, resid, jnp.ones((nq,), bool), last_sent, slot_valid, bp=bp,
        sp=sp)
    prn_rx = _gather_pruned(pruned_loc, rx_eid)
    prn_tx = _gather_pruned(pruned_cut, tx_eid)

    def cond(c):
        _, front, _, it = c
        return jnp.any(front > 0) & (it < max_iters)

    def body(c):
        d, front, n, it = c
        if ragged:
            nd, rs, k = relax_fixpoint_batch_ragged_pallas(
                d, front, rx_ct, rx_src, rx_w, rx_dst, prn_rx, vb=vb,
                eb=rx_eb, n_sweeps=n_sweeps, interpret=interpret)
        else:
            nd, rs, k = relax_fixpoint_batch_pallas(
                d, front, rx_src, rx_w, rx_dst, prn_rx, vb=vb, eb=rx_eb,
                n_sweeps=n_sweeps, interpret=interpret)
        return nd, rs, n + k, it + jnp.int32(n_sweeps)

    d2, _, nrel_extra, _ = jax.lax.while_loop(
        cond, body, (dist_pad, front_pad, jnp.zeros((nq,), jnp.int32),
                     jnp.int32(n_sweeps)))
    if ragged:
        sval, nlast, sends = send_pack_ragged(
            d2, last_pad, valid_pad, tx_ct, tx_src, tx_w, tx_seg, prn_tx,
            sb=sb, eb=tx_eb, interpret=interpret)
    else:
        sval, nlast, sends = send_pack_tiled(
            d2, last_pad, valid_pad, tx_src, tx_w, tx_seg, prn_tx, sb=sb,
            eb=tx_eb, interpret=interpret)
    return (d2[:, :block], sval[:, :n_slots], nlast[:, :n_slots], nrel_extra,
            sends)
