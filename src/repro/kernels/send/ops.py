"""jit'd wrappers + host-side slot-tiled layout builder for the send kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.relax import build_dst_ragged_layout, build_dst_tiled_layout
from repro.kernels.send.send import send_pack_ragged, send_pack_tiled

INF = float("inf")


def build_slot_ragged_layout(cut_src, cut_seg, cut_w, n_slots: int, *,
                             sb: int = 128, eb: int = 512):
    """Ragged (CSR-chunked) slot layout: cut edges -> flat [total_chunks,
    EB] rows + [total_chunks] chunk→tile map, same slot-in-destination-role
    reuse of the relax builder as ``build_slot_tiled_layout`` (padding
    sources restamped to 0 — in range, inert via +inf weight).

    Returns (src_r, w_r, segrel_r, eid_r, ctile, S_pad)."""
    src_r, w_r, segrel_r, eid_r, ctile, s_pad = build_dst_ragged_layout(
        cut_src, cut_seg, cut_w, n_slots, vb=sb, eb=eb, with_eid=True)
    pad = eid_r == len(np.asarray(cut_src))
    src_r = jnp.where(pad, 0, src_r)
    return src_r, w_r, segrel_r, eid_r, ctile, s_pad


def build_slot_tiled_layout(cut_src, cut_seg, cut_w, n_slots: int, *,
                            sb: int = 128, eb: int = 512):
    """One-time host preprocessing: cut edges -> [n_stiles, n_chunks, EB]
    grouped by message-slot tile.

    Structurally the dst-tiled relax layout with the SLOT id in the
    destination role, so the same builder is reused; the one difference is
    the padding-source sentinel: the relax layout points padding at the
    padded DISTANCE slot (``block_pad - 1``), but here the gather target is
    the distance row while the tiling target is the slot axis, so padding
    entries are restamped to source 0 (any in-range vertex — their +inf
    weight keeps them inert).

    Returns (src_t, w_t, segrel_t, eid_t, S_pad); eid_t maps tiled slots
    back to positions in the ORIGINAL cut-edge list (sentinel = len(cut_src))
    so the runtime Trishla pruned mask gathers into tiled order.
    """
    src_t, w_t, segrel_t, eid_t, s_pad = build_dst_tiled_layout(
        cut_src, cut_seg, cut_w, n_slots, vb=sb, eb=eb, with_eid=True)
    pad = eid_t == len(np.asarray(cut_src))
    src_t = jnp.where(pad, 0, src_t)
    return src_t, w_t, segrel_t, eid_t, s_pad


@partial(jax.jit, static_argnames=("sb", "eb", "interpret"))
def send_pack_pallas(dist, last_sent, slot_valid, src_t, w_t, segrel_t,
                     pruned_t, ctile=None, *, sb: int = 128, eb: int = 512,
                     interpret: bool | None = None):
    """Solver-facing wrapper: pads to kernel tile shapes, slices back.

    dist: [K, block]; last_sent: [K, S]; slot_valid: [S] bool;
    src_t/w_t/segrel_t/pruned_t: [n_stiles, n_chunks, EB] slot-tiled layout
    (pruned_t already gathered into tiled order), or — with ``ctile`` given
    — flat [total_chunks, EB] ragged rows plus the chunk→tile map. Returns
    (send_val [K, S] — INF where not improved, new_last [K, S], sends [K]).
    """
    nq, block = dist.shape
    S = last_sent.shape[1]
    n_stiles = src_t.shape[0] if ctile is None else max(-(-S // sb), 1)
    sp = n_stiles * sb
    bp = -(-block // 128) * 128      # lane-align the gathered distance row
    dist_pad = jnp.full((nq, bp), INF).at[:, :block].set(dist)
    last_pad = jnp.full((nq, sp), INF).at[:, :S].set(last_sent)
    valid_pad = jnp.zeros((sp,), jnp.int32).at[:S].set(
        slot_valid.astype(jnp.int32))
    if ctile is None:
        val, new_last, sends = send_pack_tiled(
            dist_pad, last_pad, valid_pad, src_t, w_t, segrel_t, pruned_t,
            sb=sb, eb=eb, interpret=interpret)
    else:
        val, new_last, sends = send_pack_ragged(
            dist_pad, last_pad, valid_pad, ctile, src_t, w_t, segrel_t,
            pruned_t, sb=sb, eb=eb, interpret=interpret)
    return val[:, :S], new_last[:, :S], sends


def send_payload_bucket(send_val, payload_slot):
    """Route masked slot values into the [K, P, C] bucketed payload.

    ``payload_slot[p, c]`` is the STATIC inverse of ``(slot_owner,
    slot_pos)``: the slot feeding position ``c`` of the row bound for shard
    ``p`` (sentinel = out-of-range -> INF). Because each payload position
    receives at most one slot, the runtime scatter the XLA path pays
    becomes a plain gather."""
    return jnp.take(send_val, payload_slot.reshape(-1), axis=1, mode="fill",
                    fill_value=INF).reshape(
                        send_val.shape[0], *payload_slot.shape)
