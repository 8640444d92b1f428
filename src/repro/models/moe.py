"""Top-k routed MoE FFN with hierarchical sort-based dispatch.

Two-level structure keyed to the mesh (the beyond-GShard design this repo
ships as the baseline after profiling the naive global-argsort dispatch at
565 GiB temp/device — see EXPERIMENTS.md §Perf):

  1. tokens are viewed as [G, Tg, D] where G = number of data shards
     (static); the argsort, capacity masking, and scatter into expert
     buffers are *per-group*, i.e. local to each data shard — no global
     sort, no cross-shard scatter;
  2. expert buffers [G, E, Cg, D] are sharded (G -> data, E -> model):
     the expert GEMMs are fully local (weights are E-sharded over model);
  3. the only communication is the combine: gathering each token's expert
     outputs from E-sharded buffers lowers to one all-reduce over the
     model axis (GSPMD inserts it) — the EP exchange, structurally the
     same per-destination bucket pattern as the SSSP boundary exchange.

Per-group capacity Cg = ceil(Tg·k/E · capacity_factor): group-local
capacity drops differ slightly from global-capacity semantics (documented;
standard in EP implementations).

Aux loss: Switch-style load balancing over global router stats.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P



def _routing_group(topi_g, E: int, k: int, Cg: int):
    """Index-level routing for one group — int32 arrays only, no D-wide
    tensors. topi_g: [Tg, k]. Returns:
      slot_token [E*Cg]: source token of each expert buffer slot (Tg = empty)
      pos [Tg, k], keep [Tg, k]: each assignment's capacity slot / survival
    """
    Tg = topi_g.shape[0]
    flat_e = topi_g.reshape(Tg * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    token_of = (order // k).astype(jnp.int32)
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos_sorted = (jnp.arange(Tg * k) - starts[sorted_e]).astype(jnp.int32)
    kept_sorted = pos_sorted < Cg
    # inverse map: expert-buffer slot -> token (gather-based dispatch)
    slot_of = jnp.where(kept_sorted, sorted_e * Cg + pos_sorted, E * Cg)
    slot_token = jnp.full((E * Cg,), Tg, jnp.int32).at[slot_of].set(
        token_of, mode="drop")
    # forward map back to (token, k) layout
    pos = jnp.zeros((Tg * k,), jnp.int32).at[order].set(pos_sorted)
    pos = pos.reshape(Tg, k)
    keep = pos < Cg
    return slot_token, pos, keep


def _dispatch_group(xg, slot_token, E: int, Cg: int):
    """ONE D-wide gather builds the expert buffers (backward = one
    scatter-add); empty slots read a zero row."""
    xz = jnp.concatenate([xg, jnp.zeros((1, xg.shape[1]), xg.dtype)])
    return xz[slot_token].reshape(E, Cg, xg.shape[1])


def _combine_group(out_buf, topi_g, pos, keep, topv_g, k: int):
    """Single gather of all (token, k) slots + weighted sum.

    The k-contraction is written as elementwise-mul + reduce (NOT einsum):
    the gather from the expert-sharded buffer yields a *partial* tensor,
    and GSPMD defers partial-sum resolution through elementwise ops and
    reductions but not through dot_general — with einsum the all-reduce
    moved the full [Tg, k, D] (8 GiB f32/layer on qwen3); with mul+sum it
    moves [Tg, D] after the k-reduction (8x less; §Perf iter 4)."""
    E, Cg, D = out_buf.shape
    flat = out_buf.reshape(E * Cg, D)
    flat = jnp.concatenate([flat, jnp.zeros((1, D), flat.dtype)])
    idx = jnp.where(keep, topi_g * Cg + pos, E * Cg)     # [Tg, k]
    got = flat[idx]                                      # [Tg, k, D] partial
    w = jnp.where(keep, topv_g, 0.0).astype(out_buf.dtype)
    return jnp.sum(got * w[..., None], axis=1)


def _expert_block_shmap(xg, slot_token, topi_g, pos, keep, topv_g,
                        w_gate, w_up, w_down, activation: str, ax, E: int,
                        k: int, Cg: int):
    """Expert compute + combine under manual collectives (shard_map).

    GSPMD resolves the combine's gather from E-sharded buffers by
    all-reducing the full gathered tensor (§Perf iter 4, refuted path).
    Manually: tokens are replicated within a data row, each model shard
    builds buffers and runs FFN for ITS experts only (zero-comm dispatch),
    computes its partial combine [Tg, D], and ONE psum over the model axis
    finishes the job — the minimal EP exchange for replicated-token MoE.
    """
    import jax
    from jax import lax as _lax

    act = jax.nn.silu if activation == "silu" else partial(jax.nn.gelu, approximate=True)
    model_ax = ax.model

    def body(xg_l, slot_l, topi_l, pos_l, keep_l, topv_l, wg_l, wu_l, wd_l):
        # strip leading G/E dims that shard_map leaves as local slices
        x_l = xg_l[0]                       # [Tg, D]
        sl = slot_l[0]                      # [E_loc, Cg]
        ti, po, ke, tv = topi_l[0], pos_l[0], keep_l[0], topv_l[0]
        E_loc = wg_l.shape[0]
        e0 = _lax.axis_index(model_ax) * E_loc

        xz = jnp.concatenate([x_l, jnp.zeros((1, x_l.shape[1]), x_l.dtype)])
        buf = xz[sl.reshape(-1)].reshape(E_loc, Cg, x_l.shape[1])
        g = jnp.einsum("ecd,edf->ecf", buf, wg_l)
        u = jnp.einsum("ecd,edf->ecf", buf, wu_l)
        out = jnp.einsum("ecf,efd->ecd", act(g) * u, wd_l)  # [E_loc, Cg, D]

        e_rel = ti - e0
        mine = ke & (e_rel >= 0) & (e_rel < E_loc)
        flat = out.reshape(E_loc * Cg, -1)
        flat = jnp.concatenate([flat, jnp.zeros((1, flat.shape[1]), flat.dtype)])
        idx = jnp.where(mine, e_rel * Cg + po, E_loc * Cg)
        got = flat[idx]                                      # [Tg, k, D]
        w = jnp.where(mine, tv, 0.0).astype(out.dtype)
        y_part = jnp.sum(got * w[..., None], axis=1)         # [Tg, D]
        y = _lax.psum(y_part, model_ax)                      # THE EP combine
        return y[None]                                       # restore G dim

    P_ = P
    specs = dict(
        xg=P_(ax.data, None, None),
        slot=P_(ax.data, ax.model, None),
        tok=P_(ax.data, None, None),
        w=P_(ax.model, None, None),
        out=P_(ax.data, None, None),
    )
    return jax.shard_map(
        body,
        in_specs=(specs["xg"], specs["slot"], specs["tok"], specs["tok"],
                  specs["tok"], specs["tok"], specs["w"], specs["w"],
                  specs["w"]),
        out_specs=specs["out"],
        check_vma=False,
    )(xg, slot_token.reshape(xg.shape[0], E, Cg), topi_g, pos, keep, topv_g,
      w_gate, w_up, w_down)


def moe_ffn(x, lp, moe_cfg, activation: str, ax, impl: str = "gspmd"):
    """x: [B, S, D]. lp: w_router [D,E], w_gate/w_up [E,D,F], w_down [E,F,D].
    Returns (y [B, S, D], aux_loss scalar). impl: gspmd | shmap."""
    B, S, D = x.shape
    E, k = moe_cfg.n_experts, moe_cfg.top_k
    T = B * S
    G = max(int(ax.data_shards), 1)
    if impl == "shmap":
        # shard_map body assumes exactly one token group per data shard
        assert T % G == 0, (T, G)
    else:
        while T % G:                               # smoke meshes: G=1 fallback
            G //= 2
    Tg = T // G
    Cg = max(int(Tg * k / E * moe_cfg.capacity_factor), 1)

    xf = x.reshape(T, D)
    logits = (xf @ lp["w_router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, k)
    if moe_cfg.norm_topk:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    xg = xf.reshape(G, Tg, D)
    xg = lax.with_sharding_constraint(xg, P(ax.data, None, None))
    topi_g = topi.reshape(G, Tg, k)
    topv_g = topv.reshape(G, Tg, k)

    slot_token, pos, keep = jax.vmap(
        partial(_routing_group, E=E, k=k, Cg=Cg))(topi_g)

    # ZeRO-3 weight gather at use: keep E sharded (EP over model), gather the
    # fsdp-sharded d_model dim — otherwise GSPMD all-reduces the activation
    w_gate = lax.with_sharding_constraint(lp["w_gate"], P(ax.model, None, None))
    w_up = lax.with_sharding_constraint(lp["w_up"], P(ax.model, None, None))
    w_down = lax.with_sharding_constraint(lp["w_down"], P(ax.model, None, None))

    if impl == "shmap":
        y = _expert_block_shmap(xg, slot_token, topi_g, pos, keep, topv_g,
                                w_gate, w_up, w_down, activation, ax, E, k, Cg)
        y = y.reshape(B, S, D)
    else:
        act = jax.nn.silu if activation == "silu" else partial(jax.nn.gelu, approximate=True)
        buf = jax.vmap(partial(_dispatch_group, E=E, Cg=Cg))(xg, slot_token)
        buf = lax.with_sharding_constraint(buf, P(ax.data, ax.model, None, None))
        g = jnp.einsum("gecd,edf->gecf", buf, w_gate)
        u = jnp.einsum("gecd,edf->gecf", buf, w_up)
        h = act(g) * u
        out = jnp.einsum("gecf,efd->gecd", h, w_down)
        out = lax.with_sharding_constraint(out, P(ax.data, ax.model, None, None))
        y = jax.vmap(partial(_combine_group, k=k))(out, topi_g, pos, keep,
                                                   topv_g)
        y = lax.with_sharding_constraint(y.reshape(B, S, D),
                                         P(ax.data, None, None))

    frac = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac * prob) * E
    return y, aux
