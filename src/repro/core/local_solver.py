"""Intra-partition solver — the paper's "Dijkstra within each node".

A binary-heap Dijkstra is inherently serial; the TPU-native equivalent that
preserves the paper's semantics (settle your subgraph to a local fixpoint
before communicating) is iterated *frontier-masked relaxation*:

- ``bellman``: each inner step relaxes all local edges whose source vertex
  improved since the previous step (frontier mask), via gather + scatter-min.
  Runs to local fixpoint inside ``lax.while_loop``.
- ``delta``: Δ-stepping-style near/far ordering — only frontier vertices
  within ``min_active_dist + Δ`` relax each step, reproducing Dijkstra's
  settle-in-distance-order behaviour and avoiding wasted relaxations on
  vertices whose distance will still improve (Meyer & Sanders 2003; the
  paper cites Δ-stepping as the synchronous baseline).
- ``pallas``: the dst-tiled Pallas relax kernel
  (``repro.kernels.relax``) run as a fused multi-sweep fixpoint — up to
  ``pallas_sweeps`` frontier-chased sweeps execute inside ONE
  ``pallas_call`` (no XLA re-entry per sweep, no scatter lowering); a thin
  ``lax.while_loop`` re-invokes the kernel on the residual frontier until
  empty. Requires the dst-tiled edge layout precomputed by
  ``build_shards`` (``SsspShards.rx_*``); raises when the layout is
  absent.

All functions operate on ONE shard's local arrays (no leading P dim); the
driver vmaps (sim backend) or shard_maps (distributed backend) over shards.
The driver always presents a leading QUERY axis ``K`` (multi-source
batching) via ``local_fixpoint_batch``: bellman/delta are vmapped over
queries (each query runs its own while_loop lanes; jax lifts the loop
condition to "any query still active"), while the pallas path dispatches
the natively batched kernel whose grid carries the query axis and reuses
one edge-layout stream for all K queries.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.kernels.relax import (
    relax_fixpoint_batch_pallas, relax_fixpoint_batch_ragged_pallas,
)

INF = jnp.float32(jnp.inf)


class LocalResult(NamedTuple):
    dist: jax.Array      # [block] f32
    changed: jax.Array   # scalar bool — any local improvement happened
    relaxations: jax.Array  # scalar int32 — edge relaxations performed (TEPS accounting)


def _sweep(dist, frontier, loc_src, loc_dst, loc_w, pruned_loc):
    """One masked relaxation sweep. Returns (dist', new_frontier, n_relax)."""
    src_ok = jnp.take(frontier, loc_src, mode="fill", fill_value=False)
    d_src = jnp.take(dist, loc_src, mode="fill", fill_value=float("inf"))
    w = jnp.where(pruned_loc, INF, loc_w)
    cand = jnp.where(src_ok, d_src + w, INF)
    new = dist.at[loc_dst].min(cand, mode="drop")
    new_frontier = new < dist
    n_relax = jnp.sum(src_ok & (w < INF)).astype(jnp.int32)
    return new, new_frontier, n_relax


def local_fixpoint_bellman(dist, active, loc_src, loc_dst, loc_w, pruned_loc,
                           max_iters: int) -> LocalResult:
    """Relax frontier edges until no local change (the local 'Dijkstra')."""

    def cond(carry):
        _, frontier, it, _, _ = carry
        return jnp.any(frontier) & (it < max_iters)

    def body(carry):
        dist, frontier, it, changed, nrel = carry
        new, new_frontier, n = _sweep(dist, frontier, loc_src, loc_dst, loc_w, pruned_loc)
        return (new, new_frontier, it + 1, changed | jnp.any(new_frontier), nrel + n)

    out = jax.lax.while_loop(
        cond, body, (dist, active, jnp.int32(0), jnp.bool_(False), jnp.int32(0)))
    return LocalResult(dist=out[0], changed=out[3], relaxations=out[4])


def local_fixpoint_delta(dist, active, loc_src, loc_dst, loc_w, pruned_loc,
                         max_iters: int, delta: float) -> LocalResult:
    """Near/far bucketed fixpoint: Dijkstra-order settling without a heap."""

    def cond(carry):
        _, frontier, it, _, _ = carry
        return jnp.any(frontier) & (it < max_iters)

    def body(carry):
        dist, frontier, it, changed, nrel = carry
        fdist = jnp.where(frontier, dist, INF)
        lo = jnp.min(fdist)
        near = frontier & (dist <= lo + delta)
        # always relax at least the nearest bucket; vertices outside stay
        # in the frontier for later buckets
        new, improved, n = _sweep(dist, near, loc_src, loc_dst, loc_w, pruned_loc)
        new_frontier = (frontier & ~near) | improved
        return (new, new_frontier, it + 1, changed | jnp.any(improved), nrel + n)

    out = jax.lax.while_loop(
        cond, body, (dist, active, jnp.int32(0), jnp.bool_(False), jnp.int32(0)))
    return LocalResult(dist=out[0], changed=out[3], relaxations=out[4])


def local_fixpoint_pallas(dist, active, pruned_loc, relax_layout, *,
                          vb: int, max_iters: int, sweeps: int = 8
                          ) -> LocalResult:
    """Fused Pallas fixpoint over the precomputed dst-tiled edge layout.

    ``relax_layout`` = (src_t, w_t, dstrel_t, eid_t), each
    [n_vtiles, n_chunks, EB] for THIS shard. A K=1 batch: the batched
    wrapper owns the padding / pruned-gather / residual-loop logic.
    """
    res = local_fixpoint_pallas_batch(dist[None], active[None], pruned_loc,
                                      relax_layout, vb=vb,
                                      max_iters=max_iters, sweeps=sweeps)
    return LocalResult(dist=res.dist[0], changed=res.changed[0],
                       relaxations=res.relaxations[0])


def local_fixpoint_pallas_batch(dist, active, pruned_loc, relax_layout, *,
                                vb: int, max_iters: int, sweeps: int = 8
                                ) -> LocalResult:
    """Batched pallas fixpoint: dist/active are [K, block]; the dst-tiled
    layout AND the tiled Trishla mask are shared — gathered once, reused by
    every query in the batch (the amortization the batch engine exists for).

    A 5-tuple ``relax_layout`` is the ragged CSR-chunked form (flat chunk
    rows + chunk→tile map) and dispatches the ragged-grid kernel.
    """
    if len(relax_layout) == 5:
        src_t, w_t, dstrel_t, eid_t, ctile = relax_layout
    else:
        src_t, w_t, dstrel_t, eid_t = relax_layout
        ctile = None
    eb = src_t.shape[-1]
    nq, block = dist.shape
    n_vtiles = (src_t.shape[0] if ctile is None else max(-(-block // vb), 1))
    bp = n_vtiles * vb
    # pad to the kernel's tile-aligned block; padded slots never win a min
    dist_pad = jnp.full((nq, bp), INF).at[:, :block].set(dist)
    front_pad = jnp.zeros((nq, bp), jnp.float32).at[:, :block].set(
        active.astype(jnp.float32))
    # gather the runtime pruned mask into tiled edge order (eid sentinel is
    # out of range -> fill 0 = not pruned, i.e. padding stays inert)
    pruned_t = jnp.take(pruned_loc.astype(jnp.int32), eid_t, mode="fill",
                        fill_value=0)

    def cond(c):
        _, front, _, it = c
        return jnp.any(front > 0) & (it < max_iters)

    def body(c):
        d, front, nrel, it = c
        if ctile is None:
            new_d, resid, n = relax_fixpoint_batch_pallas(
                d, front, src_t, w_t, dstrel_t, pruned_t, vb=vb, eb=eb,
                n_sweeps=sweeps)
        else:
            new_d, resid, n = relax_fixpoint_batch_ragged_pallas(
                d, front, ctile, src_t, w_t, dstrel_t, pruned_t, vb=vb,
                eb=eb, n_sweeps=sweeps)
        return new_d, resid, nrel + n, it + jnp.int32(sweeps)

    out = jax.lax.while_loop(
        cond, body,
        (dist_pad, front_pad, jnp.zeros((nq,), jnp.int32), jnp.int32(0)))
    new_dist = out[0][:, :block]
    return LocalResult(dist=new_dist,
                       changed=jnp.any(new_dist < dist, axis=-1),
                       relaxations=out[2])


# ---- local-solver registry (phase "local_solver") ------------------------
# Uniform batched signature so the driver resolves the backend by name and
# SsspConfig validates it eagerly; every entry returns LocalResult with
# dist [K, block], changed [K], relaxations [K].

@phases.register("local_solver", "bellman")
def _batch_bellman(dist, active, loc_src, loc_dst, loc_w, pruned_loc, *,
                   max_iters, delta, relax_layout, relax_vb, pallas_sweeps
                   ) -> LocalResult:
    return jax.vmap(partial(local_fixpoint_bellman, loc_src=loc_src,
                            loc_dst=loc_dst, loc_w=loc_w,
                            pruned_loc=pruned_loc,
                            max_iters=max_iters))(dist, active)


@phases.register("local_solver", "delta")
def _batch_delta(dist, active, loc_src, loc_dst, loc_w, pruned_loc, *,
                 max_iters, delta, relax_layout, relax_vb, pallas_sweeps
                 ) -> LocalResult:
    return jax.vmap(partial(local_fixpoint_delta, loc_src=loc_src,
                            loc_dst=loc_dst, loc_w=loc_w,
                            pruned_loc=pruned_loc, max_iters=max_iters,
                            delta=delta))(dist, active)


@phases.register("local_solver", "pallas")
def _batch_pallas(dist, active, loc_src, loc_dst, loc_w, pruned_loc, *,
                  max_iters, delta, relax_layout, relax_vb, pallas_sweeps
                  ) -> LocalResult:
    return local_fixpoint_pallas_batch(dist, active, pruned_loc, relax_layout,
                                       vb=relax_vb, max_iters=max_iters,
                                       sweeps=pallas_sweeps)


def local_fixpoint_batch(dist, active, loc_src, loc_dst, loc_w, pruned_loc, *,
                         solver: str = "bellman", max_iters: int = 10_000,
                         delta: float = 4.0, relax_layout=None,
                         relax_vb: int = 128, pallas_sweeps: int = 8
                         ) -> LocalResult:
    """Multi-query local solve: dist/active carry a leading [K] query axis;
    the edge arrays and the pruned mask are per-shard (query-invariant).
    Returns LocalResult with dist [K, block], changed [K], relaxations [K].
    """
    if solver == "pallas" and relax_layout is None:
        raise ValueError(
            "local_solver='pallas' needs the dst-tiled edge layout, but the "
            "shards carry none (build_shards was called with "
            "relax_layout=False)")
    impl = phases.resolve("local_solver", solver)
    return impl(dist, active, loc_src, loc_dst, loc_w, pruned_loc,
                max_iters=max_iters, delta=delta, relax_layout=relax_layout,
                relax_vb=relax_vb, pallas_sweeps=pallas_sweeps)


def local_fixpoint(dist, active, loc_src, loc_dst, loc_w, pruned_loc, *,
                   solver: str = "bellman", max_iters: int = 10_000,
                   delta: float = 4.0, relax_layout=None, relax_vb: int = 128,
                   pallas_sweeps: int = 8) -> LocalResult:
    """Single-query local solve: a K=1 batch (the batched entry point owns
    the solver dispatch and the pallas-layout check)."""
    res = local_fixpoint_batch(
        dist[None], active[None], loc_src, loc_dst, loc_w, pruned_loc,
        solver=solver, max_iters=max_iters, delta=delta,
        relax_layout=relax_layout, relax_vb=relax_vb,
        pallas_sweeps=pallas_sweeps)
    return LocalResult(dist=res.dist[0], changed=res.changed[0],
                       relaxations=res.relaxations[0])
