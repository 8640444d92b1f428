"""jit'd wrappers for embedding-bag."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.embedding_bag import embedding_bag_p


@partial(jax.jit, static_argnames=("mode", "bb", "interpret"))
def embedding_bag(table, indices, *, mode: str = "sum", bb: int = 8,
                  interpret: bool | None = None):
    """Pallas path. Pads the bag axis to a multiple of ``bb``."""
    B, L = indices.shape
    pad = (-B) % bb
    if pad:
        indices = jnp.concatenate(
            [indices, jnp.full((pad, L), table.shape[0], indices.dtype)])
    out = embedding_bag_p(table, indices, mode=mode, bb=bb, interpret=interpret)
    return out[:B]


@partial(jax.jit, static_argnames=("mode",))
def embedding_bag_jnp(table, indices, *, mode: str = "sum"):
    """XLA path (take + masked sum) — used by the AutoInt model at scale."""
    V = table.shape[0]
    valid = indices < V
    rows = jnp.take(table, indices, axis=0, mode="fill", fill_value=0.0)
    rows = jnp.where(valid[..., None], rows, 0.0)
    out = jnp.sum(rows, axis=1)
    if mode == "mean":
        cnt = jnp.maximum(jnp.sum(valid, axis=1, keepdims=True), 1)
        out = out / cnt.astype(out.dtype)
    return out
