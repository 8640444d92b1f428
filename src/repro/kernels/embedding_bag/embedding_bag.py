"""Pallas TPU kernel: embedding-bag (ragged gather + in-register reduce).

TPU adaptation: GPU embedding bags are warp-per-bag gathers; the TPU
equivalent streams the *bag* axis through the grid while the table stays in
HBM (``memory_space=ANY``) and each row is fetched as a 1-row dynamic slice
(lowers to a DMA per row — the memory-bound reality of embedding lookup;
a production deployment would double-buffer these DMAs). The per-bag L
accumulation happens in VMEM registers.

Grid: ``(n_bag_tiles,)``; per step: indices tile [BB, L] from SMEM-friendly
int32, output tile [BB, D].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import pallas_interpret


def _embag_kernel(idx_ref, table_ref, out_ref, *, bb: int, L: int, mean: bool):
    V, D = table_ref.shape
    acc = jnp.zeros((bb, D), jnp.float32)
    cnt = jnp.zeros((bb,), jnp.float32)
    for b in range(bb):          # static unroll: one bag per sublane group
        row_acc = jnp.zeros((1, D), jnp.float32)
        c = jnp.float32(0)
        for l in range(L):
            ix = idx_ref[b, l]
            valid = ix < V
            safe = jnp.where(valid, ix, 0)
            row = table_ref[pl.dslice(safe, 1), :]
            row_acc = row_acc + jnp.where(valid, row.astype(jnp.float32), 0.0)
            c = c + jnp.where(valid, 1.0, 0.0)
        acc = acc.at[b].set(row_acc[0])
        cnt = cnt.at[b].set(c)
    if mean:
        acc = acc / jnp.maximum(cnt, 1.0)[:, None]
    out_ref[...] = acc.astype(out_ref.dtype)


def embedding_bag_p(table, indices, *, mode: str = "sum", bb: int = 8,
                    interpret: bool | None = None):
    """table: [V, D]; indices: [B, L] (B % bb == 0). Returns [B, D]."""
    B, L = indices.shape
    V, D = table.shape
    grid = (B // bb,)
    kernel = functools.partial(_embag_kernel, bb=bb, L=L, mean=(mode == "mean"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, L), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # whole table in HBM
        ],
        out_specs=pl.BlockSpec((bb, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=pallas_interpret(interpret),
    )(indices, table)
