"""Pallas TPU flash attention (FlashAttention-2 schedule, GQA-aware).

Grid ``(B, Hq, nq, nkv)``; the kv axis is innermost so the (q-tile ×
head) output block and the f32 accumulators persist in VMEM scratch across
kv steps (online softmax). GQA is resolved in the k/v BlockSpec index maps
(query head h reads kv head ``h // group``) — no repeated-KV materialization.

VMEM per step: q (BQ×D), k/v (BK×D each), acc (BQ×D f32), s/p (BQ×BK f32).
With BQ=BK=512, D=128: ~2.5 MiB — comfortably inside 16 MiB v5e VMEM and
big enough to keep the MXU busy (512×128 × 128×512 matmuls).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.runtime import pallas_interpret


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, q_offset: int, kv_len: int,
                  block_q: int, block_k: int):
    i = pl.program_id(2)          # q tile
    j = pl.program_id(3)          # kv tile
    nkv = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [BQ, D]
    k = k_ref[0, 0].astype(jnp.float32)          # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)          # [BK, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [BQ, BK]

    kj = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    valid = kj < kv_len                           # mask kv padding
    if causal:
        qi = (i * block_q + q_offset
              + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
        valid = valid & (qi >= kj)
    s = jnp.where(valid, s, -jnp.inf)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # rows with everything masked keep m = -inf; exp(-inf - -inf) guards below
    p = jnp.exp(s - jnp.where(jnp.isfinite(m_new), m_new, 0.0)[:, None])
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_new, -jnp.inf))
    alpha = jnp.where(jnp.isfinite(m_prev), alpha, 0.0)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_p(q, k, v, *, scale: float, causal: bool, q_offset: int,
                      kv_len: int, block_q: int, block_k: int,
                      interpret: bool | None = None):
    """q: [B, Hq, Sq_pad, D]; k/v: [B, Hkv, Skv_pad, D] (pre-padded)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    nq, nkv = Sq // block_q, Skv // block_k
    grid = (B, Hq, nq, nkv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, q_offset=q_offset,
        kv_len=kv_len, block_q=block_q, block_k=block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),     # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),     # l (running denom)
        ],
        interpret=pallas_interpret(interpret),
    )(q, k, v)
