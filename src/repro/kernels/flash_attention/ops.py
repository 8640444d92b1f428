"""jit'd public wrapper: padding, GQA checks, decode offsets."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_p


def _pad_seq(x, block, axis):
    s = x.shape[axis]
    pad = (-s) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("causal", "scale", "q_offset", "block_q",
                                   "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Skv, D]. Returns [B, Hq, Sq, D].

    ``q_offset`` positions queries for causal decode (q_offset = Skv - Sq)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    if scale is None:
        scale = float(D) ** -0.5

    bq = min(block_q, max(Sq, 1))
    bk = min(block_k, max(Skv, 1))
    qp = _pad_seq(q, bq, 2)
    kp = _pad_seq(k, bk, 2)
    vp = _pad_seq(v, bk, 2)
    out = flash_attention_p(qp, kp, vp, scale=scale, causal=causal,
                            q_offset=q_offset, kv_len=Skv, block_q=bq,
                            block_k=bk, interpret=interpret)
    return out[:, :, :Sq, :]
