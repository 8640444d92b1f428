"""Training-loop features: gradient accumulation, schedules under jit."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.distributed.sharding import MeshAxes
from repro.models import transformer as tf
from repro.models.params import materialize
from repro.optim import AdamWConfig
from repro.optim.adamw import adamw_init

AX = MeshAxes(data=("data",), data_shards=1)
CFG = tf.TransformerConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                           n_kv_heads=2, d_ff=64, vocab_size=64,
                           dtype="float32", attn_chunk=8)


def test_microbatched_step_matches_full_batch(mesh11):
    params = materialize(tf.param_defs(CFG, AX), jax.random.key(0), "float32")
    opt = adamw_init(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 64, (4, 16))),
             "labels": jnp.asarray(rng.integers(0, 64, (4, 16)))}
    with jax.set_mesh(mesh11):
        p1, _, m1 = jax.jit(tf.make_train_step(CFG, AX, AdamWConfig()))(
            params, opt, batch)
        p4, _, m4 = jax.jit(tf.make_train_step(CFG, AX, AdamWConfig(),
                                               microbatches=4))(
            params, opt, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_dtype_fence_is_identity_forward():
    x = jnp.asarray([1.0, 2.0], jnp.float32)
    y = tf.dtype_fence(x, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # backward casts the cotangent
    g = jax.grad(lambda t: jnp.sum(tf.dtype_fence(t, jnp.bfloat16) * 3.0))(x)
    assert g.dtype == jnp.bfloat16


def test_flash_bwd_matches_xla_attention_grads():
    rng = np.random.default_rng(0)
    B, S, H, Hkv, Dh = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), jnp.float32)
    sc = Dh ** -0.5
    def f1(q, k, v):
        return jnp.sum(jnp.sin(tf._attn_chunked(q, k, v, True, 0, sc, 16)))

    def f2(q, k, v):
        return jnp.sum(jnp.sin(tf._attn_xla(q, k, v, causal=True,
                                            q_offset=0, scale=sc)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
