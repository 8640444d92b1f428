"""Per-architecture smoke: reduced config, one forward/train step on CPU,
output shapes + no NaNs. One test per assigned arch (10)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS, _load
from repro.models.params import materialize
from repro.optim import AdamWConfig
from repro.optim.adamw import adamw_init

rng = np.random.default_rng(0)


def _gnn_batch(arch, cfg):
    N, E = 64, 192
    b = dict(edge_src=jnp.asarray(rng.integers(0, N, E), jnp.int32),
             edge_dst=jnp.asarray(rng.integers(0, N, E), jnp.int32))
    if arch == "gat-cora":
        b["node_feat"] = jnp.asarray(rng.standard_normal((N, cfg.d_in)), jnp.float32)
        b["labels"] = jnp.asarray(rng.integers(0, cfg.n_classes, N), jnp.int32)
    elif arch == "egnn":
        b["node_feat"] = jnp.asarray(rng.standard_normal((N, cfg.d_in)), jnp.float32)
        b["coords"] = jnp.asarray(rng.standard_normal((N, 3)), jnp.float32)
        b["labels"] = jnp.asarray(rng.standard_normal(N), jnp.float32)
    elif arch == "mace":
        b["node_feat"] = jnp.asarray(rng.integers(0, 10, (N, 1)), jnp.float32)
        b["coords"] = jnp.asarray(rng.standard_normal((N, 3)) * 2, jnp.float32)
        b["graph_id"] = jnp.asarray(np.repeat(np.arange(8), N // 8), jnp.int32)
        b["graph_energy"] = jnp.asarray(rng.standard_normal(8), jnp.float32)
    else:  # graphcast
        b["node_feat"] = jnp.asarray(rng.standard_normal((N, cfg.n_vars)), jnp.float32)
        b["edge_feat"] = jnp.asarray(rng.standard_normal((E, cfg.d_edge_in)), jnp.float32)
        b["labels"] = jnp.asarray(rng.standard_normal((N, cfg.n_vars)), jnp.float32)
    return b


@pytest.mark.parametrize("arch", list(ARCHS))
def test_arch_smoke(arch, mesh11, ax11):
    family, cfg = _load(arch, smoke=True)
    with jax.set_mesh(mesh11):
        if family == "lm":
            from repro.models import transformer as tf
            defs = tf.param_defs(cfg, ax11)
            params = materialize(defs, jax.random.key(0), cfg.dtype)
            opt = adamw_init(params)
            B, S = 2, 32
            batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
                     "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))}
            step = jax.jit(tf.make_train_step(cfg, ax11, AdamWConfig()))
            _, _, m = step(params, opt, batch)
            assert np.isfinite(float(m["loss"]))
            # forward shape check
            logits, kvs, _ = jax.jit(
                lambda p, t: tf.forward(p, t, cfg, ax11))(
                params, batch["tokens"])
            assert logits.shape == (B, S, cfg.vocab_size)
            assert np.isfinite(np.asarray(logits)).all()
        elif family == "gnn":
            from repro.models import gnn
            loss = {"gat-cora": gnn.gat_loss, "egnn": gnn.egnn_loss,
                    "mace": gnn.mace_loss, "graphcast": gnn.graphcast_loss}[arch]
            defs = {"gat-cora": gnn.gat_param_defs, "egnn": gnn.egnn_param_defs,
                    "mace": gnn.mace_param_defs,
                    "graphcast": gnn.graphcast_param_defs}[arch](cfg, ax11)
            params = materialize(defs, jax.random.key(0))
            opt = adamw_init(params)
            batch = _gnn_batch(arch, cfg)
            step = jax.jit(gnn.make_gnn_train_step(loss, cfg, ax11,
                                                   AdamWConfig(lr=1e-3)))
            _, _, m = step(params, opt, batch)
            assert np.isfinite(float(m["loss"]))
        else:
            from repro.models import autoint as ai
            defs = ai.autoint_param_defs(cfg, ax11)
            params = materialize(defs, jax.random.key(0))
            opt = adamw_init(params)
            B = 8
            batch = {"sparse_idx": jnp.asarray(
                rng.integers(0, cfg.total_vocab, (B, cfg.n_sparse, cfg.multi_hot)),
                jnp.int32),
                "labels": jnp.asarray(rng.integers(0, 2, B), jnp.int32)}
            step = jax.jit(ai.make_autoint_train_step(cfg, ax11, AdamWConfig()))
            _, _, m = step(params, opt, batch)
            assert np.isfinite(float(m["loss"]))
            serve = jax.jit(ai.make_autoint_serve_step(cfg, ax11))
            s = serve(params, batch)
            assert s.shape == (B,) and np.isfinite(np.asarray(s)).all()


def test_lm_decode_matches_forward(mesh11, ax11):
    """Prefill + decode must reproduce the full-forward logits (KV cache
    correctness — the serving path's core invariant)."""
    from repro.models import transformer as tf
    _, cfg = _load("deepseek-7b", smoke=True)
    defs = tf.param_defs(cfg, ax11)
    params = materialize(defs, jax.random.key(1), cfg.dtype)
    B, S = 2, 24
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    with jax.set_mesh(mesh11):
        full_logits, _, _ = jax.jit(
            lambda p, t: tf.forward(p, t, cfg, ax11))(params, toks)
        # prefill first S-4 tokens, then decode the remaining 4 one by one
        pre = S - 4
        _, kvs = jax.jit(tf.make_prefill_step(cfg, ax11))(
            params, {"tokens": toks[:, :pre]})
        pad = S - pre
        caches = tuple(jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                       for t in kvs)
        serve = jax.jit(tf.make_serve_step(cfg, ax11))
        for i in range(pre, S):
            logits, caches = serve(params, toks[:, i:i + 1], caches,
                                   jnp.int32(i))
            ref = full_logits[:, i]
            np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)


def test_mace_rotation_invariance(mesh11, ax11):
    from repro.models import gnn
    _, cfg = _load("mace", smoke=True)
    defs = gnn.mace_param_defs(cfg, ax11)
    params = materialize(defs, jax.random.key(2))
    N, E = 48, 128
    coords = rng.standard_normal((N, 3)).astype(np.float32) * 2
    th = 0.9
    R = np.array([[np.cos(th), -np.sin(th), 0],
                  [np.sin(th), np.cos(th), 0], [0, 0, 1]], np.float32)
    base = dict(edge_src=jnp.asarray(rng.integers(0, N, E), jnp.int32),
                edge_dst=jnp.asarray(rng.integers(0, N, E), jnp.int32),
                node_feat=jnp.asarray(rng.integers(0, 10, (N, 1)), jnp.float32))
    with jax.set_mesh(mesh11):
        h0 = gnn.mace_forward(params, dict(base, coords=jnp.asarray(coords)),
                              cfg, ax11)
        h1 = gnn.mace_forward(params, dict(base, coords=jnp.asarray(coords @ R.T)),
                              cfg, ax11)
    np.testing.assert_allclose(np.asarray(h0[0]), np.asarray(h1[0]),
                               rtol=1e-3, atol=1e-4)
