"""End-to-end training driver with checkpoint/restart fault tolerance.

Runs reduced ("smoke") or full configs of any registered arch on whatever
mesh exists. Demonstrates the production loop:

  - data pipeline -> device batches
  - jitted train step (GSPMD-sharded)
  - periodic checkpoints (atomic commit, keep-K)
  - crash-safe resume: on start, restores the latest complete step and
    continues (elastic: the restore reshards onto the current mesh)

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt --ckpt-every 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.registry import _load
from repro.data import TokenStream, RecsysBatcher
from repro.distributed.sharding import MeshAxes
from repro.launch.mesh import make_host_mesh
from repro.models.params import materialize
from repro.optim import AdamWConfig
from repro.optim.adamw import adamw_init


def build_lm(cfg, ax, batch, seq, opt_cfg):
    from repro.models import transformer as tf
    defs = tf.param_defs(cfg, ax)
    params = materialize(defs, jax.random.key(0), cfg.dtype)
    step = tf.make_train_step(cfg, ax, opt_cfg)
    data = TokenStream(batch, seq, cfg.vocab_size)
    return params, step, data


def build_recsys(cfg, ax, batch, opt_cfg):
    from repro.models import autoint as ai
    defs = ai.autoint_param_defs(cfg, ax)
    params = materialize(defs, jax.random.key(0))
    step = ai.make_autoint_train_step(cfg, ax, opt_cfg)
    data = RecsysBatcher(batch, cfg.n_sparse, cfg.vocab_per_field,
                         cfg.multi_hot)
    return params, step, data


def build_gnn(arch, cfg, ax, opt_cfg):
    from repro.models import gnn
    from repro.data import GraphBatcher
    rng = np.random.default_rng(0)
    N, E = 256, 1024
    src = rng.integers(0, N, E).astype(np.int32)
    dst = rng.integers(0, N, E).astype(np.int32)
    loss = {"gat-cora": gnn.gat_loss, "egnn": gnn.egnn_loss,
            "mace": gnn.mace_loss, "graphcast": gnn.graphcast_loss}[arch]
    defs = {"gat-cora": gnn.gat_param_defs, "egnn": gnn.egnn_param_defs,
            "mace": gnn.mace_param_defs,
            "graphcast": gnn.graphcast_param_defs}[arch](cfg, ax)
    params = materialize(defs, jax.random.key(0))
    step = gnn.make_gnn_train_step(loss, cfg, ax, opt_cfg)

    def batch_builder(i):
        b = dict(edge_src=jnp.asarray(src), edge_dst=jnp.asarray(dst))
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
        else:
            b["node_feat"] = jnp.asarray(rng.standard_normal((N, cfg.n_vars)), jnp.float32)
            b["edge_feat"] = jnp.asarray(rng.standard_normal((E, cfg.d_edge_in)), jnp.float32)
            b["labels"] = jnp.asarray(rng.standard_normal((N, cfg.n_vars)), jnp.float32)
        return b

    return params, step, GraphBatcher(batch_builder)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="deepseek-7b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args(argv)

    mesh = make_host_mesh()
    ax = MeshAxes(data=("data",))
    family, cfg = _load(args.arch, smoke=args.smoke)
    opt_cfg = AdamWConfig(lr=args.lr)

    if family == "lm":
        params, step_fn, data = build_lm(cfg, ax, args.batch, args.seq, opt_cfg)
    elif family == "recsys":
        params, step_fn, data = build_recsys(cfg, ax, args.batch, opt_cfg)
    else:
        params, step_fn, data = build_gnn(args.arch, cfg, ax, opt_cfg)

    opt_state = adamw_init(params)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    start = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr is not None and mgr.latest() is not None:
        (params, opt_state), start = mgr.restore((params, opt_state))
        print(f"resumed from step {start}")

    it = iter(data)
    losses = []
    with jax.set_mesh(mesh):
        t0 = time.time()
        for s in range(start, args.steps):
            batch = next(it)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if (s + 1) % args.log_every == 0:
                dt = (time.time() - t0) / args.log_every
                print(f"step {s+1}: loss={losses[-1]:.4f} "
                      f"({dt*1e3:.0f} ms/step)")
                t0 = time.time()
            if mgr is not None and (s + 1) % args.ckpt_every == 0:
                mgr.save(s + 1, (params, opt_state))
    print(f"final loss: {losses[-1]:.4f} (first: {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    main()
