"""shard_map production path on 8 fake host devices (subprocess — device
count must be set before jax initializes, so this cannot share the test
process)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.graph import random_graph, dijkstra_reference
    from repro.core import SsspConfig, build_shards, solve_shmap
    from repro.distributed.collectives import ring_permute, flat_rank

    from repro import compat
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    axes = ("data", "model")

    # 1) ring_permute moves rank r's value to rank r+1 over the 2-axis ring
    def ring_prog():
        r = flat_rank(axes)
        return ring_permute(r, axes)
    out = jax.jit(jax.shard_map(lambda: ring_prog()[None], mesh=mesh,
                                   in_specs=(), out_specs=P(axes),
                                   check_vma=False))()
    got = np.asarray(out)
    want = np.roll(np.arange(8), 1)
    assert (got == want).all(), (got, want)
    print("RING OK")

    # 2) SSSP shard_map == oracle, all exchanges and detectors
    g = random_graph(220, 900, seed=11)
    sh = build_shards(g, 8)
    ref = dijkstra_reference(g, 0)
    for cfg in [SsspConfig(), SsspConfig(exchange="pmin"),
                SsspConfig(exchange="a2a_dense"),
                SsspConfig(toka="toka1"),
                SsspConfig(toka="toka2", local_solver="delta"),
                SsspConfig(local_solver="pallas")]:
        dist, stats = solve_shmap(sh, 0, cfg, mesh, axes)
        assert np.allclose(dist, ref, 1e-5, 1e-4), cfg
    print("SHMAP OK")

    # 3) LM train step under a real 2x4 mesh (GSPMD path)
    from repro.distributed.sharding import MeshAxes
    from repro.models import transformer as tf
    from repro.models.params import materialize
    from repro.optim import AdamWConfig
    from repro.optim.adamw import adamw_init
    ax = MeshAxes(data=("data",), data_shards=2)
    from repro.configs.registry import _load
    _, cfg = _load("qwen3-moe-235b-a22b", smoke=True)
    defs = tf.param_defs(cfg, ax)
    params = materialize(defs, jax.random.key(0), cfg.dtype)
    opt = adamw_init(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)))}
    # place inputs on the mesh (sharding constraints resolve against it)
    rep = jax.NamedSharding(mesh, P())
    params, opt, batch = jax.device_put((params, opt, batch), rep)
    step = jax.jit(tf.make_train_step(cfg, ax, AdamWConfig()))
    with jax.set_mesh(mesh):
        _, _, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    print("LM MESH OK")
""")


@pytest.mark.slow
def test_multidevice_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", PROG], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "RING OK" in res.stdout
    assert "SHMAP OK" in res.stdout
    assert "LM MESH OK" in res.stdout
