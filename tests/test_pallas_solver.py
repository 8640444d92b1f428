"""Pallas dst-tiled relax kernel as the production local solver.

Three layers of equivalence, binding the kernel to the system:
  1. masked single sweep  == the jnp solver sweep (frontier + pruned + count)
  2. fused fixpoint kernel == local_fixpoint_bellman on one shard
  3. local_solver="pallas" == dijkstra_reference end-to-end (sim and shmap,
     several partition counts, R-MAT and road-grid graphs)
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from _hyp import given, settings, strategies as st
from repro.core import SsspConfig, build_shards, solve_sim
from repro.core.local_solver import (_sweep, local_fixpoint_bellman,
                                     local_fixpoint_pallas)
from repro.graph import (dijkstra_reference, random_graph, rmat_graph,
                         road_grid_graph)
from repro.graph.structure import graph_to_numpy
from repro.kernels.relax import (build_dst_tiled_layout, relax_masked_pallas,
                                 relax_fixpoint_pallas)

rng = np.random.default_rng(7)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_state(n, m, seed):
    g = random_graph(n, m, seed=seed)
    src, dst, w = graph_to_numpy(g)
    dist = rng.uniform(0, 50, n).astype(np.float32)
    dist[rng.random(n) < 0.3] = np.inf
    frontier = rng.random(n) < 0.5
    pruned = rng.random(len(src)) < 0.2
    return src, dst, w, dist, frontier, pruned


def _tiled(src, dst, w, n, vb, eb, pruned):
    src_t, w_t, dr_t, eid_t, bp = build_dst_tiled_layout(
        src, dst, w, n, vb=vb, eb=eb, with_eid=True)
    pruned_t = jnp.take(jnp.asarray(pruned, jnp.int32), eid_t, mode="fill",
                        fill_value=0)
    return src_t, w_t, dr_t, pruned_t, bp


def _pad(x, bp, fill):
    return jnp.asarray(np.pad(np.asarray(x, np.float32), (0, bp - len(x)),
                              constant_values=fill))


# ------------------------------------------------- masked single sweep ----

@pytest.mark.parametrize("n,m,vb,eb,seed", [
    (100, 400, 128, 128, 0), (500, 3000, 128, 256, 1), (257, 900, 128, 512, 2),
])
def test_masked_sweep_matches_solver_sweep(n, m, vb, eb, seed):
    src, dst, w, dist, frontier, pruned = _random_state(n, m, seed)
    ref_dist, _, ref_n = _sweep(jnp.asarray(dist), jnp.asarray(frontier),
                                jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32), jnp.asarray(w),
                                jnp.asarray(pruned))
    src_t, w_t, dr_t, pruned_t, bp = _tiled(src, dst, w, n, vb, eb, pruned)
    out, nrel = relax_masked_pallas(
        _pad(dist, bp, np.inf), _pad(frontier, bp, 0.0),
        src_t, w_t, dr_t, pruned_t, vb=vb, eb=eb)
    np.testing.assert_allclose(np.asarray(out)[:n], np.asarray(ref_dist),
                               rtol=1e-6, atol=1e-6)
    assert int(nrel) == int(ref_n)


# -------------------------------------------------- fused fixpoint kernel ----

@pytest.mark.parametrize("n,m,sweeps,seed", [
    (120, 500, 1, 3), (120, 500, 4, 4), (300, 1800, 8, 5), (64, 90, 16, 6),
])
def test_fixpoint_kernel_matches_bellman(n, m, sweeps, seed):
    """Chained fixpoint calls (residual-frontier loop) reach the bellman
    fixpoint regardless of how many sweeps are fused per call."""
    src, dst, w, dist, frontier, pruned = _random_state(n, m, seed)
    ref = local_fixpoint_bellman(
        jnp.asarray(dist), jnp.asarray(frontier), jnp.asarray(src, jnp.int32),
        jnp.asarray(dst, jnp.int32), jnp.asarray(w), jnp.asarray(pruned),
        max_iters=10_000)

    vb, eb = 128, 256
    src_t, w_t, dr_t, pruned_t, bp = _tiled(src, dst, w, n, vb, eb, pruned)
    d, f = _pad(dist, bp, np.inf), _pad(frontier, bp, 0.0)
    for _ in range(200):
        d, f, _ = relax_fixpoint_pallas(d, f, src_t, w_t, dr_t, pruned_t,
                                        vb=vb, eb=eb, n_sweeps=sweeps)
        if not bool(jnp.any(f > 0)):
            break
    np.testing.assert_allclose(np.asarray(d)[:n], np.asarray(ref.dist),
                               rtol=1e-6, atol=1e-6)


def test_local_fixpoint_pallas_entry():
    """The solver-facing wrapper (padding + pruned gather + while_loop)."""
    src, dst, w, dist, frontier, pruned = _random_state(200, 900, 8)
    ref = local_fixpoint_bellman(
        jnp.asarray(dist), jnp.asarray(frontier), jnp.asarray(src, jnp.int32),
        jnp.asarray(dst, jnp.int32), jnp.asarray(w), jnp.asarray(pruned),
        max_iters=10_000)
    lay = build_dst_tiled_layout(src, dst, w, 200, vb=128, eb=256,
                                 with_eid=True)
    res = local_fixpoint_pallas(jnp.asarray(dist), jnp.asarray(frontier),
                                jnp.asarray(pruned), lay[:4], vb=128,
                                max_iters=10_000, sweeps=4)
    np.testing.assert_allclose(np.asarray(res.dist), np.asarray(ref.dist),
                               rtol=1e-6, atol=1e-6)
    assert bool(res.changed) == bool(ref.changed)


# --------------------------------------------------- end-to-end (sim) ----

def _check_sim(g, P, cfg, source=0):
    sh = build_shards(g, P)
    dist, stats = solve_sim(sh, source, cfg)
    ref = dijkstra_reference(g, source)
    np.testing.assert_allclose(dist, ref, rtol=1e-5, atol=1e-4)
    return stats


@settings(max_examples=6, deadline=None)
@given(scale=st.integers(5, 8), ef=st.integers(2, 8), p=st.integers(1, 8),
       seed=st.integers(0, 1000))
def test_pallas_solver_rmat_property(scale, ef, p, seed):
    g = rmat_graph(scale=scale, edge_factor=ef, seed=seed)
    _check_sim(g, p, SsspConfig(local_solver="pallas"))


@settings(max_examples=4, deadline=None)
@given(side=st.integers(6, 16), p=st.integers(1, 8), seed=st.integers(0, 1000))
def test_pallas_solver_road_property(side, p, seed):
    g = road_grid_graph(side=side, seed=seed)
    _check_sim(g, p, SsspConfig(local_solver="pallas"))


@pytest.mark.parametrize("p", [1, 4, 8])
def test_pallas_equals_bellman_stats(p):
    """Same distances AND same message/round trajectory as bellman — the
    pallas solver changes the local math, not the protocol."""
    g = rmat_graph(scale=7, edge_factor=6, seed=5)
    s_b = _check_sim(g, p, SsspConfig(local_solver="bellman"))
    s_p = _check_sim(g, p, SsspConfig(local_solver="pallas"))
    assert int(s_b.rounds) == int(s_p.rounds)
    assert int(s_b.msgs_sent) == int(s_p.msgs_sent)


def test_pallas_falls_back_without_layout():
    g = random_graph(150, 600, seed=9)
    sh = build_shards(g, 4, relax_layout=False)
    assert not sh.has_relax_layout
    with pytest.raises(ValueError, match="local_solver='pallas' needs"):
        solve_sim(sh, 0, SsspConfig(local_solver="pallas"))


def test_layout_built_once_in_shards():
    """build_shards carries the stacked dst-tiled layout (no per-solve
    relayout): shapes line up with the kernel contract."""
    g = random_graph(200, 800, seed=10)
    sh = build_shards(g, 4)
    P = sh.n_parts
    assert sh.rx_src.shape[0] == P
    assert sh.rx_src.shape == sh.rx_w.shape == sh.rx_dstrel.shape == sh.rx_eid.shape
    n_vtiles = sh.rx_src.shape[1]
    assert n_vtiles * sh.rx_vb >= sh.block
    # every real local edge appears exactly once in the tiled layout
    for p in range(P):
        eids = np.asarray(sh.rx_eid[p]).ravel()
        real = np.sort(eids[eids < sh.e_loc])
        valid = np.isfinite(np.asarray(sh.loc_w[p]))
        np.testing.assert_array_equal(real, np.nonzero(valid)[0])


# ------------------------------------------- acceptance matrix (slow) ----

_BENCH_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from repro import compat
    from repro.core import SsspConfig, build_shards, solve_shmap, solve_sim
    from repro.graph import rmat_graph, road_grid_graph, dijkstra_reference

    graphs = {
        "graph1-like": rmat_graph(scale=11, edge_factor=2, seed=1),
        "graph2-like": road_grid_graph(side=48, seed=2),
        "graph3-like": rmat_graph(scale=9, edge_factor=24, seed=3),
    }
    cfg = SsspConfig(local_solver="pallas", prune_online=False)
    for name, g in graphs.items():
        source = int(g.src[0])
        ref = dijkstra_reference(g, source)
        for p in (1, 4, 8):
            sh = build_shards(g, p, enumerate_triangles=False)
            d, _ = solve_sim(sh, source, cfg)
            assert np.allclose(d, ref, 1e-5, 1e-4), ("sim", name, p)
            mesh = compat.make_mesh((p,), ("d",))
            d, _ = solve_shmap(sh, source, cfg, mesh, ("d",))
            assert np.allclose(d, ref, 1e-5, 1e-4), ("shmap", name, p)
    print("PALLAS MATRIX OK")
""")


@pytest.mark.slow
def test_pallas_bench_graph_matrix():
    """Acceptance: pallas solver matches Dijkstra on all three BENCH_GRAPHS
    at P in {1, 4, 8}, in both sim and shmap backends."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", _BENCH_PROG], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PALLAS MATRIX OK" in out.stdout
