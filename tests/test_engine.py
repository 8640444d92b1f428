"""SsspEngine session API: K-bucketed compile reuse, padding parity,
streaming submit/drain, legacy-wrapper delegation.

The engine's contract under test:
  1. one compiled program per (K-bucket, cfg) serves ARBITRARY source
     batches — asserted by the engine's trace counters, sim and shmap
  2. padded-bucket results bit-match the unpadded reference (padded rows
     start converged and never touch any statistic)
  3. the five legacy entry points delegate to a cached engine and keep
     bit-identical results
  4. submit/drain coalesces streaming arrivals into bucketed batches
     without splitting a submission
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (QueryResult, SsspConfig, SsspEngine, bucket_k,
                        build_shards, engine_for, solve_sim, solve_sim_batch)
from repro.graph import dijkstra_reference, random_graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def graph_and_shards():
    g = random_graph(n=180, m=700, seed=21)
    return g, build_shards(g, 5)


def _refs(g, sources):
    return np.stack([dijkstra_reference(g, s) for s in sources])


# ------------------------------------------------------- bucket policy ----

def test_bucket_policy_powers_of_two():
    assert [bucket_k(k) for k in (1, 2, 3, 4, 5, 8, 9, 16)] == \
        [1, 2, 4, 4, 8, 8, 16, 16]
    with pytest.raises(ValueError):
        bucket_k(0)


def test_engine_build_from_graph_and_shards(graph_and_shards):
    g, sh = graph_and_shards
    eng = SsspEngine.build(sh)
    assert eng.n_vertices == g.n_vertices and eng.n_parts == 5
    eng_g = SsspEngine.build(g, n_parts=3, enumerate_triangles=False)
    assert eng_g.n_parts == 3
    res = eng_g.solve([0])
    np.testing.assert_allclose(res.dist[0], dijkstra_reference(g, 0),
                               rtol=1e-5, atol=1e-4)
    with pytest.raises(ValueError, match="shard build options"):
        SsspEngine.build(sh, n_parts=3, enumerate_triangles=False)
    with pytest.raises(ValueError, match="mesh"):
        SsspEngine.build(sh, backend="shmap")
    with pytest.raises(ValueError, match="backend"):
        SsspEngine.build(sh, backend="mpi")


# ------------------------------------------- compile reuse (tentpole) ----

def test_trace_reuse_same_bucket_sim(graph_and_shards):
    """Two solves with DIFFERENT source sets in the same K-bucket trigger
    exactly one trace; a new bucket shape traces once more."""
    g, sh = graph_and_shards
    eng = SsspEngine.build(sh)
    r1 = eng.solve([3, 17, 99])          # K=3 -> bucket 4, cold
    assert r1.compiled and r1.bucket_k == 4
    assert eng.trace_counts == {4: 1}
    r2 = eng.solve([120, 5, 66, 8])      # K=4 -> same bucket, warm
    assert not r2.compiled and r2.compile_s == 0.0
    assert eng.trace_counts == {4: 1}
    r3 = eng.solve([12])                 # K=1 -> new bucket
    assert r3.compiled and r3.bucket_k == 1
    assert eng.trace_counts == {4: 1, 1: 1}
    refs = _refs(g, [120, 5, 66, 8])
    np.testing.assert_allclose(r2.dist, refs, rtol=1e-5, atol=1e-4)


def test_padded_bucket_bitmatches_unpadded_reference(graph_and_shards):
    """Padded rows (converged from round 0) must not perturb real queries:
    the padded-bucket solve bit-matches the unpadded reference, distances
    AND per-query stats."""
    g, sh = graph_and_shards
    sources = [3, 17, 99]
    eng = SsspEngine.build(sh, SsspConfig(prune_online=False))
    padded = eng.solve(sources)               # rides the K=4 bucket
    exact = eng.solve(sources, bucket=False)  # K=3, no padding
    assert padded.bucket_k == 4 and exact.bucket_k == 3
    assert np.array_equal(padded.dist, exact.dist)
    assert np.array_equal(padded.q_rounds, exact.q_rounds)
    assert np.array_equal(padded.q_relaxations, exact.q_relaxations)
    for field in ("rounds", "relaxations", "msgs_sent", "msgs_recv"):
        assert int(getattr(padded.stats, field)) == \
            int(getattr(exact.stats, field)), field
    # and both match the legacy wrapper (which itself rides the engine)
    d, st = solve_sim_batch(sh, sources, SsspConfig(prune_online=False))
    assert np.array_equal(d, padded.dist)
    assert np.array_equal(np.asarray(st.q_rounds), padded.q_rounds)


def test_query_result_structure(graph_and_shards):
    g, sh = graph_and_shards
    eng = SsspEngine.build(sh)
    res = eng.solve([7, 11])
    assert isinstance(res, QueryResult)
    assert res.sources == (7, 11) and res.backend == "sim"
    assert res.dist.shape == (2, g.n_vertices)
    assert res.q_rounds.shape == (2,) and res.q_relaxations.shape == (2,)
    assert res.wall_s > 0 and res.compiled and res.compile_s > 0
    warm = eng.solve([1, 2])
    assert warm.compile_s == 0.0 and not warm.compiled
    with pytest.raises(ValueError, match="out of range"):
        eng.solve([g.n_vertices])
    with pytest.raises(ValueError, match="at least one source"):
        eng.solve([])


def test_warmup_precompiles(graph_and_shards):
    _, sh = graph_and_shards
    eng = SsspEngine.build(sh)
    cold_s = eng.warmup(3)
    assert cold_s > 0 and eng.trace_counts == {4: 1}
    res = eng.solve([9, 10, 11])
    assert not res.compiled
    # an already-warm bucket short-circuits: no solve is run at all
    served = eng.batches_served
    assert eng.warmup(4) == 0.0
    assert eng.batches_served == served


# ------------------------------------------------ legacy delegation ----

def test_wrappers_share_one_engine(graph_and_shards):
    """solve_sim / solve_sim_batch ride ONE cached engine per (shards,
    cfg): repeated calls with new sources add no traces."""
    _, sh = graph_and_shards
    cfg = SsspConfig(exchange="pmin")
    solve_sim_batch(sh, [0, 1], cfg)
    eng = engine_for(sh, cfg)
    assert eng.trace_counts == {2: 1}
    solve_sim_batch(sh, [40, 41], cfg)
    solve_sim(sh, 7, cfg)
    assert eng.trace_counts == {2: 1, 1: 1}
    solve_sim(sh, 8, cfg)
    assert eng.trace_counts == {2: 1, 1: 1}


# ---------------------------------------------------- submit / drain ----

def test_submit_drain_coalesces(graph_and_shards):
    g, sh = graph_and_shards
    eng = SsspEngine.build(sh, max_bucket=4)
    hs = [eng.submit(3), eng.submit([17, 99]), eng.submit(120), eng.submit(5)]
    assert eng.pending == 4 and not hs[0].done
    results = eng.drain()
    assert eng.pending == 0 and len(results) == 4
    # max_bucket=4: handles coalesce as [1+2+1] then [1] — never split
    assert [r.bucket_k for r in results] == [4, 4, 4, 1]
    for h in hs:
        assert h.done
        refs = _refs(g, h.sources)
        np.testing.assert_allclose(h.result().dist, refs, rtol=1e-5,
                                   atol=1e-4)
        assert h.result().q_rounds.shape == (len(h.sources),)


def test_handle_result_drains_on_demand(graph_and_shards):
    g, sh = graph_and_shards
    eng = SsspEngine.build(sh)
    h = eng.submit([33, 44])
    res = h.result()            # implicit drain
    assert eng.pending == 0 and h.done
    np.testing.assert_allclose(res.dist, _refs(g, [33, 44]), rtol=1e-5,
                               atol=1e-4)
    with pytest.raises(ValueError, match="out of range"):
        eng.submit(g.n_vertices + 1)   # validated at submission
    with pytest.raises(ValueError, match="at least one source"):
        eng.submit([])                 # an empty batch can never drain
    assert eng.pending == 0


def test_drain_requeues_on_failure(graph_and_shards, monkeypatch):
    """A solve failure mid-drain must not lose submissions: the failing
    batch and everything after it go back on the queue."""
    g, sh = graph_and_shards
    eng = SsspEngine.build(sh, max_bucket=2)
    h1, h2, h3 = eng.submit(1), eng.submit(2), eng.submit(3)  # two batches
    monkeypatch.setattr(eng, "solve",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            RuntimeError("backend down")))
    with pytest.raises(RuntimeError, match="backend down"):
        eng.drain()
    assert eng.pending == 3 and not h1.done
    monkeypatch.undo()
    eng.drain()
    for h, s in ((h1, 1), (h2, 2), (h3, 3)):
        assert h.done
        np.testing.assert_allclose(h.result().dist[0],
                                   dijkstra_reference(g, s),
                                   rtol=1e-5, atol=1e-4)


def test_oversized_submission_rides_own_bucket(graph_and_shards):
    g, sh = graph_and_shards
    eng = SsspEngine.build(sh, max_bucket=2)
    h = eng.submit([1, 2, 3])    # larger than max_bucket: not split
    (res,) = eng.drain()
    assert res.bucket_k == 4 and res.sources == (1, 2, 3)
    np.testing.assert_allclose(res.dist, _refs(g, [1, 2, 3]), rtol=1e-5,
                               atol=1e-4)
    assert h.result() is res


# -------------------------------------------------- shmap backend ----

_SHMAP_ENGINE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from repro import compat
    from repro.core import (SsspConfig, SsspEngine, build_shards, engine_for,
                            solve_shmap_batch)
    from repro.graph import random_graph, dijkstra_reference

    g = random_graph(n=180, m=700, seed=21)
    sh = build_shards(g, 4)
    mesh = compat.make_mesh((4,), ("d",))
    eng = SsspEngine.build(sh, SsspConfig(), backend="shmap", mesh=mesh,
                           axis_names=("d",))

    # compile reuse: one whole-solve program per K-bucket, sources traced
    r1 = eng.solve([3, 17, 99])
    assert r1.compiled and r1.bucket_k == 4 and eng.trace_counts == {4: 1}
    r2 = eng.solve([120, 5, 66])          # new sources, same bucket
    assert not r2.compiled and eng.trace_counts == {4: 1}, eng.trace_counts
    refs = np.stack([dijkstra_reference(g, s) for s in [120, 5, 66]])
    assert np.allclose(r2.dist, refs, 1e-5, 1e-4)

    # padded bucket bit-matches the unpadded reference
    exact = eng.solve([3, 17, 99], bucket=False)
    assert np.array_equal(r1.dist, exact.dist)
    assert np.array_equal(r1.q_rounds, exact.q_rounds)

    # legacy wrapper: cached engine, no rebuild/retrace across calls, and
    # out-of-range sources now rejected on the shmap path too
    d, st = solve_shmap_batch(sh, [3, 17, 99], SsspConfig(), mesh, ("d",))
    weng = engine_for(sh, SsspConfig(), "shmap", mesh, ("d",))
    t0 = dict(weng.trace_counts)
    d2, _ = solve_shmap_batch(sh, [8, 9, 10], SsspConfig(), mesh, ("d",))
    assert weng.trace_counts == t0 == {4: 1}, weng.trace_counts
    assert np.array_equal(d, r1.dist)
    try:
        solve_shmap_batch(sh, [g.n_vertices + 5], SsspConfig(), mesh, ("d",))
        raise SystemExit("out-of-range source accepted on shmap")
    except ValueError:
        pass

    # the stack is placed once, one shard per device, at engine build
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    placed = NamedSharding(mesh, P(("d",)))
    for leaf in jax.tree_util.tree_leaves(eng.shards):
        assert leaf.sharding.is_equivalent_to(placed, leaf.ndim), leaf.sharding
    # one shard per device: any other count is refused, never truncated
    try:
        SsspEngine.build(build_shards(g, 8), SsspConfig(), backend="shmap",
                         mesh=mesh, axis_names=("d",))
        raise SystemExit("8 shards accepted on a 4-device mesh")
    except ValueError as e:
        assert "one shard per device" in str(e), e
    print("SHMAP ENGINE OK")
""")


def test_engine_shmap_trace_reuse_and_validation():
    """shmap: one compiled whole-solve program per K-bucket serves
    arbitrary source sets (the old path recompiled per batch); wrapper
    calls reuse the cached engine; sources validated like sim
    (subprocess: device count must be set before jax initializes)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHMAP_ENGINE_PROG], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHMAP ENGINE OK" in out.stdout
