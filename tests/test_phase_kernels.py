"""Phase-pipeline round engine: send/merge kernels + backend registry.

Four layers, binding the pipeline to the system:
  1. kernel-vs-ref property tests (via tests/_hyp.py): the slot-tiled send
     pack and the msg-tiled merge scatter match their pure-jnp oracles on
     random graphs for K in {1, 3}
  2. e2e equivalence: every (send_backend x merge_backend) combination
     produces BIT-identical distances and per-query stats to the XLA
     baseline across all exchange modes, in sim and (subprocess) shmap
  3. config validation: unknown backend names raise eagerly at
     SsspConfig construction, not inside tracing
  4. layout fallback: pallas backends degrade to xla with a ONE-TIME
     warning when build_shards skipped the layouts
"""
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hyp import given, settings, strategies as st
from repro.core import (SsspConfig, SsspEngine, build_shards,
                        build_shards_stream, certificate_improved_sim, phases,
                        sim_phase_fns, solve_sim_batch)
from repro.core.sssp import _cert_relax_shard, _slot_min
from repro.graph import dijkstra_reference, edge_chunks_of, random_graph
from repro.graph.structure import csr_from_coo
from repro.kernels.merge import (build_msg_tiled_layout, merge_scatter_pallas,
                                 merge_scatter_ref)
from repro.kernels.send import (build_slot_tiled_layout, send_pack_pallas,
                                send_payload_bucket, send_pack_ref)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXCHANGES = ("bucket", "pmin", "a2a_dense")
BACKENDS = ("xla", "pallas")


def _sources(g, nq, seed=17):
    rng = np.random.default_rng(seed)
    return sorted(int(s) for s in
                  rng.choice(g.n_vertices, size=nq, replace=False))


# ------------------------------------------------ kernel property tests ----

def _random_send_state(n_vertices, e_cut, n_slots, nq, seed):
    """Random cut-edge pack inputs honoring the shard contract: seg ids
    sorted, last_sent only ever holds values a previous pack produced (so
    INF or a real candidate)."""
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, n_slots, size=e_cut))
    src = rng.integers(0, n_vertices, size=e_cut)
    w = rng.uniform(1, 20, size=e_cut).astype(np.float32)
    dist = rng.uniform(0, 50, size=(nq, n_vertices)).astype(np.float32)
    dist[rng.random((nq, n_vertices)) < 0.3] = np.inf
    last = rng.uniform(0, 60, size=(nq, n_slots)).astype(np.float32)
    last[rng.random((nq, n_slots)) < 0.5] = np.inf
    valid = np.zeros(n_slots, bool)
    valid[np.unique(seg)] = True
    pruned = rng.random(e_cut) < 0.2
    return src, seg, w, dist, last, valid, pruned


@settings(max_examples=6, deadline=None)
@given(n=st.integers(40, 300), e=st.integers(10, 600),
       s=st.integers(4, 200), nq=st.integers(1, 3), seed=st.integers(0, 999))
def test_send_kernel_matches_ref(n, e, s, nq, seed):
    src, seg, w, dist, last, valid, pruned = _random_send_state(
        n, e, s, nq, seed)
    w_masked = np.where(pruned, np.inf, w)
    ref = send_pack_ref(jnp.asarray(dist), jnp.asarray(src, jnp.int32),
                        jnp.asarray(w_masked), jnp.asarray(seg, jnp.int32),
                        s, jnp.asarray(valid), jnp.asarray(last))
    src_t, w_t, seg_t, eid_t, _sp = build_slot_tiled_layout(
        src, seg, w, s, sb=128, eb=256)
    pruned_t = jnp.take(jnp.asarray(pruned, jnp.int32), eid_t, mode="fill",
                        fill_value=0)
    out = send_pack_pallas(jnp.asarray(dist), jnp.asarray(last),
                           jnp.asarray(valid), src_t, w_t, seg_t, pruned_t,
                           sb=128, eb=256)
    for got, want in zip(out[:2], ref[:2]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(ref[2]))


@settings(max_examples=6, deadline=None)
@given(block=st.integers(16, 300), p=st.integers(1, 8), c=st.integers(1, 40),
       nq=st.integers(1, 3), seed=st.integers(0, 999))
def test_merge_kernel_matches_ref(block, p, c, nq, seed):
    """Random routing table + contract-consistent incoming values (a
    position without a route never carries a finite value — in the solver
    no sender owns a slot for it)."""
    rng = np.random.default_rng(seed)
    ridx = rng.integers(0, block + 1, size=(p, c))     # block = sentinel
    incoming = rng.uniform(0, 50, size=(nq, p * c)).astype(np.float32)
    incoming[rng.random((nq, p * c)) < 0.4] = np.inf
    incoming[:, (ridx == block).reshape(-1)] = np.inf
    dist = rng.uniform(0, 40, size=(nq, block)).astype(np.float32)
    dist[rng.random((nq, block)) < 0.3] = np.inf

    ref = merge_scatter_ref(jnp.asarray(dist), jnp.asarray(incoming),
                            jnp.asarray(ridx.reshape(-1), jnp.int32))
    pos_t, dr_t, v_t, _bp = build_msg_tiled_layout(ridx, block, vb=128,
                                                   eb=256)
    out = merge_scatter_pallas(jnp.asarray(dist), jnp.asarray(incoming),
                               pos_t, dr_t, v_t, vb=128, eb=256)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(ref[2]))


def test_payload_gather_matches_scatter():
    """The static payload inverse (tx_payload_slot) reproduces the XLA
    scatter exactly: each bucket position receives at most one slot."""
    g = random_graph(n=200, m=900, seed=3)
    sh = build_shards(g, 6)
    rng = np.random.default_rng(4)
    S, C, P = sh.n_slots, sh.bucket_cap, sh.n_parts
    for p in range(P):
        val = rng.uniform(0, 30, size=(2, S)).astype(np.float32)
        val[rng.random((2, S)) < 0.5] = np.inf
        val[:, ~np.asarray(sh.slot_valid[p])] = np.inf
        ref = np.stack([
            np.full((P, C), np.inf, np.float32) for _ in range(2)])
        owner = np.asarray(sh.slot_owner[p])
        pos = np.asarray(sh.slot_pos[p])
        for k in range(2):
            np.minimum.at(ref[k], (owner, pos), val[k])
        got = send_payload_bucket(jnp.asarray(val), sh.tx_payload_slot[p])
        np.testing.assert_array_equal(np.asarray(got), ref)


# ------------------------------------------- scatter-free slot min ----

def _zipf_runs(rng, n):
    return np.minimum(rng.zipf(1.6, size=n), 300)


# (run lengths, padded tail of cut_seg == S, padded slots past the real)
_SLOT_CASES = {
    "runs_of_one": lambda rng: (np.ones(37, np.int64), 0, 3),
    "one_run": lambda rng: (np.array([53]), 0, 2),
    "power_law": lambda rng: (_zipf_runs(rng, 60), 5, 4),
    "no_real_edge": lambda rng: (np.zeros(0, np.int64), 1, 1),
    "padded_tail": lambda rng: (np.array([3, 1, 7, 2, 9]), 11, 0),
}


@pytest.mark.parametrize("nq", [1, 3, 16])
@pytest.mark.parametrize("case", sorted(_SLOT_CASES))
def test_slot_min_matches_segment_min(case, nq):
    """The doubling slot min equals ``segment_min`` bit for bit, +inf
    included: unreached sources, padded slots, the padded edge tail."""
    rng = np.random.default_rng(sorted(_SLOT_CASES).index(case) * 31 + nq)
    runs, tail, pad_slots = _SLOT_CASES[case](rng)
    n_real = len(runs)
    S = max(n_real + pad_slots, 1)
    seg = np.concatenate([np.repeat(np.arange(n_real), runs),
                          np.full(tail, S)]).astype(np.int32)
    e_cut = len(seg)
    cand = rng.uniform(0, 40, size=(nq, e_cut)).astype(np.float32)
    cand[rng.random((nq, e_cut)) < 0.3] = np.inf
    cand[:, seg == S] = np.inf
    slot_last = np.zeros(S, np.int32)
    slot_last[:n_real] = np.cumsum(runs) - 1
    longest = int(runs.max()) if n_real else 1
    shard = types.SimpleNamespace(
        cut_seg=jnp.asarray(seg), slot_last=jnp.asarray(slot_last),
        slot_valid=jnp.asarray(np.arange(S) < n_real),
        seg_steps=int(np.ceil(np.log2(longest))))
    got = np.asarray(_slot_min(shard, jnp.asarray(cand)))
    ref = np.asarray(jax.vmap(lambda c: jax.ops.segment_min(
        c, jnp.asarray(seg), num_segments=S, indices_are_sorted=True))(
            jnp.asarray(cand)))
    assert got.shape == (nq, S)
    assert np.array_equal(got, ref)


def _star_graph():
    """64 vertices, 4 shards of 16: 13 sources on shard 0 and 5 on shard 1
    all point at hub 63 (shard 3), plus a ring. Shard 0's run into the
    hub is the longest: ceil(log2(13)) = 4 doubling steps."""
    n = 64
    src = np.concatenate([np.arange(13), 16 + np.arange(5), np.arange(n)])
    dst = np.concatenate([np.full(18, 63), (np.arange(n) + 1) % n])
    w = np.random.default_rng(2).uniform(1, 20, len(src)).astype(np.float32)
    return csr_from_coo(src, dst, w, n)


def _build(g, builder, layout, parts):
    if builder == "batch":
        return build_shards(g, parts, layout=layout)
    return build_shards_stream(edge_chunks_of(g, chunk_edges=97),
                               g.n_vertices, parts, layout=layout)


@pytest.mark.parametrize("graph", ["random", "star"])
@pytest.mark.parametrize("layout", ["dense", "ragged"])
@pytest.mark.parametrize("builder", ["batch", "stream"])
def test_slot_last_and_seg_steps(builder, layout, graph):
    """``slot_last`` points at each slot's last cut edge (0 on padded
    slots); ``seg_steps`` is ceil(log2) of the longest real run over every
    shard, the padded edge tail left out."""
    g = _star_graph() if graph == "star" else random_graph(200, 900, seed=8)
    sh = _build(g, builder, layout, 4)
    seg = np.asarray(sh.cut_seg)
    last = np.asarray(sh.slot_last)
    valid = np.asarray(sh.slot_valid)
    assert last.shape == (sh.n_parts, sh.n_slots)
    longest = 1
    for p in range(sh.n_parts):
        real = seg[p][seg[p] < sh.n_slots]
        n_real = int(valid[p].sum())
        runs = np.bincount(real, minlength=n_real)
        if len(real):
            longest = max(longest, int(runs.max()))
        np.testing.assert_array_equal(last[p, :n_real], np.cumsum(runs) - 1)
        assert (last[p, n_real:] == 0).all()
    assert sh.seg_steps == int(np.ceil(np.log2(longest)))
    if graph == "star":
        assert sh.seg_steps == 4


@pytest.mark.parametrize("nq", [1, 16])
def test_slot_min_solve_matches_pallas_send(nq):
    """A sim solve with the XLA send pack gives the distances, rounds and
    relaxations of the Pallas send kernel (interpret mode); the certificate
    passes both, and its one relaxation of every edge equals a plain one."""
    g = random_graph(n=180, m=700, seed=21)
    sh = build_shards(g, 5)
    sources = _sources(g, nq)
    out = {}
    for sb in BACKENDS:
        res = SsspEngine.build(sh, SsspConfig(send_backend=sb)).solve(sources)
        assert res.status == "converged"
        out[sb] = res
    a, b = out["xla"], out["pallas"]
    np.testing.assert_array_equal(a.dist, b.dist)
    np.testing.assert_array_equal(a.stats.q_rounds, b.stats.q_rounds)
    np.testing.assert_array_equal(a.stats.q_relaxations,
                                  b.stats.q_relaxations)
    # the certificate's relaxation, from a state short of the fixpoint
    P, blk, n = sh.n_parts, sh.block, g.n_vertices
    rng = np.random.default_rng(5)
    dist = np.asarray(a.dist, np.float32) + rng.choice(
        np.float32([0, 0, 3]), size=a.dist.shape)
    pad = np.full((nq, P * blk - n), np.inf, np.float32)
    dist_pk = jnp.asarray(np.concatenate([dist, pad], 1)
                          .reshape(nq, P, blk).transpose(1, 0, 2))
    new, payload = jax.vmap(_cert_relax_shard)(sh, dist_pk)
    merged = jnp.minimum(new, jnp.min(payload, axis=0).transpose(1, 0, 2))
    got = np.asarray(merged).transpose(1, 0, 2).reshape(nq, -1)[:, :n]
    m = g.n_edges
    e_src, e_dst = np.asarray(g.src)[:m], np.asarray(g.dst)[:m]
    ref = dist.copy()
    for k in range(nq):
        np.minimum.at(ref[k], e_dst, dist[k, e_src] + np.asarray(g.weight)[:m])
    np.testing.assert_array_equal(got, ref)
    improved = np.asarray(certificate_improved_sim(sh, dist_pk))
    np.testing.assert_array_equal(improved, (ref < dist).any(axis=1))


# ------------------------------------------------ e2e backend matrix ----

@pytest.mark.parametrize("nq", [1, 3])
def test_backend_matrix_bit_identical_sim(nq):
    """Every (send_backend x merge_backend) combination is BIT-identical
    to the XLA baseline — distances AND per-query q_rounds/q_relaxations —
    for every exchange mode (the kernels change the math's address order,
    never its values: min is exact)."""
    g = random_graph(n=180, m=700, seed=21)
    sh = build_shards(g, 5)
    sources = _sources(g, nq)
    refs = np.stack([dijkstra_reference(g, s) for s in sources])
    for ex in EXCHANGES:
        base = None
        for sb in BACKENDS:
            for mb in BACKENDS:
                cfg = SsspConfig(exchange=ex, send_backend=sb,
                                 merge_backend=mb, toka="toka2")
                d, stats = solve_sim_batch(sh, sources, cfg)
                np.testing.assert_allclose(d, refs, rtol=1e-5, atol=1e-4)
                key = (np.asarray(d), np.asarray(stats.q_rounds),
                       np.asarray(stats.q_relaxations),
                       int(stats.msgs_sent), int(stats.msgs_recv))
                if base is None:
                    base = key
                    continue
                np.testing.assert_array_equal(key[0], base[0], err_msg=str((ex, sb, mb)))
                np.testing.assert_array_equal(key[1], base[1], err_msg=str((ex, sb, mb)))
                np.testing.assert_array_equal(key[2], base[2], err_msg=str((ex, sb, mb)))
                assert key[3:] == base[3:], (ex, sb, mb)


_SHMAP_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from repro import compat
    from repro.core import SsspConfig, build_shards, solve_shmap_batch
    from repro.graph import random_graph, dijkstra_reference

    g = random_graph(n=180, m=700, seed=21)
    sh = build_shards(g, 4)
    mesh = compat.make_mesh((4,), ("d",))
    rng = np.random.default_rng(17)
    sources = sorted(int(s) for s in
                     rng.choice(g.n_vertices, size=3, replace=False))
    refs = np.stack([dijkstra_reference(g, s) for s in sources])
    for ex in ("bucket", "pmin", "a2a_dense"):
        base = None
        for sb in ("xla", "pallas"):
            for mb in ("xla", "pallas"):
                cfg = SsspConfig(exchange=ex, send_backend=sb,
                                 merge_backend=mb)
                d, stats = solve_shmap_batch(sh, sources, cfg, mesh, ("d",))
                assert np.allclose(d, refs, 1e-5, 1e-4), (ex, sb, mb)
                key = (np.asarray(d), np.asarray(stats.q_rounds),
                       np.asarray(stats.q_relaxations))
                if base is None:
                    base = key
                    continue
                assert (key[0] == base[0]).all(), (ex, sb, mb)
                assert (key[1] == base[1]).all(), (ex, sb, mb)
                assert (key[2] == base[2]).all(), (ex, sb, mb)
    print("SHMAP BACKEND MATRIX OK")
""")


def test_backend_matrix_shmap():
    """Same bit-identity under shard_map with real collectives on a
    spoofed 4-device mesh (subprocess: device count must be set before jax
    initializes)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHMAP_PROG], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHMAP BACKEND MATRIX OK" in out.stdout


def test_phase_fns_compose_to_round():
    """The per-phase benchmark hook drives the same stages the round
    dispatches: one manual local->send->exchange->merge pass starting from
    converged distances is a fixpoint (no new frontier, nothing sent)."""
    g = random_graph(n=120, m=500, seed=33)
    sh = build_shards(g, 4)
    cfg = SsspConfig(send_backend="pallas", merge_backend="pallas",
                     prune_online=False)
    d, _ = solve_sim_batch(sh, [0, 7], cfg)
    fns = sim_phase_fns(sh, cfg)
    nq, blk, P = 2, sh.block, sh.n_parts
    dist = jnp.asarray(
        np.moveaxis(np.pad(np.asarray(d), ((0, 0), (0, P * blk - g.n_vertices)),
                           constant_values=np.inf).reshape(nq, P, blk), 1, 0))
    active = jnp.zeros((P, nq, blk), bool)
    pruned = jnp.zeros((P, sh.e_loc + sh.e_cut), bool)
    cursor = jnp.zeros((P,), jnp.int32)
    last = jnp.full((P, nq, sh.n_slots), np.inf, jnp.float32)
    dist2, _, _, _, _ = fns["local"](dist, active, pruned, cursor)
    payload, _, sends = fns["send"](dist2, pruned, last)
    incoming = fns["exchange"](payload)
    dist3, new_active, _ = fns["merge"](dist2, incoming)
    np.testing.assert_array_equal(np.asarray(dist3), np.asarray(dist))
    assert not bool(np.asarray(new_active).any())
    # last_sent starts at INF here, so the converged distances DO transmit
    # once — but a second pass against the updated last_sent must be quiet
    _, last2, _ = fns["send"](dist2, pruned, last)
    _, _, sends2 = fns["send"](dist2, pruned, last2)
    assert not np.asarray(sends2).any()


# ------------------------------------------------ config validation ----

@pytest.mark.parametrize("field,bad", [
    ("exchange", "ring"),
    ("toka", "toka9"),
    ("local_solver", "dijkstra"),
    ("send_backend", "cuda"),
    ("merge_backend", "triton"),
    ("round", "megakernel"),
])
def test_config_rejects_unknown_backends(field, bad):
    """Eager validation: the ValueError arrives at construction and names
    the valid options."""
    with pytest.raises(ValueError, match="valid:"):
        SsspConfig(**{field: bad})


def test_registry_lists_backends():
    assert set(phases.backends("send")) == {"xla", "pallas"}
    assert set(phases.backends("merge")) == {"xla", "pallas"}
    assert set(phases.backends("exchange")) == {"bucket", "pmin", "a2a_dense",
                                                "async", "async_bucket",
                                                "async_ppermute"}
    assert set(phases.backends("local_solver")) == {"bellman", "delta",
                                                    "pallas"}
    assert set(phases.backends("round")) == {"staged", "fused"}
    with pytest.raises(ValueError, match="valid:"):
        phases.resolve("send", "nope")


# ------------------------------------------------ layout fallbacks ----

def test_pallas_backends_fall_back_with_one_time_warning():
    """A Pallas backend whose layout the shards lack raises, naming the
    backend, instead of switching to XLA."""
    g = random_graph(150, 600, seed=9)
    sh = build_shards(g, 4, relax_layout=False, comm_layout=False)
    assert not (sh.has_send_layout or sh.has_merge_layout)
    for kw, name in ((dict(send_backend="pallas"), "send_backend"),
                     (dict(merge_backend="pallas"), "merge_backend"),
                     (dict(local_solver="pallas"), "local_solver")):
        with pytest.raises(ValueError, match=f"{name}='pallas' needs"):
            solve_sim_batch(sh, [0], SsspConfig(**kw))
    d, _ = solve_sim_batch(sh, [0], SsspConfig())
    np.testing.assert_allclose(d[0], dijkstra_reference(g, 0),
                               rtol=1e-5, atol=1e-4)


def test_comm_layout_shapes():
    """build_shards carries the stacked slot/msg-tiled layouts with the
    kernel contract's shapes; every real cut edge appears exactly once."""
    g = random_graph(200, 800, seed=10)
    sh = build_shards(g, 4)
    P = sh.n_parts
    assert sh.tx_src.shape[0] == P
    assert sh.tx_src.shape == sh.tx_w.shape == sh.tx_segrel.shape == sh.tx_eid.shape
    assert sh.tx_src.shape[1] * sh.tx_sb >= sh.n_slots
    assert sh.tx_payload_slot.shape == (P, P, sh.bucket_cap)
    assert sh.mx_pos.shape == sh.mx_dstrel.shape == sh.mx_valid.shape
    assert sh.mx_pos.shape[1] * sh.mx_vb >= sh.block
    for p in range(P):
        eids = np.asarray(sh.tx_eid[p]).ravel()
        real = np.sort(eids[eids < sh.e_cut])
        valid = np.isfinite(np.asarray(sh.cut_w[p]))
        np.testing.assert_array_equal(real, np.nonzero(valid)[0])
        # merge layout covers exactly the routed positions
        routed = np.asarray(sh.recv_idx[p]).reshape(-1) < sh.block
        pos = np.asarray(sh.mx_pos[p]).ravel()
        v = np.asarray(sh.mx_valid[p]).ravel() > 0
        np.testing.assert_array_equal(np.sort(pos[v]), np.nonzero(routed)[0])


# ------------------------------------------- acceptance matrix (slow) ----

_ACCEPT_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from repro import compat
    from repro.core import (SsspConfig, build_shards, solve_shmap_batch,
                            solve_sim_batch)
    from repro.graph import rmat_graph, road_grid_graph, dijkstra_reference

    graphs = {
        "graph1-like": rmat_graph(scale=11, edge_factor=2, seed=1),
        "graph2-like": road_grid_graph(side=48, seed=2),
        "graph3-like": rmat_graph(scale=9, edge_factor=24, seed=3),
    }
    K = 8
    rng = np.random.default_rng(5)
    for name, g in graphs.items():
        sources = sorted(int(s) for s in
                         rng.choice(g.n_vertices, size=K, replace=False))
        refs = np.stack([dijkstra_reference(g, s) for s in sources])
        sh = build_shards(g, 8, enumerate_triangles=False)
        mesh = compat.make_mesh((8,), ("d",))
        for label, cfg in [
            ("staged", SsspConfig(local_solver="pallas",
                                  send_backend="pallas",
                                  merge_backend="pallas",
                                  prune_online=False)),
            ("fused", SsspConfig(round="fused", prune_online=False)),
        ]:
            d, _ = solve_sim_batch(sh, sources, cfg)
            assert np.allclose(d, refs, 1e-5, 1e-4), ("sim", label, name)
            d, _ = solve_shmap_batch(sh, sources, cfg, mesh, ("d",))
            assert np.allclose(d, refs, 1e-5, 1e-4), ("shmap", label, name)
        print(f"{name} OK")
    print("FULL PALLAS PIPELINE OK")
""")


@pytest.mark.slow
def test_full_pallas_pipeline_acceptance():
    """Acceptance: the all-pallas round (relax + send + merge kernels)
    matches Dijkstra for K=8 on all three bench graphs, sim and shmap."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _ACCEPT_PROG], env=env,
                         capture_output=True, text=True, timeout=3000)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "FULL PALLAS PIPELINE OK" in out.stdout
