"""Fused whole-round megakernel (kernels/round + round='fused').

Four layers, mirroring tests/test_phase_kernels.py for the fused round:
  1. kernel-vs-ref property tests (via tests/_hyp.py): one megakernel
     dispatch — merge + local fixpoint + send pack, rescue included —
     matches the pure-jnp oracle on random shard states for every shard,
     bucket AND dense, including deliberately-too-few in-kernel sweeps
  2. e2e bit-identity: round='fused' reproduces the staged pipeline
     EXACTLY — distances, q_rounds, q_relaxations, msgs — across
     bucket/pmin/a2a_dense x K in {1, 3}, in sim and (subprocess) shmap,
     and under an active FaultPlan with toka3 + anti-entropy resend
  3. dispatch accounting: stats.n_dispatches = 2 x rounds fused vs
     4 x rounds staged
  4. layout fallback: round='fused' degrades to the staged pipeline with
     a ONE-TIME warning when build_shards skipped the tiled layouts

The q_relaxations baseline is the staged pipeline with
local_solver='pallas': relaxation COUNTS are sweep-schedule dependent
(the megakernel replicates the batched Gauss–Seidel schedule), while
distances/rounds/msgs are schedule-independent (the fixpoint is unique
and send floors are monotone) and so are also asserted against the plain
XLA bellman baseline.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, strategies as st
from repro.core import SsspConfig, build_shards, solve_sim_batch
from repro.core.faults import FaultPlan
from repro.graph import dijkstra_reference, random_graph
from repro.kernels.round import (fused_round_pallas, fused_round_ref,
                                 fused_round_rescue)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXCHANGES = ("bucket", "pmin", "a2a_dense")
INF = np.float32(np.inf)


def _sources(g, nq, seed=17):
    rng = np.random.default_rng(seed)
    return sorted(int(s) for s in
                  rng.choice(g.n_vertices, size=nq, replace=False))


# ---------------------------------------------- kernel vs ref oracle ----

@settings(max_examples=4, deadline=None)
@given(n=st.integers(60, 220), mult=st.integers(2, 5),
       p=st.integers(2, 5), nq=st.integers(1, 3), seed=st.integers(0, 99),
       n_sweeps=st.integers(2, 8))
def test_fused_kernel_matches_ref(n, mult, p, nq, seed, n_sweeps):
    """One megakernel dispatch (plus rescue when the in-kernel sweep
    budget was too small) is bit-identical to the merge -> Jacobi
    fixpoint -> segment-min-pack oracle, on every shard, for random
    mid-solve state honoring the carry contracts."""
    g = random_graph(n=n, m=n * mult, seed=seed)
    sh = build_shards(g, p)
    block = sh.block
    rng = np.random.default_rng(seed * 31 + nq)
    for part in range(p):
        s0 = jax.tree_util.tree_map(lambda x: x[part], sh)
        S = s0.slot_owner.shape[0]
        e_loc, e_cut = s0.loc_src.shape[0], s0.cut_src.shape[0]
        dist = np.where(rng.random((nq, block)) < 0.3, INF,
                        (rng.random((nq, block)) * 10).astype(np.float32))
        front = rng.random((nq, block)) < 0.2
        live = rng.random(nq) < 0.8
        ridx = np.asarray(s0.recv_idx)
        inc_b = np.where(rng.random((nq,) + ridx.shape) < 0.5, INF,
                         (rng.random((nq,) + ridx.shape) * 10)
                         .astype(np.float32))
        inc_b = np.where((ridx == block)[None], INF, inc_b)  # routed only
        last = np.where(rng.random((nq, S)) < 0.5, INF,
                        (rng.random((nq, S)) * 10).astype(np.float32))
        last = np.where(np.asarray(s0.slot_valid)[None], last, INF)
        prn_loc = rng.random(e_loc) < 0.15
        prn_cut = rng.random(e_cut) < 0.15

        for dense in (False, True):
            if dense:
                inc = np.where(rng.random((nq, block)) < 0.5, INF,
                               (rng.random((nq, block)) * 10)
                               .astype(np.float32))
            else:
                inc = inc_b.reshape(nq, -1)
            nd, sv, nl, nrel, sends, resid = fused_round_pallas(
                jnp.asarray(dist), jnp.asarray(front), jnp.asarray(live),
                jnp.asarray(inc), jnp.asarray(last), s0.slot_valid,
                s0.relax_layout, s0.send_layout, s0.merge_layout,
                jnp.asarray(prn_loc), jnp.asarray(prn_cut), vb=sh.rx_vb,
                sb=sh.tx_sb, n_sweeps=n_sweeps, dense=dense)
            if bool(jnp.any(resid > 0)):
                nd, sv, nl, extra, sends = fused_round_rescue(
                    nd, resid, jnp.asarray(last), s0.slot_valid,
                    s0.relax_layout, s0.send_layout, jnp.asarray(prn_loc),
                    jnp.asarray(prn_cut), vb=sh.rx_vb, sb=sh.tx_sb,
                    n_sweeps=n_sweeps)
            rd, rsv, rnl, rsends = fused_round_ref(
                jnp.asarray(dist), jnp.asarray(front), jnp.asarray(live),
                jnp.asarray(inc), s0.recv_idx, jnp.asarray(last),
                s0.slot_valid, s0.loc_src, s0.loc_dst, s0.loc_w,
                jnp.asarray(prn_loc), s0.cut_src, s0.cut_seg, s0.cut_w,
                jnp.asarray(prn_cut), dense=dense)
            tag = f"part={part} dense={dense}"
            np.testing.assert_array_equal(np.asarray(nd), np.asarray(rd),
                                          err_msg=f"dist {tag}")
            np.testing.assert_array_equal(np.asarray(sv), np.asarray(rsv),
                                          err_msg=f"send_val {tag}")
            np.testing.assert_array_equal(np.asarray(nl), np.asarray(rnl),
                                          err_msg=f"new_last {tag}")
            np.testing.assert_array_equal(np.asarray(sends),
                                          np.asarray(rsends),
                                          err_msg=f"sends {tag}")


# ------------------------------------------------- e2e bit-identity ----

@pytest.mark.parametrize("nq", [1, 3])
def test_fused_round_bit_identical_sim(nq):
    """round='fused' is BIT-identical to the staged pipeline for every
    exchange mode: distances + q_rounds + msgs against BOTH staged
    baselines, q_relaxations against the pallas local solver (same
    Gauss–Seidel schedule), and n_dispatches records the 4 -> 2 fusion."""
    g = random_graph(n=180, m=700, seed=21)
    sh = build_shards(g, 5)
    sources = _sources(g, nq)
    refs = np.stack([dijkstra_reference(g, s) for s in sources])
    for ex in EXCHANGES:
        d_pal, s_pal = solve_sim_batch(
            sh, sources, SsspConfig(exchange=ex, toka="toka2",
                                    local_solver="pallas"))
        d_xla, s_xla = solve_sim_batch(
            sh, sources, SsspConfig(exchange=ex, toka="toka2"))
        d_fus, s_fus = solve_sim_batch(
            sh, sources, SsspConfig(exchange=ex, toka="toka2",
                                    round="fused"))
        np.testing.assert_allclose(d_fus, refs, rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(d_fus), np.asarray(d_pal))
        np.testing.assert_array_equal(np.asarray(d_fus), np.asarray(d_xla))
        for base in (s_pal, s_xla):
            assert int(s_fus.rounds) == int(base.rounds), ex
            np.testing.assert_array_equal(np.asarray(s_fus.q_rounds),
                                          np.asarray(base.q_rounds),
                                          err_msg=ex)
            assert int(s_fus.msgs_sent) == int(base.msgs_sent), ex
            assert int(s_fus.msgs_recv) == int(base.msgs_recv), ex
        np.testing.assert_array_equal(np.asarray(s_fus.q_relaxations),
                                      np.asarray(s_pal.q_relaxations),
                                      err_msg=ex)
        # the satellite counter: dispatch volume halves per round
        assert int(s_fus.n_dispatches) == 2 * int(s_fus.rounds)
        assert int(s_pal.n_dispatches) == 4 * int(s_pal.rounds)


def test_fused_round_few_sweeps_rescue_bit_identical():
    """pallas_sweeps=1 forces the rescue continuation on nearly every
    round; the results must not move (the rescue replays the staged outer
    relax loop and re-packs against the original last_sent)."""
    g = random_graph(n=150, m=600, seed=4)
    sh = build_shards(g, 4)
    sources = _sources(g, 2, seed=3)
    d_base, s_base = solve_sim_batch(
        sh, sources, SsspConfig(toka="toka2", local_solver="pallas",
                                pallas_sweeps=1))
    d_fus, s_fus = solve_sim_batch(
        sh, sources, SsspConfig(toka="toka2", round="fused",
                                pallas_sweeps=1))
    np.testing.assert_array_equal(np.asarray(d_fus), np.asarray(d_base))
    np.testing.assert_array_equal(np.asarray(s_fus.q_rounds),
                                  np.asarray(s_base.q_rounds))
    np.testing.assert_array_equal(np.asarray(s_fus.q_relaxations),
                                  np.asarray(s_base.q_relaxations))
    assert int(s_fus.msgs_sent) == int(s_base.msgs_sent)


def test_fused_round_faults_bit_identical():
    """The fault-injection wrapper and toka3 compose around the fused
    exchange boundary unchanged: same PRNG placement, same delivery
    accounting, same anti-entropy resend windows — every stat matches the
    staged pipeline under an aggressive FaultPlan."""
    g = random_graph(n=150, m=600, seed=9)
    sh = build_shards(g, 4)
    sources = _sources(g, 2, seed=11)
    refs = np.stack([dijkstra_reference(g, s) for s in sources])
    fp = FaultPlan(drop=0.2, delay=0.1, duplicate=0.05, seed=3, max_delay=3,
                   resend_period=4)
    for ex in ("bucket", "a2a_dense"):
        d_base, s_base = solve_sim_batch(
            sh, sources, SsspConfig(exchange=ex, toka="toka3",
                                    local_solver="pallas", faults=fp))
        d_fus, s_fus = solve_sim_batch(
            sh, sources, SsspConfig(exchange=ex, toka="toka3",
                                    round="fused", faults=fp))
        np.testing.assert_allclose(d_fus, refs, rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(d_fus), np.asarray(d_base))
        for f in ("rounds", "q_rounds", "q_relaxations", "msgs_sent",
                  "msgs_recv", "stale_merges", "resends"):
            np.testing.assert_array_equal(
                np.asarray(getattr(s_fus, f)),
                np.asarray(getattr(s_base, f)), err_msg=f"{ex} {f}")


_SHMAP_FUSED_PROG = textwrap.dedent("""
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
        cfg_s = SsspConfig(exchange=ex, local_solver="pallas")
        cfg_f = SsspConfig(exchange=ex, round="fused")
        ds, ss = solve_shmap_batch(sh, sources, cfg_s, mesh, ("d",))
        df, sf = solve_shmap_batch(sh, sources, cfg_f, mesh, ("d",))
        assert np.allclose(df, refs, 1e-5, 1e-4), ex
        assert (np.asarray(df) == np.asarray(ds)).all(), ex
        for f in ("rounds", "q_rounds", "q_relaxations", "msgs_sent",
                  "msgs_recv"):
            a, b = np.asarray(getattr(sf, f)), np.asarray(getattr(ss, f))
            assert (a == b).all(), (ex, f)
        assert int(sf.n_dispatches) == 2 * int(sf.rounds), ex
        assert int(ss.n_dispatches) == 4 * int(ss.rounds), ex
    print("SHMAP FUSED ROUND OK")
""")


def test_fused_round_bit_identical_shmap():
    """Same bit-identity under shard_map with real collectives on a
    spoofed 4-device mesh (subprocess: device count must be set before
    jax initializes)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHMAP_FUSED_PROG], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHMAP FUSED ROUND OK" in out.stdout


# ------------------------------------------------- layout fallback ----

def test_fused_round_falls_back_with_one_time_warning():
    """Without the tiled layouts the fused backend refuses to run: it
    raises, on every solve, instead of degrading to the staged pipeline."""
    g = random_graph(150, 600, seed=9)
    sh = build_shards(g, 4, relax_layout=False, comm_layout=False)
    cfg = SsspConfig(round="fused")
    for src in (0, 1):
        with pytest.raises(ValueError, match="round='fused' needs"):
            solve_sim_batch(sh, [src], cfg)
    # the staged pipeline still serves the same shards
    d, stats = solve_sim_batch(sh, [0], SsspConfig())
    np.testing.assert_allclose(d[0], dijkstra_reference(g, 0),
                               rtol=1e-5, atol=1e-4)
    assert int(stats.n_dispatches) == 4 * int(stats.rounds)
