"""Paper §IV benchmarks (Figs 1-3 analogs), executed on CPU at reduced scale.

- runtime vs number of partitions (Fig 1) and speedup (Fig 2)
- MTEPS (million traversed edges per second)
- Trishla effectiveness: edges pruned, relaxations saved
- ToKa comparison: rounds + message overhead of toka0/1/2

Graphs are generated analogs of the paper's four (ParMat/R-MAT synthetic,
road grid) scaled to CPU: the paper's *shape* (vertex/edge ratio) is kept.
"""
from __future__ import annotations

import re
import time

import jax
import jax.extend.core as jex_core
import numpy as np

from repro.core import (FaultPlan, SsspConfig, SsspEngine, build_shards,
                        build_shards_stream, sim_phase_fns,
                        solve_sim, solve_sim_batch)
from repro.core import sssp as sssp_mod
from repro.graph import (dijkstra_reference, preset_edge_stream, rmat_graph,
                         road_grid_graph)

BENCH_GRAPHS = {
    # name: builder — e/v ratios mimic graph1 (2.2), graph2 road (2.4, grid),
    # graph3 social (38)
    "graph1-like": lambda: rmat_graph(scale=11, edge_factor=2, seed=1),
    "graph2-like": lambda: road_grid_graph(side=48, seed=2),
    "graph3-like": lambda: rmat_graph(scale=9, edge_factor=24, seed=3),
}


def _solve_timed(sh, source, cfg, repeats=3):
    # warmup + compile
    dist, stats = solve_sim(sh, source, cfg)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        dist, stats = solve_sim(sh, source, cfg)
        ts.append(time.perf_counter() - t0)
    return dist, stats, min(ts)


def bench_scaling(out):
    """Fig 1/2: runtime + speedup vs partitions."""
    for name, build in BENCH_GRAPHS.items():
        g = build()
        source = int(g.src[0])
        base_t = None
        for p in (1, 2, 4, 8, 16):
            sh = build_shards(g, p, enumerate_triangles=False)
            cfg = SsspConfig(prune_online=False)
            dist, stats, t = _solve_timed(sh, source, cfg)
            base_t = base_t or t
            mteps = int(stats.relaxations) / t / 1e6
            out(f"sssp_runtime[{name}][P={p}]", t * 1e6,
                f"speedup={base_t / t:.2f} mteps={mteps:.1f} "
                f"rounds={int(stats.rounds)}")


def bench_trishla(out):
    """Trishla: pruned edges + relaxation savings (paper's TEPS argument)."""
    for name, build in BENCH_GRAPHS.items():
        g = build()
        source = int(g.src[0])
        sh = build_shards(g, 8)
        _, s0, t0 = _solve_timed(sh, source, SsspConfig(prune_online=False))
        _, s1, t1 = _solve_timed(sh, source,
                                 SsspConfig(prune_offline_passes=1,
                                            prune_online=False))
        saved = 1 - int(s1.relaxations) / max(int(s0.relaxations), 1)
        out(f"trishla[{name}]", t1 * 1e6,
            f"pruned={int(s1.pruned_edges)}/{g.n_edges} "
            f"relax_saved={saved:.1%}")


def bench_toka(out):
    """Termination detection overhead: rounds + wall time per detector."""
    g = BENCH_GRAPHS["graph1-like"]()
    source = int(g.src[0])
    sh = build_shards(g, 8, enumerate_triangles=False)
    ref = dijkstra_reference(g, source)
    for toka in ("toka0", "toka1", "toka2"):
        cfg = SsspConfig(toka=toka, prune_online=False)
        dist, stats, t = _solve_timed(sh, source, cfg)
        ok = np.allclose(dist, ref, 1e-5, 1e-4)
        out(f"toka[{toka}]", t * 1e6,
            f"rounds={int(stats.rounds)} msgs={int(stats.msgs_sent)} ok={ok}")


def bench_local_solver(out):
    """Dijkstra-order (delta) vs blind sweeps: relaxation efficiency."""
    g = BENCH_GRAPHS["graph2-like"]()
    source = int(g.src[0])
    sh = build_shards(g, 8, enumerate_triangles=False)
    for solver, delta in (("bellman", 0.0), ("delta", 4.0), ("delta", 12.0)):
        cfg = SsspConfig(local_solver=solver, delta=delta, prune_online=False)
        _, stats, t = _solve_timed(sh, source, cfg)
        out(f"local_solver[{solver}-{delta}]", t * 1e6,
            f"relax={int(stats.relaxations)} rounds={int(stats.rounds)}")


def bench_pallas_solver(out):
    """End-to-end pallas vs bellman vs delta on every bench graph.

    The dst-tiled layout rides in the shards (built once at partition
    time); interpret-mode wall times are NOT TPU perf — MTEPS here tracks
    the CPU-emulated trajectory so regressions in the kernel path are
    visible from this PR onward."""
    for name, build in BENCH_GRAPHS.items():
        g = build()
        source = int(g.src[0])
        sh = build_shards(g, 8, enumerate_triangles=False)
        ref = dijkstra_reference(g, source)
        for solver in ("bellman", "delta", "pallas"):
            cfg = SsspConfig(local_solver=solver, prune_online=False)
            dist, stats, t = _solve_timed(sh, source, cfg)
            ok = np.allclose(dist, ref, 1e-5, 1e-4)
            mteps = int(stats.relaxations) / t / 1e6
            out(f"local_solver[{solver}][{name}]", t * 1e6,
                f"mteps={mteps:.4f} relax={int(stats.relaxations)} "
                f"rounds={int(stats.rounds)} ok={ok}")


def bench_batch_throughput(out):
    """Query-engine throughput: queries/sec and aggregate MTEPS vs batch
    size K.

    One ``build_shards``, many sources: the compiled round, the per-round
    collectives, and (for pallas) the dst-tiled edge layout are shared by
    the whole batch, so the per-query cost of a round is amortized — the
    per-source launch/dispatch overhead that dominates single-source runs
    (the batching argument of the MPI+CUDA Dijkstra study) is paid once
    per K queries."""
    for name, build in BENCH_GRAPHS.items():
        g = build()
        rng = np.random.default_rng(9)
        sh = build_shards(g, 8, enumerate_triangles=False)
        cfg = SsspConfig(prune_online=False)
        for k in (1, 4, 16):
            sources = sorted(int(s) for s in
                             rng.choice(g.n_vertices, size=k, replace=False))
            solve_sim_batch(sh, sources, cfg)      # warmup + compile
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                _, stats = solve_sim_batch(sh, sources, cfg)
                ts.append(time.perf_counter() - t0)
            t = min(ts)
            mteps = int(stats.relaxations) / t / 1e6
            out(f"batch_throughput[{name}][K={k}]", t * 1e6,
                f"qps={k / t:.3f} mteps={mteps:.4f} "
                f"rounds={int(stats.rounds)}")


def bench_engine_serving(out):
    """Serving economics of the session engine: cold compile vs warm query
    latency, plus sustained queries/s over a streamed arrival trace.

    ``SsspEngine`` keeps sources TRACED, so one compiled program per
    K-bucket answers arbitrary source sets — the cold/warm gap here IS the
    compile amortization the engine exists for, and ``recompiles`` in the
    warm records must stay 0 (asserted by the trace counter, not inferred
    from timing). The stream section replays a ragged arrival trace
    (single queries mixed with small bursts) through submit/drain so the
    bucket coalescing policy is what's measured."""
    g = BENCH_GRAPHS["graph1-like"]()
    rng = np.random.default_rng(13)
    sh = build_shards(g, 8, enumerate_triangles=False)
    eng = SsspEngine.build(sh, SsspConfig(prune_online=False), max_bucket=16)
    for k in (1, 4, 16):
        sources = [int(s) for s in
                   rng.choice(g.n_vertices, size=k, replace=False)]
        cold = eng.solve(sources)
        out(f"engine_serving[cold][K={k}]", cold.wall_s * 1e6,
            f"compile_s={cold.compile_s:.3f} bucket={cold.bucket_k}")
        warm_ts, recompiles = [], 0
        for _ in range(3):
            res = eng.solve([int(s) for s in
                             rng.choice(g.n_vertices, size=k, replace=False)])
            warm_ts.append(res.wall_s)
            recompiles += int(res.compiled)
        t = min(warm_ts)
        out(f"engine_serving[warm][K={k}]", t * 1e6,
            f"qps={k / t:.3f} recompiles={recompiles} "
            f"amortization={cold.wall_s / t:.1f}x")
        assert recompiles == 0, "warm engine.solve must not recompile"
    # streamed arrival trace: 24 arrivals, ragged sizes 1/2/4, coalesced
    # into max_bucket batches by drain()
    trace0, batches0 = eng.trace_count, eng.batches_served
    handles = []
    t0 = time.perf_counter()
    for size in rng.choice([1, 1, 2, 4], size=24):
        handles.append(eng.submit([int(s) for s in
                                   rng.choice(g.n_vertices, size=int(size),
                                              replace=False)]))
    eng.drain()
    t = time.perf_counter() - t0
    nq = sum(len(h.sources) for h in handles)
    out(f"engine_serving[stream][{nq}q]", t * 1e6,
        f"qps={nq / t:.3f} batches={eng.batches_served - batches0} "
        f"recompiles={eng.trace_count - trace0}")


def bench_warm_start(out):
    """Warm-start economics: cold rounds/qps vs landmark-seeded rounds/qps
    vs result-cache hits (the `warm_start` section of BENCH_sssp.json).

    Three tiers of the cache hierarchy on the same shards:
      - cold: the baseline full-wave solve
      - landmark: repeated sources seeded from the landmark cache — the
        seed IS the pivot's solved fixpoint, so quiescence is confirmed in
        ~1 round instead of re-propagating the wave (bit-identical dist,
        asserted)
      - cache_hit: exact repeats served from the result LRU with ZERO
        rounds and no compiled program at all
    Warm paths must not recompile: the second warm solve's `compiled` flag
    is asserted False (same trace-counter discipline as engine_serving)."""
    for name in ("graph1-like", "graph2-like"):
        g = BENCH_GRAPHS[name]()
        rng = np.random.default_rng(23)
        sh = build_shards(g, 8, enumerate_triangles=False)
        # pivot from vertices WITH out-edges: an isolated source solves in
        # one round cold, leaving no rounds for the warm path to save
        candidates = np.unique(np.asarray(g.src))
        pivots = sorted(int(s) for s in
                        rng.choice(candidates, size=4, replace=False))
        cold_eng = SsspEngine.build(sh, SsspConfig(prune_online=False))
        warm_eng = SsspEngine.build(
            sh, SsspConfig(prune_online=False, warm_start="landmark"),
            result_cache=32)
        warm_eng.precompute_landmarks(pivots)
        for k in (1, 4):
            sources = pivots[:k]
            cold_eng.solve(sources)                       # warmup + compile
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                cold = cold_eng.solve(sources)
                ts.append(time.perf_counter() - t0)
            t_cold = min(ts)
            out(f"warm_start[{name}][cold][K={k}]", t_cold * 1e6,
                f"qps={k / t_cold:.3f} rounds={int(cold.stats.rounds)}")
            # landmark-seeded repeats (bypass the LRU: seed-path rounds)
            warm_eng._solve_batch(tuple(sources))         # warmup + compile
            ts, recompiles = [], 0
            for _ in range(3):
                t0 = time.perf_counter()
                warm = warm_eng._solve_batch(tuple(sources))
                ts.append(time.perf_counter() - t0)
                recompiles += int(warm.compiled)
            t_warm = min(ts)
            assert recompiles == 0, "warm landmark solves must not recompile"
            assert np.array_equal(cold.dist, warm.dist), \
                "warm-started solve must be bit-identical to cold"
            assert int(warm.stats.rounds) <= int(cold.stats.rounds)
            if int(cold.stats.rounds) > 2:
                # graphs with real round depth (the road grid always; the
                # rmat graphs at full scale) must show a STRICT decrease
                assert int(warm.stats.rounds) < int(cold.stats.rounds), \
                    "landmark seeding must cut rounds on repeated sources"
            out(f"warm_start[{name}][landmark][K={k}]", t_warm * 1e6,
                f"qps={k / t_warm:.3f} rounds={int(warm.stats.rounds)} "
                f"cold_rounds={int(cold.stats.rounds)} "
                f"speedup={t_cold / t_warm:.1f}x")
        # exact repeats: the result LRU answers without any solve
        warm_eng.solve(pivots)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            hit = warm_eng.solve(pivots)
            ts.append(time.perf_counter() - t0)
        t_hit = min(ts)
        assert hit.cache_hits == len(pivots) and int(hit.stats.rounds) == 0
        out(f"warm_start[{name}][cache_hit][K={len(pivots)}]", t_hit * 1e6,
            f"qps={len(pivots) / t_hit:.3f} rounds=0 "
            f"hits={hit.cache_hits}")


def bench_faults(out):
    """Resilience economics: rounds-to-converge and resend overhead vs
    drop rate, under anti-entropy healing with the toka3 timeout detector.

    Every faulted record carries TWO hard asserts — distances bit-identical
    to the fault-free solve and ``status == "converged"`` (the engine's
    fixpoint certificate, not the detector's word) — so this section is a
    correctness gate for the whole fault/recovery/termination stack, not
    just a perf artifact. ``resend_overhead`` is the fraction of all sent
    messages that were anti-entropy retransmissions: the price of healing
    at that drop rate."""
    g = BENCH_GRAPHS["graph2-like"]()    # road grid: real round depth
    source = int(g.src[0])
    sh = build_shards(g, 8, enumerate_triangles=False)
    base_eng = SsspEngine.build(sh, SsspConfig(prune_online=False))
    base = base_eng.solve(source)
    out(f"faults[drop=0.0][toka0]", base.wall_s * 1e6,
        f"rounds={int(base.stats.rounds)} resends=0 overhead=0.00 "
        f"status={base.status}")
    for drop in (0.1, 0.3):
        for toka in ("toka0", "toka3"):
            cfg = SsspConfig(prune_online=False, toka=toka,
                             faults=FaultPlan(drop=drop, seed=5,
                                              resend_period=4))
            eng = SsspEngine.build(sh, cfg)
            res = eng.solve(source)
            assert np.array_equal(res.dist, base.dist), \
                f"faulted solve (drop={drop}, {toka}) lost bit-identity"
            assert res.status == "converged", \
                f"faulted solve (drop={drop}, {toka}) did not certify"
            overhead = int(res.stats.resends) / max(int(res.stats.msgs_sent),
                                                    1)
            out(f"faults[drop={drop}][{toka}]", res.wall_s * 1e6,
                f"rounds={int(res.stats.rounds)} "
                f"base_rounds={int(base.stats.rounds)} "
                f"resends={int(res.stats.resends)} "
                f"overhead={overhead:.2f} status={res.status}")


def bench_async_scaling(out):
    """Sync vs deferred exchange across partition counts: the paper's
    asynchronous-mode claim (Fig 1/2 analog) as measured round/traffic
    numbers plus a clearly-labeled MODELED speedup.

    Measured per (graph, P): rounds, sim wall time, stale merges, overlap
    fraction, and wire bytes for the synchronous ``bucket`` baseline, the
    double-buffered ``async`` exchange, and the ring-streaming
    ``async_ppermute`` (all at P >= 2 — at P=1 a deferred exchange is
    degenerate: nothing ever rides the wire) — every async solve
    hard-asserted bit-identical to sync. The sim cannot time real overlap
    (its lock-step emulation serializes on one CPU, and its wall time is
    per-round dispatch overhead, not transport), so ``modeled_speedup``
    prices each run's MEASURED structure — rounds, per-round relaxations,
    per-round wire bytes, overlap fraction — with an alpha-beta transport
    model at accelerator constants:

      C        = (relaxations / rounds / P) / R        per-shard compute
      sync rnd = C_s + alpha*(1 + log2 P) + beta*B     (tree barrier)
      async rnd= of*max(C_a, h) + (1 - of)*(C_a + h),
                 h = alpha + beta*B                    (neighbor hop)

    alpha=5us (collective dispatch latency), beta=0.1ns/B, R=10M
    relaxations/s (the interpret-mode kernels' own order of magnitude;
    on the megakernel's accounting, round time at these graph scales IS
    the per-round latency, which is exactly what deferring the collective
    removes). The async speedup must be monotone non-decreasing in P on
    at least one bench graph (hard assert): more partitions means more
    barrier latency for sync to pay and less per-shard compute to pay it
    behind, which is the whole argument for the asynchronous mode."""
    ALPHA, BETA, R = 5e-6, 1e-10, 1e7
    monotone = []
    for name, build in BENCH_GRAPHS.items():
        g = build()
        source = int(g.src[0])
        speedups = []
        for p in (2, 4, 8):
            sh = build_shards(g, p, enumerate_triangles=False)
            base, s_sync, t_sync = _solve_timed(
                sh, source, SsspConfig(prune_online=False))
            r_sync = int(s_sync.rounds)
            c_sync = int(s_sync.relaxations) / r_sync / p / R
            t_sync_model = r_sync * (c_sync + ALPHA * (1 + np.log2(p)))
            for ex in ("async", "async_ppermute"):
                cfg = SsspConfig(prune_online=False, exchange=ex)
                dist, s, t = _solve_timed(sh, source, cfg)
                assert np.array_equal(np.asarray(dist), np.asarray(base)), \
                    (name, p, ex, "async exchange lost bit-identity")
                r = int(s.rounds)
                of = int(s.overlap_rounds) / r
                bpr = int(s.bytes_moved) / r
                c_async = int(s.relaxations) / r / p / R
                hop = ALPHA + BETA * bpr
                t_async_model = r * (of * max(c_async, hop)
                                     + (1 - of) * (c_async + hop))
                speedup = (t_sync_model + BETA * bpr * r_sync) \
                    / t_async_model
                if ex == "async":
                    speedups.append(speedup)
                out(f"async_scaling[{name}][{ex}][P={p}]", t * 1e6,
                    f"modeled_speedup={speedup:.2f} overlap={of:.2f} "
                    f"rounds={r} extra_rounds={r - r_sync} "
                    f"stale={int(np.asarray(s.stale_merges).sum())} "
                    f"bytes={int(s.bytes_moved)} "
                    f"sync_wall_us={t_sync * 1e6:.0f}")
        monotone.append(all(b >= a - 1e-9
                            for a, b in zip(speedups, speedups[1:])))
    assert any(monotone), (
        "modeled async speedup must be monotone non-decreasing in P on at "
        "least one bench graph")


def _pallas_grids(fn, *args):
    """All pallas_call grids inside ``fn``'s jaxpr (recursing through
    subjaxprs). The grid is the kernel's TILE-LOAD schedule: its product
    is how many layout tiles one dispatch streams from HBM, which is the
    cost that matters on a real accelerator (interpret-mode wall time on
    CPU executes every vector lane and cannot see it)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for v in eqn.params.values():
                if isinstance(v, jex_core.ClosedJaxpr):
                    walk(v.jaxpr)
                elif isinstance(v, jex_core.Jaxpr):
                    walk(v)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _grid_steps(grids):
    return sum(int(np.prod(g)) for g in grids) if grids else 0


def check_phase_grids():
    """Hard regression guards on the Pallas grids of the batched send and
    merge kernels and of the fused megakernel (``round='fused'``): each
    grid must be K-INDEPENDENT, identical at K=1 and K=16. The 85 ms cliff
    this replaced came from send/merge grids of ``(tiles, chunks, K)``
    (tile loads x16 at K=16). Interpret-mode wall time executes every
    vector lane on the CPU and cannot see tile reuse; the grid, extracted
    from the jaxpr, can. Grids follow from shapes alone, so the initial
    carry serves. Device time per phase comes from a profiler trace of a
    chip run (the round's ``jax.named_scope`` phases), not from here."""
    g = BENCH_GRAPHS["graph1-like"]()
    rng = np.random.default_rng(11)
    sh = build_shards(g, 8, enumerate_triangles=False)
    cfg = SsspConfig(prune_online=False, send_backend="pallas",
                     merge_backend="pallas", local_solver="pallas")
    fcfg = SsspConfig(prune_online=False, round="fused")
    fns, ffns = sim_phase_fns(sh, cfg), sim_phase_fns(sh, fcfg)
    grids_by_k = {}
    for k in (1, 16):
        sources = sorted(int(s) for s in
                         rng.choice(g.n_vertices, size=k, replace=False))
        carry = sssp_mod._init_carry(sh, sources, cfg, rank=None,
                                     vmapped=True)
        send_args = (carry.dist, carry.pruned, carry.last_sent)
        incoming = fns["exchange"](fns["send"](*send_args)[0])
        fcarry = sssp_mod._init_carry(sh, sources, fcfg, rank=None,
                                      vmapped=True)
        live = ~fcarry.done
        grids_by_k[k] = {
            "send": _pallas_grids(fns["send"], *send_args),
            "merge": _pallas_grids(fns["merge"], carry.dist, incoming),
            "fused": _pallas_grids(
                ffns["fused"], fcarry.dist, fcarry.active & live[..., None],
                live, fcarry.incoming, fcarry.last_sent, fcarry.pruned),
        }
    for phase in ("send", "merge", "fused"):
        g1, g16 = grids_by_k[1][phase], grids_by_k[16][phase]
        assert g1 == g16, (
            f"pallas {phase} grid scales with K ({g1} at K=1 vs {g16} at "
            f"K=16) — per-query tile re-streaming is back")
        assert g1, f"pallas {phase} traced no pallas_call (fallback?)"


def bench_scale(out, full=False):
    """Million-edge scale: MTEPS + measured bytes-per-edge per workload
    preset (the `scale` section of BENCH_sssp.json).

    Every preset is STREAM-built into ragged CSR-chunked shards — the
    memory path a 10M-edge graph must take. The 1e5 preset is additionally
    batch-built dense and solved both ways with hard asserts: ragged
    layout bytes strictly below dense, and distances bit-identical (the
    acceptance gate for the ragged layout family). The 1e6 preset is
    stream-built and measured (build time + bytes/edge) but solved only
    at `full=True`; 1e7 is `full=True` only — interpret-mode kernels are
    CPU-emulated, so its value is the LAYOUT numbers, not wall time."""
    rng = np.random.default_rng(31)
    # chunk size scales with the graph: EB rounding waste is ~EB/2 per
    # occupied tile, so small presets need small chunks to stay near the
    # CSR ideal while big ones amortize a larger (more kernel-friendly) EB
    TILES = {"scale-1e5": 128, "scale-1e6": 256, "scale-1e7": 512}
    for name in ("scale-1e5", "scale-1e6", "scale-1e7"):
        if name != "scale-1e5" and not full:
            if name == "scale-1e7":
                continue
        eb = TILES[name]
        tiles = dict(relax_eb=eb, send_eb=eb, merge_eb=eb)
        n, chunks = preset_edge_stream(name)
        P = 8
        t0 = time.perf_counter()
        sh = build_shards_stream(chunks, n, P, **tiles)
        t_build = time.perf_counter() - t0
        lb = sh.layout_bytes()
        out(f"scale[{name}][build]", t_build * 1e6,
            f"edges={lb['n_edges']} bytes_per_edge={lb['bytes_per_edge']:.2f} "
            f"ideal={lb['ideal_bytes_per_edge']:.1f} "
            f"ragged_bytes={lb['total_bytes']} dense_bytes={lb['dense_bytes']}")
        assert lb["total_bytes"] <= lb["dense_bytes"], (
            f"{name}: ragged layout ({lb['total_bytes']} B) larger than the "
            f"dense layout it replaces ({lb['dense_bytes']} B)")
        assert lb["bytes_per_edge"] <= 1.5 * lb["ideal_bytes_per_edge"], (
            f"{name}: measured {lb['bytes_per_edge']:.2f} B/edge exceeds "
            f"1.5x the CSR ideal ({lb['ideal_bytes_per_edge']:.1f} B/edge) "
            "— chunk rounding waste regressed")
        if name == "scale-1e5":
            # acceptance gate: dense twin must agree bit-for-bit, and the
            # ragged layout must be strictly smaller on this skewed graph.
            # The twin is materialized from the SAME stream (the streaming
            # generator's counter-keyed RNG differs from rmat_graph's
            # sequential draw, so preset_graph would be a different graph).
            _, chunks2 = preset_edge_stream(name)
            cs = list(chunks2)
            from repro.graph.structure import csr_from_coo
            g = csr_from_coo(np.concatenate([c[0] for c in cs]),
                             np.concatenate([c[1] for c in cs]),
                             np.concatenate([c[2] for c in cs]), n)
            dense = build_shards(g, P, enumerate_triangles=False, **tiles)
            dlb = dense.layout_bytes()
            assert lb["total_bytes"] < dlb["total_bytes"], (
                "ragged layout not smaller than dense on RMAT "
                f"({lb['total_bytes']} vs {dlb['total_bytes']} B)")
            sources = sorted(int(s) for s in
                             rng.choice(np.unique(np.asarray(g.src)), size=4,
                                        replace=False))
            cfg = SsspConfig(prune_online=False, local_solver="pallas",
                             send_backend="pallas", merge_backend="pallas")
            d_r, s_r = solve_sim_batch(sh, sources, cfg)
            d_d, s_d = solve_sim_batch(dense, sources, cfg)
            assert np.array_equal(np.asarray(d_r), np.asarray(d_d)), \
                "ragged solve lost bit-identity with dense"
            ts = []
            for _ in range(2):
                t0 = time.perf_counter()
                _, s_r = solve_sim_batch(sh, sources, cfg)
                ts.append(time.perf_counter() - t0)
            t = min(ts)
            mteps = int(s_r.relaxations) / t / 1e6
            out(f"scale[{name}][solve][K=4]", t * 1e6,
                f"mteps={mteps:.4f} rounds={int(s_r.rounds)} "
                f"ragged_vs_dense=bit-identical "
                f"mem_ratio={lb['total_bytes'] / dlb['total_bytes']:.3f}")
        elif full and name == "scale-1e6":
            # 1e7 stays build-only even at full: interpret-mode kernels
            # emulate every vector lane on CPU, so its solve measures the
            # emulator, not the layout
            source = int(np.asarray(sh.loc_src)[0, 0])
            cfg = SsspConfig(prune_online=False)
            t0 = time.perf_counter()
            _, stats = solve_sim(sh, source, cfg)
            t = time.perf_counter() - t0
            mteps = int(stats.relaxations) / t / 1e6
            out(f"scale[{name}][solve]", t * 1e6,
                f"mteps={mteps:.4f} rounds={int(stats.rounds)}")


# ------------------------------------------------------- regression gate ----

def check_against(baseline_path, records):
    """Compare this run's records against a committed baseline json.

    Fails (returns a list of violation strings) when a record present in
    BOTH runs regresses: MTEPS down more than 25%, or ANY increase in a
    recompile counter (recompiles are a correctness property of the warm
    paths — one is one too many). Records only one side has are ignored,
    so adding or retiring sections never breaks the gate."""
    import json as _json
    with open(baseline_path) as f:
        base = {r["name"]: r for r in _json.load(f)["benchmarks"]}
    _RECOMP_RE = re.compile(r"recompiles=(\d+)")
    violations = []
    for rec in records:
        b = base.get(rec["name"])
        if b is None:
            continue
        if "mteps" in rec and "mteps" in b and b["mteps"] > 0:
            ratio = rec["mteps"] / b["mteps"]
            if ratio < 0.75:
                violations.append(
                    f"{rec['name']}: MTEPS {b['mteps']:.4f} -> "
                    f"{rec['mteps']:.4f} ({ratio:.0%} of baseline, "
                    "floor 75%)")
        mb = _RECOMP_RE.search(b.get("derived", ""))
        mr = _RECOMP_RE.search(rec.get("derived", ""))
        if mb and mr and int(mr.group(1)) > int(mb.group(1)):
            violations.append(
                f"{rec['name']}: recompiles {mb.group(1)} -> {mr.group(1)}")
    return violations


def run_all(out):
    bench_scaling(out)
    bench_trishla(out)
    bench_toka(out)
    bench_local_solver(out)
    bench_pallas_solver(out)
    bench_batch_throughput(out)
    bench_engine_serving(out)
    bench_warm_start(out)
    bench_faults(out)
    bench_async_scaling(out)
    check_phase_grids()
    bench_scale(out)


# ---------------------------------------------------------------- smoke ----

SMOKE_GRAPHS = {
    # same shapes as BENCH_GRAPHS, scaled to CI seconds: the smoke profile
    # exists to catch wiring rot (recompiles on warm paths, broken bench
    # sections), not to track performance numbers.
    "graph1-like": lambda: rmat_graph(scale=8, edge_factor=2, seed=1),
    "graph2-like": lambda: road_grid_graph(side=16, seed=2),
    "graph3-like": lambda: rmat_graph(scale=7, edge_factor=8, seed=3),
}


def run_smoke(out):
    """CI-sized subset: the engine-serving, warm-start, faults and
    async-scaling sections and the Pallas grid guards on tiny graphs.
    These carry hard asserts (recompiles == 0 on warm paths, warm
    bit-identity, zero-round cache hits, faulted + async bit-identity,
    monotone modeled async speedup, K-independent send/merge/fused
    grids), so the smoke job is a correctness gate as well as an
    artifact producer."""
    global BENCH_GRAPHS
    full = BENCH_GRAPHS
    BENCH_GRAPHS = SMOKE_GRAPHS
    # distinct record names: smoke numbers must never clobber the tracked
    # full-size perf trajectory when the merged json is written locally
    def smoke_out(name, us, derived=""):
        out(f"smoke/{name}", us, derived)
    try:
        bench_engine_serving(smoke_out)
        bench_warm_start(smoke_out)
        bench_faults(smoke_out)
        bench_async_scaling(smoke_out)
        check_phase_grids()
    finally:
        BENCH_GRAPHS = full


def main(argv=None):
    import argparse
    import os
    import sys

    # script mode (`python benchmarks/sssp_bench.py`) puts benchmarks/ on
    # sys.path, not the repo root the `benchmarks.run` import needs
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)

    p = argparse.ArgumentParser(description="SP-Async SSSP benchmarks")
    p.add_argument("--smoke", action="store_true",
                   help="tiny CI profile (seconds): engine_serving + "
                        "warm_start sections with recompile/bit-identity "
                        "asserts")
    p.add_argument("--scale", action="store_true",
                   help="only the scale section (stream-built ragged "
                        "workload presets: MTEPS + bytes-per-edge, with "
                        "the 1e5 ragged-vs-dense bit-identity gate)")
    p.add_argument("--scale-full", action="store_true",
                   help="scale section including the 1e6 solve and the "
                        "1e7 stream build (minutes; nightly profile)")
    p.add_argument("--check-against", default=None, metavar="PATH",
                   help="committed baseline json to gate this run against: "
                        "fail on any shared record losing >25%% MTEPS or "
                        "gaining recompiles")
    p.add_argument("--out", default=None,
                   help="output json (default: BENCH_sssp.json for the "
                        "full run; the gitignored BENCH_sssp.smoke.json "
                        "for --smoke/--scale, so local smoke runs never "
                        "dirty the tracked perf trajectory)")
    args = p.parse_args(argv)
    from benchmarks.run import _RECORDS, _out, _write_json
    if args.scale or args.scale_full:
        bench_scale(_out, full=args.scale_full)
        _write_json(args.out or "BENCH_sssp.smoke.json")
    elif args.smoke:
        run_smoke(_out)
        _write_json(args.out or "BENCH_sssp.smoke.json")
    else:
        run_all(_out)
        _write_json(args.out or "BENCH_sssp.json")
    if args.check_against:
        violations = check_against(args.check_against, _RECORDS)
        if violations:
            print("# PERF REGRESSION vs", args.check_against)
            for v in violations:
                print("#  ", v)
            sys.exit(1)
        print(f"# perf gate vs {args.check_against}: ok")


if __name__ == "__main__":
    main()
