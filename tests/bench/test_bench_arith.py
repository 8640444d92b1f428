"""The benchmark's own arithmetic, on the CPU: percentiles, the teps count,
the window's close, the trace reduction, lookups by name, the reference,
and the refusal to run anywhere but a TPU."""
from __future__ import annotations

import hashlib
import json
import math
import os
import shutil
import subprocess
import sys
import types
from typing import NamedTuple

import numpy as np
import pytest

from bench_tiny import ROOT

from bench import graphs, loads, reference, run, stats
from bench import trace as tr


# ---------------------------------------------------------- latency ----

def test_percentile_is_nearest_rank():
    v = [5.0, 1.0, 4.0, 2.0, 3.0, 10.0, 9.0, 8.0, 7.0, 6.0]
    assert stats.percentile(v, 50) == 5.0
    assert stats.percentile(v, 90) == 9.0
    assert stats.percentile(v, 100) == 10.0
    assert stats.percentile([3.0], 90) == 3.0


def test_failed_queries_count_as_infinite():
    lat = [0.1] * 8 + [math.inf] * 2
    assert stats.percentile(lat, 50) == 0.1
    assert stats.percentile(lat, 90) == math.inf
    assert stats.percentile([0.1] * 9 + [math.inf], 90) == 0.1


# ------------------------------------------------- fake engine, clock ----

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeStats(NamedTuple):
    rounds: int
    relaxations: int
    msgs_sent: int
    q_rounds: np.ndarray = None


class FakeEngine:
    """Answers each query with the reference's row; every solve or drain
    takes ``cost`` seconds of the fake clock."""

    max_bucket = 16

    def __init__(self, g, clock, cost=1.0, bad=()):
        self.g, self.clock, self.cost, self.bad = g, clock, cost, set(bad)
        self.batches_served = 0
        self.pending = []

    def solve(self, srcs):
        self.clock.t += self.cost
        self.batches_served += 1
        k = len(srcs)
        kb = 1 << (k - 1).bit_length()
        return types.SimpleNamespace(
            sources=tuple(int(s) for s in srcs), bucket_k=kb,
            wall_s=self.clock.t,
            dist=np.stack([reference.shortest_paths(self.g, s)
                           for s in srcs]).astype(np.float32),
            q_converged=np.array([int(s) not in self.bad for s in srcs]),
            compiled=False,
            stats=FakeStats(rounds=3, relaxations=7 * k, msgs_sent=5 * k,
                            q_rounds=np.full(k, 3)))

    def submit(self, s):
        self.pending.append(s)
        return len(self.pending)

    def drain(self):
        out = []
        while self.pending:
            batch, self.pending = self.pending[:16], self.pending[16:]
            res = self.solve(batch)
            for i, s in enumerate(batch):
                out.append(types.SimpleNamespace(
                    sources=(s,), bucket_k=res.bucket_k, wall_s=res.wall_s,
                    compiled=res.compiled,
                    dist=res.dist[i:i + 1],
                    q_converged=res.q_converged[i:i + 1], stats=res.stats))
        return out


def two_components():
    """Vertices 0-1-2 in a path, 3-4 joined, 5 alone: out-degrees
    1, 2, 1, 1, 1, 0."""
    src = np.array([0, 1, 3])
    dst = np.array([1, 2, 4])
    w = np.array([1.5, 2.0, 3.0], np.float32)
    chunk = (np.r_[src, dst], np.r_[dst, src], np.r_[w, w])
    return graphs.simple_graph(6, [chunk])


def test_teps_counts_out_edges_of_reached_vertices(monkeypatch):
    g = two_components()
    assert g.out_degree.tolist() == [1, 2, 1, 1, 1, 0]
    clock = FakeClock()
    monkeypatch.setattr(loads, "perf_counter", clock)
    eligible = np.flatnonzero(g.out_degree)
    sample = loads.Reservoir(2, loads.rng_for(1, 3))
    w = loads.closed_loop(FakeEngine(g, clock), {"batch": 2}, eligible,
                          g.out_degree, 0.5, 1, sample)
    (b,) = w.batches
    # each source reaches its component: 4 out-edges in {0,1,2}, 2 in {3,4}
    per = {0: 4, 1: 4, 2: 4, 3: 2, 4: 2}
    srcs = [s for s in sample.sources]
    assert b.reached_edges == sum(per[s] for s in srcs)
    assert stats.teps(w) == b.reached_edges / 1.0


def test_window_closes_at_first_batch_after_seconds(monkeypatch):
    g = two_components()
    clock = FakeClock()
    monkeypatch.setattr(loads, "perf_counter", clock)
    w = loads.closed_loop(FakeEngine(g, clock), {"batch": 1},
                          np.flatnonzero(g.out_degree), g.out_degree, 2.5, 1,
                          loads.Reservoir(1, loads.rng_for(1, 3)))
    assert len(w.batches) == 3
    assert w.end - w.start == 3.0
    assert w.attempted == 3 and w.unconverged == 0


def test_open_loop_times_from_due_and_counts_failures(monkeypatch):
    g = two_components()
    clock = FakeClock()
    monkeypatch.setattr(loads, "perf_counter", clock)
    monkeypatch.setattr(loads, "sleep",
                        lambda s: setattr(clock, "t", clock.t + s + 1e-9))
    eligible = np.flatnonzero(g.out_degree)
    traffic = {"loop": "open", "rate_qps": 4.0}
    due, srcs = loads.arrivals(traffic, eligible, 5.0, 9)
    assert len(due) == 20 and np.all(np.diff(due) >= 0)
    bad = {int(srcs[0])}
    w = loads.open_loop(FakeEngine(g, clock, cost=0.5, bad=bad), traffic,
                        eligible, g.out_degree, 5.0, 9,
                        loads.Reservoir(4, loads.rng_for(9, 3)))
    assert w.attempted == 20
    assert w.unconverged == sum(int(s) in bad for s in srcs)
    lat = np.asarray(w.latency_s)
    assert np.isinf(lat[[int(s) in bad for s in srcs]]).all()
    ok = np.isfinite(lat)
    # answered by a drain of 0.5 s that started no earlier than the due time
    assert np.all(lat[ok] >= 0.5) and np.all(lat[ok] < 5.0)
    assert sum(b.real for b in w.batches) == 20
    assert all(b.lanes >= b.real for b in w.batches)
    assert w.backlog_at_close == 0


def test_backlog_counts_queries_still_queued_at_close(monkeypatch):
    g = two_components()
    clock = FakeClock()
    monkeypatch.setattr(loads, "perf_counter", clock)
    monkeypatch.setattr(loads, "sleep",
                        lambda s: setattr(clock, "t", clock.t + s + 1e-9))
    eligible = np.flatnonzero(g.out_degree)
    traffic = {"loop": "open", "rate_qps": 8.0}
    due, _ = loads.arrivals(traffic, eligible, 4.0, 3)
    # one drain takes 3 s: the first starts at the first due time, the
    # second at 3 s past it, the third after the window's close
    w = loads.open_loop(FakeEngine(g, clock, cost=3.0), traffic, eligible,
                        g.out_degree, 4.0, 3,
                        loads.Reservoir(1, loads.rng_for(3, 3)))
    t2 = due[0] + 3.0           # start of the second drain
    t3 = t2 + 3.0               # start of the third, past the close
    assert t3 >= 4.0
    assert w.backlog_at_close == int(np.count_nonzero(due > t2))

def test_reservoir_is_deterministic_and_spreads():
    picks = []
    for _ in range(2):
        r = loads.Reservoir(5, loads.rng_for(2**31 + 5, 3))
        for i in range(1000):
            r.offer([2 * i, 2 * i + 1], np.array([[2.0 * i], [2.0 * i + 1]]))
        picks.append(sorted(r.sources))
        assert r.rows[:, 0].tolist() == [float(s) for s in r.sources]
    assert picks[0] == picks[1]
    assert len(set(picks[0])) == 10 and max(picks[0]) > 200
    assert all(s % 2 == 0 and s + 1 in picks[0]
               for s in picks[0][::2])   # every batch whole


# ---------------------------------------------------------- trace ----

def synthetic_trace():
    return tr.Trace(
        ops={0: [(0.0, 10.0, "fusion.1"), (5.0, 20.0, "scatter.2"),
                 (30.0, 40.0, "fusion.1")]},
        spans=[("bench.window", 0.0, 100.0), ("bench.solve", 0.0, 50.0),
               ("bench.wait_arrival", 50.0, 100.0)],
        window=(0.0, 100.0))


def test_trace_busy_union_and_idle_share():
    t = synthetic_trace()
    assert tr.merge(t.ops[0]).tolist() == [[0.0, 20.0], [30.0, 40.0]]
    assert t.window_s == pytest.approx(100e-9)
    assert tr.busy_s(t) == pytest.approx(30e-9)
    assert tr.idle_share(t) == pytest.approx(70.0)
    assert tr.host_gap(t, "bench.solve") == pytest.approx(40.0)
    assert tr.host_gap(t, "bench.drain") is None


def test_trace_breakdown_attributes_gaps_to_open_span():
    parts = tr.breakdown(synthetic_trace())
    assert parts["device_ops"] == [["fusion.1", pytest.approx(20e-9)],
                                   ["scatter.2", pytest.approx(15e-9)]]
    assert parts["idle_gaps"] == [["bench.wait_arrival", pytest.approx(60e-9)],
                                  ["bench.solve", pytest.approx(10e-9)]]


def test_trace_without_device_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.solve"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert t is not None and t.ops == {}
    assert {n for n, _, _ in t.spans} == {"bench.window", "bench.solve"}
    assert tr.idle_share(t) is None and tr.breakdown(t) is None
    assert run.load_metric("device_idle.batch")(
        run.Run(setup_s=1.0, window=None, trace=t)) is None


# ------------------------------------------------------- reference ----

def test_reference_agrees_with_scipy_dijkstra():
    sp = pytest.importorskip("scipy.sparse")
    csgraph = pytest.importorskip("scipy.sparse.csgraph")
    n, chunks = graphs.generator("rmat").generate(3, scale=9, edge_factor=4)
    g = graphs.simple_graph(n, chunks)
    src = np.repeat(np.arange(g.n), g.out_degree)
    a = sp.csr_matrix((g.w.astype(np.float64), (src, g.dst)), (g.n, g.n))
    srcs = np.flatnonzero(g.out_degree)[:3]
    ref = reference.distances(g, srcs)
    for s, row in zip(srcs, ref):
        np.testing.assert_array_equal(row, csgraph.dijkstra(a, indices=s))
        np.testing.assert_array_equal(reference.shortest_paths(g, s), row)


def test_compare_reports_each_number_beside_its_limit():
    ref = np.array([[0.0, 2.0, np.inf, 4.0]])
    limits = {"unconverged": 0, "reach_mismatch": 0, "max_rel_gap": 1e-4}
    ok = reference.compare(ref.copy(), ref, 0, limits)
    assert [c.name for c in ok] == ["unconverged", "reach_mismatch",
                                    "max_rel_gap"]
    assert all(c.ok for c in ok)
    bad = reference.compare(np.array([[0.0, 2.5, 1.0, 4.0]]), ref, 1, limits)
    got = {c.name: c.value for c in bad}
    assert got == {"unconverged": 1.0, "reach_mismatch": 1.0,
                   "max_rel_gap": 0.25}
    assert not any(c.ok for c in bad)


def test_rmat_topology_is_fixed_and_seed_draws_weights():
    rmat = graphs.generator("rmat").generate
    a = rmat(2**31 + 3, scale=8, edges=500, vertices=200)
    b = rmat(2**31 + 3, scale=8, edges=500, vertices=200)
    c = rmat(2**31 + 4, scale=8, edges=500, vertices=200)
    d = rmat(2**31 + 3, scale=8, edges=500, vertices=200, topology_seed=5)
    assert a[0] == 200
    for x, y in zip(a[1][0], b[1][0]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a[1][0][0], c[1][0][0])
    np.testing.assert_array_equal(a[1][0][1], c[1][0][1])
    assert not np.array_equal(a[1][0][2], c[1][0][2])
    assert not np.array_equal(a[1][0][0], d[1][0][0])
    src, dst, w = a[1][0]
    assert src.max() < 200 and np.all(src != dst)
    assert np.all((w >= 1.0) & (w < 20.0))


def _digest(n, chunks) -> str:
    h = hashlib.sha256(np.int64(n).tobytes())
    for chunk in chunks:
        for a in chunk:
            h.update(str(a.dtype).encode())
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# sha256 of (n, then each chunk's sources, heads and weights with their
# dtypes) as the R-MAT generator gave them before it moved to
# bench/generators/rmat.py (bench/graphs.py:rmat), for seed 2**31 + 77
RMAT_DIGESTS = {
    ("graph500-s18", "tiny"): (1024, 32498, "d2cfe37f6e3afed29d080be1f45d242316ccd5ee42a3fe0a4e03d98a89cdabc8"),
    ("graph500-s18", "full"): (262144, 8387142, "f615a1ce22c90dd03e8f6b62c3f9dfb6714170bdfd53201deccc8a2ef6f3e935"),
    ("paper-graph1", "tiny"): (768, 4568, "ffc242fefc1694b720677bc8d10a0cb7917771dab3e1ffc69d9a218703acf962"),
    ("paper-graph1", "full"): (391529, 1747330, "3a8d8bf9b41046e173e311abcb52b47e26f6b0fcd6c5134207d2b1745ef17291"),
}


@pytest.mark.parametrize("config,cut", sorted(RMAT_DIGESTS))
def test_rmat_graphs_are_bit_identical_to_before_the_move(config, cut):
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    gen = graphs.generator(cfg["generator"])
    if cut == "tiny":
        cfg["graph"] = gen.tiny(cfg["graph"])
    n, chunks = graphs.generate(cfg, 2**31 + 77)
    assert (n, sum(len(c[0]) for c in chunks), _digest(n, chunks)) == \
        RMAT_DIGESTS[config, cut]


def test_generator_tiny_cuts_to_a_rehearsal_size():
    tiny = graphs.generator("rmat").tiny
    g = {"scale": 19, "edges": 873775, "vertices": 391529, "a": 0.57}
    assert tiny(g) == {"scale": 10, "edges": 2304, "vertices": 768,
                       "a": 0.57}
    assert g["scale"] == 19             # a copy, the configuration kept
    assert tiny({"scale": 8, "edge_factor": 16}) == {"scale": 8,
                                                     "edge_factor": 16}


def test_batches_carry_the_programs_scalar_counters(monkeypatch):
    g = two_components()
    clock = FakeClock()
    monkeypatch.setattr(loads, "perf_counter", clock)
    w = loads.closed_loop(FakeEngine(g, clock), {"batch": 2},
                          np.flatnonzero(g.out_degree), g.out_degree, 1.5, 1,
                          loads.Reservoir(1, loads.rng_for(1, 3)))
    assert len(w.batches) == 2
    for b in w.batches:
        # every scalar field that is set, by name; not the per-query arrays
        assert b.counters == {"rounds": 3, "relaxations": 14, "msgs_sent": 10}
        assert (b.rounds, b.relaxations, b.compiled) == (3, 14, False)


def test_local_floor_bytes_by_hand():
    # 8 bytes a lane (tail's distance read, head's min-written), 12 of
    # edge (tail, head, weight) shared by the lanes
    assert stats.local_floor_bytes(1000, 16) == 8750.0
    assert stats.local_floor_bytes(3, 1) == 60.0
    assert stats.local_floor_bytes(0, 16) == 0.0


def test_local_roofline_by_hand(monkeypatch):
    from bench import phases
    t = phases.PhaseTrace(ops={0: [(0, 5e8, "%fusion.1 = f32[8] fusion()"),
                                   (6e8, 7e8, "%fusion.2 = f32[8] fusion()")]},
                          spans=[], window=(0, 1e9),
                          phases={0: ["sssp.local", "sssp.send"]})
    monkeypatch.setattr(phases, "for_run", lambda r: t)
    window = loads.Window(batches=[
        loads.Batch(real=16, lanes=16, rounds=3, relaxations=10**9,
                    reached_edges=1),
        loads.Batch(real=3, lanes=4, rounds=3, relaxations=5 * 10**8,
                    reached_edges=1)])
    read = run.load_metric("local_roofline.batch")
    # (1e9 * 8.75 + 5e8 * 11) bytes in 0.5 s under sssp.local at 819 GB/s
    got = read(run.Run(1.0, window, t, device_kind="TPU v5 lite"))
    assert got == pytest.approx(100 * 14.25e9 / (0.5 * 819e9))
    assert got == pytest.approx(3.47985347985348)
    assert read(run.Run(1.0, window, t, device_kind=None)) is None
    t.phases[0][0] = "sssp.send"       # no time under sssp.local
    assert read(run.Run(1.0, window, t, device_kind="TPU v5 lite")) is None


def test_open_loop_brings_the_same_arrivals_in_the_seeds_order():
    eligible = np.arange(3, 400)
    traffic = {"loop": "open", "rate_qps": 2.0}
    d1, s1 = loads.arrivals(traffic, eligible, 30.0, 1)
    d2, s2 = loads.arrivals(traffic, eligible, 30.0, 2**31 + 1)
    np.testing.assert_array_equal(d1, d2)
    assert sorted(s1) == sorted(s2) and not np.array_equal(s1, s2)


class WarmEngine:
    def __init__(self):
        self.calls = []

    def warmup(self, k):
        self.calls.append(("warmup", k))
        return 2.0

    def solve(self, srcs):
        self.calls.append(("solve", tuple(int(s) for s in srcs)))
        return types.SimpleNamespace(compile_s=1.0)


def test_warm_up_solves_sources_with_no_out_edges():
    eng = WarmEngine()
    assert run.warm(eng, 2, np.array([4, 7, 9])) == 1.0
    assert run.warm(eng, 4, np.array([4, 7, 9])) == 2.0
    assert eng.calls == [("solve", (4, 7)), ("warmup", 4)]


# ---------------------------------------------------------- lookup ----

def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [w["name"] for w in benchmark_json()["workloads"]])
def test_every_cell_resolves_by_name(name):
    cell = run.load_cell(name)
    assert cell.traffic["loop"] in loads.LOOP_FNS
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(run.load_metric(m["name"]))


def test_unknown_names_fail_clearly():
    with pytest.raises(LookupError, match="no workload named 'nope'"):
        run.load_cell("nope")
    with pytest.raises(LookupError, match="no metrics named 'nope'"):
        run.load_metric("nope")
    with pytest.raises(LookupError, match="no traffic named 'nope'"):
        run.by_name("traffic", "nope", ".json")
    with pytest.raises(LookupError, match="not in bench/peaks.json"):
        run.peaks("TPU v0 imaginary")
    with pytest.raises(LookupError, match="no generators named 'nope'"):
        graphs.generate({"generator": "nope", "graph": {}}, 1)
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# --------------------------------------------------------- refusal ----

def test_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.require_tpu(1)
    assert e.value.code != 0
    assert "needs 1 TPU" in capsys.readouterr().err


def test_checkout_of_benchmark_files_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-graph1.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
