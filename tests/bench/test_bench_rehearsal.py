"""A CPU rehearsal of every benchmark cell at a tiny size, through the
harness's own path (``run_cell``): generation, the program's shard build,
warm-up, the cell's loop, the reference check, the metric readers and the
result line. Only the TPU check is skipped, here in the test."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import run_tiny, tiny_cell

from bench import run


def cells():
    with open(f"{run.ROOT}/BENCHMARK.json") as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", cells())
def test_cell_rehearses_on_cpu(name, tmp_path):
    cell = tiny_cell(name)
    res = run_tiny(cell, str(tmp_path / "trace"))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["metrics"]["setup_s"]["unit"] == "s"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"unconverged", "reach_mismatch",
                                  "max_rel_gap"}
    json.dumps(res, allow_nan=False)


def test_traced_run_reports_per_layer_metrics(tmp_path):
    cell = tiny_cell("paper-graph1.batch")
    res = run_tiny(cell, str(tmp_path / "trace"), trace=True)
    assert res["correct"] is True
    # no device plane on the CPU: the trace readers find nothing and are
    # left out; the counter readers still report
    assert set(res["metrics"]) == {"rounds_per_batch.batch",
                                   "relax_per_edge.batch"}
    assert res["metrics"]["rounds_per_batch.batch"]["value"] >= 1
    assert "busy_s" not in res["device"]


def test_traced_serve_run_reports_the_queue_wait(tmp_path, monkeypatch):
    from bench import phases
    cell = tiny_cell("paper-graph1.serve")
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setattr(phases, "TRACE_ROOT", trace_dir)
    res = run_tiny(cell, trace_dir, trace=True)
    assert res["correct"] is True
    # the engine's spans are on the host, so the queue wait reads on the
    # CPU too; the device's metrics find no device plane
    assert set(res["metrics"]) == {"batch_fill.serve",
                                   "queue_wait_p85_ms.serve",
                                   "latency_p85_ms.serve"}
    wait = res["metrics"]["queue_wait_p85_ms.serve"]
    assert wait["unit"] == "ms" and 0.0 <= wait["value"] < 60e3
    tail = res["metrics"]["latency_p85_ms.serve"]
    assert tail["unit"] == "ms" and wait["value"] <= tail["value"] < 60e3


RING = '''"""A ring: vertex i joined to i + 1, both ways, weights from the seed."""
import numpy as np


def generate(seed, vertices, weight_low=1.0, weight_high=20.0):
    src = np.arange(vertices, dtype=np.int64)
    dst = (src + 1) % vertices
    w = np.random.default_rng(seed % (1 << 64)).uniform(
        weight_low, weight_high, vertices).astype(np.float32)
    return vertices, [(np.concatenate([src, dst]), np.concatenate([dst, src]),
                       np.concatenate([w, w]))]


def tiny(graph):
    return dict(graph, vertices=min(graph["vertices"], 256))
'''

MSGS_PER_ROUND = '''"""Messages sent per round: a program counter read by name."""


def read(run):
    b = run.window.batches
    sent = [x.counters.get("msgs_sent") for x in b]
    rounds = sum(x.rounds for x in b)
    return sum(sent) / rounds if b and None not in sent and rounds else None
'''

REHEARSE = '''import json, os, sys
sys.path.insert(0, os.path.join(os.getcwd(), "tests", "bench"))
from bench_tiny import run_tiny, tiny_cell
res = run_tiny(tiny_cell("ring.batch"),
               os.path.join(os.getcwd(), ".bench_trace", "ring.batch"),
               trace=True)
print(json.dumps(res))
'''


def _digests(root) -> dict:
    """sha256 of every file under ``root``'s ``bench/`` and ``tests/``."""
    out = {}
    for d, dirs, files in (w for top in ("bench", "tests")
                           for w in os.walk(os.path.join(root, top))):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_graph_family_cell_and_counter_metric_need_new_files_only(
        tmp_path):
    """A copy of the benchmark takes a graph family, a configuration, a
    cell and a metric that reads a program counter through new files and
    new ``BENCHMARK.json`` entries alone, and rehearses the cell on the
    CPU through ``run_cell``, with no file that was there touched."""
    copy = tmp_path / "checkout"
    skip = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(run.ROOT, "bench"), copy / "bench",
                    ignore=skip)
    shutil.copytree(os.path.join(run.ROOT, "tests", "bench"),
                    copy / "tests" / "bench", ignore=skip)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    before = _digests(copy)
    (copy / "bench" / "generators" / "ring.py").write_text(RING)
    (copy / "bench" / "metrics" / "msgs_per_round.batch.py").write_text(
        MSGS_PER_ROUND)
    (copy / "rehearse.py").write_text(REHEARSE)
    with open(os.path.join(run.ROOT, "bench", "configs",
                           "paper-graph1.json")) as f:
        config = dict(json.load(f), generator="ring",
                      graph={"vertices": 1 << 20}, reduced={}, assumed=[])
    (copy / "bench" / "configs" / "ring.json").write_text(json.dumps(config))
    added = json.loads(json.dumps(bm))
    added["configs"].append({
        "name": "ring", "source": "a ring", "file": "bench/configs/ring.json",
        "reduced": [], "why": "the longest hop diameter"})
    added["workloads"].append({
        "name": "ring.batch", "config": "ring", "traffic": "batch",
        "chips": 1, "why": "a closed loop of K=16 batches on a ring"})
    added["per_layer"].append({
        "name": "msgs_per_round.batch", "unit": "msgs/round",
        "better": "lower", "source": "program_counter",
        "layer": "round pipeline", "moves": "teps",
        "workloads": ["ring.batch"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(added))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(run.ROOT, "src"))
    p = subprocess.run([sys.executable, "rehearse.py"], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # the counter metric is the only per-layer metric the new cell lists
    assert set(res["metrics"]) == {"msgs_per_round.batch"}
    assert res["metrics"]["msgs_per_round.batch"]["value"] > 0
    after = _digests(copy)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "bench/generators/ring.py", "bench/metrics/msgs_per_round.batch.py",
        "bench/configs/ring.json"}
