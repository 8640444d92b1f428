"""A CPU rehearsal of every benchmark cell at a tiny size, through the
harness's own path (``run_cell``): generation, the program's shard build,
warm-up, the cell's loop, the reference check, the metric readers and the
result line. Only the TPU check is skipped, here in the test."""
from __future__ import annotations

import json

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
