"""Shared by the benchmark's tests: its cells cut to a size the CPU runs in
seconds, driven through the harness's own ``run_cell``."""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import graphs, run  # noqa: E402

SEED = 2**31 + 77


def tiny_cell(name: str) -> run.Cell:
    """The cell ``name`` as ``BENCHMARK.json`` defines it, with its graph
    cut by its generator's ``tiny`` to at most 2**10 vertices and its
    arrival rate to 20 queries/s."""
    cell = run.load_cell(name)
    cell.config["graph"] = graphs.generator(
        cell.config["generator"]).tiny(cell.config["graph"])
    cell.config["engine"]["sssp_config"] = {"max_rounds": 64}
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_qps"] = 20.0
    return cell


def run_tiny(cell: run.Cell, trace_dir: str, seconds: float = 1.0,
             trace: bool = False, seed: int = SEED) -> dict:
    """``run_cell`` on JAX's CPU devices: the TPU check is skipped here."""
    import jax
    return run.run_cell(cell, seed, seconds, trace, jax.devices(),
                        time.perf_counter(), trace_dir)
