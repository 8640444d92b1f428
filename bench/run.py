#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic,
graph generator and metrics are read from ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/generators/<generator>.py`` (the
configuration's ``generator``) and ``bench/metrics/<metric>.py``. So a new
cell, graph family or metric needs new files and new ``BENCHMARK.json``
entries, and no edit here; a metric reads the program's counters of each
batch by name (``Batch.counters``), so a counter the program adds needs
only a new metric file.

A run generates the graph from the seed, builds the program's shards,
compiles and warms up the cell's batch shapes (all of that is ``setup_s``),
drives the engine through a window of ``--seconds``, and then compares every
answer of a sample of the window's batches with the plain reference in
``bench/reference.py``. ``--trace 1`` records a profiler trace of the window
and reports the per-layer metrics in place of the end-to-end ones.

It runs on a TPU only. Elsewhere, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import by_name, load_module  # noqa: E402
from bench.stats import peaks  # noqa: E402


def log(*parts) -> None:
    print("bench", *parts, file=sys.stderr, flush=True)


# ------------------------------------------------------------- lookup ----

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_metric(name: str):
    """The ``read(run)`` of ``bench/metrics/<name>.py``."""
    return load_module("metrics", name).read


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list     # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: dict | None = None) -> Cell:
    bm = benchmark or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise LookupError(f"no workload named {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(ROOT, configs[w["config"]]["file"])),
        traffic=load_json(by_name("traffic", w["traffic"], ".json")),
        end_to_end=[m for m in bm["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bm["per_layer"] if reports(m, name)])


# ------------------------------------------------------------- device ----

def require_tpu(chips: int):
    """JAX's devices; exits non-zero unless they are ``chips`` TPUs or more,
    of a kind the peaks table knows."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind})")
        raise SystemExit(2)
    peaks(devices[0].device_kind)
    return devices


def enable_compile_cache() -> str:
    """The program's compilation cache (``JAX_COMPILATION_CACHE_DIR``, or
    the fixed ``.jax_cache`` in the checkout), keeping every program, so
    that a second run compiles nothing."""
    import jax

    from repro.runtime import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# ---------------------------------------------------------------- run ----

@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    setup_s: float
    window: object       # loads.Window
    trace: object        # trace.Trace, or None
    device_kind: str | None = None   # JAX's, where bench/peaks.json has it


def build(cell: Cell, seed: int):
    """Graph, shards and warmed engine of the cell, with set-up timings."""
    from repro.core import SsspConfig, SsspEngine, build_shards_stream

    from bench import graphs, loads
    eng_cfg = cell.config["engine"]
    t = time.perf_counter()
    n, chunks = graphs.generate(cell.config, seed)
    g = graphs.simple_graph(n, chunks)
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    shards = build_shards_stream(iter(chunks), n, int(eng_cfg["n_parts"]),
                                 layout=eng_cfg["layout"])
    del chunks
    t_build = time.perf_counter() - t
    t = time.perf_counter()
    engine = SsspEngine.build(shards, SsspConfig(**eng_cfg["sssp_config"]),
                              backend=eng_cfg["backend"],
                              max_bucket=int(eng_cfg["max_bucket"]))
    idle = np.flatnonzero(g.out_degree == 0)
    compile_s = sum(warm(engine, k, idle) for k in loads.warm_buckets(
        cell.traffic, engine.max_bucket))
    t_warm = time.perf_counter() - t
    log(f"graph vertices={g.n} directed_edges={g.m} "
        f"eligible_sources={int(np.count_nonzero(g.out_degree))}")
    log(f"setup_split generate_s={t_gen} build_s={t_build} "
        f"compile_s={compile_s} warmup_s={t_warm - compile_s}")
    return g, engine


def warm(engine, k: int, idle: np.ndarray) -> float:
    """Compile and run once the program of a ``k``-lane bucket; its compile
    seconds. Sources with no out-edges converge in one round, so the
    warm-up does none of a real batch's work; without ``k`` of them it is
    the engine's own warm-up, a whole solve."""
    if len(idle) < k:
        return engine.warmup(k)
    return engine.solve(idle[:k]).compile_s


def check(cell: Cell, g, window, sample) -> list:
    from bench import reference
    rows = sample.rows if sample.batches else np.zeros((0, g.n))
    ref = reference.distances(g, sample.sources) if sample.batches else rows
    return reference.compare(rows, ref, window.unconverged,
                             cell.config["check"]["limits"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_process: float, trace_dir: str) -> dict:
    """One run of ``cell``: set-up, window, check, metrics; the result."""
    import jax

    from bench import loads
    from bench import trace as trace_mod
    g, engine = build(cell, seed)
    eligible = np.flatnonzero(g.out_degree)
    sample = loads.Reservoir(int(cell.config["check"]["batches"]),
                             loads.rng_for(seed, 3))
    loop = loads.LOOP_FNS[cell.traffic["loop"]]
    setup_s = time.perf_counter() - t_process
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window = loop(engine, cell.traffic, eligible, g.out_degree, seconds,
                      seed, sample)
    finally:
        if trace:
            jax.profiler.stop_trace()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": (dev.memory_stats() or {}).get(
                  "peak_bytes_in_use")}
    if window.lateness_s:
        log(f"generator_lateness_s p50={np.median(window.lateness_s)} "
            f"max={max(window.lateness_s)} "
            f"backlog_at_close={window.backlog_at_close}")
    log(f"window_s={window.end - window.start} batches={len(window.batches)} "
        f"queries={window.attempted} "
        f"window_compiles={sum(b.compiled for b in window.batches)}")
    reduced = trace_mod.load(trace_dir) if trace else None
    kind = dev.device_kind if dev.platform == "tpu" else None
    run = Run(setup_s=setup_s, window=window, trace=reduced, device_kind=kind)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_metric(m["name"])(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": None, "attempted": window.attempted,
              "failed": window.unconverged, "metrics": metrics,
              "device": device}
    if trace and reduced is not None:
        busy = trace_mod.busy_s(reduced)
        if busy is not None:
            device["busy_s"] = busy
            device["window_s"] = reduced.window_s
        parts = trace_mod.breakdown(reduced)
        if parts is not None:
            result["breakdown"] = parts
    del engine
    checks = check(cell, g, window, sample)
    result["correct"] = all(c.ok for c in checks)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name} = {c.value} (limit {c.limit})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = require_tpu(cell.chips)
    log("compile_cache_dir", enable_compile_cache())
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_PROCESS,
                      os.path.join(ROOT, ".bench_trace", cell.name))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
