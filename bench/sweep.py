#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, by one sweep of rates in
one process, on the chip.

    python3 bench/sweep.py --workload paper-graph1.serve --seed 7 \\
        --seconds 30 --rates 2 4 6 8 12 16

The cell's graph and engine are built and warmed once; each rate then runs
the cell's open loop for ``--seconds``. A rate is sustained when the backlog
at the window's close (queries due whose drain had not started) is no
larger than one bucket. The sweep stops after two rates in a row that are not. The
serve traffic's ``rate_qps`` is set by hand to 0.8 of the highest rate
sustained.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    run.require_tpu(cell.chips)
    run.enable_compile_cache()
    import numpy as np

    from bench import loads
    from bench.stats import percentile
    g, engine = run.build(cell, args.seed)
    eligible = np.flatnonzero(g.out_degree)
    misses = 0
    for i, rate in enumerate(args.rates):
        traffic = dict(cell.traffic, rate_qps=rate)
        sample = loads.Reservoir(0, loads.rng_for(args.seed, 3))
        t = time.perf_counter()
        w = loads.open_loop(engine, traffic, eligible, g.out_degree,
                            args.seconds, args.seed + i, sample)
        ok = w.backlog_at_close <= engine.max_bucket and not w.unconverged
        lanes = sum(b.lanes for b in w.batches)
        print(json.dumps({
            "rate_qps": rate, "sustained": ok, "queries": w.attempted,
            "backlog_at_close": w.backlog_at_close,
            "p50_ms": 1e3 * percentile(w.latency_s, 50),
            "p85_ms": 1e3 * percentile(w.latency_s, 85),
            "p90_ms": 1e3 * percentile(w.latency_s, 90),
            "batches": len(w.batches),
            "batch_fill": sum(b.real for b in w.batches) / lanes,
            "lateness_max_s": max(w.lateness_s),
            "run_s": time.perf_counter() - t}), flush=True)
        misses = 0 if ok else misses + 1
        if misses == 2:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
