#!/usr/bin/env python3
"""The control of a cell's comparison: the reference in bfloat16, put in the
program's place, must come out not correct.

    python3 bench/control.py --workload graph500-s18.batch --seeds 11 12 13

For each seed the cell's graph is generated, ``check.batches`` batches of
``max_bucket`` sources are drawn as the closed loop draws them, and their
rows are computed by Bellman-Ford in bfloat16 (the precision below the
float32 the configuration states). Those rows go through the same comparison
as the program's, against the float64 reference. One JSON line per seed gives each number compared
beside its limit, and ``correct``. It needs no chip and runs on the host.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from bench import graphs, loads, reference  # noqa: E402


def control(config: dict, seed: int) -> list:
    """The comparison's numbers for the bfloat16 reference on ``seed``."""
    n, chunks = graphs.generate(config, seed)
    g = graphs.simple_graph(n, chunks)
    eligible = np.flatnonzero(g.out_degree)
    k = min(int(config["engine"]["max_bucket"]), len(eligible))
    rng = loads.rng_for(seed, 2)
    sources = np.concatenate([rng.choice(eligible, size=k, replace=False)
                              for _ in range(int(config["check"]["batches"]))])
    rows = np.stack([reference.shortest_paths(g, s, "bfloat16")
                     for s in sources])
    return reference.compare(rows, reference.distances(g, sources), 0,
                             config["check"]["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import run
    config = run.load_cell(args.workload).config
    for seed in args.seeds:
        checks = control(config, seed)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": all(c.ok for c in checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
