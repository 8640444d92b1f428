"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes ``BENCH_sssp.json``
(machine-readable: per-benchmark name, wall time, MTEPS where reported) so
the perf trajectory is tracked across PRs. Sections:
  - sssp_runtime / speedup / MTEPS  (paper Figs 1-2)
  - trishla                          (paper's pruning contribution)
  - toka                             (termination-detection comparison)
  - local_solver                     (intra-node Dijkstra-order ablation,
                                      incl. the Pallas dst-tiled kernel path)
  - kernels                          (Pallas vs XLA micro)
  - roofline                         (dry-run derived terms, if artifacts exist)
"""
from __future__ import annotations

import json
import os
import re
import sys

_RECORDS: list[dict] = []
_MTEPS_RE = re.compile(r"mteps=([0-9.]+)")
_QPS_RE = re.compile(r"qps=([0-9.]+)")


def _out(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}")
    rec = {"name": name, "us": round(float(us), 1), "derived": derived}
    m = _MTEPS_RE.search(derived)
    if m:
        rec["mteps"] = float(m.group(1))
    m = _QPS_RE.search(derived)
    if m:
        rec["qps"] = float(m.group(1))
    _RECORDS.append(rec)


def _write_json(path="BENCH_sssp.json"):
    # repo root (next to benchmarks/), wherever the harness is launched from
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    full = os.path.join(root, path)
    # merge with the existing file so a partial-section run (`run.py
    # kernels`) refreshes its own records without clobbering the rest of
    # the tracked perf trajectory
    merged = {}
    if os.path.exists(full):
        try:
            with open(full) as f:
                merged = {r["name"]: r for r in json.load(f)["benchmarks"]}
        except (json.JSONDecodeError, KeyError):
            merged = {}
    merged.update((r["name"], r) for r in _RECORDS)
    with open(full, "w") as f:
        json.dump({"benchmarks": list(merged.values())}, f, indent=1)
    print(f"# wrote {path} ({len(_RECORDS)} new, {len(merged)} total records)")


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    from repro.runtime import enable_compile_cache
    enable_compile_cache()

    from benchmarks import sssp_bench, kernel_bench
    if only in (None, "sssp"):
        sssp_bench.run_all(_out)
        from benchmarks import sssp_perf_study
        sssp_perf_study.run(out=lambda s: print(f"# {s}"))
    if only in (None, "kernels"):
        kernel_bench.run_all(_out)
    if only in (None, "roofline"):
        try:
            from benchmarks import roofline
            roofline.bench_roofline(_out)
        except Exception as e:  # artifacts may not exist yet
            print(f"# roofline skipped: {e}")
    _write_json()


if __name__ == "__main__":
    main()
