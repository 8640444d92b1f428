#!/usr/bin/env python3
"""Smoke run of the SSSP engine on TPU chips, through its normal entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on a four-chip mesh

One chip: the ``scale-1e7`` RMAT preset (SCALE 19, edge factor 10, seed
700: 524,288 vertices, about 10.5M directed edges) is streamed into ragged
shards at P=8 and served by the ``sim`` engine with the default
``SsspConfig``. One K=16 batch goes through ``solve``, a second batch of 16
other sources through ``submit``/``drain``; the second batch must compile
nothing. Every query must be certified ``converged``, and one source of
each batch is checked against ``dijkstra_reference`` on the same edges the
shards were built from.

Four chips: P=4 shards of the same preset, solved by the ``shmap`` engine
on a 4-chip mesh and, as the comparison, by the ``sim`` engine on the same
shards in this process. Distances must be bit-identical, with equal
``rounds`` and ``q_rounds``.

The numbers printed before the last line are smoke numbers from one run,
not benchmark results. The last line is one JSON object naming the device.
The script refuses to run anywhere but a TPU and exits non-zero on any
failed check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

PRESET = "scale-1e7"
K = 16
SOURCE_SEED = 0


def report(key: str, value) -> None:
    print(f"smoke {key} = {value}", flush=True)


def require_tpu(chips: int):
    """The devices to run on; raises unless JAX sees ``chips`` TPUs."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}, {len(devices)} devices); "
            "it does not run on anything else")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPUs, "
                         f"JAX found {len(devices)}")
    return devices


def stream_shards(preset: str, n_parts: int):
    """Stream ``preset`` into ragged shards, keeping the chunks so the
    reference runs on exactly the edges the shards were built from."""
    from repro.core import build_shards_stream
    from repro.graph.generators import preset_edge_stream
    n, chunks = preset_edge_stream(preset)
    kept = []

    def tee():
        for c in chunks:
            kept.append(c)
            yield c

    t0 = time.perf_counter()
    sh = build_shards_stream(tee(), n, n_parts, layout="ragged")
    build_s = time.perf_counter() - t0
    return sh, kept, build_s


def pick_sources(kept, n: int, count: int) -> list[int]:
    """``count`` distinct sources with at least one out-edge."""
    has_out = np.zeros(n, bool)
    for src, _, _ in kept:
        has_out[src] = True
    rng = np.random.default_rng(SOURCE_SEED)
    return [int(s) for s in rng.choice(np.flatnonzero(has_out), size=count,
                                       replace=False)]


def reference_graph(kept, n: int):
    from repro.graph.structure import csr_from_coo
    return csr_from_coo(np.concatenate([c[0] for c in kept]),
                        np.concatenate([c[1] for c in kept]),
                        np.concatenate([c[2] for c in kept]), n)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def require_converged(res, what: str) -> None:
    require(res.status == "converged" and bool(res.q_converged.all()),
            f"{what}: status={res.status!r}, converged "
            f"{int(res.q_converged.sum())}/{len(res.sources)} queries")


def run_one_chip(preset: str = PRESET, n_parts: int = 8, k: int = K,
                 device=None) -> None:
    """Serve two K-batches with the sim engine and check them."""
    from repro.core import SsspConfig, SsspEngine
    from repro.graph import dijkstra_reference

    sh, kept, build_s = stream_shards(preset, n_parts)
    n = sh.n_vertices
    report("vertices", n)
    report("directed_edges_streamed", sum(len(c[0]) for c in kept))
    report("n_parts", n_parts)
    report("build_s", build_s)
    srcs = pick_sources(kept, n, 2 * k)
    first, second = srcs[:k], srcs[k:]

    eng = SsspEngine.build(sh, SsspConfig(), backend="sim")
    res = eng.solve(first)
    require(res.compiled, "first batch compiles its bucket program")
    require(res.dist.shape == (k, n), f"dist shape {res.dist.shape}")
    require_converged(res, "first batch")
    report("compile_s", res.compile_s)
    report("first_batch_wall_s", res.wall_s)

    traces = dict(eng.trace_counts)
    for i in range(0, k, 4):
        eng.submit(second[i:i + 4])
    drained = eng.drain()
    warm = drained[0]
    require(eng.trace_counts == traces,
            f"second batch traced: {traces} -> {eng.trace_counts}")
    require(not any(r.compiled for r in drained), "second batch compiled")
    require(len({r.bucket_k for r in drained}) == 1 and warm.bucket_k == k,
            f"drain buckets {[r.bucket_k for r in drained]}")
    for r in drained:
        require_converged(r, "second batch")
    report("warm_solve_s", warm.wall_s)
    report("rounds", int(warm.stats.rounds))
    report("relaxations", int(warm.stats.relaxations))
    stats = (device.memory_stats() or {}) if device is not None else {}
    report("peak_bytes_in_use",
           stats.get("peak_bytes_in_use", "not reported"))

    g = reference_graph(kept, n)
    t0 = time.perf_counter()
    for s, row in ((first[0], res.dist[0]), (second[0], drained[0].dist[0])):
        ref = dijkstra_reference(g, s)
        require(bool(np.allclose(row, ref, rtol=1e-5, atol=1e-4)),
                f"source {s} differs from dijkstra_reference")
    report("reference_checked_sources", 2)
    report("reference_s", time.perf_counter() - t0)


def run_four_chips(devices, preset: str = PRESET, k: int = K) -> None:
    """shmap on a 4-device mesh against sim on the same P=4 shards."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core import SsspConfig, SsspEngine

    n_parts = 4
    sh, kept, build_s = stream_shards(preset, n_parts)
    report("vertices", sh.n_vertices)
    report("n_parts", n_parts)
    report("build_s", build_s)
    srcs = pick_sources(kept, sh.n_vertices, k)
    del kept

    axes = ("chips",)
    mesh = compat.make_mesh((n_parts,), axes, devices=devices[:n_parts])
    shmap = SsspEngine.build(sh, SsspConfig(), backend="shmap", mesh=mesh,
                             axis_names=axes)
    placed = NamedSharding(mesh, P(axes))
    for leaf in jax.tree_util.tree_leaves(shmap.shards):
        require(leaf.sharding.is_equivalent_to(placed, leaf.ndim),
                f"shard leaf {leaf.shape} placed as {leaf.sharding}")
    per_dev = {}
    for leaf in jax.tree_util.tree_leaves(shmap.shards):
        for piece in leaf.addressable_shards:
            per_dev[piece.device.id] = (per_dev.get(piece.device.id, 0)
                                        + piece.data.nbytes)
    report("shard_bytes_per_device", per_dev)

    sim = SsspEngine.build(sh, SsspConfig(), backend="sim")
    r_shmap = shmap.solve(srcs)
    r_sim = sim.solve(srcs)
    require_converged(r_shmap, "shmap batch")
    require_converged(r_sim, "sim batch")
    require(np.array_equal(r_shmap.dist, r_sim.dist),
            "shmap distances are not bit-identical to sim")
    require(int(r_shmap.stats.rounds) == int(r_sim.stats.rounds),
            f"rounds {int(r_shmap.stats.rounds)} vs "
            f"{int(r_sim.stats.rounds)}")
    require(np.array_equal(r_shmap.q_rounds, r_sim.q_rounds),
            "q_rounds differ between shmap and sim")
    report("shmap_compile_s", r_shmap.compile_s)
    report("shmap_wall_s", r_shmap.wall_s)
    report("sim_wall_s", r_sim.wall_s)
    report("rounds", int(r_shmap.stats.rounds))

    src_arr = np.asarray(srcs, np.int32)
    compiled = shmap.shmap_solver.lower(shmap.shards, src_arr,
                                        np.ones((k,), bool)).compile()
    mem = compiled.memory_analysis()
    report("shmap_memory_analysis_per_device", {
        f: getattr(mem, f) for f in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip; 4: the shmap "
                         "engine on a four-chip mesh against sim")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    from repro.runtime import enable_compile_cache
    report("compile_cache_dir", enable_compile_cache())
    dev = devices[0]
    report("platform", dev.platform)
    report("device_kind", dev.device_kind)
    report("device_count", len(devices))
    if args.chips == 1:
        run_one_chip(device=dev)
    else:
        run_four_chips(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
