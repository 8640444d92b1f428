import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first — jax locks the device count at first
init, and the production meshes need 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Per cell: jit(step).lower(*abstract_inputs).compile(), then record
memory_analysis(), cost_analysis(), and collective bytes parsed from the
HLO into benchmarks/artifacts/dryrun/<cell>.json.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.registry import ARCHS, build_cell, list_cells

ARCH_FAMILY = {a: fam for a, (fam, _) in ARCHS.items()}
from repro.distributed.sharding import mesh_axes
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import collective_bytes, roofline_terms

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts", "dryrun")


def _compile_and_measure(cell, mesh, loop_scale: int = 1) -> dict:
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings)
        lowered = jitted.lower(*cell.args_struct)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, mesh.size, loop_scale=loop_scale)
    flops = float(cost.get("flops", 0.0)) if cost else 0.0
    hbm = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    return dict(
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        hlo_flops=flops, hlo_bytes=hbm, collectives=coll,
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            peak_bytes=getattr(mem, "peak_memory_in_bytes", None)))


def _lower_cost_only(cell, mesh) -> dict:
    """Unrolled flops pass without XLA compile: trace+lower, read the
    pre-optimization cost analysis (GLOBAL totals; divided by mesh.size
    for per-device roofline terms)."""
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings)
        lowered = jitted.lower(*cell.args_struct)
    cost = lowered.cost_analysis() or {}
    return dict(
        lower_s=round(time.time() - t0, 2),
        hlo_flops=float(cost.get("flops", 0.0)) / mesh.size,
        hlo_bytes=float(cost.get("bytes accessed", 0.0)) / mesh.size)


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             force: bool = False, flops_pass: bool = True) -> dict:
    """LM cells take two passes: scan-over-layers (memory_analysis with
    loop buffer reuse — the 'does it fit' proof) and unrolled (cost_analysis
    totals — XLA counts a scan body once, so the scanned pass under-reports
    FLOPs/collectives by ~n_layers). Other families are loop-free (or,
    for SSSP, per-round semantics are the intended unit) — one pass."""
    tag = f"{arch}__{shape}__{'multipod' if multi_pod else 'singlepod'}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    mesh = make_production_mesh(multi_pod=multi_pod)
    ax = mesh_axes(multi_pod)
    family = "sssp" if arch in ("sp-async", "sssp") else ARCH_FAMILY[arch]
    rec = dict(arch=arch, shape=shape, multi_pod=multi_pod,
               n_devices=mesh.size, status="ok")
    t0 = time.time()
    try:
        cell = build_cell(arch, shape, mesh, ax)
        if cell.skip:
            rec.update(status="skipped", reason=cell.skip)
        else:
            # LM cells: collectives inside the layer-scan body are scaled
            # by n_layers (cost/collectives of a while body count once)
            loop_scale = 1
            if family == "lm":
                from repro.configs.registry import _load
                loop_scale = _load(arch)[1].n_layers
            m1 = _compile_and_measure(cell, mesh, loop_scale=loop_scale)
            rec.update(kind=cell.kind, note=cell.note, model_flops=cell.model_flops,
                       lower_s=m1["lower_s"], compile_s=m1["compile_s"],
                       memory=m1["memory"], collectives=m1["collectives"])
            if family == "lm" and flops_pass:
                # honest FLOP totals: unrolled module, lower-only (no XLA opt)
                cell2 = build_cell(arch, shape, mesh, ax, scan_layers=False)
                m2 = _lower_cost_only(cell2, mesh)
                rec.update(hlo_flops=m2["hlo_flops"], hlo_bytes=m2["hlo_bytes"],
                           flops_pass=dict(lower_s=m2["lower_s"], mode="lower-only"))
            else:
                rec.update(hlo_flops=m1["hlo_flops"], hlo_bytes=m1["hlo_bytes"])
            rec["roofline"] = roofline_terms(
                rec["hlo_flops"], rec["hlo_bytes"],
                rec["collectives"]["total"], mesh.size, cell.model_flops)
            t = rec["roofline"]
            print(f"[{tag}] mem/device: args={_gb(rec['memory']['argument_bytes'])} "
                  f"temp={_gb(rec['memory']['temp_bytes'])} | "
                  f"flops={rec['hlo_flops']:.3e} bytes={rec['hlo_bytes']:.3e} "
                  f"coll={rec['collectives']['total']:.3e} "
                  f"dominant={t['dominant']} useful={t['useful_ratio']:.2f}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[{tag}] ERROR {type(e).__name__}: {e}")
    rec["wall_s"] = round(time.time() - t0, 2)
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _gb(b):
    return f"{b / 2**30:.2f}GiB" if isinstance(b, (int, float)) else "?"


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--force", action="store_true")
    p.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    args = p.parse_args()

    cells = list_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_skip = n_err = 0
    for arch, shape in cells:
        for mp in meshes:
            # multi-pod pass proves the pod axis shards (memory+compile);
            # FLOP totals come from the single-pod unrolled pass
            rec = run_cell(arch, shape, mp, args.out, force=args.force,
                           flops_pass=not mp)
            s = rec["status"]
            n_ok += s == "ok"
            n_skip += s == "skipped"
            n_err += s == "error"
    print(f"dry-run done: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
