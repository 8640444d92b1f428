"""Cell registry: (architecture × input-shape × mesh) -> lowerable step.

Every cell provides the jit-able step function, abstract input structs
(ShapeDtypeStruct — the dry-run never allocates), matching NamedShardings,
and a MODEL_FLOPS estimate for the roofline "useful compute" ratio.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import MeshAxes
from repro.models.params import abstract, specs
from repro.optim import AdamWConfig
from repro.optim.adamw import AdamWState

SDS = jax.ShapeDtypeStruct

# ---------------------------------------------------------------------------

LM_ARCHS = ["olmoe-1b-7b", "qwen3-moe-235b-a22b", "mistral-large-123b",
            "gemma-7b", "deepseek-7b"]
GNN_ARCHS = ["gat-cora", "egnn", "mace", "graphcast"]
REC_ARCHS = ["autoint"]

ARCHS = {
    "olmoe-1b-7b": ("lm", "repro.configs.olmoe_1b_7b"),
    "qwen3-moe-235b-a22b": ("lm", "repro.configs.qwen3_moe_235b_a22b"),
    "mistral-large-123b": ("lm", "repro.configs.mistral_large_123b"),
    "gemma-7b": ("lm", "repro.configs.gemma_7b"),
    "deepseek-7b": ("lm", "repro.configs.deepseek_7b"),
    "gat-cora": ("gnn", "repro.configs.gat_cora"),
    "egnn": ("gnn", "repro.configs.egnn"),
    "mace": ("gnn", "repro.configs.mace"),
    "graphcast": ("gnn", "repro.configs.graphcast"),
    "autoint": ("recsys", "repro.configs.autoint"),
}

LM_SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

def _pad512(x: int) -> int:
    """Node/edge counts padded to the 512-device lcm so 1-D sharding divides
    evenly on both production meshes (sentinel padding is the models'
    native convention)."""
    return -(-x // 512) * 512


GNN_SHAPES = {
    "full_graph_sm": dict(n_nodes=_pad512(2708), n_edges=_pad512(10556),
                          d_feat=1433, n_classes=7, batched=False,
                          note="Cora 2708v/10556e padded to /512"),
    "minibatch_lg": dict(n_nodes=_pad512(169984), n_edges=_pad512(168960),
                         d_feat=602, n_classes=41, batched=False,
                         note="sampled block: 1024 seeds, fanout 15-10 over a "
                              "233k-node graph (Reddit-like); shapes are the "
                              "padded sampler output"),
    "ogb_products": dict(n_nodes=_pad512(2449029), n_edges=_pad512(61859140),
                         d_feat=100, n_classes=47, batched=False,
                         note="ogbn-products padded to /512"),
    "molecule": dict(n_nodes=_pad512(30 * 128), n_edges=_pad512(64 * 128),
                     d_feat=16, n_classes=2, batched=True, n_graphs=128),
}

REC_SHAPES = {
    "train_batch": dict(kind="train", batch=65536),
    "serve_p99": dict(kind="serve", batch=512),
    "serve_bulk": dict(kind="serve", batch=262144),
    "retrieval_cand": dict(kind="retrieval", batch=1, n_candidates=1_000_000),
}

SSSP_SHAPES = {"graph1": {}, "graph2": {}, "graph3": {}, "graph4": {}}

SHAPES = {"lm": LM_SHAPES, "gnn": GNN_SHAPES, "recsys": REC_SHAPES,
          "sssp": SSSP_SHAPES}


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    step_fn: Callable | None
    args_struct: tuple | None
    in_shardings: tuple | None
    model_flops: float
    note: str = ""
    skip: str | None = None
    donate_argnums: tuple = ()


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _load(arch: str, smoke: bool = False):
    family, mod = ARCHS[arch]
    m = importlib.import_module(mod)
    return family, (m.SMOKE if smoke else m.CONFIG)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_cell(arch, cfg, shape_id, mesh, ax: MeshAxes,
             scan_layers: bool = True) -> Cell:
    from repro.models import transformer as tf
    sh = LM_SHAPES[shape_id]
    # scan_layers=True -> realistic memory_analysis (loop buffers reused);
    # scan_layers=False -> honest cost_analysis totals (XLA counts a scan
    # body once). The dry-run runs both passes and merges.
    cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
    if not scan_layers and cfg.moe is not None:
        # FLOPs pass: pre-optimization cost analysis does not traverse
        # shard_map bodies; lower the mathematically-identical GSPMD MoE
        # variant for counting (exactness verified to 3e-8 in tests)
        cfg = dataclasses.replace(cfg, moe_impl="gspmd")
    if shape_id == "long_500k":
        return Cell(arch, shape_id, "decode", None, None, None, 0.0,
                    skip="pure full-attention arch: 512K-token dense "
                         "attention is quadratically infeasible; skipped per "
                         "task rule (no SSM/linear-attn variant assigned). "
                         "See DESIGN.md §5.")
    defs = tf.param_defs(cfg, ax)
    p_struct = abstract(defs, cfg.dtype)
    p_spec = specs(defs)
    N_active = cfg.n_active_params()
    B, S = sh["batch"], sh["seq"]
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.hd

    if sh["kind"] == "train":
        step = tf.make_train_step(cfg, ax, AdamWConfig())
        batch_struct = {"tokens": SDS((B, S), jnp.int32),
                        "labels": SDS((B, S), jnp.int32)}
        batch_spec = {"tokens": P(ax.data, None), "labels": P(ax.data, None)}
        f32like = jax.tree_util.tree_map(
            lambda s: SDS(s.shape, jnp.float32), p_struct)
        opt_struct = AdamWState(step=SDS((), jnp.int32), m=f32like,
                                v=f32like)
        opt_spec = AdamWState(step=P(), m=p_spec, v=p_spec)
        args = (p_struct, opt_struct, batch_struct)
        shardings = (_ns(mesh, p_spec), _ns(mesh, opt_spec), _ns(mesh, batch_spec))
        flops = 6.0 * N_active * B * S
        return Cell(arch, shape_id, "train", step, args, shardings, flops)

    if sh["kind"] == "prefill":
        step = tf.make_prefill_step(cfg, ax)
        batch_struct = {"tokens": SDS((B, S), jnp.int32)}
        batch_spec = {"tokens": P(ax.data, None)}
        args = (p_struct, batch_struct)
        shardings = (_ns(mesh, p_spec), _ns(mesh, batch_spec))
        flops = 2.0 * N_active * B * S
        return Cell(arch, shape_id, "prefill", step, args, shardings, flops)

    # decode: one new token against a KV cache of seq_len
    step = tf.make_serve_step(cfg, ax)
    cache_struct = tuple(SDS((L, B, S, Hkv, Dh), cfg.dtype) for _ in range(2))
    cache_spec = tuple(P(None, ax.data, ax.model, None, None) for _ in range(2))
    args = (p_struct, SDS((B, 1), jnp.int32), cache_struct, SDS((), jnp.int32))
    shardings = (_ns(mesh, p_spec), NamedSharding(mesh, P(ax.data, None)),
                 _ns(mesh, cache_spec), NamedSharding(mesh, P()))
    # useful flops: dense read of active params + attention over the cache
    flops = 2.0 * N_active * B + 4.0 * L * B * S * Hkv * Dh
    return Cell(arch, shape_id, "decode", step, args, shardings, flops)


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

def _gnn_batch_struct(arch, cfg, sh, ax):
    N, E, Df = sh["n_nodes"], sh["n_edges"], sh["d_feat"]
    b = {"node_feat": (SDS((N, Df), jnp.float32), P(ax.all, None)),
         "edge_src": (SDS((E,), jnp.int32), P(ax.all)),
         "edge_dst": (SDS((E,), jnp.int32), P(ax.all))}
    if arch == "gat-cora":
        b["labels"] = (SDS((N,), jnp.int32), P(ax.all))
    elif arch == "egnn":
        b["coords"] = (SDS((N, 3), jnp.float32), P(ax.all, None))
        b["labels"] = (SDS((N,), jnp.float32), P(ax.all))
    elif arch == "mace":
        G = sh.get("n_graphs", 1)
        b["coords"] = (SDS((N, 3), jnp.float32), P(ax.all, None))
        b["graph_id"] = (SDS((N,), jnp.int32), P(ax.all))
        b["graph_energy"] = (SDS((G,), jnp.float32), P())
    elif arch == "graphcast":
        b["edge_feat"] = (SDS((E, cfg.d_edge_in), jnp.float32), P(ax.all, None))
        b["labels"] = (SDS((N, cfg.n_vars), jnp.float32), P(ax.all, None))
    struct = {k: v[0] for k, v in b.items()}
    spec = {k: v[1] for k, v in b.items()}
    return struct, spec


def _gnn_flops(arch, cfg, sh):
    N, E, Df = sh["n_nodes"], sh["n_edges"], sh["d_feat"]
    L = cfg.n_layers
    if arch == "gat-cora":
        D, H = cfg.d_hidden, cfg.n_heads
        return 6.0 * (N * Df * H * D + (L - 1) * E * H * D * 4 + E * H * D * 2)
    if arch == "egnn":
        D = cfg.d_hidden
        return 6.0 * L * (E * (2 * D + 1) * D * 2 + E * D * D + N * 2 * D * D * 2)
    if arch == "mace":
        C = cfg.d_hidden
        n_paths = 19  # |{(l1,l2,l3): l<=2}|
        per_edge = n_paths * C * 45          # CG contractions, l<=2 (m-dims <=5)
        per_node = 5 * C * C * 9 * 2         # channel mixes across l
        return 6.0 * L * (E * per_edge + N * per_node)
    if arch == "graphcast":
        D = cfg.d_hidden
        enc = N * Df * D + E * cfg.d_edge_in * D
        per_layer = E * (3 * D) * D + E * D * D + N * (2 * D) * D + N * D * D
        dec = N * D * cfg.n_vars
        return 6.0 * (enc + L * per_layer + dec)
    raise ValueError(arch)


def _gnn_cell(arch, cfg, shape_id, mesh, ax: MeshAxes) -> Cell:
    from repro.models import gnn
    sh = GNN_SHAPES[shape_id]
    # adapt input/output dims to the shape's graph
    if arch == "gat-cora":
        cfg = dataclasses.replace(cfg, d_in=sh["d_feat"], n_classes=sh["n_classes"])
        loss = gnn.gat_loss
        defs = gnn.gat_param_defs(cfg, ax)
    elif arch == "egnn":
        cfg = dataclasses.replace(cfg, d_in=sh["d_feat"])
        loss = gnn.egnn_loss
        defs = gnn.egnn_param_defs(cfg, ax)
    elif arch == "mace":
        loss = gnn.mace_loss
        defs = gnn.mace_param_defs(cfg, ax)
        if not sh["batched"]:
            sh = dict(sh, n_graphs=1)
    elif arch == "graphcast":
        # inputs follow the shape's d_feat; outputs stay n_vars=227
        loss = gnn.graphcast_loss
        defs = gnn.graphcast_param_defs(cfg, ax)
        defs["node_enc"] = gnn.mlp_defs(
            [sh["d_feat"], cfg.d_hidden, cfg.d_hidden], ln=True)
    else:
        raise ValueError(arch)
    p_struct = abstract(defs)
    p_spec = specs(defs)
    batch_struct, batch_spec = _gnn_batch_struct(arch, cfg, sh, ax)
    if arch == "graphcast":
        batch_struct["labels"] = SDS((sh["n_nodes"], cfg.n_vars), jnp.float32)
        batch_spec["labels"] = P(ax.all, None)

    step = gnn.make_gnn_train_step(loss, cfg, ax, AdamWConfig())
    f32like = jax.tree_util.tree_map(lambda s: SDS(s.shape, jnp.float32), p_struct)
    opt_struct = AdamWState(step=SDS((), jnp.int32), m=f32like, v=f32like)
    opt_spec = AdamWState(step=P(), m=p_spec, v=p_spec)
    args = (p_struct, opt_struct, batch_struct)
    shardings = (_ns(mesh, p_spec), _ns(mesh, opt_spec), _ns(mesh, batch_spec))
    return Cell(arch, shape_id, "train", step, args, shardings,
                _gnn_flops(arch, cfg, sh), note=sh.get("note", ""))


# ---------------------------------------------------------------------------
# recsys cells
# ---------------------------------------------------------------------------

def _rec_cell(arch, cfg, shape_id, mesh, ax: MeshAxes) -> Cell:
    from repro.models import autoint as ai
    sh = REC_SHAPES[shape_id]
    B = sh["batch"]
    defs = ai.autoint_param_defs(cfg, ax)
    p_struct = abstract(defs)
    p_spec = specs(defs)
    F, Lh = cfg.n_sparse, cfg.multi_hot
    idx_struct = SDS((B, F, Lh), jnp.int32)
    idx_spec = P(ax.data, None, None)

    D, A, H, nL = cfg.embed_dim, cfg.d_attn, cfg.n_heads, cfg.n_attn_layers
    attn_flops = nL * (3 * B * F * (H * A) * (H * A) + 2 * B * H * F * F * A)
    embed_flops = B * F * Lh * D
    base = attn_flops + embed_flops + B * F * H * A * 64

    if sh["kind"] == "train":
        step = ai.make_autoint_train_step(cfg, ax, AdamWConfig())
        batch_struct = {"sparse_idx": idx_struct, "labels": SDS((B,), jnp.int32)}
        batch_spec = {"sparse_idx": idx_spec, "labels": P(ax.data)}
        f32like = jax.tree_util.tree_map(lambda s: SDS(s.shape, jnp.float32), p_struct)
        opt_struct = AdamWState(step=SDS((), jnp.int32), m=f32like, v=f32like)
        opt_spec = AdamWState(step=P(), m=p_spec, v=p_spec)
        args = (p_struct, opt_struct, batch_struct)
        shardings = (_ns(mesh, p_spec), _ns(mesh, opt_spec), _ns(mesh, batch_spec))
        return Cell(arch, shape_id, "train", step, args, shardings, 3.0 * base)

    if sh["kind"] == "serve":
        step = ai.make_autoint_serve_step(cfg, ax)
        batch_struct = {"sparse_idx": idx_struct}
        batch_spec = {"sparse_idx": idx_spec}
        args = (p_struct, batch_struct)
        shardings = (_ns(mesh, p_spec), _ns(mesh, batch_spec))
        return Cell(arch, shape_id, "serve", step, args, shardings, base)

    Nc = sh["n_candidates"]
    step = ai.make_retrieval_step(cfg, ax)
    batch_struct = {"sparse_idx": idx_struct,
                    "cand_vecs": SDS((Nc, cfg.d_retrieval), jnp.float32)}
    # B=1 query replicated; candidates sharded over the model axis
    batch_spec = {"sparse_idx": P(None, None, None),
                  "cand_vecs": P(ax.model, None)}
    args = (p_struct, batch_struct)
    shardings = (_ns(mesh, p_spec), _ns(mesh, batch_spec))
    return Cell(arch, shape_id, "retrieval", step, args, shardings,
                base + 2.0 * B * Nc * cfg.d_retrieval)


# ---------------------------------------------------------------------------
# SSSP (paper) cells
# ---------------------------------------------------------------------------

def _sssp_abstract_shards(gspec, n_parts: int):
    from repro.core.shards import SsspShards
    s = gspec.shard_shapes(n_parts)
    Pn = n_parts
    i32, f32, b_ = jnp.int32, jnp.float32, jnp.bool_
    return SsspShards(
        loc_src=SDS((Pn, s["e_loc"]), i32), loc_dst=SDS((Pn, s["e_loc"]), i32),
        loc_w=SDS((Pn, s["e_loc"]), f32),
        cut_src=SDS((Pn, s["e_cut"]), i32), cut_w=SDS((Pn, s["e_cut"]), f32),
        cut_seg=SDS((Pn, s["e_cut"]), i32),
        slot_owner=SDS((Pn, s["S"]), i32), slot_dstl=SDS((Pn, s["S"]), i32),
        slot_pos=SDS((Pn, s["S"]), i32), slot_valid=SDS((Pn, s["S"]), b_),
        slot_last=SDS((Pn, s["S"]), i32),
        recv_idx=SDS((Pn, Pn, s["C"]), i32),
        tx_payload_slot=SDS((Pn, Pn, s["C"]), i32),
        tri_uj=SDS((Pn, s["T"]), i32), tri_ui=SDS((Pn, s["T"]), i32),
        tri_ij=SDS((Pn, s["T"]), i32), tri_valid=SDS((Pn, s["T"]), b_),
        inter_edges=SDS((Pn,), i32),
        n_vertices=gspec.n_vertices, n_parts=Pn, block=s["block"],
        # S slots over e_cut edges: no run is longer than e_cut - S + 1
        seg_steps=(s["e_cut"] - s["S"]).bit_length(),
    )


def _sssp_cell(shape_id, mesh, ax: MeshAxes, sssp_cfg=None) -> Cell:
    from repro.configs.sssp_paper import GRAPHS
    from repro.core.sssp import SsspConfig, build_shmap_solver
    gspec = GRAPHS[shape_id]
    n_parts = mesh.size
    cfg = sssp_cfg or SsspConfig(max_rounds=64)
    shards = _sssp_abstract_shards(gspec, n_parts)
    solver = build_shmap_solver(shards, cfg, mesh, ax.all, source=0)
    spec_tree = jax.tree_util.tree_map(lambda _: P(ax.all), shards)
    shardings = (_ns(mesh, spec_tree),)
    # one full relaxation of every edge + the exchange, per round; report
    # per-round useful work (min-plus relax = 1 add + 1 min per edge)
    flops = 2.0 * gspec.n_edges
    return Cell("sp-async", shape_id, "sssp",
                lambda sh: solver(sh), (shards,), shardings, flops,
                note=f"cut={gspec.cut_fraction}, rounds capped at "
                     f"{cfg.max_rounds} for the dry-run lowering")


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape_id: str, mesh, ax: MeshAxes,
               smoke: bool = False, **kw) -> Cell:
    if arch in ("sp-async", "sssp"):
        return _sssp_cell(shape_id, mesh, ax, kw.get("sssp_cfg"))
    family, cfg = _load(arch, smoke)
    if family == "lm":
        return _lm_cell(arch, cfg, shape_id, mesh, ax,
                        scan_layers=kw.get("scan_layers", True))
    if family == "gnn":
        return _gnn_cell(arch, cfg, shape_id, mesh, ax)
    return _rec_cell(arch, cfg, shape_id, mesh, ax)


def list_cells(include_sssp: bool = True):
    out = []
    for arch, (family, _) in ARCHS.items():
        for shape_id in SHAPES[family]:
            out.append((arch, shape_id))
    if include_sssp:
        for g in SSSP_SHAPES:
            out.append(("sp-async", g))
    return out
