"""Host-side shard preprocessing for SP-Async.

Splits each partition's edges into LOCAL (dst owned by the same shard) and
CUT (dst owned elsewhere) lists, and precomputes the *static message
routing* for the bucketed boundary exchange:

- Cut edges are grouped (host-side, one-time) by their boundary pair
  ``(dst_owner, dst_local)``. Each unique pair is a *message slot*.
- At runtime a shard segment-mins its cut-edge candidates into the slots
  (pre-aggregation: one message per boundary vertex, not per edge — the
  paper's future-work "message buffering" made static), scatters slots into
  a ``[P, C]`` send buffer at *precomputed static positions*, and fires one
  ``all_to_all``.
- The receive-side index table (which local vertex each incoming slot
  addresses) is also static: ``recv_idx[q, p, c]`` = the local vertex on
  shard q addressed by sender p's slot c. Built here by transposition.

Everything here is one-time host preprocessing — the paper's "Graph
Partition" phase. Besides the routing tables, three Pallas tile layouts
ride in the shards (each an instance of the same pre-tile-by-destination
pattern): ``rx_*`` (local edges by vertex tile, for the relax kernel),
``tx_*`` (cut edges by message-slot tile + the ``tx_payload_slot`` payload
inverse, for the send kernel), and ``mx_*`` (receive positions by vertex
tile, for the merge kernel).

Each layout family exists in two shapes, selected by ``layout=``:

- ``"dense"``: ``[P, n_tiles, n_chunks, EB]`` with ``n_chunks`` the max
  over tiles AND shards — every tile is padded to the worst case. Simple,
  but on power-law graphs (where one vertex tile can carry orders of
  magnitude more edges than the median) almost all of it is padding.
- ``"ragged"``: CSR-chunked — flat ``[P, total_chunks, EB]`` chunk rows
  plus a ``*_ctile [P, total_chunks]`` chunk→tile map consumed by the
  ragged-grid kernels (scalar-prefetched). Memory is proportional to
  ``sum_t ceil(count_t / EB)`` instead of ``n_tiles * max_t ceil(count_t
  / EB)``; values are bit-identical (same stable sort, same chunk split,
  minus inert padding). ``SsspShards.layout_bytes()`` reports both the
  measured bytes and the CSR ideal / dense equivalent for either form.

``build_shards`` materializes the full ``partition_1d`` intermediate —
fine up to ~1M edges. ``build_shards_stream`` consumes an edge-chunk
iterator with per-part accumulators instead, so a 10M-edge graph
partitions without ever holding a ``[P, e_max]`` dense intermediate.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph
from repro.core.partition import partition_1d
from repro.kernels.merge import build_msg_ragged_layout, build_msg_tiled_layout
from repro.kernels.relax import build_dst_ragged_layout, build_dst_tiled_layout
from repro.kernels.send import build_slot_ragged_layout, build_slot_tiled_layout


def _pad2(rows, width, fill, dtype):
    out = np.full((len(rows), width), fill, dtype)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SsspShards:
    """All static per-shard state for the SP-Async solver, stacked [P, ...]."""

    # local edges (dst owned by this shard)
    loc_src: jax.Array     # [P, e_loc] int32 local ids
    loc_dst: jax.Array     # [P, e_loc] int32 local ids
    loc_w: jax.Array       # [P, e_loc] f32 (+inf padding)
    # cut edges (dst owned elsewhere), grouped by (owner, dst_local)
    cut_src: jax.Array     # [P, e_cut] int32 local ids
    cut_w: jax.Array       # [P, e_cut] f32 (+inf padding)
    cut_seg: jax.Array     # [P, e_cut] int32 -> slot segment id (S = padded)
    # message slots (unique boundary pairs)
    slot_owner: jax.Array  # [P, S] int32 destination shard
    slot_dstl: jax.Array   # [P, S] int32 dst-local id on the destination shard
    slot_pos: jax.Array    # [P, S] int32 position within the [P, C] send row
    slot_valid: jax.Array  # [P, S] bool
    slot_last: jax.Array   # [P, S] int32 index of the slot's last cut edge
    #                        (0 for padded slots)
    # receive routing: local vertex addressed by (sender, bucket position)
    recv_idx: jax.Array    # [P, P, C] int32 (block = invalid sentinel)
    # static inverse of (slot_owner, slot_pos): the slot feeding each
    # bucketed payload position, so the payload scatter becomes a gather
    tx_payload_slot: jax.Array  # [P, P, C] int32 (sentinel = S)
    # Trishla triangle candidates: edge-id triples (uj to prune, ui, ij)
    tri_uj: jax.Array      # [P, T] int32 -> index into the *combined* edge view
    tri_ui: jax.Array      # [P, T] int32
    tri_ij: jax.Array      # [P, T] int32
    tri_valid: jax.Array   # [P, T] bool
    # ToKa1 bound inputs
    inter_edges: jax.Array  # [P] int32 per-shard cut-edge counts
    n_vertices: int = dataclasses.field(metadata=dict(static=True))
    n_parts: int = dataclasses.field(metadata=dict(static=True))
    block: int = dataclasses.field(metadata=dict(static=True))
    # ceil(log2(longest run of one slot's cut edges)) over every shard's
    # real slots: the doubling steps of the send pack's segmented min
    seg_steps: int = dataclasses.field(metadata=dict(static=True))
    # dst-tiled layout of the LOCAL edges for the Pallas relax kernel
    # (built once at partition time; None when relax_layout=False). The
    # tiled slots are a permutation of [0, e_loc) plus padding; rx_eid maps
    # each slot back to its local edge id (sentinel = e_loc) so the runtime
    # Trishla pruned mask can be gathered into tiled order per solve.
    # Dense layout: [P, n_vtiles, n_chunks, EB]. Ragged layout: flat
    # [P, total_chunks, EB] chunk rows plus the rx_ctile chunk→tile map.
    rx_src: jax.Array | None = None
    rx_w: jax.Array | None = None
    rx_dstrel: jax.Array | None = None
    rx_eid: jax.Array | None = None
    rx_ctile: jax.Array | None = None   # [P, total_chunks] int32 (ragged only;
    #                                     sentinel n_vtiles = inert padding)
    rx_vb: int = dataclasses.field(default=128, metadata=dict(static=True))
    rx_eb: int = dataclasses.field(default=512, metadata=dict(static=True))
    # slot-tiled layout of the CUT edges for the Pallas send kernel (same
    # dst-tiled pattern with the message SLOT in the destination role;
    # None when comm_layout=False). tx_eid maps tiled slots back to cut
    # edge ids (sentinel = e_cut) for the runtime Trishla pruned gather.
    tx_src: jax.Array | None = None
    tx_w: jax.Array | None = None
    tx_segrel: jax.Array | None = None
    tx_eid: jax.Array | None = None
    tx_ctile: jax.Array | None = None   # [P, total_chunks] int32 (ragged only)
    tx_sb: int = dataclasses.field(default=128, metadata=dict(static=True))
    tx_eb: int = dataclasses.field(default=512, metadata=dict(static=True))
    # msg-tiled receive routing for the Pallas merge kernel: flat incoming
    # positions [0, P*C) grouped by destination vertex tile
    mx_pos: jax.Array | None = None
    mx_dstrel: jax.Array | None = None
    mx_valid: jax.Array | None = None
    mx_ctile: jax.Array | None = None   # [P, total_chunks] int32 (ragged only)
    mx_vb: int = dataclasses.field(default=128, metadata=dict(static=True))
    mx_eb: int = dataclasses.field(default=512, metadata=dict(static=True))
    # which tile-layout family the rx/tx/mx arrays use ("dense" | "ragged")
    layout: str = dataclasses.field(default="dense",
                                    metadata=dict(static=True))

    @property
    def e_loc(self):
        return self.loc_src.shape[1]

    @property
    def e_cut(self):
        return self.cut_src.shape[1]

    @property
    def n_slots(self):
        return self.slot_owner.shape[1]

    @property
    def bucket_cap(self):
        return self.recv_idx.shape[2]

    @property
    def has_relax_layout(self):
        return self.rx_src is not None

    @property
    def relax_layout(self):
        """Per-call tuple consumed by ``local_fixpoint_batch`` (or None).
        Ragged shards append the chunk→tile map (5-tuple vs 4-tuple) —
        consumers dispatch the ragged kernels on the arity."""
        if self.rx_src is None:
            return None
        base = (self.rx_src, self.rx_w, self.rx_dstrel, self.rx_eid)
        return base if self.rx_ctile is None else base + (self.rx_ctile,)

    @property
    def has_send_layout(self):
        return self.tx_src is not None

    @property
    def send_layout(self):
        """Per-call tuple consumed by the pallas send stage (or None);
        5-tuple (with chunk→tile map) when ragged."""
        if self.tx_src is None:
            return None
        base = (self.tx_src, self.tx_w, self.tx_segrel, self.tx_eid)
        return base if self.tx_ctile is None else base + (self.tx_ctile,)

    @property
    def has_merge_layout(self):
        return self.mx_pos is not None

    @property
    def merge_layout(self):
        """Per-call tuple consumed by the pallas merge stage (or None);
        4-tuple (with chunk→tile map) when ragged."""
        if self.mx_pos is None:
            return None
        base = (self.mx_pos, self.mx_dstrel, self.mx_valid)
        return base if self.mx_ctile is None else base + (self.mx_ctile,)

    def layout_bytes(self):
        """Measured memory of each tile-layout family vs the CSR ideal and
        the dense-padded equivalent.

        Per family: ``bytes`` (actual array storage), ``items`` (real
        edges / messages it encodes), ``bytes_per_item``, ``ideal_bytes``
        (CSR lower bound: 4 B per plane per item — 4 planes for the edge
        layouts, 3 for the msg layout), and ``dense_bytes`` (what the
        worst-case-padded dense layout costs for the same data; equals
        ``bytes`` when the shards ARE dense). Top-level ``bytes_per_edge``
        divides the edge layouts (relax + send) by real edge count — the
        number the CI scale gate holds within 1.5x of the 16 B/edge ideal.
        """
        loc_edges = int(np.isfinite(np.asarray(self.loc_w)).sum())
        cut_edges = int(np.isfinite(np.asarray(self.cut_w)).sum())
        msgs = int((np.asarray(self.recv_idx) < self.block).sum())

        def _bytes(arrays):
            return int(sum(np.asarray(a).size * np.asarray(a).dtype.itemsize
                           for a in arrays if a is not None))

        def _dense_bytes(arrays, ctile, n_tiles, eb, planes):
            """Dense equivalent: P * n_tiles * max-chunks-anywhere * EB."""
            if arrays[0] is None:
                return 0
            if ctile is None:
                return _bytes(arrays)                  # already dense
            ct = np.asarray(ctile)
            max_chunks = 1
            for p in range(ct.shape[0]):
                real = ct[p][ct[p] < n_tiles]
                if real.size:
                    per_tile = np.bincount(real, minlength=n_tiles)
                    max_chunks = max(max_chunks, int(per_tile.max()))
            P = ct.shape[0]
            return int(P * n_tiles * max_chunks * eb * planes * 4)

        n_vtiles = max(-(-self.block // self.rx_vb), 1)
        n_stiles = max(-(-self.n_slots // self.tx_sb), 1)
        n_mtiles = max(-(-self.block // self.mx_vb), 1)
        groups = {}
        for name, arrays, ctile, items, planes, n_tiles, eb in (
            ("relax", (self.rx_src, self.rx_w, self.rx_dstrel, self.rx_eid,
                       self.rx_ctile), self.rx_ctile, loc_edges, 4,
             n_vtiles, self.rx_eb),
            ("send", (self.tx_src, self.tx_w, self.tx_segrel, self.tx_eid,
                      self.tx_ctile), self.tx_ctile, cut_edges, 4,
             n_stiles, self.tx_eb),
            ("merge", (self.mx_pos, self.mx_dstrel, self.mx_valid,
                       self.mx_ctile), self.mx_ctile, msgs, 3,
             n_mtiles, self.mx_eb),
        ):
            b = _bytes(arrays)
            groups[name] = {
                "bytes": b,
                "items": items,
                "bytes_per_item": b / max(items, 1),
                "ideal_bytes": items * planes * 4,
                "dense_bytes": _dense_bytes(arrays, ctile, n_tiles, eb,
                                            planes),
            }
        edge_bytes = groups["relax"]["bytes"] + groups["send"]["bytes"]
        n_edges = loc_edges + cut_edges
        return {
            "layout": self.layout,
            "groups": groups,
            "total_bytes": sum(g["bytes"] for g in groups.values()),
            "dense_bytes": sum(g["dense_bytes"] for g in groups.values()),
            "n_edges": n_edges,
            "bytes_per_edge": edge_bytes / max(n_edges, 1),
            "ideal_bytes_per_edge": 16.0,   # 4 planes x 4 B, each edge in
            #                                 exactly one edge layout
        }


def shard_distance_rows(rows, n_parts: int, block: int) -> jax.Array:
    """Re-shard host distance rows into the carry's per-shard layout.

    ``rows``: [L, n_vertices] (e.g. the L solved landmark sources) ->
    ``[P, L, block]`` with +inf on the padding vertices, matching how the
    solver's ``dist`` is blocked across shards. This is the storage layout
    of the engine's landmark cache — 4 B x L x block per shard — chosen so
    the warm-init seed is a per-shard broadcast against the resident
    ``dist`` block, with no runtime re-partitioning."""
    rows = np.asarray(rows, np.float32)
    n_land, n = rows.shape
    full = np.full((n_land, n_parts * block), np.inf, np.float32)
    full[:, :n] = rows
    return jnp.asarray(np.swapaxes(full.reshape(n_land, n_parts, block), 0, 1))


def _check_weights(w, valid):
    """Raise on NaN / non-finite / negative weights among the valid edges.

    A NaN weight propagates through every min it touches, and a negative
    weight breaks the monotonicity the whole async pipeline (and its
    termination proofs) rests on — both would otherwise surface only as
    silently wrong fixpoints. Padding edges legitimately carry +inf, so
    only the valid edges are checked."""
    bad_nan = valid & np.isnan(w)
    bad_inf = valid & ~np.isnan(w) & ~np.isfinite(w)
    bad_neg = valid & (w < 0)
    if bad_nan.any() or bad_inf.any() or bad_neg.any():
        raise ValueError(
            f"invalid edge weights: {int(bad_nan.sum())} NaN, "
            f"{int(bad_inf.sum())} non-finite, {int(bad_neg.sum())} "
            "negative — SSSP requires finite non-negative weights")


def _check_endpoints(src, dst, valid, n_vertices):
    """Raise on out-of-range endpoints among the valid edges.

    An out-of-range id would silently land in the wrong shard (owner =
    id // block) or alias a padding slot — like a bad weight, it corrupts
    the fixpoint instead of failing. Same counted-error style as the
    weight check."""
    bad_src = valid & ((src < 0) | (src >= n_vertices))
    bad_dst = valid & ((dst < 0) | (dst >= n_vertices))
    if bad_src.any() or bad_dst.any():
        raise ValueError(
            f"out-of-range edge endpoints: {int(bad_src.sum())} src, "
            f"{int(bad_dst.sum())} dst — vertex ids must lie in "
            f"[0, {n_vertices})")


def build_shards(g: Graph, n_parts: int, max_triangles_per_part: int | None = None,
                 enumerate_triangles: bool = True, relax_layout: bool = True,
                 relax_vb: int = 128, relax_eb: int = 512,
                 comm_layout: bool = True, send_sb: int = 128,
                 send_eb: int = 512, merge_vb: int = 128,
                 merge_eb: int = 512, layout: str = "dense") -> SsspShards:
    """Partition + preprocess a materialized ``Graph`` (see module doc).

    ``layout`` selects the tile-layout family for the rx/tx/mx arrays:
    ``"dense"`` (worst-case padded) or ``"ragged"`` (CSR-chunked)."""
    w_all = np.asarray(g.weight)
    v_all = np.asarray(g.valid)
    _check_weights(w_all, v_all)
    _check_endpoints(np.asarray(g.src), np.asarray(g.dst), v_all,
                     g.n_vertices)
    pg = partition_1d(g, n_parts)
    P, block, n = pg.n_parts, pg.block, pg.n_vertices

    src_l = np.asarray(pg.src_local)
    dst_o = np.asarray(pg.dst_owner)
    dst_l = np.asarray(pg.dst_local)
    w = np.asarray(pg.weight)
    valid = np.asarray(pg.valid)

    parts = []
    for p in range(P):
        vm = valid[p]
        parts.append((src_l[p][vm], dst_o[p][vm], dst_l[p][vm], w[p][vm]))
    return _assemble_shards(
        parts, n, P, block,
        max_triangles_per_part=max_triangles_per_part,
        enumerate_triangles=enumerate_triangles, relax_layout=relax_layout,
        relax_vb=relax_vb, relax_eb=relax_eb, comm_layout=comm_layout,
        send_sb=send_sb, send_eb=send_eb, merge_vb=merge_vb,
        merge_eb=merge_eb, layout=layout)


def build_shards_stream(edge_chunks, n_vertices: int, n_parts: int, *,
                        dedup: bool = True,
                        max_triangles_per_part: int | None = None,
                        enumerate_triangles: bool = False,
                        relax_layout: bool = True, relax_vb: int = 128,
                        relax_eb: int = 512, comm_layout: bool = True,
                        send_sb: int = 128, send_eb: int = 512,
                        merge_vb: int = 128, merge_eb: int = 512,
                        layout: str = "ragged") -> SsspShards:
    """Streaming shard build: consume an iterator of ``(src, dst, w)``
    edge chunks instead of a materialized ``Graph``.

    Each chunk is validated (weights + endpoints, same errors as
    ``build_shards``) and routed to its owner part (``src // block``)
    immediately, so peak memory is one chunk plus the per-part
    accumulators — never the global sorted edge list or the rectangular
    ``[P, e_max]`` ``partition_1d`` intermediate a 10M-edge graph would
    blow up on. Per part, edges are then (src, dst)-sorted and min-weight
    deduped with EXACTLY the ``csr_from_coo`` recipe, so the resulting
    shards are bit-identical to ``build_shards(csr_from_coo(...), ...)``
    on the concatenated chunks.

    ``enumerate_triangles`` defaults to False here (unlike ``build_shards``)
    — Trishla's host-side triangle enumeration is superlinear and not meant
    for the graph sizes this entry point exists for. ``layout`` defaults to
    ``"ragged"`` for the same reason."""
    block = max(-(-n_vertices // n_parts), 1)
    acc_src = [[] for _ in range(n_parts)]
    acc_dst = [[] for _ in range(n_parts)]
    acc_w = [[] for _ in range(n_parts)]
    for src, dst, w in edge_chunks:
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        w = np.asarray(w, np.float32)
        ok = np.ones(len(src), bool)
        _check_weights(w, ok)
        _check_endpoints(src, dst, ok, n_vertices)
        owner = src // block
        for p in np.unique(owner):
            m = owner == p
            acc_src[p].append(src[m])
            acc_dst[p].append(dst[m])
            acc_w[p].append(w[m])

    parts = []
    for p in range(n_parts):
        if acc_src[p]:
            src = np.concatenate(acc_src[p])
            dst = np.concatenate(acc_dst[p])
            w = np.concatenate(acc_w[p]).astype(np.float32)
        else:
            src = np.zeros(0, np.int64)
            dst = np.zeros(0, np.int64)
            w = np.zeros(0, np.float32)
        acc_src[p] = acc_dst[p] = acc_w[p] = None     # free as we go
        # mirror csr_from_coo exactly: (src, dst) sort, then min-weight
        # dedup by (key, weight) sort + keep-first — bit-identity with the
        # batch path depends on reproducing this ordering verbatim
        order = np.lexsort((dst, src))
        src, dst, w = src[order], dst[order], w[order]
        if dedup and len(src):
            key = src * n_vertices + dst
            o2 = np.lexsort((w, key))
            key, src, dst, w = key[o2], src[o2], dst[o2], w[o2]
            keep = np.ones(len(key), bool)
            keep[1:] = key[1:] != key[:-1]
            src, dst, w = src[keep], dst[keep], w[keep]
        dst_o = dst // block
        parts.append((src - p * block, dst_o, dst - dst_o * block, w))
    return _assemble_shards(
        parts, n_vertices, n_parts, block,
        max_triangles_per_part=max_triangles_per_part,
        enumerate_triangles=enumerate_triangles, relax_layout=relax_layout,
        relax_vb=relax_vb, relax_eb=relax_eb, comm_layout=comm_layout,
        send_sb=send_sb, send_eb=send_eb, merge_vb=merge_vb,
        merge_eb=merge_eb, layout=layout)


def _assemble_shards(parts, n, P, block, *, max_triangles_per_part,
                     enumerate_triangles, relax_layout, relax_vb, relax_eb,
                     comm_layout, send_sb, send_eb, merge_vb, merge_eb,
                     layout) -> SsspShards:
    """Shared assembly: per-part valid edges -> SsspShards.

    ``parts[p]`` = (src_local, dst_owner, dst_local, w), each the part's
    VALID edges in (src, dst)-sorted order (both builders guarantee it)."""
    if layout not in ("dense", "ragged"):
        raise ValueError(f"unknown layout {layout!r}: expected 'dense' or "
                         "'ragged'")

    loc_rows_src, loc_rows_dst, loc_rows_w = [], [], []
    cut_rows_src, cut_rows_w, cut_rows_seg = [], [], []
    slot_rows_owner, slot_rows_dstl, slot_rows_last = [], [], []
    inter_edges = np.zeros(P, np.int64)
    longest_run = 1

    for p in range(P):
        p_src, p_do, p_dl, p_w = parts[p]
        cm = p_do != p
        lm = ~cm
        loc_rows_src.append(p_src[lm])
        loc_rows_dst.append(p_dl[lm])
        loc_rows_w.append(p_w[lm])
        # group cut edges by (owner, dst_local)
        co, cl, cs, cw = p_do[cm], p_dl[cm], p_src[cm], p_w[cm]
        order = np.lexsort((cl, co))
        co, cl, cs, cw = co[order], cl[order], cs[order], cw[order]
        key = co.astype(np.int64) * block + cl
        if len(key):
            new_seg = np.ones(len(key), bool)
            new_seg[1:] = key[1:] != key[:-1]
            seg_id = np.cumsum(new_seg) - 1
            u_owner = co[new_seg]
            u_dstl = cl[new_seg]
            last = np.append(np.flatnonzero(new_seg[1:]), len(key) - 1)
            longest_run = max(longest_run,
                              int(np.diff(last, prepend=-1).max()))
        else:
            seg_id = np.zeros(0, np.int64)
            u_owner = np.zeros(0, np.int64)
            u_dstl = np.zeros(0, np.int64)
            last = np.zeros(0, np.int64)
        cut_rows_src.append(cs)
        cut_rows_w.append(cw)
        cut_rows_seg.append(seg_id)
        slot_rows_owner.append(u_owner)
        slot_rows_dstl.append(u_dstl)
        slot_rows_last.append(last)
        inter_edges[p] = int(cm.sum())

    e_loc = max(max((len(r) for r in loc_rows_src), default=0), 1)
    e_cut = max(max((len(r) for r in cut_rows_src), default=0), 1)
    S = max(max((len(r) for r in slot_rows_owner), default=0), 1)

    # position of each slot within its destination bucket row
    slot_pos_rows = []
    cap = 1
    for p in range(P):
        owners = slot_rows_owner[p]
        pos = np.zeros(len(owners), np.int64)
        for q in np.unique(owners):
            m = owners == q
            pos[m] = np.arange(m.sum())
            cap = max(cap, int(m.sum()))
        slot_pos_rows.append(pos)
    C = cap

    # receive routing table: recv_idx[q, p, c] = dst_local, built by transpose
    recv_idx = np.full((P, P, C), block, np.int64)
    for p in range(P):
        owners, dstl, pos = slot_rows_owner[p], slot_rows_dstl[p], slot_pos_rows[p]
        recv_idx[owners, p, pos] = dstl

    # payload-position inverse: each (owner, pos) receives at most one
    # slot, so the runtime [P, C] payload scatter becomes a gather
    # (sentinel = S, out of the [0, S) slot range -> filled with +inf)
    tx_payload_slot = np.full((P, P, C), S, np.int64)
    for p in range(P):
        owners, pos = slot_rows_owner[p], slot_pos_rows[p]
        tx_payload_slot[p, owners, pos] = np.arange(len(owners))

    # ---- Trishla triangle candidates (host-side enumeration) --------------
    # Combined per-shard edge view: local edges [0, e_loc) then cut edges
    # [e_loc, e_loc + e_cut). Triangles (u, vi, vj): u and vi owned by this
    # shard (so (vi, vj) is visible), vj arbitrary, both (u, vi), (u, vj),
    # (vi, vj) present. Candidate to prune: (u, vj).
    tri_rows = [[] for _ in range(P)]
    if enumerate_triangles:
        # per-shard edge lookup: (src_local, dst_global) -> combined edge id
        for p in range(P):
            lsrc, ldst, lw = loc_rows_src[p], loc_rows_dst[p], loc_rows_w[p]
            csrc, cw_, cseg = cut_rows_src[p], cut_rows_w[p], cut_rows_seg[p]
            # global dst of cut edges: owner*block + dst_local via slots
            cg = (slot_rows_owner[p][cseg] * block + slot_rows_dstl[p][cseg]) if len(cseg) else np.zeros(0, np.int64)
            all_src = np.concatenate([lsrc, csrc])            # local u ids
            all_dstg = np.concatenate([ldst + p * block, cg]) # global v ids
            # edge ids must match the runtime combined view, where local
            # edges are PADDED to e_loc before the cut edges are appended
            eid = np.concatenate([np.arange(len(lsrc)),
                                  e_loc + np.arange(len(csrc))])
            # adjacency (by local src) for this shard
            order = np.argsort(all_src, kind="stable")
            s_srt, d_srt, e_srt = all_src[order], all_dstg[order], eid[order]
            starts = np.searchsorted(s_srt, np.arange(block + 1))
            budget = max_triangles_per_part
            tri = tri_rows[p]
            for u in range(block):
                lo, hi = starts[u], starts[u + 1]
                if hi - lo < 2:
                    continue
                nbrs = d_srt[lo:hi]
                nbr_eids = e_srt[lo:hi]
                for a in range(len(nbrs)):
                    vi = nbrs[a]
                    if vi // block != p:
                        continue  # (vi, vj) must be visible: vi owned here
                    vi_loc = vi - p * block
                    vlo, vhi = starts[vi_loc], starts[vi_loc + 1]
                    vi_out = d_srt[vlo:vhi]
                    vi_out_eids = e_srt[vlo:vhi]
                    # intersect N(u) and N(vi)
                    common, ia, ib = np.intersect1d(nbrs, vi_out, return_indices=True)
                    for t in range(len(common)):
                        vj = common[t]
                        if vj == u + p * block or vj == vi:
                            continue
                        tri.append((nbr_eids[ia[t]], nbr_eids[a], vi_out_eids[ib[t]]))
                        if budget is not None and len(tri) >= budget:
                            break
                    if budget is not None and len(tri) >= budget:
                        break
                if budget is not None and len(tri) >= budget:
                    break
    T = max(max((len(r) for r in tri_rows), default=0), 1)
    tri_uj = np.full((P, T), 0, np.int64)
    tri_ui = np.full((P, T), 0, np.int64)
    tri_ij = np.full((P, T), 0, np.int64)
    tri_valid = np.zeros((P, T), bool)
    for p in range(P):
        for k, (a, b, c) in enumerate(tri_rows[p]):
            tri_uj[p, k], tri_ui[p, k], tri_ij[p, k] = a, b, c
            tri_valid[p, k] = True

    # ---- dst-tiled layout of the local edges (Pallas relax kernel) --------
    # Built once here — NOT per solve. Per-shard layouts share n_vtiles
    # (same block) but can differ in chunk count; pad to the max so they
    # stack into one [P, n_vtiles, n_chunks, EB] array for the sim backend
    # (the shard_map backend slices its own shard back out).
    rx = dict(rx_src=None, rx_w=None, rx_dstrel=None, rx_eid=None)
    if relax_layout and layout == "ragged":
        # CSR-chunked: each shard keeps only its own ceil(count_t/eb) chunks
        # per tile, flattened to [total_chunks, EB] with a chunk->tile map.
        # Shards stack to [P, total_chunks_max, EB]; padding chunks are
        # inert (w=+inf) and carry the ctile sentinel n_vtiles.
        per_shard = []
        for p in range(P):
            src_r, w_r, dr_r, eid_r, ct_r, block_pad = build_dst_ragged_layout(
                loc_rows_src[p], loc_rows_dst[p], loc_rows_w[p], block,
                vb=relax_vb, eb=relax_eb, with_eid=True)
            per_shard.append((np.asarray(src_r), np.asarray(w_r),
                              np.asarray(dr_r), np.asarray(eid_r),
                              np.asarray(ct_r)))
        n_vtiles = block_pad // relax_vb
        tc = max(lay[0].shape[0] for lay in per_shard)
        rx_src = np.full((P, tc, relax_eb), block_pad - 1, np.int64)
        rx_w = np.full((P, tc, relax_eb), np.inf, np.float32)
        rx_dstrel = np.zeros((P, tc, relax_eb), np.int64)
        rx_eid = np.full((P, tc, relax_eb), e_loc, np.int64)
        rx_ctile = np.full((P, tc), n_vtiles, np.int64)
        for p, (src_r, w_r, dr_r, eid_r, ct_r) in enumerate(per_shard):
            nc = src_r.shape[0]
            rx_src[p, :nc] = src_r
            rx_w[p, :nc] = w_r
            rx_dstrel[p, :nc] = dr_r
            # builder sentinel is the shard's own edge count; restamp to the
            # padded-row sentinel e_loc so the runtime gather is uniform
            eid = eid_r.astype(np.int64)
            eid[eid == len(loc_rows_src[p])] = e_loc
            rx_eid[p, :nc] = eid
            rx_ctile[p, :nc] = ct_r
        rx = dict(rx_src=jnp.asarray(rx_src, jnp.int32),
                  rx_w=jnp.asarray(rx_w, jnp.float32),
                  rx_dstrel=jnp.asarray(rx_dstrel, jnp.int32),
                  rx_eid=jnp.asarray(rx_eid, jnp.int32),
                  rx_ctile=jnp.asarray(rx_ctile, jnp.int32))
    elif relax_layout:
        per_shard = []
        for p in range(P):
            src_t, w_t, dr_t, eid_t, _bp = build_dst_tiled_layout(
                loc_rows_src[p], loc_rows_dst[p], loc_rows_w[p], block,
                vb=relax_vb, eb=relax_eb, with_eid=True)
            per_shard.append((np.asarray(src_t), np.asarray(w_t),
                              np.asarray(dr_t), np.asarray(eid_t)))
        n_vtiles = per_shard[0][0].shape[0]
        block_pad = n_vtiles * relax_vb
        n_chunks = max(lay[0].shape[1] for lay in per_shard)
        rx_src = np.full((P, n_vtiles, n_chunks, relax_eb), block_pad - 1,
                         np.int64)
        rx_w = np.full((P, n_vtiles, n_chunks, relax_eb), np.inf, np.float32)
        rx_dstrel = np.zeros((P, n_vtiles, n_chunks, relax_eb), np.int64)
        rx_eid = np.full((P, n_vtiles, n_chunks, relax_eb), e_loc, np.int64)
        for p, (src_t, w_t, dr_t, eid_t) in enumerate(per_shard):
            nc = src_t.shape[1]
            rx_src[p, :, :nc] = src_t
            rx_w[p, :, :nc] = w_t
            rx_dstrel[p, :, :nc] = dr_t
            # builder sentinel is the shard's own edge count; restamp to the
            # padded-row sentinel e_loc so the runtime gather is uniform
            eid = eid_t.astype(np.int64)
            eid[eid == len(loc_rows_src[p])] = e_loc
            rx_eid[p, :, :nc] = eid
        rx = dict(rx_src=jnp.asarray(rx_src, jnp.int32),
                  rx_w=jnp.asarray(rx_w, jnp.float32),
                  rx_dstrel=jnp.asarray(rx_dstrel, jnp.int32),
                  rx_eid=jnp.asarray(rx_eid, jnp.int32))

    # ---- slot/msg-tiled layouts for the Pallas send + merge kernels -------
    # Same one-time host build as rx_*: per-shard layouts share the tile
    # count (slots padded to S / vertices to block are shard-uniform) but
    # can differ in chunk count; pad to the max so they stack to [P, ...].
    comm = dict(tx_src=None, tx_w=None, tx_segrel=None, tx_eid=None,
                mx_pos=None, mx_dstrel=None, mx_valid=None)
    if comm_layout and layout == "ragged":
        per_shard = []
        for p in range(P):
            src_r, w_r, seg_r, eid_r, ct_r, S_pad = build_slot_ragged_layout(
                cut_rows_src[p], cut_rows_seg[p], cut_rows_w[p], S,
                sb=send_sb, eb=send_eb)
            per_shard.append((np.asarray(src_r), np.asarray(w_r),
                              np.asarray(seg_r), np.asarray(eid_r),
                              np.asarray(ct_r)))
        n_stiles = S_pad // send_sb
        tc = max(lay[0].shape[0] for lay in per_shard)
        tx_src = np.zeros((P, tc, send_eb), np.int64)
        tx_w = np.full((P, tc, send_eb), np.inf, np.float32)
        tx_segrel = np.zeros((P, tc, send_eb), np.int64)
        tx_eid = np.full((P, tc, send_eb), e_cut, np.int64)
        tx_ctile = np.full((P, tc), n_stiles, np.int64)
        for p, (src_r, w_r, seg_r, eid_r, ct_r) in enumerate(per_shard):
            nc = src_r.shape[0]
            tx_src[p, :nc] = src_r
            tx_w[p, :nc] = w_r
            tx_segrel[p, :nc] = seg_r
            # builder sentinel is the shard's own cut count; restamp to the
            # padded-row sentinel e_cut so the runtime gather is uniform
            eid = eid_r.astype(np.int64)
            eid[eid == len(cut_rows_src[p])] = e_cut
            tx_eid[p, :nc] = eid
            tx_ctile[p, :nc] = ct_r

        mx_shards = [build_msg_ragged_layout(recv_idx[q], block, vb=merge_vb,
                                             eb=merge_eb) for q in range(P)]
        n_mtiles = mx_shards[0][4] // merge_vb
        mc = max(np.asarray(lay[0]).shape[0] for lay in mx_shards)
        mx_pos = np.zeros((P, mc, merge_eb), np.int64)
        mx_dstrel = np.zeros((P, mc, merge_eb), np.int64)
        mx_valid = np.zeros((P, mc, merge_eb), np.int64)
        mx_ctile = np.full((P, mc), n_mtiles, np.int64)
        for q, (pos_r, dr_r, v_r, ct_r, _bp) in enumerate(mx_shards):
            nc = np.asarray(pos_r).shape[0]
            mx_pos[q, :nc] = np.asarray(pos_r)
            mx_dstrel[q, :nc] = np.asarray(dr_r)
            mx_valid[q, :nc] = np.asarray(v_r)
            mx_ctile[q, :nc] = np.asarray(ct_r)

        comm = dict(tx_src=jnp.asarray(tx_src, jnp.int32),
                    tx_w=jnp.asarray(tx_w, jnp.float32),
                    tx_segrel=jnp.asarray(tx_segrel, jnp.int32),
                    tx_eid=jnp.asarray(tx_eid, jnp.int32),
                    tx_ctile=jnp.asarray(tx_ctile, jnp.int32),
                    mx_pos=jnp.asarray(mx_pos, jnp.int32),
                    mx_dstrel=jnp.asarray(mx_dstrel, jnp.int32),
                    mx_valid=jnp.asarray(mx_valid, jnp.int32),
                    mx_ctile=jnp.asarray(mx_ctile, jnp.int32))
    elif comm_layout:
        per_shard = []
        for p in range(P):
            src_t, w_t, seg_t, eid_t, _sp = build_slot_tiled_layout(
                cut_rows_src[p], cut_rows_seg[p], cut_rows_w[p], S,
                sb=send_sb, eb=send_eb)
            per_shard.append((np.asarray(src_t), np.asarray(w_t),
                              np.asarray(seg_t), np.asarray(eid_t)))
        n_stiles = per_shard[0][0].shape[0]
        n_chunks = max(lay[0].shape[1] for lay in per_shard)
        tx_src = np.zeros((P, n_stiles, n_chunks, send_eb), np.int64)
        tx_w = np.full((P, n_stiles, n_chunks, send_eb), np.inf, np.float32)
        tx_segrel = np.zeros((P, n_stiles, n_chunks, send_eb), np.int64)
        tx_eid = np.full((P, n_stiles, n_chunks, send_eb), e_cut, np.int64)
        for p, (src_t, w_t, seg_t, eid_t) in enumerate(per_shard):
            nc = src_t.shape[1]
            tx_src[p, :, :nc] = src_t
            tx_w[p, :, :nc] = w_t
            tx_segrel[p, :, :nc] = seg_t
            # builder sentinel is the shard's own cut count; restamp to the
            # padded-row sentinel e_cut so the runtime gather is uniform
            eid = eid_t.astype(np.int64)
            eid[eid == len(cut_rows_src[p])] = e_cut
            tx_eid[p, :, :nc] = eid

        mx_shards = [build_msg_tiled_layout(recv_idx[q], block, vb=merge_vb,
                                            eb=merge_eb) for q in range(P)]
        n_mtiles = mx_shards[0][3] // merge_vb
        m_chunks = max(lay[0].shape[1] for lay in mx_shards)
        mx_pos = np.zeros((P, n_mtiles, m_chunks, merge_eb), np.int64)
        mx_dstrel = np.zeros((P, n_mtiles, m_chunks, merge_eb), np.int64)
        mx_valid = np.zeros((P, n_mtiles, m_chunks, merge_eb), np.int64)
        for q, (pos_t, dr_t, v_t, _bp) in enumerate(mx_shards):
            nc = pos_t.shape[1]
            mx_pos[q, :, :nc] = np.asarray(pos_t)
            mx_dstrel[q, :, :nc] = np.asarray(dr_t)
            mx_valid[q, :, :nc] = np.asarray(v_t)

        comm = dict(tx_src=jnp.asarray(tx_src, jnp.int32),
                    tx_w=jnp.asarray(tx_w, jnp.float32),
                    tx_segrel=jnp.asarray(tx_segrel, jnp.int32),
                    tx_eid=jnp.asarray(tx_eid, jnp.int32),
                    mx_pos=jnp.asarray(mx_pos, jnp.int32),
                    mx_dstrel=jnp.asarray(mx_dstrel, jnp.int32),
                    mx_valid=jnp.asarray(mx_valid, jnp.int32))

    return SsspShards(
        loc_src=jnp.asarray(_pad2(loc_rows_src, e_loc, block, np.int64), jnp.int32),
        loc_dst=jnp.asarray(_pad2(loc_rows_dst, e_loc, block, np.int64), jnp.int32),
        loc_w=jnp.asarray(_pad2(loc_rows_w, e_loc, np.inf, np.float32), jnp.float32),
        cut_src=jnp.asarray(_pad2(cut_rows_src, e_cut, block, np.int64), jnp.int32),
        cut_w=jnp.asarray(_pad2(cut_rows_w, e_cut, np.inf, np.float32), jnp.float32),
        cut_seg=jnp.asarray(_pad2(cut_rows_seg, e_cut, S, np.int64), jnp.int32),
        slot_owner=jnp.asarray(_pad2(slot_rows_owner, S, 0, np.int64), jnp.int32),
        slot_dstl=jnp.asarray(_pad2(slot_rows_dstl, S, 0, np.int64), jnp.int32),
        slot_pos=jnp.asarray(_pad2(slot_pos_rows, S, 0, np.int64), jnp.int32),
        slot_valid=jnp.asarray(_pad2([np.ones(len(r), bool) for r in slot_rows_owner], S, False, bool)),
        slot_last=jnp.asarray(_pad2(slot_rows_last, S, 0, np.int64), jnp.int32),
        recv_idx=jnp.asarray(recv_idx, jnp.int32),
        tx_payload_slot=jnp.asarray(tx_payload_slot, jnp.int32),
        tri_uj=jnp.asarray(tri_uj, jnp.int32),
        tri_ui=jnp.asarray(tri_ui, jnp.int32),
        tri_ij=jnp.asarray(tri_ij, jnp.int32),
        tri_valid=jnp.asarray(tri_valid),
        inter_edges=jnp.asarray(inter_edges, jnp.int32),
        n_vertices=n,
        n_parts=P,
        block=block,
        seg_steps=(longest_run - 1).bit_length(),
        layout=layout,
        rx_vb=relax_vb,
        rx_eb=relax_eb,
        tx_sb=send_sb,
        tx_eb=send_eb,
        mx_vb=merge_vb,
        mx_eb=merge_eb,
        **rx,
        **comm,
    )
