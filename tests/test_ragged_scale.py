"""Ragged CSR-chunked layouts + streaming shard build (million-edge scale).

The acceptance bar for the ragged layout family is BIT-IDENTITY: same
stable dst-sort, same per-tile EB split, same Gauss-Seidel visitation order
as dense — the only difference is that padding chunks (inert, w=+inf) are
absent from the flat chunk grid. So every test here compares exact arrays,
never allclose.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (SsspConfig, build_shards, build_shards_stream,
                        solve_sim_batch)
from repro.graph import (SCALE_PRESETS, edge_chunks_of, get_generator,
                         preset_edge_stream, preset_graph, rmat_edge_stream,
                         rmat_graph)
from repro.graph.structure import csr_from_coo

TILE = dict(relax_vb=32, relax_eb=64, send_sb=32, send_eb=64,
            merge_vb=32, merge_eb=64)


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(scale=7, edge_factor=8, seed=3)


@pytest.fixture(scope="module")
def shard_pair(graph):
    dense = build_shards(graph, 4, **TILE)
    ragged = build_shards(graph, 4, layout="ragged", **TILE)
    return dense, ragged


@pytest.mark.parametrize("round_", ["staged", "fused"])
@pytest.mark.parametrize("exchange", ["bucket", "async"])
@pytest.mark.parametrize("k", [1, 3])
def test_ragged_bit_identity_matrix(shard_pair, round_, exchange, k):
    """Ragged distances == dense distances, bit for bit, across the round
    x exchange x batch-size matrix on an all-Pallas pipeline."""
    dense, ragged = shard_pair
    cfg = SsspConfig(round=round_, exchange=exchange, local_solver="pallas",
                     send_backend="pallas", merge_backend="pallas",
                     pallas_sweeps=4)
    srcs = [0, 17, 90][:k]
    dd, sd = solve_sim_batch(dense, srcs, cfg)
    dr, sr = solve_sim_batch(ragged, srcs, cfg)
    assert jnp.array_equal(dd, dr)
    assert int(sd.rounds) == int(sr.rounds)


def test_ragged_skewed_power_law_smaller():
    """On a skewed degree distribution with a small chunk size, the dense
    layout pays max-tile chunks on EVERY tile; ragged pays per-tile actual.
    The gap is the whole point of the CSR-chunked grid."""
    rng = np.random.default_rng(7)
    n = 512
    # power-law-ish dst concentration: most edges land in a few tiles
    dst = (n * rng.power(8, 4000)).astype(np.int64) % n
    src = rng.integers(0, n, 4000)
    keep = src != dst
    w = rng.uniform(1, 20, keep.sum()).astype(np.float32)
    g = csr_from_coo(src[keep], dst[keep], w, n)
    dense = build_shards(g, 4, relax_vb=32, relax_eb=32, send_sb=32,
                        send_eb=32, merge_vb=32, merge_eb=32)
    ragged = build_shards(g, 4, layout="ragged", relax_vb=32, relax_eb=32,
                          send_sb=32, send_eb=32, merge_vb=32, merge_eb=32)
    lb_r, lb_d = ragged.layout_bytes(), dense.layout_bytes()
    assert lb_r["total_bytes"] < lb_d["total_bytes"]
    assert lb_r["bytes_per_edge"] < lb_d["bytes_per_edge"]
    # and it still solves identically
    cfg = SsspConfig(local_solver="pallas", send_backend="pallas",
                     merge_backend="pallas", pallas_sweeps=4)
    dd, _ = solve_sim_batch(dense, [0], cfg)
    dr, _ = solve_sim_batch(ragged, [0], cfg)
    assert jnp.array_equal(dd, dr)


def test_stream_build_equals_batch(graph):
    """build_shards_stream over edge chunks == build_shards on the
    materialized graph, field for field (the dedup + ordering mirror)."""
    ragged = build_shards(graph, 4, layout="ragged", **TILE)
    stream = build_shards_stream(edge_chunks_of(graph, chunk_edges=999),
                                 graph.n_vertices, 4, **TILE)
    assert stream.seg_steps == ragged.seg_steps
    for f in ("loc_src", "loc_dst", "loc_w", "cut_src", "cut_w", "cut_seg",
              "slot_owner", "slot_dstl", "slot_pos", "slot_last", "recv_idx",
              "rx_src", "rx_w", "rx_dstrel", "rx_eid", "rx_ctile",
              "tx_src", "tx_w", "tx_segrel", "tx_eid", "tx_ctile",
              "tx_payload_slot", "mx_pos", "mx_dstrel", "mx_valid",
              "mx_ctile"):
        a, b = getattr(stream, f), getattr(ragged, f)
        assert a.shape == b.shape and bool(jnp.array_equal(a, b)), f


def test_stream_build_chunking_invariant(graph):
    """The chunk size the consumer picks must not leak into the shards."""
    a = build_shards_stream(edge_chunks_of(graph, chunk_edges=100),
                            graph.n_vertices, 4, **TILE)
    b = build_shards_stream(edge_chunks_of(graph, chunk_edges=10_000),
                            graph.n_vertices, 4, **TILE)
    assert jnp.array_equal(a.rx_src, b.rx_src)
    assert jnp.array_equal(a.rx_w, b.rx_w)
    assert jnp.array_equal(a.tx_ctile, b.tx_ctile)
    assert jnp.array_equal(a.recv_idx, b.recv_idx)


def test_endpoint_validation():
    src = np.array([0, 1, 9])
    dst = np.array([1, -2, 3])
    w = np.ones(3, np.float32)
    with pytest.raises(ValueError, match=r"out-of-range edge endpoints: "
                                         r"1 src, 1 dst"):
        build_shards_stream(iter([(src, dst, w)]), 8, 2)
    g = rmat_graph(scale=5, edge_factor=4, seed=1)
    bad = g._replace(dst=jnp.where(jnp.arange(g.dst.shape[0]) == 0,
                                   g.n_vertices + 3, g.dst)) \
        if hasattr(g, "_replace") else None
    if bad is not None:
        with pytest.raises(ValueError, match="out-of-range"):
            build_shards(bad, 2)


def test_layout_bytes_shape():
    g = rmat_graph(scale=6, edge_factor=4, seed=2)
    for layout in ("dense", "ragged"):
        sh = build_shards(g, 2, layout=layout, **TILE)
        lb = sh.layout_bytes()
        assert lb["layout"] == layout
        assert set(lb["groups"]) == {"relax", "send", "merge"}
        assert lb["total_bytes"] > 0
        assert lb["bytes_per_edge"] >= lb["ideal_bytes_per_edge"] * 0.99
        for grp in lb["groups"].values():
            assert grp["bytes"] >= grp["ideal_bytes"] * 0.99
        if layout == "dense":
            for grp in lb["groups"].values():
                assert grp["bytes"] == grp["dense_bytes"]


def test_generator_registry_and_presets():
    assert get_generator("rmat") is rmat_graph
    with pytest.raises(KeyError, match="unknown generator"):
        get_generator("nope")
    assert set(SCALE_PRESETS) >= {"scale-1e5", "scale-1e6", "scale-1e7"}
    g = preset_graph("scale-1e5")
    assert 5e4 <= g.n_edges <= 5e5


def test_rmat_stream_chunk_invariant():
    """Same (seed, chunk_edges) -> same edge multiset regardless of how the
    consumer batches; and the stream feeds build_shards_stream end to end."""
    def collect(ce):
        cs = list(rmat_edge_stream(scale=6, edge_factor=4, seed=9,
                                   chunk_edges=ce))
        return (np.concatenate([c[0] for c in cs]),
                np.concatenate([c[1] for c in cs]),
                np.concatenate([c[2] for c in cs]))
    s1, d1, w1 = collect(64)
    s2, d2, w2 = collect(64)
    assert np.array_equal(s1, s2) and np.array_equal(w1, w2)
    n, chunks = preset_edge_stream("scale-1e5", chunk_edges=1 << 14)
    sh = build_shards_stream(chunks, n, 4)
    assert sh.layout == "ragged"
    assert sh.layout_bytes()["n_edges"] > 5e4


def test_ragged_rejects_unknown_layout():
    g = rmat_graph(scale=5, edge_factor=4, seed=1)
    with pytest.raises(ValueError, match="unknown layout"):
        build_shards(g, 2, layout="csr")
