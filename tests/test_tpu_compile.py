"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a ``v5e:2x2`` topology
that is described, not attached, so these tests catch what interpret mode
cannot: programs and kernels the chip's compiler refuses. The topology is
described inside a module-scoped fixture, never at import time, so every
pytest worker collects the same tests and only the worker given this file
loads the TPU library.

- The served sim round program compiles for one chip at the shapes of the
  ``scale-1e5`` preset.
- The shmap whole-solve program compiles on a 4-chip mesh of described
  devices, with one shard per device and the all-to-all exchange in it.
- Each SSSP Pallas family (relax, send, merge, fused round) is refused by
  the v5e compiler today. Each refusal is a strict xfail: the first change
  that makes a family compile flips its test.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

K = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # an AOT compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _preset_shards(n_parts: int):
    from repro.core import build_shards_stream
    from repro.graph.generators import preset_edge_stream
    n, chunks = preset_edge_stream("scale-1e5")
    return build_shards_stream(chunks, n, n_parts, layout="ragged")


@pytest.fixture(scope="module")
def shards8():
    return _preset_shards(8)


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_sim_round_compiles_for_one_chip(shards8, one_chip,
                                         no_persistent_cache):
    from repro.core import SsspConfig, SsspEngine
    from repro.core.sssp import _init_carry
    eng = SsspEngine(shards8, SsspConfig(), "sim")
    carry = _init_carry(shards8, np.arange(K, dtype=np.int32), eng.cfg,
                        rank=None, vmapped=True, q_valid=np.ones(K, bool))
    compiled = eng.round_fn.lower(_spec(shards8, one_chip),
                                  _spec(carry, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert eng.trace_counts == {K: 1}


def test_shmap_solve_compiles_on_four_chips(topo, no_persistent_cache):
    from repro.core import SsspConfig, SsspEngine
    sh = _preset_shards(4)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("d",))
    shape_only = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sh)
    eng = SsspEngine(shape_only, SsspConfig(), "shmap", mesh, ("d",))
    repl = NamedSharding(mesh, P())
    compiled = eng.shmap_solver.lower(
        eng.shards, jax.ShapeDtypeStruct((K,), jnp.int32, sharding=repl),
        jax.ShapeDtypeStruct((K,), jnp.bool_, sharding=repl)).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    mem = compiled.memory_analysis()
    # one shard per device: the argument bytes are a quarter of the stack
    stack = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(sh))
    assert mem.argument_size_in_bytes < stack / 2


# -------------------------------------------- Pallas kernel refusals ----

def _shard0(sh):
    return jax.tree_util.tree_map(lambda x: x[0], sh)


def _lower_relax(s, chip):
    from repro.kernels.relax import relax_fixpoint_batch_ragged_pallas
    src, w, dstrel, eid, ctile = s.relax_layout
    bp = -(-s.block // s.rx_vb) * s.rx_vb
    f32 = jax.ShapeDtypeStruct((K, bp), jnp.float32, sharding=chip)
    args = (f32, f32) + tuple(_spec((ctile, src, w, dstrel, eid), chip))
    return relax_fixpoint_batch_ragged_pallas.lower(
        *args, vb=s.rx_vb, eb=s.rx_eb, n_sweeps=8, interpret=False)


def _lower_send(s, chip):
    from repro.kernels.send import send_pack_pallas
    src, w, seg, eid, ctile = s.send_layout
    S = s.slot_valid.shape[0]
    return send_pack_pallas.lower(
        jax.ShapeDtypeStruct((K, s.block), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((K, S), jnp.float32, sharding=chip),
        *_spec((s.slot_valid, src, w, seg, eid, ctile), chip),
        sb=s.tx_sb, eb=s.tx_eb, interpret=False)


def _lower_merge(s, chip):
    from repro.kernels.merge import merge_scatter_pallas
    pos, dstrel, valid, ctile = s.merge_layout
    M = s.recv_idx.size
    return merge_scatter_pallas.lower(
        jax.ShapeDtypeStruct((K, s.block), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((K, M), jnp.float32, sharding=chip),
        *_spec((pos, dstrel, valid, ctile), chip),
        vb=s.mx_vb, eb=s.mx_eb, interpret=False)


def _lower_round(s, chip):
    from repro.kernels.round import fused_round_pallas
    S = s.slot_valid.shape[0]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    return fused_round_pallas.lower(
        sds((K, s.block), jnp.float32), sds((K, s.block), jnp.bool_),
        sds((K,), jnp.bool_), sds((K, s.recv_idx.size), jnp.float32),
        sds((K, S), jnp.float32), _spec(s.slot_valid, chip),
        _spec(s.relax_layout, chip), _spec(s.send_layout, chip),
        _spec(s.merge_layout, chip), sds(s.loc_src.shape, jnp.bool_),
        sds(s.cut_src.shape, jnp.bool_), vb=s.rx_vb, sb=s.tx_sb, n_sweeps=8,
        dense=False, interpret=False)


# What the v5e compiler says today. Each family is first refused for its
# (1, EB) edge-chunk blocks; under that, its in-kernel gathers are refused
# too ("Only 2D gather is supported" for the 1-D jnp.take of relax,
# "Shape mismatch in input, indices and output" for the [K, bp]-by-[EB]
# jnp.take(axis=1) of send, merge and the fused round).
_BLOCK = ("block shape (1, 512) refused: the last two block dims must be "
          "divisible by 8 and 128")
_REFUSALS = {
    "relax": (_lower_relax, f"{_BLOCK}; then 'Only 2D gather is supported'"),
    "send": (_lower_send, f"{_BLOCK}; then 'Shape mismatch in input, "
             "indices and output' in the axis=1 gather"),
    "merge": (_lower_merge, f"{_BLOCK}; then 'Shape mismatch in input, "
              "indices and output' in the axis=1 gather"),
    "round": (_lower_round, f"{_BLOCK}; then both gather refusals"),
}


@pytest.mark.parametrize("family", [
    pytest.param(name, marks=pytest.mark.xfail(strict=True, reason=why,
                                               raises=ValueError))
    for name, (_, why) in _REFUSALS.items()])
def test_pallas_family_compiles_for_v5e(family, shards8, one_chip,
                                        no_persistent_cache):
    lower, _ = _REFUSALS[family]
    try:
        lower(_shard0(shards8), one_chip).compile()
    except ValueError as e:
        # only the TPU lowering's refusal counts as the expected failure
        assert "Pallas TPU lowering" in str(e), e
        raise
