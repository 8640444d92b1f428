"""SP-Async driver (paper Algorithm 2), batched over a query axis.

The paper solves ONE source per run; this driver is a multi-source *query
engine*: every solve takes K sources at once against the same partitioned
graph, so the one-time preprocessing (partitioning, message routing,
Trishla triangle enumeration, the dst-tiled Pallas edge layout) is
amortized across the whole batch. Sources are a TRACED ``[K]`` input —
``_init_carry`` scatters the source bit inside the program — so one
compiled program per K serves arbitrary source sets on both backends.
The public session surface lives in :mod:`repro.core.engine`
(``SsspEngine``); the free functions at the bottom of this module are
deprecated thin wrappers over it.

The round is an explicit *phase pipeline*: every phase (local, send,
exchange, merge, termination) is a stage resolved from the backend
registry in ``core/phases.py``, keyed by ``SsspConfig`` — so backends
compose freely (e.g. ``local_solver="pallas", send_backend="pallas",
merge_backend="xla"``) in both the sim and shmap drivers, and new stages
slot in without touching the loop. The send and merge phases each have an
``xla`` backend (a scatter-free segmented min / ``at[].min``) and a ``pallas``
backend (the slot-tiled ``kernels/send`` pack and msg-tiled
``kernels/merge`` scatter, over layouts precomputed by ``build_shards``).

Round structure (one outer round = one inter-partition Bellman-Ford step):

  1. *Local phase* — every shard with a non-empty frontier (in ANY live
     query) runs its local solver to a fixpoint for all K queries at once
     (the paper's intra-node Dijkstra, batched). Idle shards take the other
     branch of a ``lax.cond`` and evaluate a chunk of Trishla triangle
     candidates instead (the paper's "idle processes do edge elimination";
     pruning is query-invariant, so it is shared by the batch).
  2. *Send phase* — candidate distances over cut edges are pre-aggregated
     per boundary vertex (segment-min, per query) and placed into a
     statically-routed ``[K, P, C]`` send buffer; only improvements over
     ``last_sent`` are transmitted.
  3. *Exchange* — ONE collective moves the whole batch: bucketed
     ``all_to_all`` (default), dense ``all_reduce(min)`` (``pmin``), or
     dense ``all_to_all`` + local min (``a2a_dense``). The K payloads ride
     in the same transfer — batching multiplies payload bytes, not message
     count or latency terms.
  4. *Merge phase* — incoming messages scatter-min into the local distance
     block per query; improved vertices form the next frontier.
  5. *ToKa* — termination detection (see ``core/toka.py``), PER QUERY: a
     converged-query mask keeps finished queries from relaxing or sending
     while stragglers run; the round loop exits only when all K are done.

Backends:
  - ``sim``: the same phases vmapped over a stacked [P, ...] representation
    on one device, exchanges realized as array transposes/reductions. Used
    for correctness tests at any partition count without real devices.
  - ``shmap``: ``jax.shard_map`` over a mesh; the outer loop is a
    ``lax.while_loop`` *inside* the shard_map body so the whole solve is a
    single compiled program with collectives on the wire. This is the path
    the multi-pod dry-run lowers.

Per-shard state layout: ``dist``/``active`` are [K, block], ``last_sent``
is [K, S]; the Trishla ``pruned`` mask and triangle cursor carry no query
axis (edge pruning is a property of the graph, not of the source).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import faults as faults_mod
from repro.core import phases
from repro.core import warmstart  # noqa: F401  (registers warm_init backends)
from repro.core import toka as toka_mod
from repro.core.local_solver import local_fixpoint_batch
from repro.core.shards import SsspShards
from repro.core import trishla
from repro.distributed.collectives import (
    all_to_all_tiled, and_reduce, flat_rank, flat_size, or_reduce,
    ring_permute, ring_permute_rev,
)
from repro.kernels.merge import merge_scatter_pallas
from repro.kernels.round import fused_round_pallas, fused_round_rescue
from repro.kernels.send import send_pack_pallas, send_payload_bucket

INF = jnp.float32(jnp.inf)


@dataclasses.dataclass(frozen=True)
class SsspConfig:
    exchange: str = "bucket"        # bucket | pmin | a2a_dense
                                    #   | async | async_bucket | async_ppermute
    toka: str = "toka0"             # toka0 | toka1 | toka2 | toka3
    async_lag: int = 1              # rounds a deferred exchange buffers sends
    local_solver: str = "bellman"   # bellman | delta | pallas
    send_backend: str = "xla"       # xla | pallas (cut-edge segment-min pack)
    merge_backend: str = "xla"      # xla | pallas (incoming scatter-min)
    round: str = "staged"           # staged | fused (whole-round megakernel)
    warm_start: str = "none"        # none | landmark (engine-owned seed cache)
    delta: float = 4.0
    local_iters: int = 10_000
    pallas_sweeps: int = 8          # relaxation sweeps fused per pallas_call
    prune_online: bool = True       # Trishla in the idle branch
    prune_offline_passes: int = 0   # vectorized Trishla before the solve
    tri_chunk: int = 256
    max_rounds: int = 100_000
    faults: faults_mod.FaultPlan | None = None  # message failure model
    toka3_safety: float = 2.0       # toka3 quiet-streak safety factor

    def __post_init__(self):
        # eager validation against the phase registry: a typo'd backend
        # name fails HERE with the valid options, not deep inside tracing
        phases.validate("exchange", self.exchange)
        phases.validate("toka", self.toka)
        phases.validate("local_solver", self.local_solver)
        phases.validate("send", self.send_backend)
        phases.validate("merge", self.merge_backend)
        phases.validate("round", self.round)
        phases.validate("warm_init", self.warm_start)
        if self.faults is not None and not isinstance(self.faults,
                                                      faults_mod.FaultPlan):
            raise TypeError(f"cfg.faults must be a FaultPlan or None, got "
                            f"{type(self.faults).__name__}")
        if self.toka3_safety <= 0:
            raise ValueError("toka3_safety must be > 0")
        if self.async_lag < 1:
            raise ValueError("async_lag must be >= 1 (1 = double-buffered)")
        if self.async_lag != 1 and self.exchange not in ("async",
                                                         "async_bucket"):
            raise ValueError(
                f"async_lag={self.async_lag} only applies to the buffered "
                f"deferred exchanges ('async'/'async_bucket'); "
                f"exchange={self.exchange!r} ignores it "
                "(async_ppermute's lag is the ring distance)")

    @property
    def fault_plan(self) -> faults_mod.FaultPlan | None:
        """The ACTIVE fault plan (an all-zero plan degenerates to None, so
        the fault-free pipeline carries no fault state or RNG)."""
        if self.faults is not None and self.faults.active:
            return self.faults
        return None


class SsspStats(NamedTuple):
    rounds: jax.Array        # outer rounds until the LAST query converged
    relaxations: jax.Array   # total edge relaxations (TEPS numerator)
    msgs_sent: jax.Array
    msgs_recv: jax.Array
    pruned_edges: jax.Array
    q_rounds: jax.Array = None        # [K] rounds each query was live
    q_relaxations: jax.Array = None   # [K] edge relaxations per query
    q_converged: jax.Array = None     # [K] detector-done mask per query
    stale_merges: jax.Array = None    # improving late (queued/lagged) deliveries
    resends: jax.Array = None         # anti-entropy retransmissions
    n_dispatches: jax.Array = None    # data-plane dispatches (rounds x per-round)
    overlap_rounds: jax.Array = None  # rounds overlapping comm with compute
    bytes_moved: jax.Array = None     # logical payload bytes on the wire


class _Carry(NamedTuple):
    dist: Any         # [K, block] per shard
    active: Any       # [K, block] per shard
    pruned: Any       # [e_all] per shard (query-invariant)
    tri_cursor: Any
    last_sent: Any    # [K, S] per shard
    msg_count: Any    # [K] per shard
    toka2: Any        # Toka2State with [K]-leading fields
    done: Any         # [K] converged-query mask (globally agreed)
    rounds: Any
    q_rounds: Any     # [K]
    relaxations: Any  # [K]
    msgs_sent: Any    # [K]
    msgs_recv: Any    # [K]
    faults: Any       # FaultState per shard, or None (fault-free)
    streak: Any       # [K] consecutive globally-quiet rounds (toka3)
    stale: Any        # [K] improving stale merges from the fault queue
    resent: Any       # [K] anti-entropy retransmissions
    incoming: Any = None   # fused round: delivered-but-unmerged messages
    front_any: Any = None  # fused round: [K] "some frontier bit next round"
    inflight: Any = None   # deferred exchange: tuple of undelivered payloads
    overlap: Any = None    # scalar: rounds with comm/compute overlap
    comm_bytes: Any = None  # scalar: logical payload bytes this shard moved


# --------------------------------------------------------------------------
# per-shard phases (no leading P dim; vmapped by sim, direct under shard_map)
# --------------------------------------------------------------------------

def _phase_local(shard: SsspShards, dist, active, pruned, cursor, cfg: SsspConfig):
    """Batched local solve (any frontier non-empty) or Trishla chunk (idle).

    ``dist``/``active``: [K, block]. The pruned mask and cursor are shared
    across the batch."""
    e_loc = shard.loc_src.shape[0]
    nq = dist.shape[0]
    idle = ~jnp.any(active)

    def solve(dist, pruned, cursor):
        res = local_fixpoint_batch(
            dist, active, shard.loc_src, shard.loc_dst, shard.loc_w,
            pruned[:e_loc], solver=cfg.local_solver,
            max_iters=cfg.local_iters, delta=cfg.delta,
            relax_layout=shard.relax_layout, relax_vb=shard.rx_vb,
            pallas_sweeps=cfg.pallas_sweeps)
        return res.dist, pruned, cursor, res.relaxations, jnp.int32(0)

    @jax.named_scope("sssp.prune")
    def prune(dist, pruned, cursor):
        nrel0 = jnp.zeros((nq,), jnp.int32)
        if not cfg.prune_online:
            return dist, pruned, cursor, nrel0, jnp.int32(0)
        w_all = jnp.concatenate([shard.loc_w, shard.cut_w])
        new_pruned, new_cursor, n = trishla.prune_chunk(
            w_all, pruned, cursor, shard.tri_uj, shard.tri_ui, shard.tri_ij,
            shard.tri_valid, cfg.tri_chunk)
        return dist, new_pruned, new_cursor, nrel0, n

    return lax.cond(idle, prune, solve, dist, pruned, cursor)


def _scatter_dense(shard: SsspShards, send_val, blk: int):
    """Masked slot values -> dense [K, P, block] candidate rows addressed
    by (owner, dst_local). Shared by both send backends: the dense payload
    is bandwidth-bound assembly over S slots, not a reduction over the
    cut edges — there is nothing for a kernel to win."""
    Pn = shard.recv_idx.shape[0]
    return jax.vmap(
        lambda v: jnp.full((Pn, blk), INF, jnp.float32)
        .at[shard.slot_owner, shard.slot_dstl].min(v))(send_val)


def _slot_min(shard: SsspShards, cand):
    """Per-slot min of the cut-edge candidates ``cand`` [K, e_cut] -> [K, S],
    +inf on padded slots.

    The cut edges are sorted by slot, so each slot's candidates are one
    contiguous run of ``cut_seg``. A segmented inclusive min-scan by
    log-step doubling (``shard.seg_steps`` steps: shift right by k, keep the
    min where position i - k lies in the same run) leaves each run's min at
    its last edge, which one gather at ``slot_last`` reads out. No scatter:
    each step is one elementwise pass in the candidates' own layout. Min is
    exact, so the result is bit-identical to ``segment_min``."""
    seg = shard.cut_seg
    m = cand
    for step in range(shard.seg_steps):
        k = 1 << step
        same = seg == jnp.pad(seg[:-k], (k, 0), constant_values=-1)
        prev = jnp.pad(m[:, :-k], ((0, 0), (k, 0)), constant_values=INF)
        m = jnp.where(same, jnp.minimum(m, prev), m)
    return jnp.where(shard.slot_valid,
                     jnp.take(m, shard.slot_last, axis=1), INF)


@phases.register("send", "xla")
def _phase_send_xla(shard: SsspShards, dist, pruned, last_sent, *,
                    dense: bool, cfg: SsspConfig):
    """Generic XLA pack: gather of the cut edges' candidates, the per-slot
    min by doubling over the slot-sorted edges (``_slot_min``), the
    improvement masking, and the bucketed payload as a static gather
    (``tx_payload_slot``). No scatter, except the dense payload's.

    Returns (payload [K, P, C] (bucket) or [K, P, block] (dense),
    last_sent' [K, S], sends [K])."""
    e_loc = shard.loc_src.shape[0]
    w_cut = jnp.where(pruned[e_loc:], INF, shard.cut_w)            # [e_cut]
    d_src = jnp.take(dist, shard.cut_src, axis=1, mode="fill",
                     fill_value=float("inf"))                      # [K, e_cut]
    slot_val = _slot_min(shard, d_src + w_cut)                     # [K, S]
    improved = shard.slot_valid & (slot_val < last_sent)
    send_val = jnp.where(improved, slot_val, INF)
    new_last = jnp.where(improved, slot_val, last_sent)
    sends = jnp.sum(improved, axis=-1).astype(jnp.int32)           # [K]

    if dense:
        payload = _scatter_dense(shard, send_val, dist.shape[1])
    else:
        payload = send_payload_bucket(send_val, shard.tx_payload_slot)
    return payload, new_last, sends


@phases.register("send", "pallas")
def _phase_send_pallas(shard: SsspShards, dist, pruned, last_sent, *,
                       dense: bool, cfg: SsspConfig):
    """Slot-tiled Pallas pack (``kernels/send``): the segment-min, the
    ``last_sent`` improvement masking, and the send counts all run in ONE
    kernel over the ``tx_*`` layout precomputed by ``build_shards``; the
    bucketed payload scatter becomes a static gather (``tx_payload_slot``).
    Bit-identical to the XLA backend (min is exact; same per-edge sums)."""
    e_loc = shard.loc_src.shape[0]
    lay = shard.send_layout
    if len(lay) == 5:                       # ragged: + chunk→tile map
        src_t, w_t, segrel_t, eid_t, ctile = lay
    else:
        src_t, w_t, segrel_t, eid_t = lay
        ctile = None
    pruned_t = jnp.take(pruned[e_loc:].astype(jnp.int32), eid_t,
                        mode="fill", fill_value=0)
    send_val, new_last, sends = send_pack_pallas(
        dist, last_sent, shard.slot_valid, src_t, w_t, segrel_t, pruned_t,
        ctile, sb=shard.tx_sb, eb=shard.tx_eb)
    if dense:
        payload = _scatter_dense(shard, send_val, dist.shape[1])
    else:
        payload = send_payload_bucket(send_val, shard.tx_payload_slot)
    return payload, new_last, sends


def _merge_dense(dist, incoming):
    """Dense incoming is already owner-addressed: elementwise min, no
    scatter exists for a kernel to replace (shared by both backends)."""
    new = jnp.minimum(dist, incoming)
    recvs = jnp.sum(incoming < dist, axis=-1).astype(jnp.int32)
    return new, new < dist, recvs


@phases.register("merge", "xla")
def _phase_merge_xla(shard: SsspShards, dist, incoming, *, dense: bool,
                     cfg: SsspConfig):
    """Generic XLA scatter-min of incoming messages, per query.

    ``incoming``: [K, P, C] (bucket) or [K, block] (dense). Returns
    (new_dist [K, block], new_active [K, block], recvs [K])."""
    if dense:
        return _merge_dense(dist, incoming)
    nq = dist.shape[0]
    flat_val = incoming.reshape(nq, -1)
    flat_idx = shard.recv_idx.reshape(-1)   # sentinel = block -> dropped
    new = jax.vmap(
        lambda d, v: d.at[flat_idx].min(v, mode="drop"))(dist, flat_val)
    recvs = jnp.sum(jnp.isfinite(flat_val), axis=-1).astype(jnp.int32)
    return new, new < dist, recvs


@phases.register("merge", "pallas")
def _phase_merge_pallas(shard: SsspShards, dist, incoming, *, dense: bool,
                        cfg: SsspConfig):
    """Msg-tiled Pallas scatter (``kernels/merge``) over the static ``mx_*``
    routing layout: scatter-min, next-frontier, and receive counts in ONE
    kernel. Receive counting is bit-identical to the XLA backend because a
    payload position outside the layout (``recv_idx`` sentinel) can only
    ever carry +inf — no sender owns a slot for it."""
    if dense:
        return _merge_dense(dist, incoming)
    nq = dist.shape[0]
    lay = shard.merge_layout
    if len(lay) == 4:                       # ragged: + chunk→tile map
        mx_pos, mx_dstrel, mx_valid, ctile = lay
    else:
        mx_pos, mx_dstrel, mx_valid = lay
        ctile = None
    return merge_scatter_pallas(
        dist, incoming.reshape(nq, -1), mx_pos, mx_dstrel, mx_valid, ctile,
        vb=shard.mx_vb, eb=shard.mx_eb)


# --------------------------------------------------------------------------
# communication backends
# --------------------------------------------------------------------------

class ShmapComm:
    """Collectives inside a shard_map body (axis_names = flattened ring).

    Payloads carry a leading query axis [K, P, ...]; each exchange is still
    ONE collective — the batch is moved by transposing the query axis in,
    not by issuing K transfers."""

    def __init__(self, axis_names):
        self.axes = tuple(axis_names)

    def rank(self):
        return flat_rank(self.axes)

    def exchange_bucket(self, payload):
        recv = all_to_all_tiled(jnp.swapaxes(payload, 0, 1), self.axes)
        return jnp.swapaxes(recv, 0, 1)                          # [K, P, C]

    def exchange_pmin(self, payload):
        merged = lax.pmin(payload, self.axes)                    # [K, P, block]
        return lax.dynamic_index_in_dim(merged, self.rank(), 1,
                                        keepdims=False)          # [K, block]

    def exchange_a2a_dense(self, payload):
        recv = all_to_all_tiled(jnp.swapaxes(payload, 0, 1), self.axes)
        return jnp.min(recv, axis=0)                             # [K, block]

    def ring(self, tok):
        return ring_permute(tok, self.axes)

    def size(self) -> int:
        return flat_size(self.axes)

    def dest_dirs(self):
        """[P] bool routing table of the bidirectional ring transport:
        True = destination column d travels the FORWARD ring from this
        rank (ties at P/2 go forward). Routing the short way bounds every
        message's delivery lag by floor(P/2) hops."""
        Pn = self.size()
        r = self.rank()
        d = jnp.arange(Pn, dtype=jnp.int32)
        return ((d - r) % Pn) <= ((r - d) % Pn)

    def async_hop(self, fwd, bwd):
        """One bidirectional ring hop of the dense transit buffers
        ``[K, P, block]`` (column p = messages destined for rank p):
        advance ``fwd`` one hop forward and ``bwd`` one hop backward,
        deliver (and clear) the own-rank column of each. Each hop is a
        collective-permute whose operand is carried state, available at
        round START — XLA can run it concurrently with the relax kernel,
        which is the whole overlap story of ``exchange='async_ppermute'``.
        """
        fwd = ring_permute(fwd, self.axes)
        bwd = ring_permute_rev(bwd, self.axes)
        r = self.rank()
        inc = jnp.minimum(
            lax.dynamic_index_in_dim(fwd, r, 1, keepdims=False),
            lax.dynamic_index_in_dim(bwd, r, 1, keepdims=False))
        clear = jnp.full_like(inc, INF)
        fwd = lax.dynamic_update_index_in_dim(fwd, clear, r, 1)
        bwd = lax.dynamic_update_index_in_dim(bwd, clear, r, 1)
        return inc, fwd, bwd

    def min_all(self, x):
        return lax.pmin(x, self.axes)

    def all_any(self, flag):
        return or_reduce(flag, self.axes)

    def all_all(self, flag):
        return and_reduce(flag, self.axes)

    def total(self, x):
        return lax.psum(x, self.axes)


class SimComm:
    """Same contracts on stacked [P, ...] arrays (single-device simulator).

    Reductions act over the shard axis (axis 0) only, leaving the query
    axis intact: flags are [P, K], payloads [P_src, K, P_dst, ...]."""

    def __init__(self, n_parts: int):
        self.P = n_parts

    def rank(self):
        return jnp.arange(self.P, dtype=jnp.int32)

    # payload: [P_src, K, P_dst, *] stacked over senders
    def exchange_bucket(self, payload):
        return jnp.swapaxes(payload, 0, 2)            # [P_dst, K, P_src, C]

    def exchange_pmin(self, payload):
        # dense: [P_src, K, P_owner, block] -> per-owner min over senders
        return jnp.swapaxes(jnp.min(payload, axis=0), 0, 1)  # [P_owner, K, block]

    exchange_a2a_dense = exchange_pmin  # same single-device realization

    def ring(self, tok):
        return jax.tree_util.tree_map(lambda x: jnp.roll(x, 1, axis=0), tok)

    def size(self) -> int:
        return self.P

    def dest_dirs(self):
        # stacked [P_src, P_dst] forward-routing mask (see ShmapComm)
        Pn = self.P
        r = self.rank()[:, None]
        d = jnp.arange(Pn, dtype=jnp.int32)[None, :]
        return ((d - r) % Pn) <= ((r - d) % Pn)

    def async_hop(self, fwd, bwd):
        # stacked [P, K, P, block]: the +1/-1 rolls over the shard axis
        # are the single-device realization of the two ring permutes —
        # bit-level oracle of the shmap transport (same hop schedule)
        fwd = jnp.roll(fwd, 1, axis=0)
        bwd = jnp.roll(bwd, -1, axis=0)

        def one(f, b, r):
            inc = jnp.minimum(
                lax.dynamic_index_in_dim(f, r, 1, keepdims=False),
                lax.dynamic_index_in_dim(b, r, 1, keepdims=False))
            clear = jnp.full_like(inc, INF)
            f = lax.dynamic_update_index_in_dim(f, clear, r, 1)
            b = lax.dynamic_update_index_in_dim(b, clear, r, 1)
            return inc, f, b

        return jax.vmap(one)(fwd, bwd, self.rank())

    def all_any(self, flag):
        return jnp.broadcast_to(jnp.any(flag, axis=0), flag.shape)

    def all_all(self, flag):
        return jnp.broadcast_to(jnp.all(flag, axis=0), flag.shape)

    def total(self, x):
        return jnp.broadcast_to(jnp.sum(x, axis=0), x.shape)


# --------------------------------------------------------------------------
# exchange + termination stages (comm-parameterized)
# --------------------------------------------------------------------------

class ExchangeStage(NamedTuple):
    """Registry entry for an exchange mode: ``dense`` selects the payload
    shape the send/merge stages build/consume ([K, P, block] vs the
    bucketed [K, P, C]); ``run(comm, payload)`` realizes the transfer on
    either comm backend.

    ``deferred=True`` marks an ASYNCHRONOUS exchange: the round does not
    call ``run`` — it splits the transfer around the local compute so the
    collective only ever consumes state carried from previous rounds
    (``carry.inflight``), which is ready at round START and therefore
    overlappable with the relax kernel:

    - ``recv(comm, inflight) -> (incoming, inflight_mid)`` issues the
      collective over carried payloads and returns this round's delivered
      batch (round r receives what round r-1-lag sent);
    - ``push(comm, inflight_mid, payload) -> inflight'`` enqueues this
      round's fresh sends into the in-flight buffer (no collective);
    - ``init_inflight(sh, nq, cfg, vmapped)`` builds the empty (+inf)
      buffer pytree; ``flush(comm, inflight) -> [incoming, ...]`` drains
      every undelivered batch at exit time (``make_finalize``).
    """
    name: str
    dense: bool
    run: Any
    deferred: bool = False
    recv: Any = None
    push: Any = None
    init_inflight: Any = None
    flush: Any = None


def _async_bucket_recv(comm, inflight):
    # the all_to_all consumes ONLY carried state -> overlappable; the
    # oldest buffered payload is delivered, the rest keep aging
    return comm.exchange_bucket(inflight[0]), inflight[1:]


def _async_bucket_push(comm, inflight, payload):
    return inflight + (payload,)


def _async_bucket_init(sh, nq: int, cfg, vmapped: bool):
    Pn, C = sh.n_parts, sh.recv_idx.shape[-1]
    shape = (Pn, nq, Pn, C) if vmapped else (nq, Pn, C)
    return tuple(jnp.full(shape, INF, jnp.float32)
                 for _ in range(cfg.async_lag))


def _async_bucket_flush(comm, inflight):
    return [comm.exchange_bucket(b) for b in inflight]


def _async_ppermute_recv(comm, inflight):
    inc, fwd, bwd = comm.async_hop(*inflight)
    return inc, (fwd, bwd)


def _async_ppermute_push(comm, inflight, payload):
    # min-combine fresh sends into the transit buffers: the dense payload
    # is owner/vertex-addressed, so en-route combining is exact (bucketed
    # slot positions are source-relative and could NOT be combined here)
    fwd, bwd = inflight
    go_fwd = comm.dest_dirs()
    mask = (go_fwd[:, None, :, None] if go_fwd.ndim == 2
            else go_fwd[None, :, None])
    fwd = jnp.minimum(fwd, jnp.where(mask, payload, INF))
    bwd = jnp.minimum(bwd, jnp.where(mask, INF, payload))
    return (fwd, bwd)


def _async_ppermute_init(sh, nq: int, cfg, vmapped: bool):
    Pn, blk = sh.n_parts, sh.block
    shape = (Pn, nq, Pn, blk) if vmapped else (nq, Pn, blk)
    z = jnp.full(shape, INF, jnp.float32)
    return (z, z)


def _async_ppermute_flush(comm, inflight):
    # short-way routing bounds any message's remaining ring distance by
    # floor(P/2) hops; min-merge order is irrelevant (monotone merge)
    out = []
    for _ in range(comm.size() // 2):
        inc, inflight = _async_ppermute_recv(comm, inflight)
        out.append(inc)
    return out


phases.register("exchange", "bucket")(ExchangeStage(
    "bucket", dense=False, run=lambda comm, p: comm.exchange_bucket(p)))
phases.register("exchange", "pmin")(ExchangeStage(
    "pmin", dense=True, run=lambda comm, p: comm.exchange_pmin(p)))
phases.register("exchange", "a2a_dense")(ExchangeStage(
    "a2a_dense", dense=True, run=lambda comm, p: comm.exchange_a2a_dense(p)))

# deferred (asynchronous) exchanges: round r's relax runs concurrently
# with delivery of round r-1's sends, merged one round late. "async" is
# the double-buffered bucketed all-to-all (cfg.async_lag buffers; the
# sim realization is the bit-level oracle of the shmap one);
# "async_ppermute" decomposes the dense all-to-all into bidirectional
# ppermute neighbor hops over the partition ring — per-round latency is
# one neighbor hop instead of a full all-to-all barrier, at the price of
# ring-distance delivery lag (extra rounds). The ``run`` members are the
# synchronous realizations, used only by phase-isolation tooling.
_ASYNC_BUCKET = ExchangeStage(
    "async", dense=False, run=lambda comm, p: comm.exchange_bucket(p),
    deferred=True, recv=_async_bucket_recv, push=_async_bucket_push,
    init_inflight=_async_bucket_init, flush=_async_bucket_flush)
phases.register("exchange", "async")(_ASYNC_BUCKET)
phases.register("exchange", "async_bucket")(
    _ASYNC_BUCKET._replace(name="async_bucket"))
phases.register("exchange", "async_ppermute")(ExchangeStage(
    "async_ppermute", dense=True,
    run=lambda comm, p: comm.exchange_a2a_dense(p),
    deferred=True, recv=_async_ppermute_recv, push=_async_ppermute_push,
    init_inflight=_async_ppermute_init, flush=_async_ppermute_flush))

# round pipeline shape: the staged local/send/merge phase chain, or the
# whole-round Pallas megakernel (kernels/round) with one data-plane
# dispatch per round besides the exchange
phases.register("round", "staged")("staged")
phases.register("round", "fused")("fused")


def _round_mode(sh: SsspShards, cfg: SsspConfig) -> str:
    """Resolved round pipeline. ``round='fused'`` needs ALL THREE tiled
    layouts (relax ``rx_*``, send ``tx_*``, merge ``mx_*``) and raises when
    any is missing, like the per-phase pallas backends."""
    if cfg.round != "fused":
        return "staged"
    if sh.has_relax_layout and sh.has_send_layout and sh.has_merge_layout:
        return "fused"
    raise ValueError(
        "round='fused' needs the dst-/slot-/msg-tiled layouts, but the "
        "shards are missing some (build_shards was called with "
        "relax_layout=False or comm_layout=False)")


def dispatches_per_round(sh: SsspShards, cfg: SsspConfig) -> int:
    """Data-plane dispatches per round: the staged pipeline launches 4
    (local solve, send pack, exchange collective, merge scatter); the
    fused round launches 2 (megakernel + exchange collective)."""
    return 2 if _round_mode(sh, cfg) == "fused" else 4


def _vcall(fn, vmapped, *args, in_axes=0):
    """vmap ``fn`` over the query axis (always) and the shard axis (sim)."""
    f = jax.vmap(fn, in_axes=in_axes)
    if vmapped:
        f = jax.vmap(f)
    return f(*args)


def _quiescent(comm, new_active):
    """Globally-agreed [K] mask: no shard has a live frontier for query k."""
    idle = ~jnp.any(new_active, axis=-1)            # [K] (or [P, K] in sim)
    return comm.all_all(idle), idle


def _pending_inflight(inflight, vmapped: bool):
    """Per-query "this shard still holds undelivered async payload" bits
    ([K], or [P, K] stacked) — the deferred-exchange analogue of the fault
    queue's ``pending``: ORed into the termination view so no detector can
    declare quiescence over in-flight messages."""
    lead = 2 if vmapped else 1
    bits = None
    for a in jax.tree_util.tree_leaves(inflight):
        b = jnp.any(jnp.isfinite(a), axis=tuple(range(lead, a.ndim)))
        bits = b if bits is None else (bits | b)
    return bits


def _mask_payload(payload):
    """Mask unused per-(query, destination) payload columns to +inf and
    price this round's transfer. A column is used iff the send pack routed
    at least one ``last_sent`` improvement into it, so finiteness over the
    trailing slot/vertex axis IS the improvement-count mask; the masking
    enforces (rather than assumes) that unimproved columns ship no values,
    and the byte count is the honest wire cost the dense payloads hide at
    high P: 4 B x column width x used columns, summed over queries and
    destination ranks (and, in the stacked sim, over sender shards)."""
    used = jnp.any(jnp.isfinite(payload), axis=-1)
    nbytes = (jnp.int32(4 * payload.shape[-1])
              * jnp.sum(used).astype(jnp.int32))
    return jnp.where(used[..., None], payload, INF), nbytes


def _count_improving(shard: SsspShards, dist, incoming, dense: bool):
    """[K] improving deliveries of a batch vs the pre-merge distances.

    Under a deferred exchange EVERY delivered batch is at least one round
    old, so its improving merges are by definition stale merges — this is
    the per-round ``stale_merges`` accounting for the async modes (the
    fault injector's own stale counter is skipped there: queue releases
    are already min-merged into the delivered batch, and counting the
    final batch once avoids double counting)."""
    if dense:
        return jnp.sum(incoming < dist, axis=-1).astype(jnp.int32)
    nq = dist.shape[0]
    flat = incoming.reshape(nq, -1)
    d_t = jnp.take(dist, shard.recv_idx.reshape(-1), axis=1, mode="fill",
                   fill_value=-float("inf"))
    return jnp.sum(flat < d_t, axis=-1).astype(jnp.int32)


# Per-query termination stages: every detector runs K independent instances
# (toka2 circulates K tokens in the same ring hop). Uniform signature
# returning ([K] done mask, toka2', streak'). ``new_active`` here is the
# TERMINATION view of the frontier: under fault injection the round ORs in
# per-query ``pending`` bits (messages still in the delay queue, or drops
# awaiting an anti-entropy resend), so no detector can declare quiescence
# over in-flight state — the real frontier in the carry stays untouched.

@phases.register("toka", "toka0")
def _toka0_stage(cfg, comm, carry, new_active, sends, recvs, inter_edges,
                 n_parts, rank, vmapped: bool):
    quiescent, _ = _quiescent(comm, new_active)
    return quiescent, carry.toka2, carry.streak


@phases.register("toka", "toka1")
def _toka1_stage(cfg, comm, carry, new_active, sends, recvs, inter_edges,
                 n_parts, rank, vmapped: bool):
    quiescent, _ = _quiescent(comm, new_active)
    ie = inter_edges[:, None] if vmapped else inter_edges
    vote = toka_mod.toka1_vote(carry.msg_count + recvs, ie, n_parts)
    return quiescent | comm.all_all(vote), carry.toka2, carry.streak


@phases.register("toka", "toka2")
def _toka2_stage(cfg, comm, carry, new_active, sends, recvs, inter_edges,
                 n_parts, rank, vmapped: bool):
    # Safra's counter invariant (sum of sent-received returns to 0)
    # only holds for message transports. The dense exchanges (pmin /
    # a2a_dense) are broadcasts — a sent improvement is not 1:1 with a
    # counted receive — so they run the color-only DFG variant
    # (counters zeroed; sound under BSP where nothing is in flight at
    # round boundaries). Found by the §Perf study: with counters, the
    # ring never observes a zero sum and toka2 spins to max_rounds.
    # Fault injection breaks the invariant the same way (a dropped send
    # is never received; a released duplicate is an unmatched receive),
    # so an active FaultPlan also forces the color-only variant — the
    # pending-aware idle bit already holds the ring open for in-flight
    # messages.
    _, idle = _quiescent(comm, new_active)
    counters_ok = (not phases.resolve("exchange", cfg.exchange).dense
                   and cfg.fault_plan is None)
    if counters_ok:
        acct = _vcall(toka_mod.toka2_account, vmapped, carry.toka2,
                      sends, recvs)
    else:
        zero = jnp.zeros_like(sends)
        acct = _vcall(toka_mod.toka2_account, vmapped, carry.toka2,
                      zero, zero)
        # blacken on send still applies (color drives termination)
        color = jnp.where(sends > 0, jnp.int32(1), acct.color)
        acct = acct._replace(color=color)
    st, outgoing = _vcall(partial(toka_mod.toka2_forward, n_parts=n_parts),
                          vmapped, acct, rank, idle, in_axes=(0, None, 0))
    incoming = comm.ring(outgoing)
    st = _vcall(toka_mod.toka2_absorb, vmapped, st, incoming)
    return comm.all_all(st.seen_red), st, carry.streak


@phases.register("toka", "toka3")
def _toka3_stage(cfg, comm, carry, new_active, sends, recvs, inter_edges,
                 n_parts, rank, vmapped: bool):
    # The paper's timeout heuristic: count consecutive rounds with NO
    # global activity for a query (no frontier, no sends, no receives,
    # nothing pending in a fault queue) and stop once the streak reaches
    # the bound computed from inter-edge and partition counts
    # (toka.toka3_bound; fault plans widen it by their slack). Activity is
    # agreed by one all-reduce, so every shard advances the same streak
    # and the vote needs no second collective.
    slack = 0 if cfg.fault_plan is None else cfg.fault_plan.fault_slack
    ex_st = phases.resolve("exchange", cfg.exchange)
    if getattr(ex_st, "deferred", False):
        # a deferred exchange keeps messages legitimately in flight across
        # round boundaries: widen the timeout by the worst-case delivery
        # lag (the buffered rounds, plus the short-way ring radius for the
        # dense hop transport). The pending bits already hold the streak
        # at zero while payload is in flight; the slack covers the gap
        # between a send and its first visibility as pending activity.
        slack += cfg.async_lag + (n_parts // 2 if ex_st.dense else 0)
    # the bound must be computed from the GLOBAL cut count: a per-shard
    # bound lets devices disagree on the timeout, which under shard_map
    # means different while-loop trip counts — a collective rendezvous
    # deadlock. comm.total() also matches the host-side toka3_timeout
    # tool, which has always taken the total inter-edge count.
    ie_total = comm.total(jnp.asarray(inter_edges).astype(jnp.int32))
    bound = toka_mod.toka3_bound(ie_total, n_parts, cfg.toka3_safety,
                                 slack)
    act = jnp.any(new_active, axis=-1) | (sends > 0) | (recvs > 0)
    busy = comm.all_any(act)
    streak = jnp.where(busy, 0, carry.streak + 1)
    if vmapped:
        bound = bound[:, None]          # [P] totals -> broadcast [P, K]
    return streak >= bound, carry.toka2, streak


# --------------------------------------------------------------------------
# pipeline resolution + round
# --------------------------------------------------------------------------

class RoundPipeline(NamedTuple):
    """The round's stages, resolved once per (shards, config) from the
    backend registry. ``local``/``send``/``merge`` are per-shard callables
    (vmapped by the sim backend, direct under shard_map); ``exchange`` is
    an :class:`ExchangeStage`; ``toka`` is the termination stage."""
    local: Any
    send: Any
    exchange: ExchangeStage
    merge: Any
    toka: Any


def build_pipeline(sh: SsspShards, cfg: SsspConfig) -> RoundPipeline:
    """Resolve every phase backend for these shards.

    Pallas send/merge backends need the ``tx_*``/``mx_*`` layouts from
    ``build_shards``; when absent (``comm_layout=False``) this raises,
    like the pallas local solver's ``relax_layout`` rule. An active
    ``cfg.faults`` plan wraps the resolved exchange stage with the
    fault-injecting decorator (:func:`repro.core.faults.wrap_exchange`) —
    the transfer itself is untouched; delivery goes through the injector."""
    ex = phases.resolve("exchange", cfg.exchange)
    if cfg.fault_plan is not None:
        ex = faults_mod.wrap_exchange(ex, cfg.fault_plan)
    if cfg.send_backend == "pallas" and not sh.has_send_layout:
        raise ValueError(
            "send_backend='pallas' needs the slot-tiled cut-edge layout, but "
            "the shards carry none (build_shards was called with "
            "comm_layout=False)")
    if cfg.merge_backend == "pallas" and not sh.has_merge_layout:
        raise ValueError(
            "merge_backend='pallas' needs the msg-tiled receive layout, but "
            "the shards carry none (build_shards was called with "
            "comm_layout=False)")
    return RoundPipeline(
        local=partial(_phase_local, cfg=cfg),
        send=partial(phases.resolve("send", cfg.send_backend),
                     dense=ex.dense, cfg=cfg),
        exchange=ex,
        merge=partial(phases.resolve("merge", cfg.merge_backend),
                      dense=ex.dense, cfg=cfg),
        toka=phases.resolve("toka", cfg.toka))


def _phase_fused(shard: SsspShards, dist, front_in, live, incoming, last_sent,
                 pruned, *, dense: bool, cfg: SsspConfig):
    """One megakernel dispatch: merge + local fixpoint + send pack
    (``kernels/round``), plus the payload assembly.

    Returns (new_dist, payload, last_sent', sends, nrel, resid) — a
    non-empty ``resid`` row means ``cfg.pallas_sweeps`` in-kernel sweeps
    did not reach the local fixpoint and the caller must rescue the round
    with :func:`_phase_fused_rescue` before using the send outputs."""
    e_loc = shard.loc_src.shape[0]
    nq = dist.shape[0]
    inc = incoming if dense else incoming.reshape(nq, -1)
    new_dist, send_val, new_last, nrel, sends, resid = fused_round_pallas(
        dist, front_in, live, inc, last_sent, shard.slot_valid,
        shard.relax_layout, shard.send_layout, shard.merge_layout,
        pruned[:e_loc], pruned[e_loc:], vb=shard.rx_vb, sb=shard.tx_sb,
        n_sweeps=cfg.pallas_sweeps, dense=dense)
    if dense:
        payload = _scatter_dense(shard, send_val, dist.shape[1])
    else:
        payload = send_payload_bucket(send_val, shard.tx_payload_slot)
    return new_dist, payload, new_last, sends, nrel, resid


def _phase_fused_rescue(shard: SsspShards, dist, resid, last_sent, pruned, *,
                        dense: bool, cfg: SsspConfig):
    """Finish a fused round whose in-kernel sweeps left a residual
    frontier: continue the fixpoint with the batched relax kernel and
    re-pack the sends against the ORIGINAL ``last_sent`` (the megakernel's
    send outputs were computed from unconverged distances). Returns
    (new_dist, payload, last_sent', sends, nrel_extra)."""
    e_loc = shard.loc_src.shape[0]
    new_dist, send_val, new_last, nrel_extra, sends = fused_round_rescue(
        dist, resid, last_sent, shard.slot_valid, shard.relax_layout,
        shard.send_layout, pruned[:e_loc], pruned[e_loc:], vb=shard.rx_vb,
        sb=shard.tx_sb, n_sweeps=cfg.pallas_sweeps,
        max_iters=cfg.local_iters)
    if dense:
        payload = _scatter_dense(shard, send_val, dist.shape[1])
    else:
        payload = send_payload_bucket(send_val, shard.tx_payload_slot)
    return new_dist, payload, new_last, sends, nrel_extra


def make_finalize(sh: SsspShards, cfg: SsspConfig, comm, vmapped: bool):
    """Exit-time ``fn(carry) -> dist`` merging every delivered-but-unmerged
    and in-flight message batch, or None when nothing can be outstanding
    (staged round + synchronous exchange).

    The fused round rotates the phase chain — a round merges the PREVIOUS
    round's delivered messages — so the loop can exit with one batch of
    delivered-but-unmerged messages in ``carry.incoming``. A deferred
    (async) exchange can additionally exit with undelivered payload in
    ``carry.inflight`` (e.g. a ``max_rounds`` or toka1-budget exit while
    messages ride the pipe): its ``flush`` drains every buffered batch
    here. In both cases accounting already happened (or the detectors held
    termination open via the pending bits); only the value merges are
    outstanding, and min-merge order is irrelevant. The merges run
    unconditionally: correctness of the final distances must not depend on
    the detector's reasoning."""
    ex = phases.resolve("exchange", cfg.exchange)
    deferred = bool(getattr(ex, "deferred", False))
    fused = _round_mode(sh, cfg) == "fused"
    if not fused and not deferred:
        return None
    dense = ex.dense

    def fin(shard, dist, incoming):
        if dense:
            return jnp.minimum(dist, incoming)
        nq = dist.shape[0]
        flat_val = incoming.reshape(nq, -1)
        flat_idx = shard.recv_idx.reshape(-1)
        return jax.vmap(
            lambda d, v: d.at[flat_idx].min(v, mode="drop"))(dist, flat_val)

    if vmapped:
        merge = lambda dist, incoming: jax.vmap(fin)(sh, dist, incoming)
    else:
        merge = lambda dist, incoming: fin(sh, dist, incoming)

    @jax.named_scope("sssp.finalize")
    def finalize(carry: _Carry):
        dist = carry.dist
        if fused:
            dist = merge(dist, carry.incoming)
        if deferred:
            for inc in ex.flush(comm, carry.inflight):
                dist = merge(dist, inc)
        return dist

    return finalize


def _make_round_fused(sh: SsspShards, cfg: SsspConfig, comm, vmapped: bool,
                      n_parts: int):
    """The fused-round variant of :func:`_make_round`.

    The phase chain is ROTATED relative to the staged round so the three
    dst-tiled phases land in one dispatch: round r merges the messages
    DELIVERED in round r-1 (held un-merged in ``carry.incoming``), chases
    the resulting frontier to the local fixpoint, packs the sends, and
    exchanges — all activity accounting (receives, frontier-any bits, the
    termination view) happens at delivery time from ``new_dist`` and the
    raw payload, so every per-round statistic and every detector sees
    exactly the sequence the staged pipeline produces (bit-identity is
    enforced by tests/test_fused_round.py). The idle branch (Trishla
    pruning) runs BEFORE the kernel as its own ``lax.cond`` — merge and
    send must still run on idle rounds, so only the prune work is gated."""
    ex = phases.resolve("exchange", cfg.exchange)
    fp = cfg.fault_plan
    if fp is not None:
        ex = faults_mod.wrap_exchange(ex, fp)
    dense = ex.dense
    deferred = bool(getattr(ex, "deferred", False))
    toka_f = phases.resolve("toka", cfg.toka)
    fused_f = partial(_phase_fused, dense=dense, cfg=cfg)
    rescue_f = partial(_phase_fused_rescue, dense=dense, cfg=cfg)

    def prune_f(shard, idle, pruned, cursor):
        if not cfg.prune_online:
            return pruned, cursor

        def prune(p, c):
            w_all = jnp.concatenate([shard.loc_w, shard.cut_w])
            new_p, new_c, _n = trishla.prune_chunk(
                w_all, p, c, shard.tri_uj, shard.tri_ui, shard.tri_ij,
                shard.tri_valid, cfg.tri_chunk)
            return new_p, new_c

        return lax.cond(idle, prune, lambda p, c: (p, c), pruned, cursor)

    def account_f(shard, dist, incoming):
        """Receive counts + per-query any-improvement bits of a delivered
        batch against the post-relax distances — the staged merge phase's
        accounting, computed WITHOUT merging (the values merge next
        round). Bucket: a message improves iff it beats the distance at
        its routed target (sentinel rows gather -inf, never true). Also
        returns the improving-delivery count ``n_imp`` — the deferred
        exchanges' stale-merge tally (see :func:`_count_improving`)."""
        if dense:
            n_imp = jnp.sum(incoming < dist, axis=-1).astype(jnp.int32)
            recvs = n_imp
            any_imp = n_imp > 0
        else:
            nq = dist.shape[0]
            flat = incoming.reshape(nq, -1)
            idx = shard.recv_idx.reshape(-1)
            recvs = jnp.sum(jnp.isfinite(flat), axis=-1).astype(jnp.int32)
            d_t = jnp.take(dist, idx, axis=1, mode="fill",
                           fill_value=-float("inf"))
            n_imp = jnp.sum(flat < d_t, axis=-1).astype(jnp.int32)
            any_imp = n_imp > 0
        return any_imp, recvs, n_imp

    deliver_f = getattr(ex, "deliver", None)
    prune_v, fused_v, rescue_v, account_v = (prune_f, fused_f, rescue_f,
                                             account_f)
    if vmapped:
        prune_v = jax.vmap(prune_f)
        fused_v = jax.vmap(fused_f)
        rescue_v = jax.vmap(rescue_f)
        account_v = jax.vmap(account_f)
        if deliver_f is not None:
            deliver_f = jax.vmap(deliver_f)

    def rounds_fn(carry: _Carry) -> _Carry:
        live = ~carry.done                             # [K] ([P, K] sim)
        idle = ~jnp.any(carry.front_any & live, axis=-1)

        # deferred exchange: issue the collective FIRST — it consumes only
        # carried state, so XLA is free to overlap it with the megakernel.
        # With async the total merge lag is 2 (one round of incoming
        # rotation + one round in flight); correctness is lag-independent
        # (monotone min merge), only round counts move.
        incoming_new = inflight_mid = delivering = None
        if deferred:
            with jax.named_scope("sssp.exchange"):
                pend0 = _pending_inflight(carry.inflight, vmapped)
                delivering = jnp.any(pend0, axis=-1)    # per-shard bool
                incoming_new, inflight_mid = ex.recv(comm, carry.inflight)

        with jax.named_scope("sssp.prune"):
            pruned, cursor = prune_v(sh, idle, carry.pruned,
                                     carry.tri_cursor)
        # injected frontier (warm-start seeds / source bits on round 0;
        # zeroed by every fused round thereafter)
        front_in = carry.active & live[..., None]

        # anti-entropy resend window (same latch protocol as the staged
        # round; see _make_round)
        resend_now = None
        last_in = carry.last_sent
        if fp is not None and fp.resend_period > 0:
            with jax.named_scope("sssp.send"):
                period = jnp.int32(fp.resend_period)
                period_hit = (carry.rounds % period) == (period - 1)
                need = comm.all_any(carry.faults.unhealed)
                resend_now = period_hit & need
                last_in = jnp.where(resend_now[..., None], INF,
                                    carry.last_sent)

        # rescue: predicate reduced over the WHOLE shard stack, so the sim
        # backend branches for real (an unbatched lax.cond) and the common
        # all-converged round never pays for the continuation
        def rescue(args):
            d, pl_, ls, sd, nr, rs, li, pr = args
            d2, pl2, ls2, sd2, extra = rescue_v(sh, d, rs, li, pr)
            return d2, pl2, ls2, sd2, nr + extra

        def keep(args):
            d, pl_, ls, sd, nr, _rs, _li, _pr = args
            return d, pl_, ls, sd, nr

        with jax.named_scope("sssp.fused"):
            dist, payload, last_sent, sends, nrel, resid = fused_v(
                sh, carry.dist, front_in, live, carry.incoming, last_in,
                pruned)
            dist, payload, last_sent, sends, nrel = lax.cond(
                jnp.any(resid > 0), rescue, keep,
                (dist, payload, last_sent, sends, nrel, resid, last_in,
                 pruned))

        with jax.named_scope("sssp.send"):
            payload, nbytes = _mask_payload(payload)
        with jax.named_scope("sssp.exchange"):
            if deferred:
                inflight = ex.push(comm, inflight_mid, payload)
            else:
                incoming_new = ex.run(comm, payload)
                inflight = carry.inflight

        fstate, stale, pending = carry.faults, None, None
        if deliver_f is not None:
            with jax.named_scope("sssp.deliver"):
                if resend_now is not None:
                    fstate = fstate._replace(
                        unhealed=jnp.where(resend_now, False,
                                           fstate.unhealed))
                rkey = jax.random.fold_in(jax.random.PRNGKey(fp.seed),
                                          carry.rounds)
                rank = comm.rank()
                if vmapped:
                    keys = jax.vmap(
                        lambda r: jax.random.fold_in(rkey, r))(rank)
                else:
                    keys = jax.random.fold_in(rkey, rank)
                incoming_new, fstate, stale, pending = deliver_f(
                    sh, dist, incoming_new, fstate, keys)

        with jax.named_scope("sssp.merge"):
            any_imp, recvs, n_imp = account_v(sh, dist, incoming_new)

        # the detectors only consume any(new_active, -1), so a synthetic
        # [.., K, 1] mask carrying the any-improvement bit is equivalent
        # to the staged merge's full frontier plane
        with jax.named_scope("sssp.toka"):
            toka_flag = any_imp
            if pending is not None:
                toka_flag = toka_flag | pending
            if deferred:
                toka_flag = toka_flag | _pending_inflight(inflight, vmapped)
            done, toka2, streak = toka_f(
                cfg, comm, carry, toka_flag[..., None], sends, recvs,
                sh.inter_edges, n_parts, comm.rank(), vmapped)

        stale_c, resent_c = carry.stale, carry.resent
        if deferred:
            # every delivered batch is >= 1 round old: its improving
            # merges ARE the stale merges (queue releases were already
            # min-merged into it, so the injector's counter is skipped)
            stale_c = stale_c + n_imp
        elif stale is not None:
            stale_c = stale_c + stale
        if resend_now is not None:
            resent_c = resent_c + jnp.where(resend_now, sends,
                                            0).astype(jnp.int32)
        overlap_c = carry.overlap
        if deferred:
            flag = delivering & ~idle
            bit = jnp.any(flag) if vmapped else comm.all_any(flag)
            overlap_c = overlap_c + bit.astype(jnp.int32)
        running = (~carry.done).astype(jnp.int32)
        return _Carry(
            dist=dist, active=jnp.zeros_like(carry.active), pruned=pruned,
            tri_cursor=cursor, last_sent=last_sent,
            msg_count=carry.msg_count + recvs, toka2=toka2,
            done=carry.done | done, rounds=carry.rounds + 1,
            q_rounds=carry.q_rounds + running,
            relaxations=carry.relaxations + nrel.astype(jnp.int32),
            msgs_sent=carry.msgs_sent + sends.astype(jnp.int32),
            msgs_recv=carry.msgs_recv + recvs.astype(jnp.int32),
            faults=fstate, streak=streak, stale=stale_c, resent=resent_c,
            incoming=incoming_new, front_any=any_imp, inflight=inflight,
            overlap=overlap_c, comm_bytes=carry.comm_bytes + nbytes)

    return rounds_fn


def _make_round(shard_or_stack: SsspShards, cfg: SsspConfig, comm, vmapped: bool,
                n_parts: int):
    """Returns round(carry) -> carry, shared by both backends.

    ``vmapped=True``: per-shard phases are vmapped over stacked arrays.
    ``vmapped=False``: phases run directly on a single shard's slice
    (inside shard_map)."""
    sh = shard_or_stack
    if _round_mode(sh, cfg) == "fused":
        return _make_round_fused(sh, cfg, comm, vmapped, n_parts)
    pipe = build_pipeline(sh, cfg)
    fp = cfg.fault_plan
    ex = pipe.exchange
    deferred = bool(getattr(ex, "deferred", False))

    local_f, send_f, merge_f = pipe.local, pipe.send, pipe.merge
    deliver_f = getattr(pipe.exchange, "deliver", None)
    stale_f = partial(_count_improving, dense=ex.dense)
    if vmapped:
        local_f = jax.vmap(local_f)
        send_f = jax.vmap(send_f)
        merge_f = jax.vmap(merge_f)
        stale_f = jax.vmap(stale_f)
        if deliver_f is not None:
            deliver_f = jax.vmap(deliver_f)

    def rounds_fn(carry: _Carry) -> _Carry:
        # deferred exchange: the collective is issued FIRST and consumes
        # only carried state (round r delivers round r-1-lag's sends), so
        # XLA is free to overlap it with the local relax below — the
        # paper's asynchronous mode: no per-round barrier between a
        # shard's compute and the delivery of its neighbors' messages
        incoming = inflight_mid = delivering = None
        if deferred:
            with jax.named_scope("sssp.exchange"):
                pend0 = _pending_inflight(carry.inflight, vmapped)
                delivering = jnp.any(pend0, axis=-1)    # per-shard bool
                incoming, inflight_mid = ex.recv(comm, carry.inflight)

        # converged-query mask: finished queries stop relaxing and sending
        # while stragglers run (their frontier is forced empty)
        act = carry.active & ~carry.done[..., None]
        with jax.named_scope("sssp.local"):
            dist, pruned, cursor, nrel, nprune = local_f(
                sh, carry.dist, act, carry.pruned, carry.tri_cursor)

        # anti-entropy: every resend_period-th round, senders forget their
        # last_sent floor for any query some receiver reported an unhealed
        # mattering drop on (one all-reduce of the latches), so the send
        # phase retransmits EVERY current slot minimum for it — slot
        # values are monotone non-increasing, so the recomputed floor is
        # correct and the dropped message is healed by this round's copy
        # (unless dropped again; the receiver's latch re-arms and keeps
        # termination open). Gating on the latch — rather than resending
        # unconditionally — is what lets the system ever look quiet: a
        # periodic blind burst would blacken toka2's ring and reset
        # toka3's streak forever.
        resend_now = None
        last_in = carry.last_sent
        with jax.named_scope("sssp.send"):
            if fp is not None and fp.resend_period > 0:
                period = jnp.int32(fp.resend_period)
                period_hit = (carry.rounds % period) == (period - 1)
                need = comm.all_any(carry.faults.unhealed)  # [K] ([P, K] sim)
                resend_now = period_hit & need
                last_in = jnp.where(resend_now[..., None], INF,
                                    carry.last_sent)
            payload, last_sent, sends = send_f(sh, dist, pruned, last_in)
            payload, nbytes = _mask_payload(payload)
        with jax.named_scope("sssp.exchange"):
            if deferred:
                inflight = ex.push(comm, inflight_mid, payload)
            else:
                incoming = ex.run(comm, payload)
                inflight = carry.inflight

        fstate, stale, pending = carry.faults, None, None
        if deliver_f is not None:
            with jax.named_scope("sssp.deliver"):
                if resend_now is not None:
                    # this resend round retransmits everything: clear the
                    # unhealed latch BEFORE injection so only drops of the
                    # resent copies themselves re-arm it
                    fstate = fstate._replace(
                        unhealed=jnp.where(resend_now, False,
                                           fstate.unhealed))
                rkey = jax.random.fold_in(jax.random.PRNGKey(fp.seed),
                                          carry.rounds)
                rank = comm.rank()
                if vmapped:
                    keys = jax.vmap(
                        lambda r: jax.random.fold_in(rkey, r))(rank)
                else:
                    keys = jax.random.fold_in(rkey, rank)
                incoming, fstate, stale, pending = deliver_f(
                    sh, dist, incoming, fstate, keys)

        stale_async = None
        with jax.named_scope("sssp.merge"):
            if deferred:
                # improving entries of the FINAL delivered batch (post
                # fault injection) against the pre-merge distances: under
                # a lagged delivery every improving merge is by definition
                # stale
                stale_async = stale_f(sh, dist, incoming)
            dist, new_active, recvs = merge_f(sh, dist, incoming)

        # termination sees pending in-flight state as activity; the real
        # frontier stays clean (a fake frontier bit would cause spurious
        # relaxation work, not just a held-open detector)
        with jax.named_scope("sssp.toka"):
            pend_bits = pending
            if deferred:
                ab = _pending_inflight(inflight, vmapped)
                pend_bits = ab if pend_bits is None else (pend_bits | ab)
            toka_active = new_active
            if pend_bits is not None:
                toka_active = new_active | pend_bits[..., None]
            done, toka2, streak = pipe.toka(
                cfg, comm, carry, toka_active, sends, recvs, sh.inter_edges,
                n_parts, comm.rank(), vmapped)

        stale_c, resent_c = carry.stale, carry.resent
        if stale_async is not None:
            # the injector's own stale counter is skipped: queue releases
            # are already min-merged into the delivered batch above
            stale_c = stale_c + stale_async
        elif stale is not None:
            stale_c = stale_c + stale
        if resend_now is not None:
            resent_c = resent_c + jnp.where(resend_now, sends,
                                            0).astype(jnp.int32)
        overlap_c = carry.overlap
        if deferred:
            # a round overlaps when some shard had payload on the wire
            # while some shard had a live frontier to relax
            computing = jnp.any(act, axis=(-2, -1))
            flag = delivering & computing
            bit = jnp.any(flag) if vmapped else comm.all_any(flag)
            overlap_c = overlap_c + bit.astype(jnp.int32)
        running = (~carry.done).astype(jnp.int32)
        return _Carry(
            dist=dist, active=new_active, pruned=pruned, tri_cursor=cursor,
            last_sent=last_sent, msg_count=carry.msg_count + recvs,
            toka2=toka2, done=carry.done | done, rounds=carry.rounds + 1,
            q_rounds=carry.q_rounds + running,
            relaxations=carry.relaxations + nrel.astype(jnp.int32),
            msgs_sent=carry.msgs_sent + sends.astype(jnp.int32),
            msgs_recv=carry.msgs_recv + recvs.astype(jnp.int32),
            faults=fstate, streak=streak, stale=stale_c, resent=resent_c,
            inflight=inflight, overlap=overlap_c,
            comm_bytes=carry.comm_bytes + nbytes)

    return rounds_fn


def sim_phase_fns(sh: SsspShards, cfg: SsspConfig):
    """Jitted per-phase callables over the stacked sim representation:
    each phase of the round (local / send / exchange / merge, and the
    fused megakernel where the shards carry its layouts) can be driven in
    isolation, e.g. to read its Pallas grid. Shapes follow the sim carry
    convention (leading [P], then [K]). Device time per phase comes from
    the round's ``jax.named_scope`` phases in a profiler trace."""
    comm = SimComm(sh.n_parts)
    pipe = build_pipeline(sh, cfg)
    fns = {
        "local": jax.jit(lambda dist, active, pruned, cursor:
                         jax.vmap(pipe.local)(sh, dist, active, pruned,
                                              cursor)),
        "send": jax.jit(lambda dist, pruned, last_sent:
                        jax.vmap(pipe.send)(sh, dist, pruned, last_sent)),
        "exchange": jax.jit(lambda payload: pipe.exchange.run(comm, payload)),
        "merge": jax.jit(lambda dist, incoming:
                         jax.vmap(pipe.merge)(sh, dist, incoming)),
    }
    if sh.has_relax_layout and sh.has_send_layout and sh.has_merge_layout:
        fused = partial(_phase_fused, dense=pipe.exchange.dense, cfg=cfg)
        fns["fused"] = jax.jit(
            lambda dist, front_in, live, incoming, last_sent, pruned:
            jax.vmap(fused)(sh, dist, front_in, live, incoming, last_sent,
                            pruned))
    return fns


def _toka2_init_batch(rank, nq: int):
    """K independent token-ring states (shard 0 holds all K tokens)."""
    st = toka_mod.toka2_init(rank)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (nq,) + jnp.shape(x)), st)


@jax.named_scope("sssp.init")
def _init_carry(sh: SsspShards, sources, cfg: SsspConfig, rank,
                vmapped: bool, q_valid=None, seed_dist=None):
    """Stacked init (sim) or per-shard init (shard_map) for K sources.

    ``sources`` is a TRACED [K] int32 array (a python sequence is accepted
    and converted): the source bit is scattered, not baked, so one compiled
    program serves any source batch of a given K. ``q_valid`` masks padded
    bucket rows — an invalid query starts with an empty frontier and
    ``done=True``, so it never relaxes, sends, or counts in any statistic.

    ``seed_dist`` is the TRACED warm-start input ([P, K, block] stacked /
    [K, block] per shard, or None for the cold +inf start): per-vertex
    upper bounds produced by a ``warm_init`` stage. Every finitely-seeded
    vertex starts ACTIVE — a seeded value must still be relaxed *from*,
    otherwise a neighbor whose shortest path runs through it could get
    stuck above its true distance. The source bit is min-scattered to 0 on
    top of the seed, so the monotone pipeline reaches the same fixpoint as
    the cold start, just from a much closer initialization.
    """
    block = sh.block
    n_parts = sh.n_parts
    sources = jnp.asarray(sources, jnp.int32)
    nq = int(sources.shape[0])
    if q_valid is None:
        q_valid = jnp.ones((nq,), bool)
    else:
        q_valid = jnp.asarray(q_valid, bool)
    owner = sources // block
    local = sources % block
    qi = jnp.arange(nq)

    if vmapped:
        Pn = n_parts
        if seed_dist is None:
            dist = (jnp.full((Pn, nq, block), INF, jnp.float32)
                    .at[owner, qi, local].set(jnp.where(q_valid, 0.0, INF)))
            active = (jnp.zeros((Pn, nq, block), bool)
                      .at[owner, qi, local].set(q_valid))
        else:
            dist = seed_dist.at[owner, qi, local].min(
                jnp.where(q_valid, 0.0, INF))
            active = jnp.isfinite(dist) & q_valid[None, :, None]
        e_all = sh.loc_w.shape[1] + sh.cut_w.shape[1]
        pruned = jnp.zeros((Pn, e_all), bool)
        last_sent = jnp.full((Pn, nq, sh.slot_owner.shape[1]), INF, jnp.float32)
        cursor = jnp.zeros((Pn,), jnp.int32)
        zeroq = jnp.zeros((Pn, nq), jnp.int32)
        toka2 = jax.vmap(lambda r: _toka2_init_batch(r, nq))(
            jnp.arange(Pn, dtype=jnp.int32))
        done = jnp.broadcast_to(~q_valid, (Pn, nq))
    else:
        mine = (owner == rank) & q_valid
        if seed_dist is None:
            dist = (jnp.full((nq, block), INF, jnp.float32)
                    .at[qi, local].set(jnp.where(mine, 0.0, INF)))
            active = jnp.zeros((nq, block), bool).at[qi, local].set(mine)
        else:
            dist = seed_dist.at[qi, local].min(jnp.where(mine, 0.0, INF))
            active = jnp.isfinite(dist) & q_valid[:, None]
        e_all = sh.loc_w.shape[0] + sh.cut_w.shape[0]
        pruned = jnp.zeros((e_all,), bool)
        last_sent = jnp.full((nq, sh.slot_owner.shape[0]), INF, jnp.float32)
        cursor = jnp.zeros((), jnp.int32)
        zeroq = jnp.zeros((nq,), jnp.int32)
        toka2 = _toka2_init_batch(rank, nq)
        done = ~q_valid

    if cfg.prune_offline_passes > 0:
        off = partial(trishla.prune_offline, n_passes=cfg.prune_offline_passes)
        if vmapped:
            pruned = jax.vmap(off)(sh.loc_w, sh.cut_w, sh.tri_uj, sh.tri_ui,
                                   sh.tri_ij, sh.tri_valid)
        else:
            pruned = off(sh.loc_w, sh.cut_w, sh.tri_uj, sh.tri_ui, sh.tri_ij,
                         sh.tri_valid)

    fstate = None
    fp = cfg.fault_plan
    if fp is not None:
        # one queue slot per flat payload position of the resolved
        # exchange: block for the dense modes, P*C for the bucket routing
        if phases.resolve("exchange", cfg.exchange).dense:
            n_msgs = block
        else:
            n_msgs = n_parts * sh.recv_idx.shape[-1]
        fstate = faults_mod.init_state(fp, nq, n_msgs,
                                       n_parts if vmapped else None)

    ex_stage = phases.resolve("exchange", cfg.exchange)
    inflight = None
    if getattr(ex_stage, "deferred", False):
        # empty (+inf) in-flight buffers: round 0's recv delivers nothing,
        # round 0's sends arrive in round async_lag (ring distance for the
        # hop transport) — the generalized form of the fused round's
        # incoming rotation, deferring the exchange itself
        inflight = ex_stage.init_inflight(sh, nq, cfg, vmapped)

    incoming = front_any = None
    if _round_mode(sh, cfg) == "fused":
        # the fused carry holds last round's delivered-but-unmerged
        # messages; an all-INF batch makes round 0's merge the identity
        # (base case of the bit-identity induction with the staged round)
        C = sh.recv_idx.shape[-1]
        dense = phases.resolve("exchange", cfg.exchange).dense
        if vmapped:
            shape = (n_parts, nq, block) if dense else (n_parts, nq,
                                                        n_parts, C)
        else:
            shape = (nq, block) if dense else (nq, n_parts, C)
        incoming = jnp.full(shape, INF, jnp.float32)
        front_any = jnp.any(active, axis=-1)

    return _Carry(dist=dist, active=active, pruned=pruned, tri_cursor=cursor,
                  last_sent=last_sent, msg_count=zeroq, toka2=toka2, done=done,
                  rounds=jnp.zeros((), jnp.int32), q_rounds=zeroq,
                  relaxations=zeroq, msgs_sent=zeroq, msgs_recv=zeroq,
                  faults=fstate, streak=zeroq, stale=zeroq, resent=zeroq,
                  incoming=incoming, front_any=front_any, inflight=inflight,
                  overlap=jnp.zeros((), jnp.int32),
                  comm_bytes=jnp.zeros((), jnp.int32))


# --------------------------------------------------------------------------
# fixpoint certificate
# --------------------------------------------------------------------------
#
# "One extra relax round produces no improvement" — the exact convergence
# test gating QueryResult.status in the engine. Distances computed by ANY
# run of the monotone pipeline are upper bounds on the true fixpoint d*
# (every finite value is a realized path length); if dist >= d* and
# dist != d*, then some single edge relaxation improves some vertex. The
# certificate therefore relaxes EVERY edge once — local and cut, ignoring
# frontiers, last_sent floors, and even Trishla pruning (a pruned edge
# can never be the sole witness, but including it costs nothing and keeps
# the check independent of the pruning logic) — and reports, per query,
# whether anything improved. No improvement <=> dist IS the fixpoint.

def _cert_relax_shard(shard: SsspShards, dist):
    """One unmasked relaxation of this shard's edges from ``dist`` [K, block].

    Returns (new_local [K, block] after local-edge relaxation, dense cut
    payload [K, P, block]); the caller min-combines the exchanged payloads
    with the local result and compares against ``dist``."""
    d_src = jnp.take(dist, shard.loc_src, axis=1, mode="fill",
                     fill_value=float("inf"))
    new = jax.vmap(lambda d, c: d.at[shard.loc_dst].min(c, mode="drop"))(
        dist, d_src + shard.loc_w)
    d_cut = jnp.take(dist, shard.cut_src, axis=1, mode="fill",
                     fill_value=float("inf"))
    slot_val = _slot_min(shard, d_cut + shard.cut_w)
    return new, _scatter_dense(shard, slot_val, dist.shape[1])


@jax.named_scope("sssp.certificate")
def certificate_improved_sim(sh: SsspShards, dist):
    """Certificate over the stacked sim state: ``dist`` [P, K, block] ->
    ``improved`` [K] bool (True = NOT at the fixpoint)."""
    comm = SimComm(sh.n_parts)
    new, payload = jax.vmap(_cert_relax_shard)(sh, dist)
    merged = jnp.minimum(new, comm.exchange_pmin(payload))
    return jnp.any(merged < dist, axis=(0, 2))


def build_shmap_certificate(sh_spec: SsspShards, mesh, axis_names,
                            on_trace=None):
    """Jitted ``fn(shards_stacked, dist [P, K, block]) -> improved [K]``
    running the certificate under shard_map (one pmin + one or-reduce on
    the wire). ``on_trace`` mirrors the solver's compile accounting but
    feeds the engine's SEPARATE certificate counter — tests pin
    ``trace_counts`` to solver traces only."""
    axes = tuple(axis_names)
    comm = ShmapComm(axes)

    @jax.named_scope("sssp.certificate")
    def body(sh_local: SsspShards, dist_loc):
        sh1 = jax.tree_util.tree_map(lambda x: x[0], sh_local)
        d = dist_loc[0]
        new, payload = _cert_relax_shard(sh1, d)
        merged = jnp.minimum(new, comm.exchange_pmin(payload))
        return or_reduce(jnp.any(merged < d, axis=-1), axes)

    pspec = P(axes)
    in_specs = (jax.tree_util.tree_map(lambda _: pspec, sh_spec), pspec)
    shm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)

    def run(stacked, dist):
        if on_trace is not None:
            on_trace(int(dist.shape[1]))
        return shm(stacked, dist)

    return jax.jit(run)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

def _as_sources(source_or_sources, n_vertices: int | None = None) -> tuple[int, ...]:
    if isinstance(source_or_sources, (int, np.integer)):
        sources = (int(source_or_sources),)
    else:
        sources = tuple(int(s) for s in source_or_sources)
    if n_vertices is not None:
        for s in sources:
            # an out-of-range id would be silently dropped by the init
            # scatter (all-INF result) or land on a padding vertex
            if not 0 <= s < n_vertices:
                raise ValueError(
                    f"source {s} out of range [0, {n_vertices})")
    return sources


def build_shmap_solver_traced(sh_spec: SsspShards, cfg: SsspConfig, mesh,
                              axis_names, on_trace=None, warm: bool = False):
    """Traced-sources shard_map solver: one compiled program per K.

    Returns a jitted ``fn(shards_stacked, sources [K] i32, q_valid [K] bool)
    -> (dist [P, K, block], stats)``. ``sources`` and ``q_valid`` are traced
    inputs replicated across the mesh — the source bit is scattered inside
    the body, so the SAME compiled program answers arbitrary source batches
    of a given K (the old per-batch recompile is gone). The outer round
    loop is a ``lax.while_loop`` inside the shard_map body; the whole solve
    is one XLA program (this is what the dry-run lowers for the production
    meshes). ``on_trace(K)`` is called once per trace (compile accounting
    for :class:`~repro.core.engine.SsspEngine`).

    ``warm=True`` builds the landmark-seeded variant: the returned fn takes
    a fourth TRACED input ``land [P, L, block]`` (the engine's sharded
    landmark cache, partitioned like the shards) and runs the resolved
    ``warm_init`` stage inside the body — one small [L, K] all-reduce to
    gather the landmark-at-source bounds, then a per-shard seed that
    ``_init_carry`` consumes. Landmark distances stay sharded on the wire;
    only the [L, K] gather is replicated."""
    axes = tuple(axis_names)
    n_parts = sh_spec.n_parts
    comm = ShmapComm(axes)
    warm_stage = phases.resolve("warm_init", cfg.warm_start) if warm else None
    if warm and warm_stage.seed_shard is None:
        raise ValueError(
            f"warm=True needs a seeding warm_init backend; "
            f"cfg.warm_start={cfg.warm_start!r} does not seed")

    def body(sh_local: SsspShards, sources, q_valid, *warm_args):
        sh1 = jax.tree_util.tree_map(lambda x: x[0], sh_local)  # strip P dim
        # recv_idx arrives as [1, P, C] -> [P, C]; inter_edges scalar
        rank = comm.rank()
        seed = None
        if warm:
            land_loc = warm_args[0][0]                   # [L, block]
            seed = warm_stage.seed_shard(land_loc, sources, q_valid, rank,
                                         sh_spec.block, comm.min_all)
        carry = _init_carry(sh1, sources, cfg, rank=rank, vmapped=False,
                            q_valid=q_valid, seed_dist=seed)
        round_fn = _make_round(sh1, cfg, comm, vmapped=False, n_parts=n_parts)

        def cond(c: _Carry):
            return (~jnp.all(c.done)) & (c.rounds < cfg.max_rounds)

        carry = lax.while_loop(cond, round_fn, carry)
        fin = make_finalize(sh1, cfg, comm, vmapped=False)
        dist_final = carry.dist if fin is None else fin(carry)
        dpr = jnp.int32(dispatches_per_round(sh1, cfg))
        stats = SsspStats(
            rounds=carry.rounds,
            relaxations=comm.total(jnp.sum(carry.relaxations)),
            msgs_sent=comm.total(jnp.sum(carry.msgs_sent)),
            msgs_recv=comm.total(jnp.sum(carry.msgs_recv)),
            pruned_edges=comm.total(jnp.sum(carry.pruned).astype(jnp.int32)),
            q_rounds=carry.q_rounds,
            q_relaxations=comm.total(carry.relaxations),
            q_converged=carry.done,
            stale_merges=comm.total(jnp.sum(carry.stale)),
            resends=comm.total(jnp.sum(carry.resent)),
            n_dispatches=carry.rounds * dpr,
            overlap_rounds=carry.overlap,     # globally agreed each round
            bytes_moved=comm.total(carry.comm_bytes))
        return dist_final[None], stats  # restore leading P dim

    pspec = P(axes)
    rspec = P()
    in_specs = jax.tree_util.tree_map(lambda _: pspec, sh_spec)
    in_specs = (in_specs, rspec, rspec) + ((pspec,) if warm else ())
    out_specs = (pspec, SsspStats(*([rspec] * len(SsspStats._fields))))
    shm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)

    def run(stacked, sources, q_valid, *warm_args):
        # trace-time side effect: runs once per (K, shard avals) jit entry
        if on_trace is not None:
            on_trace(int(sources.shape[0]))
        return shm(stacked, sources, q_valid, *warm_args)

    return jax.jit(run)


# --------------------------------------------------------------------------
# legacy entry points — thin wrappers over the session engine
#
# The five free functions below predate repro.core.engine.SsspEngine and are
# kept for compatibility; each delegates to a cached engine (engine_for) so
# repeated calls share one compiled program per (K-bucket, cfg). Prefer:
#
#     eng = SsspEngine.build(shards_or_graph, cfg, backend=...)
#     res = eng.solve(sources)          # QueryResult
# --------------------------------------------------------------------------


def solve_sim_batch(sh: SsspShards, sources: Sequence[int],
                    cfg: SsspConfig = SsspConfig()):
    """Single-device simulator, K sources.

    .. deprecated:: delegate of :meth:`SsspEngine.solve` (``backend="sim"``);
       kept for compatibility. Returns (dist [K, n_vertices], SsspStats with
       per-query q_rounds / q_relaxations [K])."""
    from repro.core.engine import engine_for
    res = engine_for(sh, cfg, "sim").solve(sources)
    return res.dist, res.stats


def solve_sim(sh: SsspShards, source: int, cfg: SsspConfig = SsspConfig()):
    """Single-source wrapper: a K=1 batch.

    .. deprecated:: use :meth:`SsspEngine.solve` — this delegates to it."""
    dist, stats = solve_sim_batch(sh, (int(source),), cfg)
    return dist[0], stats


def build_shmap_solver(sh_spec: SsspShards, cfg: SsspConfig, mesh,
                       axis_names, source):
    """Returns a jittable fn(shards_stacked) -> (dist [P, K, block], stats).

    .. deprecated:: the engine's traced solver
       (:func:`build_shmap_solver_traced`) serves ANY source batch of a
       given K from one compiled program; this wrapper bakes ``source``
       into a closure for callers that still expect a fn(shards) handle
       (e.g. the dry-run lowering). No padding is applied: K = len(source).
    """
    from repro.core.engine import engine_for
    sources = _as_sources(source, sh_spec.n_vertices)
    eng = engine_for(sh_spec, cfg, "shmap", mesh, axis_names)
    srcs = np.asarray(sources, np.int32)
    q_valid = np.ones((len(sources),), bool)
    return lambda stacked: eng.shmap_solver(stacked, srcs, q_valid)


def solve_shmap_batch(sh: SsspShards, sources: Sequence[int], cfg: SsspConfig,
                      mesh, axis_names):
    """shard_map backend, K sources. Returns (dist [K, n_vertices], stats).

    .. deprecated:: delegate of :meth:`SsspEngine.solve`
       (``backend="shmap"``); kept for compatibility. Sources are validated
       against ``n_vertices`` exactly like the sim path (out-of-range ids
       raise instead of silently vanishing), and repeated calls reuse the
       engine's compiled solver instead of re-running build_shmap_solver."""
    from repro.core.engine import engine_for
    res = engine_for(sh, cfg, "shmap", mesh, axis_names).solve(sources)
    return res.dist, res.stats


def solve_shmap(sh: SsspShards, source: int, cfg: SsspConfig, mesh, axis_names):
    """Single-source wrapper: a K=1 batch.

    .. deprecated:: use :meth:`SsspEngine.solve` — this delegates to it."""
    dist, stats = solve_shmap_batch(sh, (int(source),), cfg, mesh, axis_names)
    return dist[0], stats
