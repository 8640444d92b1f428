"""Session-oriented SSSP query engine: build once, stream queries.

The ROADMAP's serving story made concrete: ``SsspEngine`` is the ONE public
surface over the SP-Async solver. It owns the partitioned shards, the
resolved :class:`~repro.core.sssp.RoundPipeline`, and a per-engine compile
cache, replacing five free functions with divergent signatures
(``solve_sim`` / ``solve_sim_batch`` / ``solve_shmap`` /
``solve_shmap_batch`` / ``build_shmap_solver`` — now thin deprecated
wrappers that delegate here).

    eng = SsspEngine.build(graph_or_shards, cfg, backend="sim")
    res = eng.solve([3, 17, 1999])        # QueryResult
    h = eng.submit(42); eng.submit([7, 9])
    eng.drain()                           # coalesced, bucketed batches
    h.result().dist

Compile reuse — the engine's core contract
------------------------------------------

``sources`` is a TRACED input on both backends (scattered inside the
program by ``_init_carry``, never baked into the trace), so one compiled
program per (K-bucket, cfg) serves ARBITRARY source sets. ``solve`` pads
any batch up to the next power-of-two bucket: padded rows start with an
empty frontier and ``done=True``, so they never relax, send, or count in
any statistic — padded-bucket results are bit-identical to the unpadded
solve (queries are independent along the vmapped/batched query axis). The
per-source launch overhead that dominates GPU/MPI Dijkstra once the graph
is resident (arXiv:2504.03667) is paid once per bucket shape, not once per
query batch; this is what the old shmap path got wrong (a fresh XLA
compile per ``solve_shmap_batch`` call, sources baked into the body).

Trace accounting is first-class: every trace of the round (sim) or the
whole-solve program (shmap) bumps ``engine.trace_counts[K]`` — the compile
-reuse tests and the ``engine_serving`` benchmark assert on it directly.

Streaming arrivals
------------------

``submit`` enqueues a query (or query batch) and returns a
:class:`QueryHandle`; ``drain`` coalesces everything pending into
bucketed batches of at most ``max_bucket`` queries (whole handles are
never split across batches) and solves them. ``handle.result()`` drains
on demand, so a caller may also just submit and ask.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import phases
from repro.core.shards import SsspShards, build_shards, shard_distance_rows
from repro.core.sssp import (SimComm, SsspConfig, SsspStats, _as_sources,
                             _init_carry, _make_round,
                             build_shmap_certificate,
                             build_shmap_solver_traced,
                             certificate_improved_sim, dispatches_per_round,
                             make_finalize)
from repro.core.warmstart import CachedRow, LandmarkCache, ResultCache


def bucket_k(k: int) -> int:
    """Bucket policy: the next power of two >= k (so at most 2x padding,
    and a stream of ragged batch sizes folds onto O(log K) compiled
    programs)."""
    if k < 1:
        raise ValueError("at least one source is required")
    return 1 << (k - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class QueryResult:
    """Structured result of one solved (sub)batch.

    ``dist``/``q_rounds``/``q_relaxations`` are views over the REAL queries
    (padded bucket rows already sliced away); ``stats`` carries the same
    per-query columns plus the aggregate totals. ``compile_s`` is the
    cold-start cost (first invocation of this bucket's program, tracing and
    XLA compilation included) and is 0.0 on warm calls.

    ``status`` replaces the old silent ``max_rounds`` truncation:

    - ``"converged"``  — every query passed the fixpoint certificate (one
      extra unmasked relax round produced no improvement); distances are
      exact.
    - ``"max_rounds"`` — the round budget ran out before the detectors
      fired for some query; distances are upper bounds.
    - ``"degraded"``   — a detector declared termination but the
      certificate found a remaining improvement (e.g. a dropped message
      under ``FaultPlan(resend_period=0)``); distances are upper bounds.

    Per-query resolution lives in ``stats.q_converged`` /
    :attr:`q_converged`. Non-converged results are never admitted to the
    result LRU or the landmark cache."""

    dist: np.ndarray            # [K, n_vertices] per-query distances
    sources: tuple              # the K query sources, as submitted
    stats: SsspStats            # aggregates + per-query q_rounds/q_relaxations
    bucket_k: int               # compiled batch shape (0: fully cache-served)
    backend: str                # "sim" | "shmap"
    wall_s: float               # end-to-end solve wall time
    compile_s: float            # cold-start time (0.0 when warm)
    compiled: bool              # True iff this call traced a new program
    cache_hits: int = 0         # queries answered from the result cache
    warm_started: bool = False  # landmark-seeded (vs cold +inf) init
    status: str = "converged"   # converged | max_rounds | degraded

    @property
    def q_rounds(self) -> np.ndarray:
        return np.asarray(self.stats.q_rounds)

    @property
    def q_relaxations(self) -> np.ndarray:
        return np.asarray(self.stats.q_relaxations)

    @property
    def q_converged(self) -> np.ndarray:
        return np.asarray(self.stats.q_converged)

    @property
    def overlap_fraction(self) -> float:
        """Fraction of rounds where in-flight async payload coexisted with
        local relax work — the measurable form of the deferred exchanges'
        communication/computation overlap claim. 0.0 for synchronous
        exchanges, zero-round (fully cache-served) solves, or results
        predating the counter."""
        if self.stats.overlap_rounds is None:
            return 0.0
        rounds = int(self.stats.rounds)
        return float(int(self.stats.overlap_rounds)) / rounds if rounds else 0.0


class QueryHandle:
    """A submitted-but-possibly-unsolved query batch; ``result()`` drains
    the owning engine on demand."""

    __slots__ = ("sources", "_engine", "_result")

    def __init__(self, engine: "SsspEngine", sources: tuple):
        self.sources = sources
        self._engine = engine
        self._result: QueryResult | None = None

    @property
    def done(self) -> bool:
        return self._result is not None

    def result(self) -> QueryResult:
        if self._result is None:
            self._engine.drain()
        return self._result

    def __repr__(self):
        state = "done" if self.done else "pending"
        return f"QueryHandle(sources={self.sources}, {state})"


class SsspEngine:
    """One per-graph session: owns the shards, the resolved phase pipeline,
    and the compiled programs that answer query streams against them."""

    def __init__(self, shards: SsspShards, cfg: SsspConfig, backend: str,
                 mesh=None, axis_names=None, max_bucket: int = 16,
                 result_cache: int = 0, certify: bool = True):
        if backend not in ("sim", "shmap"):
            raise ValueError(f"unknown backend {backend!r}; valid: "
                             "['shmap', 'sim']")
        if backend == "shmap" and (mesh is None or axis_names is None):
            raise ValueError("backend='shmap' requires mesh and axis_names")
        if backend == "shmap" and shards.n_parts != mesh.size:
            # each device solves x[0] of its block: any other count would
            # silently drop shards (or leave devices without one)
            raise ValueError(
                f"backend='shmap' needs one shard per device: n_parts="
                f"{shards.n_parts} but the mesh has {mesh.size} devices")
        self.shards = shards
        self.cfg = cfg
        self.backend = backend
        self.mesh = mesh
        self.axis_names = tuple(axis_names) if axis_names else None
        self.max_bucket = int(max_bucket)
        self._pending: list[QueryHandle] = []
        self.batches_served = 0
        self.queries_served = 0
        # warm-start cache hierarchy (see core/warmstart.py): the result
        # LRU serves exact repeats with ZERO rounds; the landmark cache
        # (precompute_landmarks) seeds every other query's dist with
        # triangle-inequality upper bounds when cfg.warm_start="landmark".
        # graph_epoch keys both: bumping it (invalidate_caches) orphans
        # every cached row without a scan.
        self.graph_epoch = 0
        self.result_cache = ResultCache(result_cache)
        self.landmarks: LandmarkCache | None = None
        self._warm_stage = phases.resolve("warm_init", cfg.warm_start)
        if self._warm_stage.seed_stacked is not None:
            # counted like the round program: the seed's jit entries are
            # per (L, K) shape, and its first trace is a real compile that
            # must show up in compiled/compile_s (the shmap warm program
            # counts via on_trace; keep the accounting symmetric)
            seed_stacked = self._warm_stage.seed_stacked

            def counted_seed(land, sources, q_valid):
                self._note_trace(int(sources.shape[0]))
                return seed_stacked(land, sources, q_valid)

            self._warm_seed = jax.jit(counted_seed)
        else:
            self._warm_seed = None
        self._warm_solver = None        # lazily built shmap warm program
        self._warm_traced: set = set()  # (K-bucket, L) warm-program traces
        # per-engine compile cache: ONE jitted program per backend whose
        # jit cache holds one entry per K-bucket; trace_counts[K] counts
        # them (a trace-time side effect, so reuse is directly assertable)
        self.trace_counts: dict[int, int] = {}
        self._compile_s: dict[int, float] = {}
        # fixpoint certificate: one extra unmasked relax round over the
        # final distances gates QueryResult.status. Its program is traced
        # once per bucket but counted SEPARATELY (cert_traces) — the
        # compile-reuse tests pin trace_counts to solver traces only.
        self.certify = bool(certify)
        self.cert_traces = 0
        self._cert_shmap = None     # lazily built shmap certificate
        if backend == "sim":
            # the shards are a jit ARGUMENT of every sim program, never a
            # closure: a captured array is embedded in the program as a
            # constant, which at real graph sizes bloats compilation
            comm = SimComm(shards.n_parts)
            n_parts = shards.n_parts

            # A program's name is part of JAX's compilation-cache key, its
            # op_names are not: the names set the scoped round and
            # certificate apart from unscoped executables cached by older
            # versions, which would load without their phases. They also
            # name the programs in a trace (``jit_sssp_round``).
            def sssp_round(sh, carry):
                self._note_trace(int(carry.dist.shape[1]))
                return _make_round(sh, cfg, comm, vmapped=True,
                                   n_parts=n_parts)(carry)

            def sssp_certificate(sh, dist_pk):
                self.cert_traces += 1
                return certificate_improved_sim(sh, dist_pk)

            self.round_fn = jax.jit(sssp_round)
            self._cert_fn = jax.jit(sssp_certificate)
            # fused round / deferred (async) exchange: the loop can exit
            # with delivered-but-unmerged messages in carry.incoming and
            # undelivered payload in carry.inflight (see sssp.make_finalize)
            if make_finalize(shards, cfg, comm, vmapped=True) is not None:
                self._finalize_fn = jax.jit(
                    lambda sh, carry: make_finalize(sh, cfg, comm,
                                                    vmapped=True)(carry))
            else:
                self._finalize_fn = None
            self.shmap_solver = None
        else:
            # place the stack once, one shard per device; every solve then
            # passes arrays already laid out as the program's in_specs
            placed = NamedSharding(mesh, P(self.axis_names))

            def place(x):
                if isinstance(x, jax.ShapeDtypeStruct):   # shape-only shards
                    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                sharding=placed)
                return jax.device_put(x, placed)

            self.shards = jax.tree_util.tree_map(place, shards)
            self.round_fn = None
            self._cert_fn = None
            self._finalize_fn = None
            self.shmap_solver = build_shmap_solver_traced(
                self.shards, cfg, mesh, self.axis_names,
                on_trace=self._note_trace)

    # ---------------------------------------------------------- build ----

    @classmethod
    def build(cls, graph_or_shards, cfg: SsspConfig | None = None,
              backend: str = "sim", mesh=None, axis_names=None, *,
              n_parts: int = 8, max_bucket: int = 16, result_cache: int = 0,
              certify: bool = True, **shard_kwargs) -> "SsspEngine":
        """Create a session over a :class:`SsspShards` (used as-is) or a
        :class:`~repro.graph.structure.Graph` (partitioned here with
        ``n_parts`` and any ``build_shards`` keyword). ``result_cache``
        sizes the exact-repeat LRU (0 = disabled, the bit-compatible
        default: every solve runs the full pipeline)."""
        if isinstance(graph_or_shards, SsspShards):
            if shard_kwargs:
                raise ValueError("shard build options only apply when "
                                 "building from a Graph")
            sh = graph_or_shards
        else:
            sh = build_shards(graph_or_shards, n_parts, **shard_kwargs)
        return cls(sh, cfg or SsspConfig(), backend, mesh, axis_names,
                   max_bucket=max_bucket, result_cache=result_cache,
                   certify=certify)

    @property
    def n_vertices(self) -> int:
        return self.shards.n_vertices

    @property
    def n_parts(self) -> int:
        return self.shards.n_parts

    @property
    def trace_count(self) -> int:
        """Total traces across every bucket program this engine compiled."""
        return sum(self.trace_counts.values())

    def _note_trace(self, kb: int) -> None:
        self.trace_counts[kb] = self.trace_counts.get(kb, 0) + 1

    # ---------------------------------------------------------- solve ----

    def _warm_active(self) -> bool:
        """True when solves should seed from the landmark cache: the config
        opted in AND a cache for the CURRENT graph epoch exists."""
        return (self._warm_stage.needs_landmarks
                and self.landmarks is not None
                and self.landmarks.epoch == self.graph_epoch)

    def solve(self, sources, *, bucket: bool = True) -> QueryResult:
        """Solve a source batch (int or sequence). Pads to the next
        power-of-two K-bucket (``bucket=False`` keeps K exact — same
        results bit-for-bit, one extra compiled shape) and answers from
        the bucket's compiled program.

        With a result cache enabled, exact repeats of a source (within the
        current graph epoch) are answered from the LRU with ZERO rounds,
        and cached sources are stripped from the batch BEFORE padding — a
        partially-cached batch rides a smaller bucket. Cached rows report
        ``q_rounds == 0`` (this call did no work for them); distances are
        the stored rows, bit-identical to the solve that produced them."""
        srcs = _as_sources(sources, self.shards.n_vertices)
        if len(srcs) < 1:
            raise ValueError("at least one source is required")
        if self.result_cache.maxsize == 0:
            return self._solve_batch(srcs, bucket=bucket)
        return self._solve_cached(srcs, bucket=bucket)

    @partial(annotate_function, name="sssp.solve")
    def _solve_batch(self, srcs: tuple, *, bucket: bool = True,
                     use_warm: bool = True) -> QueryResult:
        """Run the compiled pipeline for ``srcs`` (no result-cache layer).
        ``use_warm=False`` forces the cold +inf init — used to solve the
        landmark pivots themselves.

        Each host step runs in a profiler span on the trace's clock
        (``sssp.init``, ``sssp.round`` — ``sssp.compile`` while the bucket
        has no program yet — ``sssp.sync``, ``sssp.finalize``,
        ``sssp.copy_out``, ``sssp.stats``, ``sssp.certificate``); the spans
        add no sync of their own."""
        k = len(srcs)
        kb = bucket_k(k) if bucket else k
        src_arr = np.zeros((kb,), np.int32)
        src_arr[:k] = srcs
        q_valid = np.zeros((kb,), bool)
        q_valid[:k] = True
        warm = use_warm and self._warm_active()

        traces0 = self.trace_count
        t0 = time.perf_counter()
        compile_s = 0.0
        if self.backend == "sim":
            with TraceAnnotation("sssp.init"):
                seed = None
                if warm:
                    tc = time.perf_counter()
                    seed = self._warm_seed(self.landmarks.dist,
                                           jnp.asarray(src_arr),
                                           jnp.asarray(q_valid))
                    if self.trace_count > traces0:
                        jax.block_until_ready(seed)
                        compile_s += time.perf_counter() - tc
                    # solve-time coverage, keyed (bucket, L) like the
                    # shmap path: the seed program is separate from the
                    # round, so a cold trace of this bucket does not make
                    # the warm path compile-free (warmup() consults this)
                    self._warm_traced.add((kb, self.landmarks.n_landmarks))
                carry = _init_carry(self.shards, src_arr, self.cfg,
                                    rank=None, vmapped=True, q_valid=q_valid,
                                    seed_dist=seed)
            r = 0
            traces_loop = self.trace_count
            while r < self.cfg.max_rounds:
                fresh = self.trace_count == traces_loop
                tc = time.perf_counter()
                span = ("sssp.round" if self.trace_counts.get(kb)
                        else "sssp.compile")
                with TraceAnnotation(span):
                    carry = self.round_fn(self.shards, carry)
                    if fresh and self.trace_count > traces_loop:
                        jax.block_until_ready(carry)
                        compile_s += time.perf_counter() - tc
                r += 1
                with TraceAnnotation("sssp.sync"):
                    done = bool(np.asarray(carry.done).all())
                if done:
                    break
            dist_pk = carry.dist
            if self._finalize_fn is not None:
                with TraceAnnotation("sssp.finalize"):
                    dist_pk = self._finalize_fn(self.shards, carry)
            with TraceAnnotation("sssp.copy_out"):
                done_k = np.asarray(carry.done)[0][:k]  # globally agreed
                # [P, K, block] -> per-query global distance vectors
                dist = np.moveaxis(np.asarray(dist_pk), 0, 1)
                dist = dist.reshape(kb, -1)[:k, : self.shards.n_vertices]
            # host sums in int64: a K=16 batch at real graph sizes comes
            # near 2**31 relaxations
            with TraceAnnotation("sssp.stats"):
                stats = SsspStats(
                    rounds=carry.rounds,
                    relaxations=np.sum(np.asarray(carry.relaxations),
                                       dtype=np.int64),
                    msgs_sent=np.sum(np.asarray(carry.msgs_sent),
                                     dtype=np.int64),
                    msgs_recv=np.sum(np.asarray(carry.msgs_recv),
                                     dtype=np.int64),
                    pruned_edges=np.sum(np.asarray(carry.pruned),
                                        dtype=np.int64),
                    q_rounds=np.max(np.asarray(carry.q_rounds), axis=0)[:k],
                    q_relaxations=np.sum(np.asarray(carry.relaxations),
                                         axis=0)[:k],
                    stale_merges=np.sum(np.asarray(carry.stale),
                                        dtype=np.int64),
                    resends=np.sum(np.asarray(carry.resent), dtype=np.int64),
                    n_dispatches=np.int32(
                        int(np.asarray(carry.rounds))
                        * dispatches_per_round(self.shards, self.cfg)),
                    overlap_rounds=np.int32(np.asarray(carry.overlap)),
                    bytes_moved=np.int64(np.asarray(carry.comm_bytes)))
        else:
            tc = time.perf_counter()
            if warm:
                if self._warm_solver is None:
                    self._warm_solver = build_shmap_solver_traced(
                        self.shards, self.cfg, self.mesh, self.axis_names,
                        on_trace=self._note_trace, warm=True)
                dist_pk, stats = self._warm_solver(self.shards, src_arr,
                                                   q_valid,
                                                   self.landmarks.dist)
                # coverage recorded at SOLVE time, keyed (bucket, L): the
                # warm program is distinct from the cold solver AND its
                # jit entries depend on the landmark aval; recording at
                # trace time would go stale when a jit-cache hit skips the
                # trace (e.g. re-precompute with the same pivot count)
                self._warm_traced.add((kb, self.landmarks.n_landmarks))
            else:
                dist_pk, stats = self.shmap_solver(self.shards, src_arr,
                                                   q_valid)
            jax.block_until_ready(dist_pk)
            if self.trace_count > traces0:
                compile_s = time.perf_counter() - tc
            with TraceAnnotation("sssp.copy_out"):
                done_k = np.asarray(stats.q_converged)[:k]
                dist = np.moveaxis(np.asarray(dist_pk), 0, 1)  # [K, P, block]
                dist = dist.reshape(kb, -1)[:k, : self.shards.n_vertices]
            stats = stats._replace(q_rounds=stats.q_rounds[:k],
                                   q_relaxations=stats.q_relaxations[:k])

        # fixpoint certificate: the detectors' word (done_k) is a claim;
        # one extra unmasked relax round is the proof. Certified truth
        # overrides the detector in BOTH directions — a run that exhausted
        # max_rounds at the fixpoint is converged, a detector that fired
        # over a dropped message is not.
        if self.certify:
            with TraceAnnotation("sssp.certificate"):
                if self.backend == "sim":
                    improved = np.asarray(self._cert_fn(self.shards,
                                                        dist_pk))[:k]
                else:
                    if self._cert_shmap is None:
                        self._cert_shmap = build_shmap_certificate(
                            self.shards, self.mesh, self.axis_names,
                            on_trace=lambda _k: setattr(
                                self, "cert_traces", self.cert_traces + 1))
                    improved = np.asarray(
                        self._cert_shmap(self.shards, dist_pk))[:k]
            q_conv = ~improved
        else:
            q_conv = done_k.copy()
        if bool(q_conv.all()):
            status = "converged"
        elif bool((~q_conv & ~done_k).any()):
            status = "max_rounds"
        else:
            status = "degraded"
        stats = stats._replace(q_converged=q_conv)

        wall_s = time.perf_counter() - t0
        compiled = self.trace_count > traces0
        if compiled:
            self._compile_s[kb] = compile_s
        self.batches_served += 1
        self.queries_served += k
        return QueryResult(dist=dist, sources=srcs, stats=stats, bucket_k=kb,
                           backend=self.backend, wall_s=wall_s,
                           compile_s=compile_s, compiled=compiled,
                           warm_started=warm, status=status)

    def _solve_cached(self, srcs: tuple, *, bucket: bool) -> QueryResult:
        """Result-cache layer over ``_solve_batch``: strip the sources the
        LRU can answer (and in-batch duplicates) BEFORE bucket padding,
        solve the remainder, then reassemble rows in submitted order."""
        t0 = time.perf_counter()
        epoch = self.graph_epoch
        hits: dict[int, CachedRow] = {}
        uncached: list[int] = []
        for s in dict.fromkeys(srcs):
            row = self.result_cache.get(s, epoch)
            if row is None:
                uncached.append(s)
            else:
                hits[s] = row
        raw = None
        if uncached:
            raw = self._solve_batch(tuple(uncached), bucket=bucket)
            for i, s in enumerate(uncached):
                # graceful degradation: only certified-converged rows may
                # enter the LRU — a degraded/max_rounds row is an upper
                # bound, and a cache would launder it into later batches
                # as if it were exact
                if not bool(raw.stats.q_converged[i]):
                    continue
                # copy: a view would pin the whole [kb, n] batch array in
                # the LRU for as long as any one of its rows stays cached
                self.result_cache.put(s, epoch,
                                      CachedRow(dist=raw.dist[i].copy()))
        raw_col = {s: i for i, s in enumerate(uncached)}

        k = len(srcs)
        dist = np.empty((k, self.shards.n_vertices), np.float32)
        q_rounds = np.zeros((k,), np.int32)
        q_relax = np.zeros((k,), np.int32)
        q_conv = np.ones((k,), bool)    # LRU rows were certified on entry
        n_hit = 0
        for j, s in enumerate(srcs):
            if s in hits:
                dist[j] = hits[s].dist
                n_hit += 1
            else:
                i = raw_col[s]
                dist[j] = raw.dist[i]
                q_rounds[j] = raw.q_rounds[i]
                q_relax[j] = raw.q_relaxations[i]
                q_conv[j] = bool(raw.stats.q_converged[i])
        zero = np.int32(0)
        if raw is not None:
            stats = raw.stats._replace(q_rounds=q_rounds,
                                       q_relaxations=q_relax,
                                       q_converged=q_conv)
        else:
            # every source served from the LRU: zero rounds, no program run
            stats = SsspStats(rounds=zero, relaxations=zero, msgs_sent=zero,
                              msgs_recv=zero, pruned_edges=zero,
                              q_rounds=q_rounds, q_relaxations=q_relax,
                              q_converged=q_conv, stale_merges=zero,
                              resends=zero, n_dispatches=zero,
                              overlap_rounds=zero, bytes_moved=zero)
            self.batches_served += 1
        # _solve_batch already counted the uncached subset it ran
        self.queries_served += k - len(uncached)
        return QueryResult(
            dist=dist, sources=srcs, stats=stats,
            bucket_k=raw.bucket_k if raw is not None else 0,
            backend=self.backend, wall_s=time.perf_counter() - t0,
            compile_s=raw.compile_s if raw is not None else 0.0,
            compiled=raw.compiled if raw is not None else False,
            cache_hits=n_hit,
            warm_started=raw.warm_started if raw is not None else False,
            status=raw.status if raw is not None else "converged")

    # ------------------------------------------------------ warm start ----

    def precompute_landmarks(self, l_sources) -> LandmarkCache:
        """Solve the L pivot sources once (cold) and cache their distances
        sharded ``[L, block]`` per shard — ``4 B x L x block`` per shard.
        With ``cfg.warm_start="landmark"`` every later solve seeds its
        distance vector with ``min_l(land[l, src] + land[l, v])`` instead
        of +inf and converges in fewer rounds, bit-identically. The pivot
        rows also populate the result cache (a landmark solve IS an exact
        solve of its pivot).

        REQUIRES symmetric distances (``d(u, v) == d(v, u)``, true for
        every undirected generator in :mod:`repro.graph.generators`): on a
        directed graph the bound uses ``d(l, src)`` where the triangle
        inequality needs ``d(src, l)``, and an invalid (too-low) seed
        would be silently kept by the monotone pipeline. The solved pivot
        rows give the ``L x L`` cross-distance matrix for free, so
        detectable asymmetry raises here instead of corrupting solves —
        a necessary check, not a sufficient one (a directed graph can be
        symmetric between the sampled pivots yet asymmetric elsewhere)."""
        srcs = _as_sources(l_sources, self.shards.n_vertices)
        if len(srcs) < 1:
            raise ValueError("at least one landmark source is required")
        res = self._solve_batch(tuple(dict.fromkeys(srcs)), use_warm=False)
        # landmark rows seed EVERY later solve: admit only certified
        # fixpoints (a degraded pivot row could under-bound d(l, src) +
        # d(l, v) nowhere but over-bound it everywhere — still wrong as a
        # "converges bit-identically" warm start), and never NaN (one NaN
        # seed poisons every distance downstream of it)
        if res.status != "converged":
            raise ValueError(
                f"landmark precompute did not converge (status="
                f"{res.status!r}): refusing to cache non-fixpoint seeds — "
                "raise max_rounds or fix the fault/termination config")
        if np.isnan(res.dist).any():
            raise ValueError(
                "landmark precompute produced NaN distances: the seed rows "
                "are not finite upper bounds (check edge weights)")
        cross = res.dist[:, list(res.sources)]      # [L, L] pivot pairs
        if not np.allclose(cross, cross.T, rtol=1e-4, atol=1e-4):
            raise ValueError(
                "landmark warm start requires symmetric distances, but the "
                "pivot cross-distances are asymmetric (directed graph?): "
                "the triangle-inequality seed would not be an upper bound")
        land = shard_distance_rows(res.dist, self.shards.n_parts,
                                   self.shards.block)
        self.landmarks = LandmarkCache(sources=res.sources, dist=land,
                                       epoch=self.graph_epoch)
        for i, s in enumerate(res.sources):
            self.result_cache.put(s, self.graph_epoch,
                                  CachedRow(dist=res.dist[i].copy()))
        return self.landmarks

    def invalidate_caches(self) -> int:
        """Graph-epoch bump: orphans every result-cache row and drops the
        landmark cache. Call after mutating the underlying graph/shards —
        cached distances are state that must not survive a graph change
        (the SSSP-Del invalidation story). Returns the new epoch."""
        self.graph_epoch += 1
        self.result_cache.clear()
        self.landmarks = None
        self._warm_traced.clear()
        return self.graph_epoch

    def warmup(self, k: int = 1) -> float:
        """Compile the bucket program serving batches of size ``k`` ahead
        of traffic; returns the cold-start seconds (0.0 if already warm).
        Bypasses the result cache (repeated sources must not shrink the
        compiled shape below the requested bucket). Warms the programs
        traffic will actually ride: on a landmark-warm engine that
        includes the warm path (the shmap whole-solve warm program / the
        sim seed program), which a cold trace of the same bucket (e.g.
        from ``precompute_landmarks``) does not cover."""
        kb = bucket_k(k)
        if self._warm_active():
            already = (kb, self.landmarks.n_landmarks) in self._warm_traced
        else:
            already = self.trace_counts.get(kb, 0) > 0
        if already:
            return 0.0
        res = self._solve_batch((0,) * kb, bucket=False)
        return res.compile_s

    # ------------------------------------------------------- streaming ----

    def submit(self, sources) -> QueryHandle:
        """Enqueue a query (or query batch) for the next ``drain``; sources
        are validated NOW so a bad id fails at submission, not mid-drain."""
        srcs = _as_sources(sources, self.shards.n_vertices)
        if len(srcs) < 1:
            raise ValueError("at least one source is required")
        h = QueryHandle(self, srcs)
        self._pending.append(h)
        return h

    @property
    def pending(self) -> int:
        return len(self._pending)

    @partial(annotate_function, name="sssp.drain")
    def drain(self) -> list[QueryResult]:
        """Coalesce pending arrivals into bucketed batches and solve them.

        Consecutive handles are packed while the combined size stays within
        ``max_bucket``; a handle is never split, so an oversized submission
        simply rides its own (larger) bucket. Each handle receives a
        :class:`QueryResult` view of its own rows; batch-level aggregates
        (rounds, totals, timing) are shared by every handle in the batch.
        If a solve fails mid-drain, every unsolved handle (including the
        failing batch) is re-queued before the error propagates — no
        submission is silently lost."""
        pending, self._pending = self._pending, []
        results: list[QueryResult] = []
        i = 0
        while i < len(pending):
            start = i
            group = [pending[i]]
            total = len(pending[i].sources)
            i += 1
            while (i < len(pending)
                   and total + len(pending[i].sources) <= self.max_bucket):
                group.append(pending[i])
                total += len(pending[i].sources)
                i += 1
            try:
                batch = self.solve([s for h in group for s in h.sources])
            except BaseException:
                self._pending = pending[start:] + self._pending
                raise
            off = 0
            for h in group:
                kk = len(h.sources)
                sl = slice(off, off + kk)
                conv = np.asarray(batch.stats.q_converged)[sl]
                h._result = dataclasses.replace(
                    batch, dist=batch.dist[sl], sources=h.sources,
                    status="converged" if bool(conv.all()) else batch.status,
                    stats=batch.stats._replace(
                        q_rounds=batch.stats.q_rounds[sl],
                        q_relaxations=batch.stats.q_relaxations[sl],
                        q_converged=conv))
                results.append(h._result)
                off += kk
        return results

    def __repr__(self):
        return (f"SsspEngine(backend={self.backend!r}, "
                f"n_vertices={self.n_vertices}, n_parts={self.n_parts}, "
                f"buckets={sorted(self.trace_counts)}, "
                f"pending={self.pending})")


# --------------------------------------------------------------------------
# engine cache backing the legacy free-function wrappers
# --------------------------------------------------------------------------

# One engine per (shards object, cfg, backend, mesh/axes): the legacy
# solve_* wrappers answer many calls against the same partitioned graph and
# must keep the compile-reuse the engine exists for. A cached engine holds
# its shards (and mesh) strongly, so the id() halves of a live entry's key
# cannot be recycled into an alias; the cache is bounded. This replaces the
# old module-global _SIM_ROUND_CACHE — the compiled programs now live in
# the engines.
_ENGINE_CACHE: dict = {}
_ENGINE_CACHE_MAX = 16


def engine_for(sh: SsspShards, cfg: SsspConfig, backend: str = "sim",
               mesh=None, axis_names=None) -> SsspEngine:
    """Cached engine lookup for the legacy wrappers (and anything else that
    holds shards + cfg instead of a session)."""
    axes = tuple(axis_names) if axis_names else None
    key = (id(sh), cfg, backend, None if mesh is None else id(mesh), axes)
    # the entry keeps the caller's shards object: a shmap engine holds a
    # device-placed copy, so eng.shards is not the identity to compare
    hit = _ENGINE_CACHE.get(key)
    if hit is not None and hit[0] is sh and hit[1].mesh is mesh:
        return hit[1]
    eng = SsspEngine(sh, cfg, backend, mesh, axes)
    if len(_ENGINE_CACHE) >= _ENGINE_CACHE_MAX:
        _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
    _ENGINE_CACHE[key] = (sh, eng)
    return eng
