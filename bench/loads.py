"""The two loops that drive the engine through a measured window.

One general generator reads a traffic file's parameters:

- ``"loop": "closed"``: batches of ``batch`` distinct sources go back to
  back through ``SsspEngine.solve``. The window closes at the first batch
  that completes after ``seconds``, so no part-batch is lost.
- ``"loop": "open"``: ``round(rate_qps * seconds)`` single-source queries
  arrive at uniform random times in ``[0, seconds)`` (a Poisson process
  conditioned on its count). The times and the set of sources are one
  fixed draw, and the seed draws the order in which the sources arrive:
  drawn anew from each seed, times and sources spread the latency tail
  over seeds by a third, more than any change a later PR could claim.
  Each due query is submitted; whenever queries are pending, ``drain``
  answers them. Every query due in the window is answered, after the
  window if need be.

Sources are uniform among the vertices with out-degree >= 1 (Graph500's
search keys). The check compares whole batches: a reservoir of
``check.batches`` of the window's batches, every row of each.
"""
from __future__ import annotations

import dataclasses
from time import perf_counter, sleep

import numpy as np
from jax.profiler import TraceAnnotation

@dataclasses.dataclass
class Batch:
    """One batch the program solved."""

    real: int            # queries in it
    lanes: int           # the padded bucket it ran in
    rounds: int          # the program's ``stats.rounds``
    relaxations: int     # the program's ``stats.relaxations``
    reached_edges: int   # out-edges of the vertices its queries reached
    compiled: bool = False   # its solve traced a new program
    counters: dict = dataclasses.field(default_factory=dict)
    # every scalar field of the program's ``stats`` that is set, by name:
    # a metric reads a counter with ``counters.get(name)``, so a counter
    # that a later program adds needs a metric file and no edit here


def counters(stats) -> dict:
    """The scalar fields of a ``SsspStats`` that are not None, as ints.
    They are on the host once ``solve`` has returned."""
    return {k: int(v) for k, v in stats._asdict().items()
            if v is not None and np.ndim(v) == 0}


def batch_of(res, real: int, reached_edges: int) -> Batch:
    """The :class:`Batch` of a ``QueryResult`` (one per batch, or the first
    of a drained batch's results)."""
    return Batch(real=real, lanes=res.bucket_k, rounds=int(res.stats.rounds),
                 relaxations=int(res.stats.relaxations),
                 reached_edges=reached_edges, compiled=bool(res.compiled),
                 counters=counters(res.stats))


@dataclasses.dataclass
class Window:
    start: float = 0.0   # perf_counter at the window's open
    end: float = 0.0     # perf_counter at its close (closed loop: the last
                         # batch's completion)
    batches: list = dataclasses.field(default_factory=list)
    latency_s: list = dataclasses.field(default_factory=list)
    lateness_s: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    unconverged: int = 0
    backlog_at_close: int = 0   # open loop: queries due in the window
                                # whose drain had not started at its close


class Reservoir:
    """A uniform sample of ``size`` batches from a stream of unknown length
    (Algorithm R), drawn from its own generator: the sources and distance
    rows of each batch kept."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen = size, rng, 0
        self.batches = []   # [(sources, rows [k, n])]

    def offer(self, sources, rows: np.ndarray) -> None:
        j = self.seen if self.seen < self.size else int(
            self.rng.integers(0, self.seen + 1))
        self.seen += 1
        if j < self.size:
            item = (tuple(int(s) for s in sources), np.array(rows))
            if j < len(self.batches):
                self.batches[j] = item
            else:
                self.batches.append(item)

    @property
    def sources(self) -> list[int]:
        return [s for srcs, _ in self.batches for s in srcs]

    @property
    def rows(self) -> np.ndarray:
        return np.concatenate([r for _, r in self.batches])


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed % (1 << 64)])


def warm_buckets(traffic: dict, max_bucket: int) -> list[int]:
    """The batch shapes the traffic will use, to compile before the window."""
    if traffic["loop"] == "closed":
        return [1 << (int(traffic["batch"]) - 1).bit_length()]
    return [1 << i for i in range(max_bucket.bit_length())
            if 1 << i <= max_bucket]


def _count(window: Window, res) -> None:
    conv = np.asarray(res.q_converged, bool)
    window.attempted += len(res.sources)
    window.unconverged += int(np.count_nonzero(~conv))


def closed_loop(engine, traffic: dict, eligible: np.ndarray,
                out_degree: np.ndarray, seconds: float, seed: int,
                sample: Reservoir) -> Window:
    k = int(traffic["batch"])
    rng = rng_for(seed, 2)
    deg = out_degree.astype(np.float32)
    w = Window()
    clock = perf_counter
    with TraceAnnotation("bench.window"):
        w.start = clock()
        while True:
            srcs = rng.choice(eligible, size=k, replace=False)
            with TraceAnnotation("bench.solve"):
                res = engine.solve(srcs)
            with TraceAnnotation("bench.check"):
                reached = np.isfinite(res.dist).astype(np.float32) @ deg
                w.batches.append(batch_of(
                    res, len(res.sources),
                    int(np.sum(reached, dtype=np.float64))))
                _count(w, res)
                sample.offer(res.sources, res.dist)
            w.end = clock()
            if w.end - w.start >= seconds:
                break
    return w


def arrivals(traffic: dict, eligible: np.ndarray, seconds: float,
             seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Due times (seconds from the window's open) and sources: the same
    times and set of sources for every seed, the sources in the seed's
    order."""
    count = int(round(float(traffic["rate_qps"]) * seconds))
    fixed = rng_for(0, 4)
    due = np.sort(fixed.uniform(0.0, seconds, count))
    sources = fixed.choice(eligible, size=count)
    return due, rng_for(seed, 2).permutation(sources)


def open_loop(engine, traffic: dict, eligible: np.ndarray,
              out_degree: np.ndarray, seconds: float, seed: int,
              sample: Reservoir) -> Window:
    due, sources = arrivals(traffic, eligible, seconds, seed)
    w = Window()
    clock = perf_counter
    handles = []            # (query index, handle) submitted, not answered
    drain_start = np.full(len(due), np.inf)
    i = 0
    with TraceAnnotation("bench.window"):
        w.start = t0 = clock()
        while i < len(due) or handles:
            now = clock() - t0
            while i < len(due) and due[i] <= now:
                with TraceAnnotation("bench.submit"):
                    handles.append((i, engine.submit(int(sources[i]))))
                w.lateness_s.append(now - due[i])
                i += 1
            if not handles:
                with TraceAnnotation("bench.wait_arrival"):
                    sleep(max(0.0, due[i] - (clock() - t0)))
                continue
            served0 = engine.batches_served
            drain_start[[q for q, _ in handles]] = clock() - t0
            with TraceAnnotation("bench.drain"):
                results = engine.drain()
            done = clock() - t0
            if len(results) != len(handles):
                raise RuntimeError(f"drain answered {len(results)} of "
                                   f"{len(handles)} pending queries")
            with TraceAnnotation("bench.check"):
                for (q, _), res in zip(handles, results):
                    ok = bool(np.asarray(res.q_converged).all())
                    w.latency_s.append(done - due[q] if ok else np.inf)
                    _count(w, res)
                for batch, rs in _batches(results,
                                          engine.batches_served - served0):
                    w.batches.append(batch)
                    sample.offer([s for r in rs for s in r.sources],
                                 np.concatenate([r.dist for r in rs]))
                handles = []
        w.end = clock()
    # every query is due in the window; those still queued at its close
    w.backlog_at_close = int(np.count_nonzero(drain_start >= seconds))
    return w


def _batches(results, n_batches: int) -> list[tuple[Batch, list]]:
    """The batches of one drain, each with its results: one result per
    handle, in order, and those of one batch share the batch's ``wall_s``
    and ``bucket_k``."""
    out = []
    for r in results:
        key = (r.wall_s, r.bucket_k)
        if out and out[-1][0] == key:
            out[-1][1].real += len(r.sources)
            out[-1][2].append(r)
        else:
            out.append((key, batch_of(r, len(r.sources), 0), [r]))
    if len(out) != n_batches:
        raise RuntimeError(f"drain ran {n_batches} batches but its results "
                           f"group into {len(out)}")
    return [(b, rs) for _, b, rs in out]


LOOP_FNS = {"closed": closed_loop, "open": open_loop}
