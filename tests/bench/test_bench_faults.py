"""The comparison that decides ``correct`` fails what it must.

- The control, the plain reference in bfloat16 put in the program's place,
  comes out not correct on every configuration.
- A run whose timed path is broken underneath comes out not correct, once
  for each fault a cell can have: a round that returns its state unchanged,
  half of each batch left out, the exchange between partitions left out,
  and an answer altered where the engine produces it. Each is driven through
  the harness's own ``run_cell`` on the CPU, for a closed-loop and an
  open-loop cell, each with its own ``check``: the whole batches it
  samples.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench_tiny import run_tiny, tiny_cell

import jax.numpy as jnp

from bench import control


@pytest.mark.parametrize("name", ["graph500-s18.batch", "paper-graph1.batch"])
def test_control_in_bfloat16_is_not_correct(name):
    checks = control.control(tiny_cell(name).config, 2**31 + 9)
    assert not all(c.ok for c in checks)
    gap = {c.name: c for c in checks}["max_rel_gap"]
    assert gap.value > gap.limit


def unchanged_state(monkeypatch):
    from repro.core import engine
    monkeypatch.setattr(engine, "_make_round",
                        lambda *a, **k: (lambda carry: carry))


def _wrap_solve(monkeypatch, change):
    from repro.core.engine import SsspEngine
    solve = SsspEngine.solve

    def broken(self, sources, **kw):
        return change(self, solve, list(sources) if not isinstance(
            sources, (int, np.integer)) else [int(sources)], kw)

    monkeypatch.setattr(SsspEngine, "solve", broken)


def half_batch_left_out(monkeypatch):
    def change(self, solve, srcs, kw):
        keep = max(1, len(srcs) // 2)
        res = solve(self, srcs[:keep], **kw)
        if keep == len(srcs):      # a batch of one: its answer is lost
            dist = np.full_like(res.dist, np.inf)
        else:
            dist = np.concatenate([res.dist, np.full(
                (len(srcs) - keep, res.dist.shape[1]), np.inf,
                res.dist.dtype)])
        conv = np.ones(len(srcs), bool)
        return dataclasses.replace(
            res, dist=dist, sources=tuple(srcs),
            stats=res.stats._replace(
                q_converged=conv,
                q_rounds=np.resize(res.stats.q_rounds, len(srcs)),
                q_relaxations=np.resize(res.stats.q_relaxations, len(srcs))))
    _wrap_solve(monkeypatch, change)


def exchange_left_out(monkeypatch):
    from repro.core.sssp import SimComm
    monkeypatch.setattr(
        SimComm, "exchange_bucket",
        lambda self, payload: jnp.full_like(jnp.swapaxes(payload, 0, 2),
                                            jnp.inf))


def answer_altered(monkeypatch):
    def change(self, solve, srcs, kw):
        res = solve(self, srcs, **kw)
        dist = res.dist.copy()
        for row in dist:
            reached = np.flatnonzero(np.isfinite(row) & (row > 0))
            if reached.size:
                row[reached[-1]] += 1.0
        return dataclasses.replace(res, dist=dist)
    _wrap_solve(monkeypatch, change)


FAULTS = [unchanged_state, half_batch_left_out, exchange_left_out,
          answer_altered]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", ["graph500-s18.batch", "paper-graph1.serve"])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch, tmp_path):
    cell = tiny_cell(name)
    fault(monkeypatch)
    res = run_tiny(cell, str(tmp_path / "trace"), seconds=0.5)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
