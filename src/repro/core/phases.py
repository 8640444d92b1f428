"""Backend registry for the SP-Async round pipeline.

The outer round is a fixed sequence of phases — local solve, send pack,
exchange, merge, termination — but each phase has interchangeable
*backends* (e.g. the send pack can run as an XLA segmented min or as the
slot-tiled Pallas kernel). This module is the small registry that maps
``(phase, backend_name) -> implementation`` so:

- ``SsspConfig`` can validate every backend name EAGERLY at construction
  (a typo raises ``ValueError`` listing the valid names instead of failing
  deep inside tracing),
- the solver builds its round by resolution, never by ``if`` ladders, and
  new stages/backends (query caching, landmark reuse, new exchange modes)
  slot in with a ``@register(...)`` decorator without touching the loop.

Registered phases and their config keys:

  ============== ======================= ====================================
  phase          config key              backends
  ============== ======================= ====================================
  round          ``cfg.round``           staged | fused
  local_solver   ``cfg.local_solver``    bellman | delta | pallas
  send           ``cfg.send_backend``    xla | pallas
  exchange       ``cfg.exchange``        bucket | pmin | a2a_dense | async
                                         | async_bucket | async_ppermute
  merge          ``cfg.merge_backend``   xla | pallas
  toka           ``cfg.toka``            toka0 | toka1 | toka2 | toka3
  warm_init      ``cfg.warm_start``      none | landmark
  ============== ======================= ====================================

The ``async*`` exchanges are DEFERRED: the round never barriers on their
collective — round r's relax overlaps delivery of round r-1's sends,
merged one round late (``async``/``async_bucket``: double-buffered
all-to-all, ``cfg.async_lag`` buffers; ``async_ppermute``: bidirectional
``ppermute`` neighbor hops over the partition ring). Registered in
``sssp.py`` next to the synchronous stages.

``round`` selects the SHAPE of the pipeline rather than one phase's
implementation: ``staged`` dispatches local/send/exchange/merge as
separate programs (4 data-plane dispatches per round); ``fused`` runs
merge + local fixpoint + send pack as ONE Pallas megakernel
(``kernels/round``), leaving 2 dispatches (megakernel + exchange) and
making the ``local_solver``/``send_backend``/``merge_backend`` keys
moot for the fused rounds.

Implementations live next to the machinery they use (``local_solver.py``
registers the local solvers, ``sssp.py`` the send/exchange/merge/toka
stages); this module stays dependency-free so anything may import it.
"""
from __future__ import annotations

_REGISTRY: dict[str, dict[str, object]] = {}


def register(phase: str, name: str):
    """Decorator: register ``obj`` as backend ``name`` of ``phase``."""

    def deco(obj):
        _REGISTRY.setdefault(phase, {})[name] = obj
        return obj

    return deco


def resolve(phase: str, name: str):
    """Look up a backend; unknown names raise a ``ValueError`` that names
    the valid options (this is what makes ``SsspConfig`` validation eager
    and its errors actionable)."""
    impls = _REGISTRY.get(phase, {})
    if name not in impls:
        raise ValueError(
            f"unknown {phase} backend {name!r}; valid: {sorted(impls)}")
    return impls[name]


def backends(phase: str) -> tuple[str, ...]:
    """Registered backend names for a phase (stable order)."""
    return tuple(sorted(_REGISTRY.get(phase, ())))


def validate(phase: str, name: str) -> str:
    """``resolve`` for its side effect only; returns ``name`` unchanged."""
    resolve(phase, name)
    return name
