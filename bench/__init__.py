"""Chip benchmark of the SSSP engine: cells, traffic, reference and trace
reduction. ``bench/run.py`` is the entry point; see ``PERF.md``.

What belongs to one configuration, traffic mix, graph generator or metric
is a file of its own, found by its name: ``bench/<kind>/<name><suffix>``.
"""
from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def by_name(kind: str, name: str, suffix: str) -> str:
    """Path of ``bench/<kind>/<name><suffix>``; a clear error if absent."""
    path = os.path.join(BENCH, kind, name + suffix)
    if not os.path.isfile(path):
        have = sorted(f[: -len(suffix)] for f in os.listdir(
            os.path.join(BENCH, kind)) if f.endswith(suffix))
        raise LookupError(f"no {kind} named {name!r} (bench/{kind}/{name}"
                          f"{suffix}); have {have}")
    return path


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name}", by_name(kind, name, ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
