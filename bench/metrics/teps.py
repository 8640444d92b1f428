"""Traversed edges per second over the window (Graph500's count, directed)."""
from bench.stats import teps


def read(run):
    return teps(run.window)
