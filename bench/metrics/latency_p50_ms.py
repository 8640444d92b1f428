"""Median time from a query's due time to the drain that answered it."""
from bench.stats import percentile


def read(run):
    lat = run.window.latency_s
    return 1e3 * percentile(lat, 50) if lat else None
