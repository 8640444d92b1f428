"""85th percentile (nearest rank) of the time from a query's due time to the
drain that answered it: at 82 queries a window, 12 lie beyond it. Read per
layer: over those few queries the tail swings with how the drains happen
to coalesce them, by more than any bound could hold."""
from bench.stats import percentile


def read(run):
    lat = run.window.latency_s
    return 1e3 * percentile(lat, 85) if lat else None
