"""85th percentile (nearest rank) of the time each query waited from its
due time to the start of its batch's solve (``bench.phases.queue_waits``):
the queueing part of ``latency_p85_ms``, timed from the same due time; the
rest is its own batch's solve and those after it in the same drain."""
from bench import phases
from bench.stats import percentile


def read(run):
    waits = phases.queue_waits(phases.for_run(run), run.window)
    return 1e3 * percentile(waits, 85) if waits else None
