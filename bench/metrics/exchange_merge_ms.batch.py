"""Device milliseconds per batch under ``sssp.exchange`` or
``sssp.merge``: the payload's transfer and its scatter-min into the
receivers' distances."""
from bench.phases import ms_per_batch


def read(run):
    return ms_per_batch(run, ("sssp.exchange", "sssp.merge"))
