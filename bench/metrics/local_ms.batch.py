"""Device milliseconds per batch under the local fixpoint's scope,
``sssp.local`` (the Trishla prune inside it is ``sssp.prune``)."""
from bench.phases import ms_per_batch


def read(run):
    return ms_per_batch(run, ("sssp.local",))
