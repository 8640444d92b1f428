"""Device milliseconds per batch under the send pack's scope,
``sssp.send``: the cut-edge segment-min and the payload masking."""
from bench.phases import ms_per_batch


def read(run):
    return ms_per_batch(run, ("sssp.send",))
