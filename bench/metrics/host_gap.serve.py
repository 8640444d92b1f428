"""Percent of the time inside ``drain`` calls with the device idle."""
from bench.trace import host_gap


def read(run):
    return host_gap(run.trace, "bench.drain")
