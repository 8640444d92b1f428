"""Percent of the time inside ``solve`` calls with the device idle: the
per-round launch and done read, the certificate and the host copy."""
from bench.trace import host_gap


def read(run):
    return host_gap(run.trace, "bench.solve")
