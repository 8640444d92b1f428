"""Percent of the traced window with no operation on the device."""
from bench.trace import idle_share


def read(run):
    return idle_share(run.trace)
