"""Percent of the padded bucket lanes that held a real query."""


def read(run):
    lanes = sum(b.lanes for b in run.window.batches)
    return 100.0 * sum(b.real for b in run.window.batches) / lanes if lanes else None
