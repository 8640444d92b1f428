"""Chip benchmark of the SSSP engine: cells, traffic, reference and trace
reduction. ``bench/run.py`` is the entry point; see ``PERF.md``."""
