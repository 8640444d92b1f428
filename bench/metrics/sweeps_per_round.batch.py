"""Sweeps of the local fixpoint per round: the trips of the loop opened in
``sssp.local`` in the traced window (``bench.phases.loop_trips``; a trip
relaxes every lane and part at once, as many as the longest needs) over
the program's ``stats.rounds`` of the window's batches."""
from bench import phases


def read(run):
    trips = phases.loop_trips(phases.for_run(run), "sssp.local")
    rounds = sum(b.rounds for b in run.window.batches)
    return trips / rounds if trips and rounds else None
