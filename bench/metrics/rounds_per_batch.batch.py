"""Mean of the program's ``stats.rounds`` per batch."""


def read(run):
    b = run.window.batches
    return sum(x.rounds for x in b) / len(b) if b else None
