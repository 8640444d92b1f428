"""The program's relaxations over the harness's reached out-edges, for the
same batches. Local-edge relaxations only: compare two versions of the
program by it, not against 1."""


def read(run):
    edges = sum(b.reached_edges for b in run.window.batches)
    return sum(b.relaxations for b in run.window.batches) / edges if edges else None
