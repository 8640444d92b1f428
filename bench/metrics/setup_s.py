"""Process start to window open: generation, shard build, compile, warm-up."""


def read(run):
    return run.setup_s
