"""Process start to the first timed request: weights, compile or cache
reads, warm-up of the cell's shapes (host clock)."""


def read(run):
    return run.setup_s
