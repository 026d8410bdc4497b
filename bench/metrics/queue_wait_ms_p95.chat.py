"""95th percentile over every request due in the window of its due time
to the start of the step in which it first held a slot."""
from bench.metrics._common import due_in_window, p95


def read(run):
    end = run.window.drained
    return p95([1e3 * ((t.admit if t.admit is not None else end) - t.due)
                for t in due_in_window(run)])
