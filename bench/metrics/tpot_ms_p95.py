"""95th percentile over every request due in the window of (last token
time - first token time) / (tokens - 1)."""
from bench.metrics._common import due_in_window, p95


def read(run):
    return p95([1e3 * (t.last - t.first) / (t.n_out - 1)
                for t in due_in_window(run) if t.n_out >= 2])
