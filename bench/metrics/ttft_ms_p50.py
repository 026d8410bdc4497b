"""Median over every request due in the window of its due time to its
first output token; one with no token counts to the drain's end."""
import numpy as np

from bench.metrics._common import due_in_window


def read(run):
    end = run.window.drained
    ttft = [1e3 * ((t.first if t.first is not None else end) - t.due)
            for t in due_in_window(run)]
    return float(np.median(ttft)) if ttft else None
