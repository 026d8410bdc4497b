"""Shared arithmetic of the metric readers."""
import numpy as np


def p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95)) \
        if len(values) else None


def decode_steps(run):
    return [s for s in run.window.steps if s.decode and s.work]


def due_in_window(run):
    w = run.window
    return [t for t in w.tracked if w.start <= t.due < w.end]


def kernel_share(run, kernel, least_s):
    """Least time over the kernel's device time in the trace, in %."""
    if run.trace is None:
        return None
    spent = run.trace["kernel_s"].get(kernel, 0.0)
    return 100.0 * least_s / spent if spent > 0 and least_s > 0 else None
