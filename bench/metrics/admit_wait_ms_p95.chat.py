"""95th percentile over every request due in the window of the start of the
admission round that first gave it a slot less its submission, both stamped
by the program (``Request.admitted_at`` / ``submitted_at``): the wait for a
slot as the scheduler saw it.  None where the program stamps no admission."""
from bench.metrics._common import due_in_window, p95


def read(run):
    return p95([1e3 * (admit - t.req.submitted_at) for t in due_in_window(run)
                if (admit := getattr(t.req, "admitted_at", None)) is not None])
