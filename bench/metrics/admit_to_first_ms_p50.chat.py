"""Median over every request due in the window of its first token time less
the start of the admission round that first gave it a slot, both stamped by
the program (``Request.first_token_at`` / ``admitted_at``): prefill plus one
decode step, without the queue.  None where the program stamps neither."""
import numpy as np

from bench.metrics._common import due_in_window


def read(run):
    gaps = [1e3 * (first - admit) for t in due_in_window(run)
            if (admit := getattr(t.req, "admitted_at", None)) is not None
            and (first := getattr(t.req, "first_token_at", None)) is not None]
    return float(np.median(gaps)) if gaps else None
