"""Output tokens over occupied slot-steps in the window."""
from bench.metrics._common import decode_steps


def read(run):
    steps = decode_steps(run)
    slot_steps = sum(len(s.work) for s in steps)
    return sum(n for s in steps for _, n in s.work) / slot_steps \
        if slot_steps else None
