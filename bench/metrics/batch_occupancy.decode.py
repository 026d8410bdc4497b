"""Mean occupied slots per decode step over the window, over the slots, %.
A slot is occupied in a step when its request received tokens in it."""
from bench.metrics._common import decode_steps


def read(run):
    steps = decode_steps(run)
    if not steps:
        return None
    occ = sum(len(s.work) for s in steps) / len(steps)
    return 100.0 * occ / run.mix["server"]["slots"]
