"""Output tokens emitted in the window over the window's seconds; tokens
count as they appear after each step, finished or not (host clock)."""


def read(run):
    w = run.window
    toks = sum(n for s in w.steps if s.end <= w.end for _, n in s.work)
    return toks / (w.end - w.start)
