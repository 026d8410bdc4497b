"""Reduce a profiler trace by the program's own names: device seconds and
executions of each jitted program, the scheduler's ``serve.*`` spans with
their self time, and the device's idle time put down to the innermost
``bench.*`` or ``serve.*`` span open over each part of it.

The serving program names its jitted programs (``jit_serve_step``,
``jit_admit_group``, ...: ``repro.serving.scheduler.named``) and wraps each
scheduler iteration in ``serve.*`` ``TraceAnnotation``s, which share the
profiler's clock with the device.  A program's executions are the events of
the ``XLA Modules`` line of each ``/device:...`` plane, named
``jit_<name>(<hash>)``; they are read inside ``bench.window`` after the same
skew shift as ``bench.trace.reduce`` makes, so the two agree on busy and
idle time.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import trace as TR

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "serve."
MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
OUTSIDE = "outside bench spans"


def program_name(module: str) -> str:
    """``jit_serve_step(123)`` -> ``serve_step``."""
    return MODULE_NAME.match(module).group(1)


def module_events(pd) -> dict:
    """{device plane: [(start_ns, end_ns, program)]} of program runs."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                    program_name(e.name))
                                   for e in line.events]
    return out


def nest(spans):
    """Properly nested spans sorted by start (outer first at a tie), and
    each one's parent index (-1 at the top)."""
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    parent, stack = [], []
    for i, (s, _, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return spans, parent


def self_times(spans) -> list:
    """[(start, end, name, self)]: each span's duration less the part its
    child spans cover."""
    spans, parent = nest(spans)
    own = [e - s for s, e, _ in spans]
    for i, p in enumerate(parent):
        if p >= 0:
            own[p] -= min(spans[i][1], spans[p][1]) - spans[i][0]
    return [(*sp, o) for sp, o in zip(spans, own)]


def segments(spans, lo, hi) -> list:
    """Sorted disjoint (start, end, name) pieces of [lo, hi], each named
    by the innermost span open over it ("outside bench spans" where none
    is)."""
    spans, parent = nest(spans)
    kids = defaultdict(list)
    for i, p in enumerate(parent):
        kids[p].append(i)
    out = []
    for i, (s, e, name) in [(-1, (lo, hi, OUTSIDE))] + list(enumerate(spans)):
        t = s
        for k in kids[i]:
            ks, ke = spans[k][:2]
            if ks > t:
                out.append((t, ks, name))
            t = max(t, ke)
        if e > t:
            out.append((t, e, name))
    return sorted((max(s, lo), min(e, hi), n) for s, e, n in out
                  if s < hi and e > lo)


def split(gaps, segs) -> dict:
    """{name: time} of the sorted disjoint ``gaps`` over ``segments``."""
    out, j = defaultdict(float), 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            out[segs[k][2]] += min(ge, segs[k][1]) - max(gs, segs[k][0])
            k += 1
    return out


def reduce_program(pd) -> dict:
    """``module_s`` / ``module_calls``: device seconds and executions per
    device of each program inside the window; ``program_spans``: for each
    ``serve.*`` span name in the window, {calls, s, self_s}; and
    ``idle_gaps_program``: the idle time that ``bench.trace.reduce`` gives
    under ``idle_gaps``, by the innermost ``bench.*`` or ``serve.*`` span,
    every label kept.  Where ``idle_gaps`` names a whole gap by the span
    at its middle, a gap here is cut at span edges: the device's wait
    between two steps shows as the parts spent in ``serve.sync``,
    ``serve.apply``, ``serve.reap``, ``serve.admit`` and ``serve.decode``."""
    bench = TR.host_spans(pd)
    serve = TR.host_spans(pd, SPAN_PREFIX)
    wins = [(s, e) for s, e, n in bench if n == TR.WINDOW]
    if not wins:
        raise ValueError(f"no {TR.WINDOW!r} span in the trace")
    lo, hi = wins[0]
    devs = {k: v for k, v in TR.device_events(pd).items() if v}
    if not devs:
        raise ValueError("no device operations in the trace")
    mods = module_events(pd)
    steps = TR.union([(s, e) for s, e, name in bench if name == TR.STEP])
    segs = segments([x for x in bench + serve if x[2] != TR.WINDOW], lo, hi)
    n = len(devs)
    msec, mcalls = defaultdict(float), defaultdict(int)
    idle = defaultdict(float)
    for plane, events in devs.items():
        d = TR.skew(TR.union([(s, e) for s, e, _ in events]), steps)
        for s, e, name in mods.get(plane, ()):
            s, e = max(s + d, lo), min(e + d, hi)
            if e > s:
                msec[name] += e - s
                mcalls[name] += 1
        merged = TR.union([(max(s + d, lo), min(e + d, hi))
                           for s, e, _ in events if e + d > lo and s + d < hi])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for name, t in split([(gs, ge) for gs, ge in
                              zip(edges[::2], edges[1::2]) if ge > gs],
                             segs).items():
            idle[name] += t
    spans = defaultdict(lambda: {"calls": 0, "s": 0.0, "self_s": 0.0})
    for s, e, name, own in self_times(serve):
        if lo <= s < hi:
            spans[name]["calls"] += 1
            spans[name]["s"] += (e - s) * 1e-9
            spans[name]["self_s"] += own * 1e-9
    ns = 1e-9
    return {"module_s": {k: v * ns / n for k, v in msec.items()},
            "module_calls": {k: v / n for k, v in mcalls.items()},
            "program_spans": dict(spans),
            "idle_gaps_program": [[k, v * ns / n] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])]}
