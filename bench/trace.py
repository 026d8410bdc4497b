"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-kernel
device time and the run's ``breakdown``.

Device operations are the events of the ``XLA Ops`` line of every
``/device:...`` plane.  On a TPU an event's name is its HLO instruction
text, and a loop's event (``%while``) encloses its body's events on the
same line: busy time is the union of all of them, and the breakdown counts
the innermost ones only.  The benchmark's own host spans are its
``bench.*`` ``TraceAnnotation``s; ``bench.window`` brackets the measured
window.

The device's clock in the trace is off the host's by a millisecond or two
(on a v5e its ops show before the host span that launched them).  The host
waits for every step inside its ``bench.step_once`` span, so the device
events are shifted by the offset that lays the most busy time inside those
spans, searched over +-``MAX_SKEW_NS`` in ``SKEW_STEP_NS`` steps.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
STEP = "bench.step_once"
MAX_SKEW_NS, SKEW_STEP_NS = 10_000_000, 50_000


def load(log_dir):
    from jax.profiler import ProfileData
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(str(found[-1]))


def device_events(pd) -> dict:
    """{device plane: [(start_ns, end_ns, name)]} of device ops."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events]
    return out


def host_spans(pd, prefix: str = "bench.") -> list:
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name.startswith(prefix)]
    return sorted(spans)


def union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def overlap(a, b) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def coarse(intervals, gap: float) -> list:
    """Sorted disjoint intervals with gaps shorter than ``gap`` filled."""
    out = []
    for s, e in intervals:
        if out and s - out[-1][1] < gap:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def skew(busy, steps) -> float:
    """The shift (ns) to add to device times that lays the most of
    ``busy`` inside the host's ``steps`` spans."""
    busy = coarse(busy, SKEW_STEP_NS)
    best = (-1.0, 0.0)
    for d in range(-MAX_SKEW_NS, MAX_SKEW_NS + 1, SKEW_STEP_NS):
        got = overlap([(s + d, e + d) for s, e in busy], steps)
        if got > best[0] or (got == best[0] and abs(d) < abs(best[1])):
            best = (got, float(d))
    return best[1]


def leaves(events) -> list:
    """The events that enclose no other event of the list."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    parent, stack = set(), []
    for i in order:
        s, e = events[i][:2]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            parent.add(stack[-1])
        stack.append(i)
    return [ev for i, ev in enumerate(events) if i not in parent]


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[8,64,12800]{...} fusion(...)`` -> ``%fusion.3 =
    bf16[8,64,12800]``: the op and its first result's type."""
    head = hlo.split("{", 1)[0].split(" custom-call(", 1)[0]
    return head[:120]


class HostLabels:
    """The innermost ``bench.*`` span (other than the window) at a time."""

    def __init__(self, spans):
        self.spans = [s for s in spans if s[2] != WINDOW]
        self.starts = [s[0] for s in self.spans]

    def __call__(self, t) -> str:
        i = bisect.bisect_right(self.starts, t)
        best = None
        for s, e, name in self.spans[max(0, i - 8):i]:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "outside bench spans"


def reduce(pd, kernels: dict, top: int = 10) -> dict:
    """``kernels``: {kernel: regex over the op's HLO text}.  Returns busy_s
    and window_s (averaged over the devices in the trace), each kernel's
    device seconds and calls per device, and the breakdown: the innermost
    ops that took most time, and the device's idle time inside the window
    by the host span it fell in."""
    spans = host_spans(pd)
    wins = [(s, e) for s, e, n in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = wins[0]
    devs = {k: v for k, v in device_events(pd).items() if v}
    if not devs:
        raise ValueError("no device operations in the trace")
    pats = {k: re.compile(p) for k, p in kernels.items()}
    label = HostLabels(spans)
    steps = union([(s, e) for s, e, name in spans if name == STEP])
    n = len(devs)
    busy = 0.0
    ksec, kcalls = defaultdict(float), defaultdict(int)
    by_op, idle = defaultdict(float), defaultdict(float)
    skews = []
    for events in devs.values():
        d = skew(union([(s, e) for s, e, _ in events]), steps)
        skews.append(d)
        inside = [(max(s + d, lo), min(e + d, hi), name)
                  for s, e, name in events if e + d > lo and s + d < hi]
        merged = union([(s, e) for s, e, _ in inside])
        busy += sum(e - s for s, e in merged)
        for s, e, name in leaves(inside):
            kernel = next((k for k, p in pats.items() if p.search(name)),
                          None)
            if kernel:
                ksec[kernel] += e - s
                kcalls[kernel] += 1
            by_op[f"{kernel} ({short_name(name)})" if kernel
                  else short_name(name)] += e - s
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                idle[label((gs + ge) / 2)] += ge - gs
    ns = 1e-9

    def ranked(d):
        return [[k, v * ns / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"devices": n, "skew_s": [d * ns for d in skews],
            "busy_s": busy * ns / n, "window_s": (hi - lo) * ns,
            "kernel_s": {k: ksec[k] * ns / n for k in kernels},
            "kernel_calls": {k: kcalls[k] / n for k in kernels},
            "breakdown": {"device_ops": ranked(by_op),
                          "idle_gaps": ranked(idle)}}
