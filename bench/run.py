#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from ``BENCHMARK.json``
and the files it names (``bench/manifest.py``).  Set-up makes the weights on
the device from the seed and warms every shape the mix uses; then the window
runs for ``--seconds``; then the program's state is freed and a sample of
the served requests is compared with the plain float32 reference.  With
``--trace 1`` the window runs under the profiler and the line carries the
cell's per-layer metrics, ``busy_s``/``window_s`` and ``breakdown``; with
``--trace 0`` its end-to-end metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result: there is no CPU fallback.  Earlier lines on stderr carry
medians, sample counts, the generator's lateness and the compiles counted
inside the window; the last lines on stderr, and the result's ``check``
key, give every number compared with its limit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import check as C  # noqa: E402
from bench import manifest  # noqa: E402

TRACE_DIR = ".bench_trace"       # inside the checkout, removed after reading


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


class CompileClock:
    """Backend-compile seconds (persistent-cache reads included: a hit shows
    as a short compile), compile count and persistent-cache hits and misses,
    counted between ``start`` and ``stop`` (JAX monitoring events)."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if self._on and event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif self._on and event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def start(self):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        self._on = True

    def stop(self) -> dict:
        self._on = False
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


class RunView:
    """What a metric reader sees of one run."""

    def __init__(self, cell, setup_s, compile_setup, window, trace, device,
                 root):
        self.cell, self.conf, self.mix = cell.name, cell.config, cell.mix
        self.chips = cell.chips
        self.setup_s, self.compile_setup = setup_s, compile_setup
        self.window, self.trace = window, trace
        self._device, self._root = device, root

    @property
    def peaks(self) -> dict:
        """The chip's peaks; a device_kind not in the table is an error."""
        return manifest.peaks(self._device.device_kind, self._root)

    def counts(self, kernel):
        return manifest.counts(kernel, self._root)


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _summary(w) -> dict:
    """Medians and counts for the log (not the result line)."""
    tr = w.tracked
    due = [t for t in tr if w.start <= t.due < w.end]
    first = [1e3 * (t.first - t.due) for t in due if t.first is not None]
    steps = [s.end - s.start for s in w.steps]
    return {"requests_due": len(due), "with_first_token": len(first),
            "ttft_ms_median": float(np.median(first)) if first else None,
            "steps": len(steps),
            "step_ms_median": 1e3 * float(np.median(steps)) if steps else None,
            "step_ms_p99": (1e3 * float(np.percentile(steps, 99))
                            if steps else None),
            "step_ms_max": 1e3 * max(steps) if steps else None,
            "tokens": sum(n for s in w.steps for _, n in s.work),
            "generator_late_ms_median": (1e3 * float(np.median(w.late_s))
                                         if w.late_s else None),
            "generator_late_ms_max": (1e3 * float(np.max(w.late_s))
                                      if w.late_s else None)}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             root: Path = ROOT, t_start: float = T_START) -> dict:
    """Set-up, window, check and metrics of one run; the result dict."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    log("compile cache:", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = devices[0]
    clock = CompileClock()
    clock.start()
    driver = manifest.driver(cell.config["driver"], root)
    served = driver.Served(cell.config, cell.mix, seed, devices)
    setup_s = time.monotonic() - t_start
    compile_setup = clock.stop()
    log("setup:", json.dumps({"setup_s": setup_s, **compile_setup}))

    tdir = root / TRACE_DIR
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        ann = jax.profiler.TraceAnnotation
    else:
        def ann(_name):
            return contextlib.nullcontext()
    clock.start()
    with ann("bench.window"):
        window = served.window(seconds, ann)
    if trace:
        jax.profiler.stop_trace()
    in_window = clock.stop()
    log("window:", json.dumps({**_summary(window),
                               "window_s": window.end - window.start,
                               "compiles_in_window": in_window["compiles"],
                               "cache_misses_in_window":
                                   in_window["cache_misses"]}))
    memory = peak_bytes(devices)

    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    k = cell.mix["check"]["sample_requests"]
    samples = served.samples(rng, k)
    served.free()
    del served
    tracked = window.tracked
    if cell.mix["loop"] == "open":
        unfinished = sum(t.req.status != "done" for t in tracked)
    else:
        unfinished = sum(t.req.status in ("failed", "cancelled")
                         for t in tracked)
    gap = None
    if samples:
        t0 = time.monotonic()
        ref = manifest.reference(cell.config["reference"], root)
        g, _ = C.served_gaps(ref, cell.config, seed, samples, k,
                             cell.mix["server"]["max_len"])
        gap = float(g.max())
        log("reference:", json.dumps({
            "seconds": time.monotonic() - t0, "requests": len(samples),
            "served_tokens": int(g.size),
            "gap_median": float(np.median(g)),
            "gap_zero_share": float(np.mean(g == 0))}))
    judged = C.judge({"logit_gap": gap, "unfinished": unfinished,
                      "step_failures": window.step_failures}, cell.limits)

    reduced = None
    if trace:
        from bench import trace as TR
        kernels = {m["name"][:-len("_roofline")]:
                   manifest.counts(m["name"][:-len("_roofline")], root).MATCH
                   for m in cell.per_layer if m["name"].endswith("_roofline")}
        reduced = TR.reduce(TR.load(tdir), kernels)
        shutil.rmtree(tdir, ignore_errors=True)
        log("trace:", json.dumps({k: reduced[k] for k in
                                  ("devices", "busy_s", "window_s",
                                   "kernel_s", "kernel_calls")}))

    view = RunView(cell, setup_s, compile_setup, window, reduced, dev, root)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.reader(m["name"], root).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": C.passed(judged), "attempted": len(tracked),
              "failed": unfinished, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["check"] = judged
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devices)} {devices[0].platform} device(s). "
            "No CPU fallback.")
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips])
    for name, v in result["check"].items():
        log(f"check {name} {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
