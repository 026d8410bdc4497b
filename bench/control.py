#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap`` limit; not part of a benchmark run.

For each seed, in one process: the cell's own set-up and window through the
program, then the same sample of served requests as a run takes, scored by
the float32 reference twice:

- ``program``: the widest gap of the served tokens (what a run compares);
- ``control``: the widest gap of the tokens that the reference computed in
  float8 e4m3 (the step below the configuration's bfloat16) puts first at
  the same positions.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

The limit lies above the largest program reading and below the smallest
control reading (``bench/checks/<cell>.json``).  Needs the chip the cell
asks for; prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import check as C  # noqa: E402
from bench import manifest  # noqa: E402


def readings(cell, seed: int, seconds: float, devices, root=ROOT) -> dict:
    """Program and control gaps of one seed at the cell's own size."""
    import contextlib
    driver = manifest.driver(cell.config["driver"], root)
    served = driver.Served(cell.config, cell.mix, seed, devices)
    window = served.window(seconds, lambda _n: contextlib.nullcontext())
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    k = cell.mix["check"]["sample_requests"]
    samples = served.samples(rng, k)
    served.free()
    ref = manifest.reference(cell.config["reference"], root)
    prog, ctrl = C.served_gaps(ref, cell.config, seed, samples, k,
                               cell.mix["server"]["max_len"], lowp=True)
    return {"seed": seed, "requests": len(window.tracked),
            "served_tokens": int(prog.size), "program": float(prog.max()),
            "control": float(ctrl.max()),
            "program_median": float(np.median(prog)),
            "control_median": float(np.median(ctrl))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        r = readings(cell, seed, args.seconds, devices[:cell.chips])
        r["seconds"] = time.monotonic() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
