"""Record ``tp4_v5e_generate.xplane.pb``: one static batch of the
tensor-parallel engine on four TPU chips, traced with the benchmark's
``bench.*`` spans around the engine's ``tp.*`` spans and ``jit_tp_*``
programs.

    python3 tests/bench/fixtures/record_tp_spans.py --out DIR

The model is ``openpangu-7b-tp4`` at its published widths cut to 2 layers,
TP=4 over a (1, 4) mesh, Medusa on the 64-node tree; the batch is the
``offline-batch`` mix's 16 slots and 1024-token bucket with 4 new tokens, so
it runs one prefill and a few decode steps.  Prints the reduction, both
per-layer readers of the exchange, the engine's ``stats``, the programs and
spans found, and every all-reduce op's text, and writes
``--out``/``trimmed.xplane.pb`` (``record_serve_spans.trim``: names and
times only), which is the fixture.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

LAYERS, NEW = 2, 4


def tiny():
    conf = json.loads(
        (ROOT / "bench" / "configs" / "openpangu-7b-tp4.json").read_text())
    conf["num_hidden_layers"] = LAYERS
    mix = json.loads(
        (ROOT / "bench" / "traffic" / "offline-batch.json").read_text())
    mix["output"] = {"median": NEW, "sigma": 0.0, "min": NEW, "max": NEW}
    return conf, mix


def readers(reduced, conf, mix, device_kind):
    """Both readers of the exchange on a reduced trace."""
    from bench import manifest
    view = SimpleNamespace(trace=reduced, conf=conf, mix=mix,
                           chips=reduced["devices"], counts=manifest.counts,
                           _device=SimpleNamespace(device_kind=device_kind))
    return {m: manifest.reader(m).read(view)
            for m in ("all_reduce_roofline", "collective_share.tp4")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=2**33 + 15)
    args = ap.parse_args(argv)
    import jax

    from bench import manifest
    from bench import program_trace as PT
    from bench import trace as TR
    from record_serve_spans import trim
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        print("needs four TPU chips", file=sys.stderr)
        return 3
    conf, mix = tiny()
    served = manifest.driver("tp_generate").Served(conf, mix, args.seed,
                                                   devices[:4])
    before = served.tpe.stats               # the warm-up batch
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(args.out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        window = served.window(0.0, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    stats = {k: v - before[k] for k, v in served.tpe.stats.items()
             if k != "psums_per_step"}
    stats["psums_per_step"] = before["psums_per_step"]
    pb = sorted(Path(args.out).rglob("*.xplane.pb"))[-1]
    trimmed = Path(args.out) / "trimmed.xplane.pb"
    trim(pb, trimmed)
    pd = jax.profiler.ProfileData.from_file(str(trimmed))
    reduced = TR.reduce(pd, {"all_reduce": manifest.counts("all_reduce").MATCH})
    ops = Counter(n for evs in TR.device_events(pd).values() for *_, n in evs
                  if re.search(r" all-reduce\(", n))
    out = {"recorded_bytes": pb.stat().st_size,
           "trimmed_bytes": trimmed.stat().st_size,
           "batches": len(window.steps), "stats_in_window": stats,
           "reduce": reduced,
           "readers": readers(reduced, conf, mix, devices[0].device_kind),
           "programs": Counter(p for evs in PT.module_events(pd).values()
                               for *_, p in evs),
           "tp_spans": Counter(n for *_, n in TR.host_spans(pd, "tp.")),
           "all_reduce_ops": {n[:300]: c for n, c in ops.items()}}
    print(json.dumps(out, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
