"""Record ``v5e_serve_spans.xplane.pb``: a short open-loop window of a tiny
served model on one TPU, traced with the benchmark's ``bench.*`` spans around
the server's own ``serve.*`` spans and named programs.

    python3 tests/bench/fixtures/record_serve_spans.py --out DIR [--seconds S]
        [--host-level N]

The model is the benchmark's dense GQA configuration cut to 2 layers, width
1024 (8/2 heads of 128), FFN 2048, vocab 4096, bf16, Medusa on the 64-node
tree, verify fusion and the Pallas kernels on; 2 slots, one 64-token bucket.
Prints the trace's size, its reduction and every device op whose text names
one of the program's Pallas kernels, and writes ``--out``/``trimmed.xplane.pb``:
the same trace with only what the reductions read (the device's ``XLA Ops``
and ``XLA Modules`` lines, the host's events, each event's name and times;
no HLO protos, no per-event statistics), which is the fixture.

    python3 tests/bench/fixtures/record_serve_spans.py --trim IN.xplane.pb OUT

trims an earlier recording (needs TensorFlow's ``xplane_pb2``).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KERNELS = ("flash_decode", "unembed_verify_stats", "fused_qkv_rope_commit",
           "commit_rows", "commit_rows_paged")


def tiny():
    conf = json.loads(
        (ROOT / "bench" / "configs" / "openpangu-7b-pp2.json").read_text())
    conf.update(hidden_size=1024, num_attention_heads=8, num_key_value_heads=2,
                head_dim=128, intermediate_size=2048, vocab_size=4096,
                num_hidden_layers=2)
    mix = {"loop": "open", "requests": 64, "rate_per_s": 40.0, "drain_s": 2,
           "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
           "output": {"median": 4, "sigma": 0.3, "min": 2, "max": 6},
           "server": {"slots": 2, "max_len": 512, "prompt_buckets": [64]},
           "check": {"sample_requests": 1}}
    return conf, mix


def trim(src, dst):
    """Keep the device's XLA Ops / XLA Modules lines and the host plane,
    with names and times only."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(src).read_bytes())
    keep = [p for p in space.planes
            if p.name.startswith("/device:TPU") or p.name == "/host:CPU"]
    out = xplane_pb2.XSpace()
    for plane in keep:
        p = out.planes.add(id=plane.id, name=plane.name)
        lines = [ln for ln in plane.lines
                 if plane.name.startswith("/host:")
                 or ln.name in ("XLA Ops", "XLA Modules")]
        used = set()
        for ln in lines:
            q = p.lines.add(id=ln.id, display_id=ln.display_id, name=ln.name,
                            timestamp_ns=ln.timestamp_ns,
                            duration_ps=ln.duration_ps)
            for e in ln.events:
                q.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps,
                             duration_ps=e.duration_ps)
                used.add(e.metadata_id)
        for mid in used:
            md = plane.event_metadata[mid]
            p.event_metadata[mid].id = md.id
            p.event_metadata[mid].name = md.name
    Path(dst).write_bytes(out.SerializeToString())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--host-level", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2**33 + 13)
    ap.add_argument("--trim", nargs=2, metavar=("IN", "OUT"))
    args = ap.parse_args(argv)
    if args.trim:
        trim(*args.trim)
        return 0
    if not args.out:
        ap.error("--out is required to record")
    import jax

    from bench import manifest
    from bench import program_trace as PT
    from bench import trace as TR
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    conf, mix = tiny()
    served = manifest.driver("spec_server").Served(conf, mix, args.seed,
                                                   jax.devices()[:1])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = args.host_level
    jax.profiler.start_trace(args.out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        served.window(args.seconds, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    pb = sorted(Path(args.out).rglob("*.xplane.pb"))[-1]
    trimmed = Path(args.out) / "trimmed.xplane.pb"
    trim(pb, trimmed)
    pd = jax.profiler.ProfileData.from_file(str(trimmed))
    ops = {n for evs in TR.device_events(pd).values() for *_, n in evs}
    out = {"recorded_bytes": pb.stat().st_size,
           "trimmed_bytes": trimmed.stat().st_size,
           "stats": served.srv.stats,
           "reduce": TR.reduce(pd, {k: manifest.counts(k).MATCH for k in
                                    ("flash_decode", "unembed_verify_stats")}),
           "program": PT.reduce_program(pd),
           "ops_naming_a_kernel": {k: sorted(n[:300] for n in ops if k in n)
                                   for k in KERNELS}}
    print(json.dumps(out, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
