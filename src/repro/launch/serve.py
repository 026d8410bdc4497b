"""Serving launcher: continuous-batching speculative server with a
pluggable proposer (DESIGN.md §13).

By default it serves the reduced (CPU-sized) variant of ``--arch``; with
``--published-widths`` it serves the published config in bf16, cut only in
depth by ``--layers``.  Engines run the Pallas kernels: compiled on a TPU,
interpreted on the CPU.

  PYTHONPATH=src python -m repro.launch.serve --arch openpangu-7b \
      --requests 16 --slots 4 --max-new 24 --proposer ngram
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import sys
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.configs.base import SamplingParams, SchedulerParams
from repro.configs.registry import ALL_ARCHS, get_config
from repro.core import medusa as M
from repro.core.engine import build_engine
from repro.distributed.sharding import split_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model
from repro.models.frontends import frontend_embeds
from repro.serving.scheduler import FamilySpecServer, SpecServer


def serving_config(arch: str, *, published: bool = False, layers: int = 0,
                   **fields):
    """The config to serve: ``arch``'s reduced CPU variant, or with
    ``published`` its published widths; ``layers`` cuts depth only (0 keeps
    the config's own).  ``fields`` override cache/verify knobs."""
    cfg = get_config(arch, reduced=not published)
    if layers:
        if not 0 < layers <= cfg.num_layers:
            raise SystemExit(f"--layers {layers}: {cfg.name} has "
                             f"{cfg.num_layers} layers")
        fields["num_layers"] = layers
    return dataclasses.replace(cfg, **fields) if fields else cfg


def _backbone_init(cfg):
    model = get_model(cfg)
    return lambda key: model.init_params(key, cfg, dtype=cfg.dtype)


def weight_shapes(cfg):
    """(ShapeDtypeStruct tree, logical-axes tree) of the backbone weights,
    allocated nowhere — what a sharding plan is computed from."""
    return split_params(jax.eval_shape(_backbone_init(cfg),
                                       jax.random.PRNGKey(0)))


def init_weights(cfg, seed: int = 0, out_shardings=None):
    """Random backbone weights in ``cfg.dtype`` from one jitted init.

    The layers are drawn straight into their stacked leaves and placed by
    ``out_shardings`` as they are made (one device for a replica, the TP
    plan for ``--tp``), so a published-width model never passes through a
    float32 copy, a per-layer copy or the default device."""
    init = _backbone_init(cfg)
    return jax.jit(lambda k: split_params(init(k))[0],
                   out_shardings=out_shardings)(jax.random.PRNGKey(seed))


def proposer_params(kind: str, cfg, eng, out_shardings=None):
    """Proposer-side weights for ``kind`` in ``cfg.dtype``: Medusa heads,
    draft-model weights, or nothing (the train-free n-gram lookup)."""
    if kind == "medusa":
        def init(k):
            return M.init_medusa(k, cfg, eng.tb.K, dtype=cfg.dtype)
    elif kind == "draft":
        dc = eng.proposer.dc

        def init(k):
            return get_model(dc).init_params(k, dc, dtype=dc.dtype)
    else:
        return None
    return jax.jit(lambda k: split_params(init(k))[0],
                   out_shardings=out_shardings)(jax.random.PRNGKey(1))


def random_prompts(cfg, n: int, lo: int = 4, hi: int = 48, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def tp_generate(cfg, prompts, *, tp: int, data: int = 1,
                proposer: str = "medusa", gamma: int = 4,
                accept: str = "greedy", sampling=None, slots: int = 4,
                max_len: int = 512, max_new: int = 24):
    """Static-batch generation through the shard_map engine (DESIGN.md §18).

    Weights are drawn directly into the TP plan's shardings (heads, ffn and
    vocab split over the model axis; proposer weights replicated), so no
    device ever holds the whole model.  Each batch of ``slots`` prompts
    runs one jitted ``generate``.  Returns ([per-prompt output tokens],
    seconds spent generating)."""
    import jax.numpy as jnp

    from repro.distributed import profiles
    from repro.distributed.tp import build_tp_engine, make_tp_mesh
    mesh = make_tp_mesh(tp, data=data)
    tpe = build_tp_engine(cfg, mesh, proposer, gamma=gamma, accept=accept,
                          sampling=sampling)
    shapes, axes = weight_shapes(cfg)
    specs = tpe.param_specs(shapes, axes)
    sp = init_weights(cfg, out_shardings=profiles.to_named(specs, mesh))
    pp = proposer_params(proposer, cfg, tpe,
                         out_shardings=NamedSharding(mesh, P()))
    B = slots
    outs = []
    t0 = time.time()
    for i in range(0, len(prompts), B):
        batch = prompts[i:i + B]
        S = max(len(p) for p in batch)
        tok = np.zeros((B, S), np.int32)
        plen = np.zeros((B,), np.int32)
        for j, p in enumerate(batch):
            tok[j, :len(p)] = p
            plen[j] = len(p)
        for j in range(len(batch), B):      # ragged tail: duplicate row 0
            tok[j], plen[j] = tok[0], plen[0]
        cache = tpe.init_cache(B, max_len)
        out, n_out, _ = tpe.generate(sp, pp, tpe.replicate(jnp.asarray(tok)),
                                     tpe.replicate(jnp.asarray(plen)), cache,
                                     max_new)
        out, n_out = np.asarray(out), np.asarray(n_out)
        outs += [out[j, :n_out[j]] for j in range(len(batch))]
    return outs, time.time() - t0


def serve_tp(args, cfg, sampling):
    """--tp path: ``tp_generate`` over the launcher's random prompts; with
    ``--profile`` under the profiler (the engine's ``tp.*`` spans and
    ``jit_tp_*`` programs, DESIGN.md §18)."""
    if args.mesh_shape:
        try:
            d, m = (int(x) for x in args.mesh_shape.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh-shape wants DATAxMODEL (e.g. '1x4'), "
                             f"got {args.mesh_shape!r}")
        if m != args.tp:
            raise SystemExit(f"--mesh-shape model dim {m} != --tp {args.tp}")
    else:
        d, m = 1, args.tp
    prompts = random_prompts(cfg, args.requests)
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        outs, dt = tp_generate(cfg, prompts, tp=m, data=d,
                               proposer=args.proposer, gamma=args.gamma,
                               accept=args.accept, sampling=sampling,
                               slots=args.slots, max_len=args.max_len,
                               max_new=args.max_new)
    toks = sum(len(o) for o in outs)
    print(f"tp={args.tp} mesh=({d}x{m}) proposer={args.proposer}: "
          f"{len(prompts)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s across {d * m} {device_label()} devices)")
    return 0


def device_label() -> str:
    dev = jax.devices()[0]
    return f"{dev.platform} {dev.device_kind}"


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="openpangu-7b", choices=ALL_ARCHS)
    ap.add_argument("--published-widths", action="store_true",
                    help="serve the arch's published widths with bf16 "
                         "weights instead of its reduced CPU variant")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers (depth only; "
                         "0 = the config's own depth)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--proposer", default="medusa",
                    choices=("medusa", "draft", "ngram"),
                    help="draft policy (DESIGN.md §13): trained Medusa "
                         "heads, a 2-layer draft-model sibling, or "
                         "train-free n-gram prompt lookup")
    ap.add_argument("--gamma", type=int, default=4,
                    help="chain length for the draft/ngram proposers "
                         "(medusa uses its static tree)")
    ap.add_argument("--families", default="",
                    help="comma-separated proposer kinds (e.g. "
                         "'medusa,ngram,draft'): serve through one "
                         "FamilySpecServer with a slot-group lane per kind "
                         "— each lane owns its proposer and compiled step "
                         "graphs; requests round-robin across lanes and "
                         "--proposer is ignored (DESIGN.md §17)")
    ap.add_argument("--admission", default="batched",
                    choices=("batched", "serial"),
                    help="scheduler v2 batched bucketed prefill (default) "
                         "or v1-style per-request admission")
    ap.add_argument("--cache-dtype", default="", choices=("", "int8"),
                    help="KV-cache storage dtype (DESIGN.md §10); int8 "
                         "halves cache bytes per slot")
    ap.add_argument("--cache-layout", default="dense",
                    choices=("dense", "paged"),
                    help="KV-cache layout (DESIGN.md §12): dense per-slot "
                         "rows, or a paged global block pool with per-slot "
                         "block tables")
    ap.add_argument("--page-size", type=int, default=64,
                    help="paged layout: logical rows per pool block")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="reuse shared prompt-prefix blocks across requests "
                         "(requires --cache-layout paged; DESIGN.md §12)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="chunked prefill: admit long prompts in pieces of "
                         "this many tokens interleaved with decode steps; "
                         "0 = whole-prompt prefill (DESIGN.md §14)")
    ap.add_argument("--preemption", action="store_true",
                    help="optimistic block allocation with preempt-and-"
                         "requeue on pool exhaustion (requires "
                         "--cache-layout paged; DESIGN.md §14)")
    ap.add_argument("--adaptive-gamma", action="store_true",
                    help="adapt speculation depth per step from the recent "
                         "acceptance EMA, switching among pre-compiled "
                         "step graphs (DESIGN.md §14)")
    ap.add_argument("--accept", default="greedy", choices=("greedy", "sample"),
                    help="verification mode: greedy argmax match or lossless "
                         "stochastic rejection sampling (DESIGN.md §11)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (accept=sample; "
                         "0 is exact greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus truncation (accept=sample)")
    ap.add_argument("--verify-fusion", action="store_true",
                    help="fold unembed + acceptance into the decode kernel "
                         "epilogue — no [B, T, V] logits round-trip; "
                         "requires top-p 1.0 under accept=sample "
                         "(DESIGN.md §15)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through a prefix-affinity ReplicaRouter "
                         "over this many independent server replicas: "
                         "requests route to the replica whose pool already "
                         "holds their prompt-prefix blocks, least-loaded "
                         "otherwise, with queue-depth backpressure "
                         "(DESIGN.md §18); 0 = single server")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel decode: run the speculative step "
                         "under shard_map on a tp-way model axis — heads, "
                         "ffn, vocab and the KV pools shard; the verify "
                         "reduction is a psum epilogue (DESIGN.md §18). "
                         "0 = single device.  Needs tp devices (CPU: set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    ap.add_argument("--mesh-shape", default="",
                    help="explicit DATAxMODEL device mesh for --tp (e.g. "
                         "'2x4'); default '1x<tp>'")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="write a profiler trace of the serving loop to DIR: "
                         "the scheduler's serve.* spans (with --tp the "
                         "engine's tp.* spans) and the named programs on "
                         "the device's clock (README, Tracing)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    fields = {}
    if args.cache_dtype or args.cache_layout != "dense" or args.verify_fusion:
        fields = dict(cache_dtype=args.cache_dtype,
                      cache_layout=args.cache_layout,
                      page_size=args.page_size,
                      verify_fusion=args.verify_fusion)
    cfg = serving_config(args.arch, published=args.published_widths,
                         layers=args.layers, **fields)
    sampling = SamplingParams(temperature=args.temperature, top_p=args.top_p)
    sched = SchedulerParams(chunk_size=args.chunk_size,
                            preemption=args.preemption,
                            adaptive_gamma=args.adaptive_gamma)
    kinds = [k.strip() for k in args.families.split(",") if k.strip()]
    if args.tp:
        if kinds or args.replicas:
            raise SystemExit("--tp serves static batches through the sharded "
                             "engine; it does not combine with --families "
                             "or --replicas")
        return serve_tp(args, cfg, sampling)
    if args.replicas and kinds:
        raise SystemExit("--replicas routes across single-proposer replicas; "
                         "it does not combine with --families")

    weights = {}     # device -> backbone weights placed there

    def make_server(kind, device=None):
        # proposer weights before the backbone's: a Medusa init holds one
        # head of scratch, which must not sit on top of a full backbone
        eng = build_engine(cfg, kind, gamma=args.gamma, accept=args.accept,
                           sampling=sampling, use_kernel=True)
        place = None if device is None else SingleDeviceSharding(device)
        pp = proposer_params(kind, cfg, eng, out_shardings=place)
        if device not in weights:
            weights[device] = init_weights(cfg, out_shardings=place)
        return SpecServer(eng, weights[device], pp, batch_slots=args.slots,
                          max_len=args.max_len, admission=args.admission,
                          prefix_cache=args.prefix_cache, sched=sched,
                          device=device)

    if args.replicas:
        # prefix-affinity front door over N independent replicas (§18), one
        # device each (round-robin when there are fewer devices)
        from repro.serving.router import ReplicaRouter
        devs = jax.devices()
        srv = ReplicaRouter(
            {f"r{i}": make_server(args.proposer, devs[i % len(devs)])
             for i in range(args.replicas)},
            page_size=args.page_size)
    elif kinds:
        # one façade, one slot-group lane per proposer kind (DESIGN.md §17)
        srv = FamilySpecServer({k: make_server(k) for k in kinds})
    else:
        srv = make_server(args.proposer)
    rng = np.random.default_rng(0)
    # under the router, requests share a handful of prompt-prefix chains so
    # affinity has something to bite on (the §12 prefix-cache demo shape)
    bases = [rng.integers(0, cfg.vocab_size,
                          size=2 * args.page_size).astype(np.int32)
             for _ in range(4)] if args.replicas else []
    t0 = time.time()
    rids = []
    for r in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(4, 48))).astype(np.int32)
        if bases:
            prompt = np.concatenate([bases[r % len(bases)], prompt])
        kw = dict(max_new=args.max_new, temperature=args.temperature,
                  top_p=args.top_p)
        if cfg.family == "encdec":
            kw["extra_embeds"] = np.asarray(
                frontend_embeds(cfg, 1, key=jax.random.PRNGKey(r))[0],
                np.float32)
        if kinds:
            kw["group"] = kinds[r % len(kinds)]   # round-robin across lanes
        rids.append(srv.submit(prompt, **kw))
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        iters = srv.run()
    dt = time.time() - t0
    done = [srv.result(r) for r in rids]
    toks = sum(len(r.output) for r in done if r.status == "done")
    failed = [r.rid for r in done if r.status != "done"]
    print(f"{len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({iters} scheduler iterations, {toks/dt:.1f} tok/s on "
          f"{device_label()})")
    if failed:
        print(f"FAILED: {len(failed)} request(s) did not finish: {failed}",
              file=sys.stderr)
    _report(args, srv, kinds, done)
    return 1 if failed else 0


def _report(args, srv, kinds, done):
    if args.replicas:
        snap = srv.snapshot()
        total = snap["affinity_hits"] + snap["affinity_misses"]
        print(f"router (DESIGN.md §18): {snap['affinity_hits']}/{total} "
              f"affinity hits, {snap['rebalances']} rebalances, "
              f"{snap['requeues']} requeues; routed "
              + ", ".join(f"{n}={c}" for n, c in snap["routed"].items()))
        return
    if kinds:
        for k in kinds:
            st = srv.stats[k]
            print(f"lane {k}: {st['admitted']} admissions, {st['steps']} "
                  f"decode steps in {st['prefill_calls']} prefill calls")
        return
    print(f"proposer={args.proposer} admission={args.admission}: "
          f"{srv.stats['admitted']} slot admissions (incl. retries) in "
          f"{srv.stats['prefill_calls']} prefill calls, "
          f"{srv.stats['step_failures']} recovered step failures")
    slot_steps = srv.stats["slot_steps"]
    print(f"acceptance: {srv.stats['accepted_tokens']} tokens accepted over "
          f"{slot_steps} slot-steps "
          f"({srv.stats['accepted_tokens'] / max(slot_steps, 1):.3f} per "
          f"slot-step, before EOS/budget clipping)")
    if args.cache_layout == "paged":
        print(f"paged: peak {srv.stats['peak_blocks']}/{srv.n_blocks - 1} "
              f"blocks, {srv.stats['deferred']} deferred admissions, "
              f"{srv.stats['cached_tokens']} prompt tokens served from the "
              f"prefix cache ({srv.stats['cow_copies']} CoW copies)")
    if args.chunk_size or args.preemption or args.adaptive_gamma:
        gs = ", ".join(f"gamma{g}={n}" for g, n in
                       sorted(srv.stats["gamma_steps"].items()))
        print(f"overload (DESIGN.md §14): {srv.stats['chunk_calls']} chunk "
              f"calls, {srv.stats['preemptions']} preemptions "
              f"({srv.stats['resumed']} resumed admissions), "
              f"{srv.stats['reclaimed_blocks']} blocks reclaimed at reap, "
              f"{srv.stats['grown_blocks']} grown in-place; steps {gs}")
    for r in done[:4]:
        print(f"  req {r.rid}: {r.status} steps={r.steps} "
              f"tokens/step={len(r.output)/max(r.steps,1):.2f}")


if __name__ == "__main__":
    sys.exit(main())
