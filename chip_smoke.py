#!/usr/bin/env python3
"""Chip smoke test: serve openpangu-7b at its published widths on a TPU.

One chip (the default) drives the serving path a user calls —
``launch.serve``'s weight init, ``build_engine(..., use_kernel=True)`` and a
4-slot ``SpecServer`` — at openpangu-7b's published widths (d_model 4096,
32 query / 8 KV heads of 128, d_ff 12,800, vocab 153,376) with random bf16
weights from a fixed seed, cut in depth to 17 of 34 layers: one chip's
share of a two-stage pipeline.  17 x 398 MB of layers, 2.51 GB of untied
embedding and lm head and 5.16 GB of Medusa heads make 14.4 GB of a v5e's
16 GiB; the 4 x 512-token KV cache adds 0.14 GB.  Three phases:

  medusa   the paper's 64-node Medusa tree (medusa_63, T=64)
  ngram    prompt-lookup chain, gamma 4
  fusion   Medusa with verify fusion: the fused qkv/rope/commit write and
           the unembed_verify_stats epilogue

Each phase serves 8 requests (prompts of 16-200 tokens, 32 new tokens,
greedy acceptance) and fails unless every request ends ``done`` with 32
tokens, no scheduler iteration failed, the compiled decode step holds a
Pallas TPU kernel (``tpu_custom_call``), and one decode step's logits are
finite and agree between the kernel path and the ``use_kernel=False`` jnp
path within LOGIT_RTOL.  It reports compile seconds (persistent-cache
reads included), wall seconds, how many requests match ``ar_generate``
token for token (report only: argmax over random bf16 weights is fragile)
and the device's peak bytes in use.

``--four-chips`` runs only the four-chip phases: all 34 layers under TP=4
through ``tp_generate`` (the ``serve.py --tp`` path), TP=4 prefill logits
against one chip at 17 layers, and a ReplicaRouter over four one-chip
replicas against one replica serving the same batches.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # four chips

The last line of stdout is ``{"ok": true, "device": {...}}``; the exit
status is 0 only when every check passed.  Without a TPU it exits 2 before
any work: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "openpangu-7b"
LAYERS = 17                  # of 34: one chip's share of a 2-stage pipeline
SLOTS, MAX_LEN, BUCKET = 4, 512, 256
N_REQ, MAX_NEW, GAMMA = 8, 32, 4
PROMPT_LENS = np.linspace(16, 200, N_REQ).astype(int)
SEED = 0
# bf16 tolerance on one step's logits: ||a - b||_2 / ||b||_2.  The two
# paths read the same bf16 weights and differ only in reduction order and
# where activations round to bf16 (relative step 2^-8).  On a CPU
# rehearsal (d_model 256, 4-17 layers) either bf16 path sat 1.4-1.7% from
# an f32 reference and 1.3-1.6% from the other; 5% leaves 3x of that,
# while a wrong mask, merge or write moves logits by O(1).
LOGIT_RTOL = 5e-2


class CompileClock:
    """Backend-compile seconds (persistent-cache reads included — a hit
    shows as a short compile) and persistent-cache hits/misses, counted
    between ``start`` and ``stop``."""

    def __init__(self):
        import jax
        self._on = False
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if self._on and event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif self._on and event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def start(self):
        self.seconds, self.hits, self.misses, self._on = 0.0, 0, 0, True

    def stop(self) -> dict:
        self._on = False
        return {"compile_s": round(self.seconds, 3), "cache_hits": self.hits,
                "cache_misses": self.misses}


def make_prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def pad_batch(prompts):
    toks = np.zeros((len(prompts), BUCKET), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return toks, np.asarray([len(p) for p in prompts], np.int32)


def soften_attention(params, cfg):
    """Rescale random attention weights in place: wq/wk/wv to fan-in
    d_model, wo to fan-in H*hd (same draws, same seed).

    ``layers.dense_init`` takes a 3-D weight's fan-in from dim 1 (the rule
    for stacked experts [E, d, f]), i.e. the head count for wq [d, H, hd].
    At published widths that puts attention logits near +-256: every
    softmax is a hard argmax, bf16 rounding flips which key wins, and two
    correct attention paths drift apart by O(1) within a few layers (30%
    at 4 layers in the CPU rehearsal).  Trained models are not like that;
    with the scale fixed the same weights stay comparable."""
    import math

    import jax
    d, hq = cfg.d_model, cfg.num_heads
    hkv = cfg.num_kv_heads

    def fix(params):
        units = dict(params["units"])
        pos = dict(units["pos0"])
        a = dict(pos["attn"])
        for name, f in (("wq", math.sqrt(hq / d)), ("wk", math.sqrt(hkv / d)),
                        ("wv", math.sqrt(hkv / d)), ("wo", 1 / math.sqrt(hq))):
            a[name] = (a[name] * f).astype(a[name].dtype)
        pos["attn"] = a
        units["pos0"] = pos
        return {**params, "units": units}

    return jax.jit(fix, donate_argnums=0)(params)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def report(tag: str, **fields):
    print(f"{tag} " + json.dumps(fields, default=str), flush=True)


# ----------------------------------------------------------------- checks

def ar_reference(cfg, params, prompts):
    """Greedy ``ar_generate`` tokens for every prompt, [N, MAX_NEW]."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import ar_generate
    from repro.models.api import init_cache
    toks, plens = pad_batch(prompts)
    fn = jax.jit(lambda p, t, l: ar_generate(
        cfg, p, t, l, init_cache(cfg, len(prompts), MAX_LEN), MAX_NEW)[0])
    return np.asarray(fn(params, jnp.asarray(toks), jnp.asarray(plens)))


def decode_step_check(cfg, eng, params, prompts):
    """One decode step over a prefilled batch, through the kernels and
    through the jnp path.  Returns (failure strings, reported errors)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as KO
    from repro.kernels import ref as KR
    from repro.models.api import get_model, init_cache
    model, dt = get_model(cfg), eng.dtree
    toks, plens = pad_batch(prompts)
    B = len(prompts)
    cand = jax.random.randint(jax.random.PRNGKey(SEED + 1), (B, dt.T), 0,
                              cfg.vocab_size, jnp.int32)

    def step(params, toks, plens, cand, use_kernel):
        cache = init_cache(cfg, B, MAX_LEN)
        _, cache = model.prefill(params, cfg, toks, plens, cache)
        hidden, _ = model.decode(params, cfg, cache, cand, plens,
                                 jnp.asarray(dt.mask), jnp.asarray(dt.depths),
                                 use_kernel=use_kernel)
        return hidden, model.unembed(params, cfg, hidden).astype(jnp.float32)

    args = (params, jnp.asarray(toks), jnp.asarray(plens), cand)
    h_k, logits_k = jax.jit(lambda *a: step(*a, True))(*args)
    _, logits_r = jax.jit(lambda *a: step(*a, False))(*args)
    logits_k, logits_r = np.asarray(logits_k), np.asarray(logits_r)
    fails = []
    if not (np.isfinite(logits_k).all() and np.isfinite(logits_r).all()):
        fails.append("non-finite decode logits")
    err = rel_err(logits_k, logits_r)
    argmax_agree = float((logits_k.argmax(-1) == logits_r.argmax(-1)).mean())
    if not err <= LOGIT_RTOL:
        fails.append(f"kernel vs jnp logits rel err {err:.3e} > {LOGIT_RTOL}")
    out = {"logits_rel_err": err, "argmax_agree": argmax_agree}
    if cfg.verify_fusion:
        # the fused epilogue against the repo's jnp oracle on the same hidden
        tmax = jnp.ones((B,), jnp.float32)
        ker = jax.jit(KO.verify_stats)(h_k, params["lm_head"], cand, tmax)
        ref = jax.jit(KR.verify_stats_ref)(h_k, params["lm_head"], cand,
                                           tmax)
        argm, m, l, cw = (np.asarray(x) for x in ker)
        r_argm, r_m, r_l, r_cw = (np.asarray(x) for x in ref)
        row = np.asarray(logits_k)
        at_argm = np.take_along_axis(row, argm[..., None], -1)[..., 0]
        stats_err = {"m": rel_err(m, r_m), "l": rel_err(l, r_l),
                     "cand_w": rel_err(cw, r_cw),
                     # the kernel's argmax must hold a maximal logit (ties
                     # within one bf16 step may pick another index)
                     "argm_gap": float(np.max(np.abs(at_argm - r_m))),
                     "argm_agree": float((argm == r_argm).mean())}
        out["verify_stats"] = stats_err
        if not all(np.isfinite(x).all() for x in (m, l, cw)):
            fails.append("non-finite verify stats")
        for k in ("m", "l", "cand_w"):
            if not stats_err[k] <= LOGIT_RTOL:
                fails.append(f"verify_stats {k} rel err {stats_err[k]:.3e}")
        if not stats_err["argm_gap"] <= 2 ** -6 * np.abs(r_m).max():
            fails.append(f"verify_stats argmax misses the max by "
                         f"{stats_err['argm_gap']:.3e}")
    return fails, out


def step_kernel_count(srv) -> int:
    """``tpu_custom_call`` ops in the server's compiled decode step."""
    import jax
    import jax.numpy as jnp
    B = srv.B
    zi = jnp.zeros((B,), jnp.int32)
    text = srv._step_jit.lower(
        srv.params, srv.proposer_params, srv.cache, srv.lengths, srv.base,
        srv.pstate, srv.n_out, jax.random.PRNGKey(0),
        jnp.zeros((B,), bool), zi, zi, jnp.zeros((B,), jnp.float32),
        jnp.ones((B,), jnp.float32)).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def serve_requests(srv, prompts):
    rids = [srv.submit(p, max_new=MAX_NEW) for p in prompts]
    srv.run()
    return [srv.result(r) for r in rids]


def request_failures(reqs) -> list:
    bad = [(i, r.status if r else None, len(r.output) if r else 0)
           for i, r in enumerate(reqs)
           if r is None or r.status != "done" or len(r.output) != MAX_NEW]
    return [f"requests not done with {MAX_NEW} tokens: {bad}"] if bad else []


# ------------------------------------------------------------ one chip

def run_phase(name, cfg, kind, params, pp, prompts, ar_out, clock) -> bool:
    import jax

    from repro.core.engine import build_engine
    from repro.serving.scheduler import SpecServer
    dev = jax.devices()[0]
    eng = build_engine(cfg, kind, gamma=GAMMA, use_kernel=True)
    srv = SpecServer(eng, params, pp, batch_slots=SLOTS, max_len=MAX_LEN,
                     prompt_buckets=(BUCKET,))
    clock.start()
    t0 = time.perf_counter()
    reqs = serve_requests(srv, prompts)
    wall = time.perf_counter() - t0
    timing = clock.stop()
    fails = request_failures(reqs)
    if srv.stats["step_failures"]:
        fails.append(f"{srv.stats['step_failures']} scheduler iterations "
                     "failed and were recovered")
    ar_match = sum(np.array_equal(np.asarray(r.output), ar_out[i])
                   for i, r in enumerate(reqs) if r is not None)
    kernels = step_kernel_count(srv)
    if not kernels:
        fails.append("compiled decode step holds no tpu_custom_call")
    more, errs = decode_step_check(cfg, eng, params, prompts[:SLOTS])
    fails += more
    report(f"phase {name}:", ok=not fails, wall_s=round(wall, 3), **timing,
           steps=srv.stats["steps"],
           tokens=sum(len(r.output) for r in reqs if r is not None),
           ar_match=f"{ar_match}/{len(reqs)}", step_kernels=kernels,
           peak_bytes=peak_bytes(dev), **errs, failures=fails)
    del srv
    gc.collect()
    return not fails


def one_chip(cfg) -> bool:
    import jax

    from repro.core.engine import build_engine
    from repro.launch.serve import init_weights, proposer_params
    dev = jax.devices()[0]
    clock = CompileClock()
    prompts = make_prompts(cfg.vocab_size)
    clock.start()
    t0 = time.perf_counter()
    # Medusa heads first: their init holds one head (1.26 GB) of scratch,
    # which must not sit on top of the backbone
    pp = proposer_params("medusa", cfg, build_engine(cfg, "medusa"))
    params = soften_attention(init_weights(cfg, seed=SEED), cfg)
    jax.block_until_ready((pp, params))
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves((params, pp)))
    report("weights:", s=round(time.perf_counter() - t0, 3), **clock.stop(),
           layers=cfg.num_layers, weight_bytes=weight_bytes,
           peak_bytes=peak_bytes(dev))
    clock.start()
    t0 = time.perf_counter()
    ar_out = ar_reference(cfg, params, prompts)
    report("ar_generate reference:", s=round(time.perf_counter() - t0, 3),
           **clock.stop())
    ok = True
    for name, kind, fused in (("medusa", "medusa", False),
                              ("ngram", "ngram", False),
                              ("fusion", "medusa", True)):
        pcfg = dataclasses.replace(cfg, verify_fusion=True) if fused else cfg
        ok &= run_phase(name, pcfg, kind, params,
                        pp if kind == "medusa" else None, prompts, ar_out,
                        clock)
    return ok


# ---------------------------------------------------------- four chips

def four_chips(cfg, full) -> bool:
    """``cfg``: the 17-layer cut; ``full``: all layers, for TP=4 alone.

    TP=4 at full depth runs first, on empty chips: ``SpecServer`` keeps its
    jitted steps as bound methods, so a server (and the weights it holds)
    outlives ``del`` until the process ends — the router phase goes last."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.core.engine import build_engine
    from repro.distributed.tp import build_tp_engine, make_tp_mesh
    from repro.launch.serve import init_weights, tp_generate, weight_shapes
    from repro.models.api import get_model, init_cache
    from repro.serving.router import ReplicaRouter
    from repro.serving.scheduler import SpecServer
    devs = jax.devices()[:4]
    clock = CompileClock()
    prompts = make_prompts(cfg.vocab_size)
    toks, plens = pad_batch(prompts[:SLOTS])
    ok = True

    # all 34 layers under TP=4 through the serve.py --tp path
    clock.start()
    outs, secs = tp_generate(full, prompts, tp=4, proposer="medusa",
                             slots=SLOTS, max_len=MAX_LEN, max_new=MAX_NEW)
    timing = clock.stop()
    short = [i for i, o in enumerate(outs) if len(o) != MAX_NEW]
    fails = [f"TP=4 requests short of {MAX_NEW} tokens: {short}"] \
        if short else []
    report(f"tp4 generate ({full.num_layers} layers, medusa):",
           ok=not fails, generate_s=round(secs, 3), **timing,
           tokens=sum(len(o) for o in outs),
           peak_bytes=[peak_bytes(d) for d in devs], failures=fails)
    ok &= not fails
    gc.collect()

    # TP=4 against one chip, prefill logits, 17 layers
    clock.start()
    t0 = time.perf_counter()
    params = soften_attention(init_weights(
        cfg, seed=SEED, out_shardings=SingleDeviceSharding(devs[0])), cfg)
    model = get_model(cfg)
    one = np.asarray(jax.jit(lambda p, t, l: model.unembed(
        p, cfg, model.prefill(p, cfg, t, l,
                              init_cache(cfg, SLOTS, MAX_LEN))[0]))(
        params, jnp.asarray(toks), jnp.asarray(plens)), np.float32)
    tpe = build_tp_engine(cfg, make_tp_mesh(4), "medusa")
    sharded = tpe.shard_params(params, weight_shapes(cfg)[1])
    four = np.asarray(tpe.prefill_logits(
        sharded, tpe.replicate(jnp.asarray(toks)),
        tpe.replicate(jnp.asarray(plens)), tpe.init_cache(SLOTS, MAX_LEN)),
        np.float32)
    del sharded
    err = rel_err(four, one)
    fails = [] if np.isfinite(one).all() and np.isfinite(four).all() \
        else ["non-finite prefill logits"]
    if not err <= LOGIT_RTOL:
        fails.append(f"TP=4 vs one chip prefill logits rel err {err:.3e}")
    report(f"tp4 vs one chip ({cfg.num_layers} layers):", ok=not fails,
           s=round(time.perf_counter() - t0, 3), **clock.stop(),
           logits_rel_err=err,
           argmax_agree=float((four.argmax(-1) == one.argmax(-1)).mean()),
           failures=fails)
    ok &= not fails

    # ReplicaRouter over four one-chip replicas (n-gram proposer).  Each
    # replica's requests are then replayed through one replica in the same
    # batches: same programs, so the tokens must be equal.  Served all at
    # once, one replica batches them differently (a prefill group of 4, not
    # 2) — other compiled shapes, other bf16 rounding, so greedy tokens may
    # flip; that count is reported, not checked.
    clock.start()
    t0 = time.perf_counter()
    servers = {}
    for i, dev in enumerate(devs):
        w = params if i == 0 else jax.device_put(params, dev)
        servers[f"r{i}"] = SpecServer(
            build_engine(cfg, "ngram", gamma=GAMMA, use_kernel=True), w,
            None, batch_slots=SLOTS, max_len=MAX_LEN,
            prompt_buckets=(BUCKET,), device=dev)
    router = ReplicaRouter(servers, page_size=cfg.page_size)
    rids = [router.submit(p, max_new=MAX_NEW) for p in prompts]
    router.run()
    routed = [router.result(r) for r in rids]
    groups = {}
    for i, rid in enumerate(rids):
        name, inner = router.routes[rid][:2]
        groups.setdefault(name, []).append((inner, i))
    replayed = [None] * len(prompts)
    for name in sorted(groups):
        idx = [i for _, i in sorted(groups[name])]
        for i, r in zip(idx, serve_requests(servers["r0"],
                                            [prompts[i] for i in idx])):
            replayed[i] = r
    alone = serve_requests(servers["r0"], prompts)
    fails = (request_failures(routed) + request_failures(replayed)
             + request_failures(alone))

    def equal(xs):
        return sum(a is not None and b is not None and a.output == b.output
                   for a, b in zip(routed, xs))

    same = equal(replayed)
    if same != len(prompts):
        fails.append(f"router outputs differ from one replica's on the "
                     f"same batches: {same}/{len(prompts)} equal")
    placed = sorted({d.id for s in servers.values()
                     for d in jax.tree.leaves(s.params)[0].devices()})
    if placed != sorted(d.id for d in devs):
        fails.append(f"replica weights on devices {placed}")
    snap = router.snapshot()
    report("router 4 replicas vs one:", ok=not fails,
           s=round(time.perf_counter() - t0, 3), **clock.stop(),
           equal_same_batches=f"{same}/{len(prompts)}",
           equal_one_replica_batching=f"{equal(alone)}/{len(prompts)}",
           routed=snap["routed"],
           peak_bytes=[peak_bytes(d) for d in devs], failures=fails)
    return ok and not fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (TP=4, router)")
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    need = 4 if args.four_chips else 1
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s). No CPU fallback.",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import serving_config
    cache = enable_compile_cache()
    cfg = serving_config(ARCH, published=True, layers=LAYERS)
    report("device:", platform=devs[0].platform, kind=devs[0].device_kind,
           count=len(devs), compile_cache=cache, arch=cfg.name,
           layers=cfg.num_layers)
    ok = (four_chips(cfg, serving_config(ARCH, published=True))
          if args.four_chips else one_chip(cfg))
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
