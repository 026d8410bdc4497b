"""Tensor-parallel decode + prefix-affinity routing benchmark (§18).

Three claims, three measurements:

* **Modeled per-device HBM traffic** — the deterministic gate.  A decode
  step's per-device bytes = its param-shard read + its KV-shard sweep +
  its logits-slice write, computed from ``eval_shape`` on the FULL-SCALE
  config (no allocation).  TP divides every heads/ff/vocab-sharded term
  by N while the embedding and norms replicate, so the reduction at TP=4
  lands well above the 1.6x gate — and a sharding-plan regression (a
  leaf silently going replicated) drags it straight down.
* **Prefix-affinity hit rate** — a fixed trace (4 shared prompt
  prefixes x 6 requests each) through a real ``ReplicaRouter`` over live
  ``SpecServer`` replicas.  Every prefix's first visit misses, the rest
  must hit: 20/24 ≈ 0.83, gated at ≥ 0.7.
* **Wall-clock + token identity** — when ≥ 2 devices exist (CI forces
  8 host devices via XLA_FLAGS), the sharded engine must emit the exact
  token stream of the single-device engine while being timed; wall-clock
  rows stay advisory (shared runners), identity is an assert.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.bench_tp [--smoke]
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit, write_bench_json
from repro.configs.registry import get_config
from repro.core import medusa as M
from repro.core.engine import build_engine
from repro.distributed.sharding import split_params
from repro.models.api import get_model, init_cache

B, PROMPT, NEW, SEQ_KV = 2, 24, 16, 4096

# the param logical axes TP shards (distributed/tp.py shard_params rules);
# a leaf carrying any of them holds 1/N of the tensor per device
_SHARDED = {"heads", "kv_heads", "ff", "vocab"}


# --------------------------------------------------------------- byte model

def param_shard_bytes(cfg, tp: int) -> int:
    """Per-device parameter bytes under the §18 plan, from abstract shapes
    (full-scale config, nothing allocated).  The embedding replicates —
    its vocab axis feeds a token-id take — which is exactly why the
    reduction saturates below N."""
    model = get_model(cfg)
    tree = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    vals, axes = split_params(tree)
    total = 0
    flat_v, treedef = jax.tree.flatten(vals)
    flat_a = treedef.flatten_up_to(axes)
    top_embed = vals.get("embed")
    for leaf, ax in zip(flat_v, flat_a):
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        sharded = leaf is not top_embed and any(
            a in _SHARDED for a in ax if a)
        total += nbytes // tp if sharded else nbytes
    return total


def decode_step_bytes(cfg, tp: int, batch: int, seq_kv: int, t_nodes: int) -> int:
    """Per-device HBM bytes of one speculative decode step: param read +
    KV sweep over ``seq_kv`` committed rows + the [B, T, V/tp] logits the
    verify epilogue materialises (under TP the full [B, T, V] row never
    exists on any one device — the §18 psum/all-gather epilogue)."""
    p = param_shard_bytes(cfg, tp)
    kv = cfg.kv_cache_bytes_per_token() * seq_kv * batch // tp
    logits = batch * t_nodes * (cfg.vocab_size // tp) * 4
    return p + kv + logits


# ------------------------------------------------------------ affinity trace

def affinity_trace(n_replicas: int = 2, prefixes: int = 4, per: int = 6):
    """Fixed trace through a real router over live reduced-config servers:
    ``prefixes`` shared chains, ``per`` requests each, interleaved so every
    replica stays busy.  Returns the router snapshot plus the hit rate."""
    from repro.serving.router import ReplicaRouter
    from repro.serving.scheduler import SpecServer

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = get_model(cfg)
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))

    def make_server():
        eng = build_engine(cfg, "ngram", gamma=4)
        return SpecServer(eng, params, None, batch_slots=2, max_len=160)

    ps = 16
    # the whole trace submits before the servers drain, so a production
    # max_queue would trip backpressure mid-trace; the bench measures
    # affinity in isolation (backpressure has its own router unit test)
    router = ReplicaRouter({f"r{i}": make_server()
                            for i in range(n_replicas)}, page_size=ps,
                           max_queue=2 * prefixes * per)
    rng = np.random.default_rng(0)
    bases = [rng.integers(0, cfg.vocab_size, size=2 * ps).astype(np.int32)
             for _ in range(prefixes)]
    rids = []
    for j in range(per):
        for b, base in enumerate(bases):
            tail = rng.integers(0, cfg.vocab_size,
                                size=4 + b).astype(np.int32)
            rids.append(router.submit(np.concatenate([base, tail]),
                                      max_new=4))
    router.run()
    assert all(router.result(r) is not None
               and router.result(r).status == "done" for r in rids)
    snap = router.snapshot()
    total = snap["affinity_hits"] + snap["affinity_misses"]
    snap["hit_rate"] = snap["affinity_hits"] / max(total, 1)
    return snap


# ------------------------------------------------------- sharded wall-clock

def tp_wallclock(rows, smoke: bool):
    """TP=2 vs single-device on the forced-host mesh: token identity is
    asserted, wall-clock is advisory.  Skips (returning None) when the
    host exposes fewer than 2 devices so the gated metrics above stay
    runnable anywhere."""
    if len(jax.devices()) < 2:
        return None
    from repro.distributed.tp import build_tp_engine, make_tp_mesh

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = get_model(cfg)
    params, axes = split_params(model.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    ref = build_engine(cfg, "medusa")
    pp, _ = split_params(M.init_medusa(jax.random.PRNGKey(1), cfg,
                                       ref.tb.K))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (B, PROMPT)),
                       jnp.int32)
    plens = jnp.asarray([PROMPT, PROMPT - 5], jnp.int32)
    smax = PROMPT + NEW + ref.tb.T + 8
    iters = 2 if smoke else 6

    ref_fn = jax.jit(lambda p, m, t, l, c: ref.generate(p, m, t, l, c, NEW))
    t_ref = timeit(ref_fn, params, pp, toks, plens, init_cache(cfg, B, smax),
                   iters=iters, warmup=1)
    out_r, n_r, _ = ref_fn(params, pp, toks, plens, init_cache(cfg, B, smax))

    mesh = make_tp_mesh(2)
    tpe = build_tp_engine(cfg, mesh, "medusa")
    sp = tpe.shard_params(params, axes)
    ppr = tpe.replicate(pp)
    toks_r, plens_r = tpe.replicate(toks), tpe.replicate(plens)
    t_tp = timeit(lambda c: tpe.generate(sp, ppr, toks_r, plens_r, c, NEW),
                  tpe.init_cache(B, smax), iters=iters, warmup=1)
    out_t, n_t, _ = tpe.generate(sp, ppr, toks_r, plens_r,
                                 tpe.init_cache(B, smax), NEW)

    # losslessness while being timed: the sharded step must emit the
    # single-device token stream bit-for-bit (the §18 identity contract)
    np.testing.assert_array_equal(np.asarray(n_r), np.asarray(n_t))
    for b in range(B):
        np.testing.assert_array_equal(np.asarray(out_r)[b, :int(n_r[b])],
                                      np.asarray(out_t)[b, :int(n_t[b])])
    rows.append(("tp/tok_s/single", t_ref * 1e6, f"{B * NEW / t_ref:.1f}"))
    rows.append(("tp/tok_s/tp2", t_tp * 1e6, f"{B * NEW / t_tp:.1f}"))
    return {"devices": len(jax.devices()), "identity_checked": 1}


def run(smoke: bool = False):
    rows = []
    full = get_config("openpangu-7b")          # full scale: the real ratio
    t_nodes = 8
    b1 = decode_step_bytes(full, 1, B, SEQ_KV, t_nodes)
    b4 = decode_step_bytes(full, 4, B, SEQ_KV, t_nodes)
    model_extra = {
        "bytes_per_step_tp1": b1,
        "bytes_per_step_tp4": b4,
        "hbm_reduction_tp4": b1 / b4,
        "param_bytes_tp1": param_shard_bytes(full, 1),
        "param_bytes_tp4": param_shard_bytes(full, 4),
    }
    rows.append(("tp/model/hbm_reduction_tp4", 0.0,
                 f"{model_extra['hbm_reduction_tp4']:.2f}x"))
    assert model_extra["hbm_reduction_tp4"] >= 1.6, model_extra

    snap = affinity_trace()
    rows.append(("tp/affinity/hit_rate", 0.0, f"{snap['hit_rate']:.3f}"))
    assert snap["hit_rate"] >= 0.7, snap

    wall = tp_wallclock(rows, smoke)
    write_bench_json("tp", rows, smoke=smoke, extra={
        "model": model_extra,
        "affinity": {"hit_rate": snap["hit_rate"],
                     "rebalances": snap["rebalances"],
                     "requeues": snap["requeues"]},
        "wallclock": wall or {"devices": len(jax.devices()),
                              "identity_checked": 0},
    })
    return rows


if __name__ == "__main__":
    import argparse
    # 8 forced host devices for the CPU identity rows — set only when run
    # as a script, before the first backend use (importing jax starts none),
    # so importing this module never changes another program's devices
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for name, us, derived in run(smoke=args.smoke):
        print(f"{name:44s} {us:10.1f} us  {derived}")
