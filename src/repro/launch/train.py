"""Training launcher: LM pretraining or Medusa-head training with
checkpoint/restart fault tolerance (CPU-scale here; the same step functions
are what the dry-run lowers onto the production mesh).

  PYTHONPATH=src python -m repro.launch.train --arch openpangu-7b --reduced \
      --mode heads --steps 200 --ckpt-dir /tmp/ck --resume
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ALL_ARCHS, get_config
from repro.core import medusa as M
from repro.distributed.sharding import split_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model
from repro.training import checkpoint as C
from repro.training import data as D
from repro.training import optimizer as O
from repro.training import steps as ST


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="openpangu-7b", choices=ALL_ARCHS)
    ap.add_argument("--mode", default="heads", choices=["lm", "heads"])
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--heads", type=int, default=3)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    model = get_model(cfg)
    params, _ = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    corpus = D.synthetic_chat(D.SyntheticChatConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq + 1,
        n_samples=max(args.batch * 8, 128)))
    it = D.batches(corpus, args.batch, seed=1)
    ck = C.AsyncCheckpointer(args.ckpt_dir, keep=3)

    if args.mode == "lm":
        opt = O.adamw_init(params)
        state = {"params": params, "opt": opt}
        step_fn = jax.jit(lambda p, o, x, y: ST.lm_train_step(
            p, o, cfg, x, y, lr=args.lr),
            donate_argnums=(0, 1))  # speclint: donates=p,o
    else:
        mp, _ = split_params(M.init_medusa(jax.random.PRNGKey(1), cfg, args.heads,
                                           base_lm_head=params.get("lm_head")))
        opt = O.adamw_init(mp)
        state = {"params": mp, "opt": opt}
        step_fn = jax.jit(lambda p, o, t: ST.medusa_train_step(
            p, o, params, cfg, t, args.heads, lr=args.lr,
            pad_id=D.special_id(cfg.vocab_size, D.PAD)),
            donate_argnums=(0, 1))  # speclint: donates=p,o

    start = 0
    if args.resume:
        latest = C.restore_latest(args.ckpt_dir, state)
        if latest:
            start, state, _ = latest
            print(f"[resume] step {start}")

    p, o = state["params"], state["opt"]
    t0 = time.time()
    for i in range(start, args.steps):
        b = jnp.asarray(next(it))
        if args.mode == "lm":
            p, o, met = step_fn(p, o, b[:, :-1], b[:, 1:])
        else:
            p, o, met = step_fn(p, o, b)
        if i % 25 == 0 or i == args.steps - 1:
            extra = ""
            if "head_acc" in met:
                extra = f" top1={np.round(np.asarray(met['head_acc']), 3)}"
            print(f"step {i:5d} loss {float(met['loss']):.4f}{extra} "
                  f"({(time.time()-t0):.0f}s)", flush=True)
        if (i + 1) % args.ckpt_every == 0:
            ck.save(i + 1, {"params": p, "opt": o})
    ck.wait()
    print("done")


if __name__ == "__main__":
    main()
