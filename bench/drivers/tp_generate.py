"""Serve a configuration through the program's tensor-parallel engine.

The timed path is ``serve.py --tp``'s: ``build_tp_engine(cfg, mesh,
proposer)`` over a ("data", "model") mesh of the cell's chips, weights
drawn straight into the TP plan's shardings (``bench.weights`` with the
engine's ``param_specs``), Medusa heads replicated, and one jitted
``generate`` per static batch of ``server.slots`` prompts, right-padded to
the mix's single prompt bucket so that every batch runs one compiled
program.  Batches run back to back; the window ends with the last whole
batch that started inside it.
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import weights as W
from bench.model import program_config
from bench.record import Step, Tracked, Window
from bench.traffic import generator as G


class Served:
    def __init__(self, conf: dict, mix: dict, seed: int, devices):
        from repro.core import medusa as M
        from repro.distributed import profiles
        from repro.distributed.sharding import split_params
        from repro.distributed.tp import build_tp_engine, make_tp_mesh
        from repro.launch.serve import weight_shapes
        s = conf["serving"]
        self.conf, self.mix = conf, mix
        self.cfg = program_config(conf)
        mesh = make_tp_mesh(len(devices))
        self.tpe = build_tp_engine(self.cfg, mesh, s["proposer"],
                                   accept=s["accept"])
        if self.tpe.dtree.T != s["tree_tokens"]:
            raise ValueError(f"tree of {self.tpe.dtree.T} tokens, config "
                             f"says {s['tree_tokens']}")
        G.check_mix(mix, self.tpe.dtree.T)
        key = jax.random.fold_in(W.base_key(seed), 0x4D45)
        self.pp = jax.jit(lambda k: split_params(M.init_medusa(
            k, self.cfg, self.tpe.tb.K, dtype=self.cfg.dtype))[0],
            out_shardings=NamedSharding(mesh, P()))(key)
        shapes, axes = weight_shapes(self.cfg)
        specs = self.tpe.param_specs(shapes, axes)
        self.params = W.program_tree(conf, seed,
                                     out_shardings=profiles.to_named(specs,
                                                                     mesh))
        self.items = G.schedule(mix, seed, conf["vocab_size"])
        self.bucket = max(mix["server"]["prompt_buckets"])
        self.B = mix["server"]["slots"]
        self.done = []
        # warm the one program: a batch of the longest prompts
        warm = [G.Item(np.zeros(self.bucket, np.int32), it.max_new, 0.0)
                for it in self.items[:self.B]]
        self._generate(warm)

    def _generate(self, batch):
        """One static batch through ``generate``: (outputs, counts)."""
        toks = np.zeros((self.B, self.bucket), np.int32)
        plen = np.zeros((self.B,), np.int32)
        for j, it in enumerate(batch):
            toks[j, :len(it.prompt)] = it.prompt
            plen[j] = len(it.prompt)
        tpe = self.tpe
        cache = tpe.init_cache(self.B, self.mix["server"]["max_len"])
        out, n_out, _ = tpe.generate(
            self.params, self.pp, tpe.replicate(jnp.asarray(toks)),
            tpe.replicate(jnp.asarray(plen)), cache,
            int(self.mix["output"]["max"]))
        return np.asarray(out), np.asarray(n_out)

    def window(self, seconds: float, ann, clock=time.monotonic) -> Window:
        items = iter(self.items * 64)
        tracked, steps = [], []
        t0 = clock()
        while not steps or steps[-1].end - t0 < seconds:
            batch = [next(items) for _ in range(self.B)]
            start = clock()
            with ann("bench.submit"):
                rows = [Tracked(0, len(it.prompt), it.max_new, start, start)
                        for it in batch]
            with ann("bench.step_once"):
                out, n_out = self._generate(batch)
            end = clock()
            step = Step(start, end, True)
            with ann("bench.scan_tokens"):
                for t, it, o, n in zip(rows, batch, out, n_out):
                    n = min(int(n), it.max_new)
                    t.req = SimpleNamespace(
                        status="done" if n == it.max_new else "failed",
                        prompt=it.prompt, output=[int(x) for x in o[:n]],
                        max_new=it.max_new)
                    t.rid, t.admit, t.first, t.last, t.n_out = (
                        len(self.done), start, end, end, n)
                    step.work.append((len(it.prompt), n))
                    self.done.append(t.req)
            tracked += rows
            steps.append(step)
        return Window(t0, steps[-1].end, steps[-1].end, steps, tracked, [], 0)

    def samples(self, rng, k: int):
        done = [r for r in self.done if r.status == "done"]
        if not done:
            return []
        longest = max(range(len(done)),
                      key=lambda i: len(done[i].prompt) + len(done[i].output))
        rest = [i for i in range(len(done)) if i != longest]
        pick = [longest] + [rest[j] for j in rng.permutation(len(rest))[:k - 1]]
        return [(np.asarray(done[i].prompt), done[i].output) for i in pick]

    def free(self):
        self.params = self.pp = self.tpe = None
        jax.clear_caches()
        gc.collect()
