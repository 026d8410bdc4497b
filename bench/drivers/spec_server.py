"""Serve a configuration through the program's continuous-batching server.

The timed path is the one users run: ``build_engine(cfg, proposer,
use_kernel=...)`` under ``SpecServer``, driven by ``submit`` and
``step_once``.  The benchmark makes the backbone weights (``bench.weights``)
and the Medusa heads from the seed, warms every admission shape the mix can
ask for (each prompt bucket times each power-of-two group up to the slot
count) and the decode step, then measures a window.

Everything is read from outside after each ``step_once``: a request's
``output`` and ``status``, and ``stats["steps"]`` / ``stats["step_failures"]``.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import weights as W
from bench.model import program_config
from bench.record import Step, Tracked, Window
from bench.traffic import generator as G


def _pow2_upto(n: int):
    out, p = [], 1
    while p <= n:
        out.append(p)
        p *= 2
    return out


class Served:
    def __init__(self, conf: dict, mix: dict, seed: int, devices):
        from repro.core import medusa as M
        from repro.core.engine import build_engine
        from repro.distributed.sharding import split_params
        from repro.serving.scheduler import SpecServer
        s = conf["serving"]
        self.conf, self.mix = conf, mix
        self.cfg = program_config(conf)
        eng = build_engine(self.cfg, s["proposer"], use_kernel=s["use_kernel"],
                           accept=s["accept"])
        if eng.dtree.T != s["tree_tokens"]:
            raise ValueError(f"tree of {eng.dtree.T} tokens, config says "
                             f"{s['tree_tokens']}")
        G.check_mix(mix, eng.dtree.T)
        key = jax.random.fold_in(W.base_key(seed), 0x4D45)
        pp = None
        if s["proposer"] == "medusa":
            # heads before the backbone: their init holds one head of scratch
            pp = jax.jit(lambda k: split_params(M.init_medusa(
                k, self.cfg, eng.tb.K, dtype=self.cfg.dtype))[0])(key)
        params = W.program_tree(conf, seed)
        srv = mix["server"]
        self.srv = SpecServer(eng, params, pp, batch_slots=srv["slots"],
                              max_len=srv["max_len"],
                              prompt_buckets=tuple(srv["prompt_buckets"]))
        self.items = G.schedule(mix, seed, conf["vocab_size"])
        self._warm(np.random.default_rng(seed & 0xFFFFFFFF))

    def _warm(self, rng):
        """Run each admission shape once (group of n prompts in one bucket),
        and the decode step, then start from a fresh server."""
        srv = self.srv
        T = srv.engine.dtree.T
        for bucket in srv.buckets:
            plen = min(bucket, srv.max_len - T - 3)
            for n in _pow2_upto(srv.B):
                for _ in range(n):
                    srv.submit(rng.integers(0, self.conf["vocab_size"], plen,
                                            dtype=np.int32), max_new=1)
                while srv.busy:
                    srv.step_once()
        jax.block_until_ready(srv.cache)
        bad = [r for r in srv.done.values() if r.status != "done"]
        if bad or srv.stats["step_failures"]:
            raise RuntimeError(f"warm-up: {len(bad)} requests not done, "
                               f"{srv.stats['step_failures']} step failures")
        srv.reset()

    # ------------------------------------------------------------- window

    def _submit(self, item, due, now, tracked, ann):
        with ann("bench.submit"):
            rid = self.srv.submit(item.prompt, max_new=item.max_new)
            t = Tracked(rid, len(item.prompt), item.max_new, due, now,
                        self.srv.queue[-1])
        tracked.append(t)
        return t

    def _step(self, live, steps, ann, clock):
        srv = self.srv
        n0 = srv.stats["steps"]
        start = clock()
        with ann("bench.step_once"):
            srv.step_once()
        end = clock()
        step = Step(start, end, srv.stats["steps"] > n0)
        finished = []
        with ann("bench.scan_tokens"):
            for t in live:
                req = t.req
                if t.admit is None and req.status != "queued":
                    t.admit = start
                n = len(req.output)
                if n > t.n_out:
                    step.work.append((t.prompt_len + t.n_out, n - t.n_out))
                    if t.n_out == 0:
                        t.first = end
                    t.n_out = n
                    t.last = end
                if req.status not in ("queued", "running"):
                    finished.append(t)
            for t in finished:
                live.remove(t)
        steps.append(step)
        return finished

    def window(self, seconds: float, ann, clock=time.monotonic) -> Window:
        if self.mix["loop"] == "closed":
            return self._closed(seconds, ann, clock)
        return self._open(seconds, ann, clock)

    def _closed(self, seconds, ann, clock):
        """``clients`` clients, each sending its next request as soon as
        the last one ends; the window closes at the first step end past
        ``seconds``."""
        items = iter(self.items * 64)
        tracked, live, steps = [], [], []
        t0 = clock()
        for _ in range(self.mix["clients"]):
            live.append(self._submit(next(items), t0, t0, tracked, ann))
        while True:
            for t in self._step(live, steps, ann, clock):
                now = clock()
                live.append(self._submit(next(items), now, now, tracked, ann))
            if steps[-1].end - t0 >= seconds:
                break
        end = steps[-1].end
        return Window(t0, end, end, steps, tracked, [],
                      self.srv.stats["step_failures"])

    def _open(self, seconds, ann, clock):
        """Poisson arrivals on the mix's fixed schedule; submission stops
        at ``seconds``, then a drain of at most ``drain_s`` lets every
        request due in the window finish."""
        items = [it for it in self.items if it.due_s < seconds]
        if len(items) == len(self.items):
            raise ValueError("the mix's schedule ends inside the window")
        tracked, live, steps, late = [], [], [], []
        i, t0 = 0, clock()
        limit = seconds + float(self.mix["drain_s"])
        while True:
            now = clock() - t0
            while i < len(items) and items[i].due_s <= now:
                due = t0 + items[i].due_s
                live.append(self._submit(items[i], due, clock(), tracked, ann))
                late.append(tracked[-1].submit - due)
                i += 1
            if self.srv.busy:
                self._step(live, steps, ann, clock)
            elif i < len(items):
                with ann("bench.wait_arrival"):
                    time.sleep(max(0.0, items[i].due_s - (clock() - t0)))
            else:
                break
            if clock() - t0 > limit:
                break
        end = t0 + seconds
        return Window(t0, end, max(end, clock()), steps, tracked, late,
                      self.srv.stats["step_failures"])

    # -------------------------------------------------------------- check

    def samples(self, rng, k: int):
        """``k`` finished requests drawn from the seed, the longest first:
        [(prompt, served tokens)]."""
        done = [r for r in self.srv.done.values()
                if r.status == "done" and len(r.output) == r.max_new]
        if not done:
            return []
        done.sort(key=lambda r: r.rid)
        longest = max(done, key=lambda r: (len(r.prompt) + len(r.output),
                                           -r.rid))
        rest = [r for r in done if r is not longest]
        pick = [longest] + [rest[j] for j in rng.permutation(len(rest))[:k - 1]]
        return [(np.asarray(r.prompt), list(r.output)) for r in pick]

    def free(self):
        """Drop the program's weights and state so the reference has the
        chip's memory; the server's jitted methods would keep them alive."""
        srv = self.srv
        for name in ("params", "proposer_params", "cache", "pstate", "lengths",
                     "base", "n_out"):
            setattr(srv, name, None)
        self.srv = None
        jax.clear_caches()
        gc.collect()
