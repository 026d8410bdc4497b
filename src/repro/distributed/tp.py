"""Tensor-parallel speculative decode under ``shard_map`` (DESIGN.md §18).

``TPSpecEngine`` drives the unmodified ``SpecEngine`` step — prefill,
tree-attention decode, verify, commit — inside a ``shard_map_compat`` body
on an N-way mesh axis.  The trick is a *local config*: each shard runs a
``SpecEngine`` built over ``replace(cfg, num_heads=H/tp, num_kv_heads=
Hkv/tp, tp_axis=axis)``, so every einsum in the model sees its slice as
the whole world, and the only cross-shard traffic is

  * one ``lax.psum`` after each row-parallel contraction
    (``layers.tp_reduce`` — attention wo, mlp down-projection),
  * the verify epilogue's stats reduction (``SpecEngine._verify_tp``), and
  * a per-row ``all_gather`` when a full [B, V] logits row is genuinely
    needed (prefill base token, residual resample).

Sharding plan (``shard_params`` / ``profiles.tp_cache_pspecs``):

  column-parallel  wq/wk/wv on heads, mlp wi/wg on ff, lm_head on vocab
  row-parallel     attention wo on heads, mlp wo on ff  (psum epilogue)
  replicated       embed (token-id take), norms, proposer params/state,
                   tokens/lengths/base/keys, block tables
  KV cache         kv-head axis (index 3), pool-form and dense alike

Proposer state, PRNG keys and every replicated input stay bit-identical
across shards by determinism, so the wrapped step runs with
``check=False`` and replicated out_specs — the same discipline as
``collectives.ag_matmul``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, SamplingParams
from repro.core.engine import build_engine
from repro.core.tree import TreeBuffers
from repro.distributed import profiles
from repro.distributed.collectives import shard_map_compat
from repro.distributed.sharding import spec_for
from repro.models import api as model_api

_TP_PROPOSERS = ("medusa", "ngram")


def make_tp_mesh(tp: int, data: int = 1) -> Mesh:
    """("data", "model") mesh over the first ``data * tp`` local devices.

    On a TPU host these are its chips; the CPU tests materialise devices
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
    importing jax (the forced-host mesh the §18 identity tests run on)."""
    n = data * tp
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"need {n} devices for a ({data}, {tp}) mesh, have {len(devs)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "importing jax)")
    return Mesh(np.asarray(devs[:n]).reshape(data, tp), ("data", "model"))


def _validate(cfg: ModelConfig, proposer: str, tp: int):
    if cfg.tp_axis:
        raise ValueError("cfg already carries a tp_axis — pass the global "
                         "config, TPSpecEngine derives the local one")
    if cfg.family != "dense":
        raise ValueError(
            f"tensor-parallel decode supports the dense family only; "
            f"{cfg.family!r} has non-TP mixers (DESIGN.md §18)")
    if cfg.tie_embeddings:
        raise ValueError("TP shards the lm_head over vocab; tied embeddings "
                         "would shard the token-id take too (DESIGN.md §18)")
    if cfg.verify_fusion:
        raise ValueError("verify_fusion's Pallas epilogue is single-device; "
                         "TP has its own stats epilogue (DESIGN.md §18)")
    if proposer not in _TP_PROPOSERS:
        raise ValueError(f"TP proposers: {_TP_PROPOSERS}; {proposer!r} runs "
                         "its own forward that is not head-sharded")
    for name, dim in (("num_heads", cfg.num_heads),
                      ("num_kv_heads", cfg.num_kv_heads),
                      ("d_ff", cfg.d_ff),
                      ("vocab_size", cfg.vocab_size)):
        if dim % tp != 0:
            raise ValueError(f"{name}={dim} does not divide over tp={tp}")


class TPSpecEngine:
    """``SpecEngine`` façade whose step runs sharded on ``mesh[axis]``.

    Call order: ``shard_params(params, axes)`` once (it fixes the param
    spec tree the wrapped calls close over), then ``init_cache`` /
    ``prefill`` / ``spec_step`` / ``generate`` exactly like the
    single-device engine.  Outputs are replicated (every shard computes
    the same tokens/verdicts by determinism); the cache stays sharded on
    its kv-head axis across calls.
    """

    def __init__(self, cfg: ModelConfig, mesh: Mesh, *, axis: str = "model",
                 proposer: str = "medusa", tb: Optional[TreeBuffers] = None,
                 gamma: int = 4, max_n: int = 3, min_n: int = 1,
                 accept: str = "greedy",
                 sampling: Optional[SamplingParams] = None):
        tp = int(mesh.shape[axis])
        _validate(cfg, proposer, tp)
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.tp = tp
        self.local_cfg = dataclasses.replace(
            cfg, num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp,
            head_dim=cfg.resolved_head_dim, tp_axis=axis)
        self.local = build_engine(self.local_cfg, proposer, tb=tb,
                                  gamma=gamma, max_n=max_n, min_n=min_n,
                                  accept=accept, sampling=sampling)
        self.proposer = self.local.proposer
        self.tb = self.local.tb
        self.dtree = self.local.dtree
        self.accept = self.local.accept
        self.sampling = self.local.sampling
        self._pspecs = None
        self._fns = {}

    # ------------------------------------------------------------ placement

    def param_specs(self, params, axes):
        """PartitionSpec tree of the TP plan for a ``split_params`` (values,
        axes) pair — values may be arrays or ``ShapeDtypeStruct``s, so the
        plan exists before any weight does — remembered for the wrapped
        calls."""
        rules = {"heads": self.axis, "kv_heads": self.axis,
                 "ff": self.axis, "vocab": self.axis}

        def one(ax, arr):
            return spec_for(tuple(ax), rules, shape=arr.shape,
                            mesh=self.mesh)

        specs = jax.tree.map(
            one, axes, params,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        if "embed" in specs:
            # the embedding's vocab axis must NOT shard: embed_tokens is a
            # global-token-id take, replicated on purpose (DESIGN.md §18)
            specs["embed"] = P()
        self._pspecs = specs
        return specs

    def shard_params(self, params, axes):
        """Place a ``split_params`` (values, axes) pair onto the mesh per
        the TP plan and remember the spec tree for the wrapped calls."""
        specs = self.param_specs(params, axes)
        return jax.device_put(params, profiles.to_named(specs, self.mesh))

    def shard_cache(self, cache):
        specs = profiles.tp_cache_pspecs(cache, self.cfg, self.mesh,
                                         self.axis)
        return jax.device_put(cache, profiles.to_named(specs, self.mesh))

    def replicate(self, tree):
        return jax.device_put(
            tree, jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                               tree))

    def init_cache(self, batch: int, max_len: int, n_blocks=None):
        """Global-shape cache (full Hkv), device_put sharded on the kv-head
        axis — inside the shard_map body each shard sees the [.., Hkv/tp,
        ..] slice its local config expects."""
        cache = model_api.init_cache(self.cfg, batch, max_len,
                                     n_blocks=n_blocks)
        return self.shard_cache(cache)

    def init_proposer_state(self, batch: int, capacity: int):
        return self.replicate(self.local.init_proposer_state(batch, capacity))

    # ------------------------------------------------------- wrapped calls

    def _require_specs(self):
        if self._pspecs is None:
            raise RuntimeError("call shard_params(...) before running the "
                               "TP engine — the wrapped step closes over "
                               "the param spec tree")
        return self._pspecs

    def _cached(self, name, build):
        fn = self._fns.get(name)
        if fn is None:
            fn = self._fns[name] = build()
        return fn

    def prefill(self, params, proposer_params, tokens, lengths, cache,
                key=None, state=None):
        pspecs, eng = self._require_specs(), self.local
        cspec = profiles.tp_cache_pspecs(cache, self.cfg, self.mesh,
                                         self.axis)

        def build():
            def fn(params, pp, tokens, lengths, cache, key, state):
                return eng.prefill(params, pp, tokens, lengths, cache,
                                   key=key, state=state)
            return jax.jit(shard_map_compat(
                fn, mesh=self.mesh,
                in_specs=(pspecs, P(), P(), P(), cspec, P(), P()),
                out_specs=(cspec, P(), P(), P()), check=False))

        return self._cached("prefill", build)(
            params, proposer_params, tokens, lengths, cache, key, state)

    def prefill_logits(self, params, tokens, lengths, cache):
        """[B, V] next-token logits after each prompt, replicated — the
        sharded prefill's output before any proposer or verify step (what
        a single-device prefill is compared against)."""
        pspecs, local = self._require_specs(), self.local
        cspec = profiles.tp_cache_pspecs(cache, self.cfg, self.mesh,
                                         self.axis)

        def build():
            def logits_fn(params, tokens, lengths, cache):
                h, _ = local.model.prefill(params, local.cfg, tokens,
                                           lengths, cache)
                return local.model.unembed(params, local.cfg, h)
            return jax.jit(shard_map_compat(
                logits_fn, mesh=self.mesh, in_specs=(pspecs, P(), P(), cspec),
                out_specs=P(), check=False))

        return self._cached("prefill_logits", build)(params, tokens, lengths,
                                                     cache)

    def spec_step(self, params, proposer_params, cache, lengths, base, state,
                  key):
        pspecs, eng = self._require_specs(), self.local
        cspec = profiles.tp_cache_pspecs(cache, self.cfg, self.mesh,
                                         self.axis)

        def build():
            def fn(params, pp, cache, lengths, base, state, key):
                return eng.spec_step(params, pp, cache, lengths, base,
                                     state, key)
            return jax.jit(shard_map_compat(
                fn, mesh=self.mesh,
                in_specs=(pspecs, P(), cspec, P(), P(), P(), P()),
                out_specs=(cspec, P(), P(), P()), check=False))

        return self._cached("spec_step", build)(
            params, proposer_params, cache, lengths, base, state, key)

    def generate(self, params, proposer_params, tokens, prompt_lengths,
                 cache, max_new: int, key=None, state=None):
        pspecs, eng = self._require_specs(), self.local
        cspec = profiles.tp_cache_pspecs(cache, self.cfg, self.mesh,
                                         self.axis)

        def build():
            def fn(params, pp, tokens, plens, cache, key, state):
                return eng.generate(params, pp, tokens, plens, cache,
                                    max_new, key=key, state=state)
            return jax.jit(shard_map_compat(
                fn, mesh=self.mesh,
                in_specs=(pspecs, P(), P(), P(), cspec, P(), P()),
                out_specs=P(), check=False))

        return self._cached(("generate", int(max_new)), build)(
            params, proposer_params, tokens, prompt_lengths, cache, key,
            state)


def build_tp_engine(cfg: ModelConfig, mesh: Mesh, proposer: str = "medusa",
                    **kw) -> TPSpecEngine:
    """``build_engine`` sibling for the sharded step (DESIGN.md §18)."""
    return TPSpecEngine(cfg, mesh, proposer=proposer, **kw)
