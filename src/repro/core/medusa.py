"""Medusa multi-head prediction architecture (paper §3.1).

K parallel decoding heads on the frozen backbone's final hidden state.
Each head k is a residual MLP block (zero-initialised, so heads start as
the identity) followed by its own vocabulary projection, predicting the
token at t + k + 1.

This module is pure head math (init/apply, and a top-k selected by
reduces over the vocabulary, not by sorting it); the speculation-side
consumer is ``core.proposers.MedusaProposer``, which turns ``medusa_topk``
output into candidate trees for the generic engine (DESIGN.md §13).
Training lives in ``training/steps.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import Param


def init_medusa(key, cfg: ModelConfig, K: int, base_lm_head=None, dtype=None):
    """Stacked params for K heads. ``base_lm_head`` [d, V] seeds the vocab
    projections (Medusa's init recipe: copy the backbone's lm head)."""
    d, V = cfg.d_model, cfg.vocab_size
    dt = jnp.dtype(dtype or cfg.param_dtype)
    ks = jax.random.split(key, K)
    if base_lm_head is not None:
        lm = jnp.broadcast_to(base_lm_head.astype(dt)[None], (K, d, V)) + 0
    else:
        # one head at a time into the stacked buffer: drawing all K at once
        # (stack or vmap) holds a second [K, d, V] copy at the peak — 5 GB
        # more than the heads themselves at a 150k vocab
        lm = jnp.zeros((K, d, V), dt)
        for i, k in enumerate(ks):
            lm = lm.at[i].set(jax.random.normal(k, (d, V), dt)
                              / jnp.sqrt(d * 1.0))
    return {
        # zero init => resblock starts as identity
        "w1": Param(jnp.zeros((K, d, d), dt), ("medusa", "embed", "medusa_ff")),
        "b1": Param(jnp.zeros((K, d), dt), ("medusa", "medusa_ff")),
        "lm": Param(lm, ("medusa", "embed", "vocab")),
    }


def medusa_hidden(mp, hidden):
    """hidden [..., d] -> per-head hidden [K, ..., d] (residual SiLU block)."""
    h = jnp.einsum("...d,kde->k...e", hidden, mp["w1"].astype(hidden.dtype))
    h = jax.nn.silu(h + jnp.expand_dims(
        mp["b1"].astype(hidden.dtype), tuple(range(1, hidden.ndim))))
    return hidden[None] + h


def medusa_logits(mp, hidden):
    """hidden [..., d] -> logits [K, ..., V]."""
    hk = medusa_hidden(mp, hidden)
    return jnp.einsum("k...d,kdv->k...v", hk, mp["lm"].astype(hidden.dtype))


def _first(a, b):
    """Of two (logit, id) pairs, the one ``lax.top_k`` ranks first: the
    larger logit, and on a tie the lower id."""
    (av, ai), (bv, bi) = a, b
    pick = (av > bv) | ((av == bv) & (ai < bi))
    return jax.lax.select(pick, av, bv), jax.lax.select(pick, ai, bi)


def medusa_topk(mp, hidden, max_topk: int):
    """-> (tokens [K, ..., max_topk] int32, probs same shape float32).

    The ids, in ``lax.top_k``'s order (logit descending, the lower id first
    on a tie), and their softmax probabilities over the whole vocabulary.
    Nothing sorts the vocabulary: selection i is one fused reduce over it
    for the first (logit, id) pair that ranks strictly after selection
    i-1's, and the softmax normaliser is one more.  The cost is therefore
    ``max_topk + 1`` reads of the logits, growing linearly with
    ``max_topk`` (10 for ``medusa_63``, 1 for a chain).
    """
    x = medusa_logits(mp, hidden)
    ax, V = x.ndim - 1, x.shape[-1]
    ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, ax)
    v = jnp.full(x.shape[:-1] + (1,), jnp.inf, x.dtype)
    j = jnp.full(x.shape[:-1] + (1,), -1, jnp.int32)
    vals, idx = [], []
    for _ in range(max_topk):
        after = (x < v) | ((x == v) & (ids > j))
        v, j = jax.lax.reduce(
            (jnp.where(after, x, -jnp.inf), jnp.where(after, ids, V)),
            (jnp.array(-jnp.inf, x.dtype), jnp.int32(V)), _first, (ax,))
        v, j = v[..., None], j[..., None]
        vals.append(v)
        idx.append(j)
    # softmax's own arithmetic for these entries: exp(x - max) / sum
    vals = jnp.concatenate(vals, -1).astype(jnp.float32)
    m = vals[..., :1]
    den = jnp.sum(jnp.exp(x.astype(jnp.float32) - m), -1, keepdims=True)
    return jnp.concatenate(idx, -1), jnp.exp(vals - m) / den
