"""Unified decoder-only stack covering dense / MoE / SSM / hybrid / VLM.

The layer stack is expressed as a repeating *unit* (1 layer for homogeneous
families; ``hybrid_period`` layers for Jamba) scanned with stacked params —
HLO stays O(1) in depth, which is what makes 40-cell multi-pod dry-runs
compile in seconds and keeps production compile times sane.

Decode is the paper's static speculative step: T tree/chain tokens are
verified in one forward with a static visibility mask; ``commit`` performs
the zero-copy KV compaction (gather accepted rows, write back at the
sequence head) and, for SSM layers, per-prefix state selection.
All decode-side state supports per-batch lengths (continuous batching).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import Param, logical
from repro.kernels import paging as P
from repro.kernels import quant as Q
from repro.models import layers as L
from repro.models import ssm as S

# cache-pytree key holding the paged layout's block-table state; it is not a
# layer entry (no leading n_units axis), so every scan over the cache splits
# it off first (DESIGN.md §12)
PAGES_KEY = "_pages"

# suffix marking the speculation-root SSM checkpoint inside a spec cache
# (DESIGN.md §17): ``decode`` stashes the pre-chain recurrent state under
# ``<name> + SSM_CKPT`` and ``commit`` selects it (over the advanced
# per-prefix states) for rows whose effective accepted length is zero, so
# masked/inactive serving slots never absorb the chain's dead recurrence
# writes.  Checkpoint keys exist only in the transient spec cache between
# ``decode`` and ``commit`` — never in the persistent cache.
SSM_CKPT = "_ckpt"


def split_pages(cache):
    """(layer_entries, pages_or_None).  ``pages`` is ``{"table":
    [B, max_blocks] int32}`` under the paged layout, None under dense."""
    if PAGES_KEY in cache:
        return {k: v for k, v in cache.items() if k != PAGES_KEY}, \
            cache[PAGES_KEY]
    return cache, None


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

def unit_structure(cfg: ModelConfig):
    """[(mixer_kind, ffn_kind)] for each position inside the repeating unit."""
    if cfg.family == "ssm":
        return [("ssm", "none")]
    if cfg.family == "hybrid" and cfg.hybrid_period:
        out = []
        for pos in range(cfg.hybrid_period):
            mix = "attn" if pos == cfg.attn_index else "ssm"
            ffn = "moe" if (cfg.num_experts and pos % cfg.moe_every == cfg.moe_offset) else "dense"
            out.append((mix, ffn))
        return out
    ffn = "moe" if cfg.num_experts else "dense"
    return [("attn", ffn)]


def n_units(cfg: ModelConfig) -> int:
    u = len(unit_structure(cfg))
    assert cfg.num_layers % u == 0, (cfg.num_layers, u)
    return cfg.num_layers // u


def tree_stack(trees):
    """Stack unit params; Param leaves gain a leading 'layers' logical axis."""
    from repro.distributed.sharding import is_param

    def stack(*xs):
        if is_param(xs[0]):
            return Param(jnp.stack([x.value for x in xs]), ("layers",) + xs[0].axes)
        return jnp.stack(xs)

    return jax.tree.map(stack, *trees, is_leaf=is_param)


def init_units(keys, cfg: ModelConfig):
    """``tree_stack([init_unit(k, cfg) for k in keys])`` without the stack:
    the units are drawn under ``vmap`` straight into their stacked leaves
    (same values — per-key draws do not depend on batching), so a full-
    width init never holds the per-unit trees and their stacked copy at
    once."""
    from repro.distributed.sharding import is_param
    stacked = jax.vmap(lambda k: init_unit(k, cfg))(keys)
    return jax.tree.map(lambda p: Param(p.value, ("layers",) + p.axes),
                        stacked, is_leaf=is_param)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_position(key, cfg: ModelConfig, mix: str, ffn: str):
    ks = jax.random.split(key, 4)
    p = {"norm1": L.init_norm(ks[0], cfg)}
    if mix == "attn":
        p["attn"] = L.init_attention(ks[1], cfg)
    else:
        p["ssm"] = S.init_mamba2(ks[1], cfg)
    if ffn != "none":
        p["norm2"] = L.init_norm(ks[2], cfg)
        p["ffn"] = L.init_moe(ks[3], cfg) if ffn == "moe" else L.init_mlp(ks[3], cfg)
    return p


def init_unit(key, cfg: ModelConfig):
    struct = unit_structure(cfg)
    ks = jax.random.split(key, len(struct))
    return {f"pos{i}": _init_position(ks[i], cfg, mix, ffn)
            for i, (mix, ffn) in enumerate(struct)}


def init_params(key, cfg: ModelConfig, dtype: Optional[str] = None):
    """Full model params (Param-wrapped leaves; use sharding.split_params)."""
    if dtype is not None:
        cfg = __import__("dataclasses").replace(cfg, param_dtype=dtype)
    nu = n_units(cfg)
    ks = jax.random.split(key, nu + 4)
    dt = jnp.dtype(cfg.param_dtype)
    params = {
        "embed": L.dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                              ("vocab", "embed"), dt, scale=0.02),
        "units": init_units(ks[1:nu + 1], cfg),
        "final_norm": L.init_norm(ks[nu + 1], cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[nu + 2], (cfg.d_model, cfg.vocab_size),
                                         ("embed", "vocab"), dt)
    if cfg.frontend:
        fd = cfg.frontend_dim or cfg.d_model
        params["frontend_proj"] = L.dense_init(ks[nu + 3], (fd, cfg.d_model),
                                               (None, "embed"), dt)
    return params


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)   # gemma convention
    return x


def unembed_local(params, cfg: ModelConfig, hidden):
    """Logits over whatever vocab slice this shard's lm_head holds —
    [..., V] on a single device, [..., V/N] inside a TP shard_map body
    (DESIGN.md §18).  The TP verify epilogue consumes this directly so the
    full [B, T, V] tensor never materialises per device."""
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    logits = jnp.einsum("...d,dv->...v", hidden, w.astype(hidden.dtype))
    return logical(logits, "batch", "seq", "act_vocab") if logits.ndim == 3 else logits


def unembed(params, cfg: ModelConfig, hidden):
    logits = unembed_local(params, cfg, hidden)
    if cfg.tp_axis and logits.shape[-1] != cfg.vocab_size:
        # vocab-sharded lm_head under TP: gather the column slices so every
        # full-logits consumer (prefill base token, row resample, fallback
        # verify) sees the same [..., V] row as a single device would
        logits = jax.lax.all_gather(logits, cfg.tp_axis, axis=logits.ndim - 1,
                                    tiled=True)
    return logits


def frontend_prefix(params, cfg: ModelConfig, extra_embeds):
    """Project stub modality embeddings ([B, F, fd]) into the model stream."""
    return jnp.einsum("bfe,ed->bfd", extra_embeds.astype(jnp.dtype(cfg.dtype)),
                      params["frontend_proj"].astype(jnp.dtype(cfg.dtype)))


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill body)
# ---------------------------------------------------------------------------

def _unit_full(unit_p, x, cfg: ModelConfig, valid=None, return_state=False,
               collect_router=False):
    """One unit, full-sequence. Returns (x, state_dict, router_logits_list)."""
    states, routers = {}, []
    for i, (mix, ffn) in enumerate(unit_structure(cfg)):
        p = unit_p[f"pos{i}"]
        h = L.apply_norm(p["norm1"], x, cfg)
        if mix == "attn":
            y = L.attention_full(p["attn"], h, cfg)
        else:
            if return_state:
                y, st = S.mamba2_full(p["ssm"], h, cfg, return_state=True)
                states[f"pos{i}"] = st
            else:
                y = S.mamba2_full(p["ssm"], h, cfg)
        x = x + y
        if ffn != "none":
            h = L.apply_norm(p["norm2"], x, cfg)
            if ffn == "moe":
                y, rl = L.moe(p["ffn"], h, cfg)
                if collect_router:
                    routers.append(rl)
            else:
                y = L.mlp(p["ffn"], h, cfg)
            x = x + y
        x = logical(x, "batch", "seq", "act_embed")
    return x, states, routers


def forward_hidden(params, cfg: ModelConfig, tokens, extra_embeds=None,
                   remat: bool = False, collect_router: bool = False):
    """Token ids -> final hidden states [B, S(+F), d] (full causal)."""
    x = embed_tokens(params, cfg, tokens)
    if cfg.frontend and extra_embeds is not None:
        x = jnp.concatenate([frontend_prefix(params, cfg, extra_embeds), x], axis=1)
    x = logical(x, "batch", "seq", "act_embed")

    def body(carry, unit_p):
        h, aux = carry
        h, _, routers = _unit_full(unit_p, h, cfg, collect_router=collect_router)
        if collect_router:
            aux = aux + sum(L.moe_aux_loss(r) for r in routers)
        return (h, aux), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["units"])
    x = L.apply_norm(params["final_norm"], x, cfg)
    return x, aux


def forward_train(params, cfg: ModelConfig, tokens, extra_embeds=None,
                  remat: bool = True):
    """-> (logits [B, S, V], moe_aux_loss scalar)."""
    hidden, aux = forward_hidden(params, cfg, tokens, extra_embeds,
                                 remat=remat, collect_router=cfg.num_experts > 0)
    return unembed(params, cfg, hidden), aux


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               abstract: bool = False, n_blocks=None):
    """Static decode state. Mirrors the unit structure; leading dim = n_units.

    The attention-cache storage dtype follows ``cfg.resolved_cache_dtype``
    (overridable via ``dtype``).  For int8 each attn entry carries the
    quantized layout (DESIGN.md §10): ``k``/``v`` [nu, B, S, Hkv, D] int8
    plus ``k_scale``/``v_scale`` [nu, B, S, Hkv, 1] float32.

    Under ``cfg.cache_layout == "paged"`` (DESIGN.md §12) the attention
    entries become pool-form — ``k``/``v`` [nu, n_blocks, page_size, Hkv, D]
    (scales [nu, n_blocks, page_size, Hkv, 1]) — plus a top-level
    ``"_pages"`` entry holding the shared block table [B, max_blocks] int32
    with max_blocks = ceil(max_len / page_size).  With ``n_blocks=None``
    the pool is sized for the allocator-free identity table (one contiguous
    block run per slot plus the reserved trash block 0); an explicit
    ``n_blocks`` (the serving scheduler's HBM-budgeted pool) starts with
    all-zero tables for the allocator to populate.  SSM entries stay
    per-slot — only attention state pages.
    """
    dt = jnp.dtype(dtype or cfg.resolved_cache_dtype)
    nu = n_units(cfg)
    mk = (jax.ShapeDtypeStruct if abstract
          else (lambda shape, d: jnp.zeros(shape, d)))
    cache = {}
    hd = cfg.resolved_head_dim
    paged = cfg.paged
    if paged:
        ps = cfg.page_size
        mb = P.blocks_for(max_len, ps)
        nb = (1 + batch * mb) if n_blocks is None else int(n_blocks)
        kv_shape = (nu, nb, ps, cfg.num_kv_heads, hd)
        sc_shape = (nu, nb, ps, cfg.num_kv_heads, 1)
        if abstract:
            table = jax.ShapeDtypeStruct((batch, mb), jnp.int32)
        elif n_blocks is None:
            table = P.identity_table(batch, mb)
        else:
            table = jnp.zeros((batch, mb), jnp.int32)
        cache[PAGES_KEY] = {"table": table}
    else:
        kv_shape = (nu, batch, max_len, cfg.num_kv_heads, hd)
        sc_shape = (nu, batch, max_len, cfg.num_kv_heads, 1)
    for i, (mix, _) in enumerate(unit_structure(cfg)):
        if mix == "attn":
            cache[f"pos{i}"] = {"k": mk(kv_shape, dt), "v": mk(kv_shape, dt)}
            if Q.is_quantized(dt):
                cache[f"pos{i}"]["k_scale"] = mk(sc_shape, jnp.float32)
                cache[f"pos{i}"]["v_scale"] = mk(sc_shape, jnp.float32)
        else:
            cache[f"pos{i}"] = {
                "conv_x": mk((nu, batch, cfg.d_inner, cfg.ssm_conv - 1), dt),
                "conv_bc": mk((nu, batch, 2 * cfg.ssm_state, cfg.ssm_conv - 1), dt),
                "ssm": mk((nu, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                          jnp.float32),
            }
    return cache


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, tokens, lengths, cache, extra_embeds=None):
    """Process padded prompts, fill the cache, return last hidden per row.

    tokens [B, S_p] (right-padded), lengths [B] true lengths (incl. frontend
    prefix if any).  Returns (hidden_last [B, d], cache).

    Paged cache (DESIGN.md §12): the prompt window writes through the block
    table — rows [0, S_p) of slot b land in pool blocks
    ``table[b, 0:ceil(S_p/page_size)]``; attention itself is layout-blind
    here (prefill computes full causal attention from activations, never
    reading the cache).
    """
    cache, pages = split_pages(cache)
    table = None if pages is None else pages["table"]
    B, S_p = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    if cfg.frontend and extra_embeds is not None:
        x = jnp.concatenate([frontend_prefix(params, cfg, extra_embeds), x], axis=1)
    S_tot = x.shape[1]
    valid = jnp.arange(S_tot)[None, :] < lengths[:, None]

    def body(h, xs):
        unit_p, cache_u = xs
        new_cache = {}
        for i, (mix, ffn) in enumerate(unit_structure(cfg)):
            p = unit_p[f"pos{i}"]
            hh = L.apply_norm(p["norm1"], h, cfg)
            if mix == "attn":
                y, (k, v) = L.attention_full(p["attn"], hh, cfg, return_kv=True)
                new_cache[f"pos{i}"] = _write_prefix(
                    cache_u[f"pos{i}"], k, v, table=table,
                    page_size=cfg.page_size)
            else:
                y, (cx, cbc, ssm_st) = S.mamba2_full(
                    p["ssm"], hh, cfg, return_state=True, valid=valid, lengths=lengths)
                new_cache[f"pos{i}"] = {"conv_x": cx, "conv_bc": cbc, "ssm": ssm_st}
            h = h + y
            if ffn != "none":
                hh = L.apply_norm(p["norm2"], h, cfg)
                y = L.moe(p["ffn"], hh, cfg)[0] if ffn == "moe" else L.mlp(p["ffn"], hh, cfg)
                h = h + y
        return h, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["units"], cache))
    if pages is not None:
        new_cache[PAGES_KEY] = pages
    x = L.apply_norm(params["final_norm"], x, cfg)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, new_cache


# ---------------------------------------------------------------------------
# speculative decode step (tree / chain) + commit
# ---------------------------------------------------------------------------

def _write_prefix(entry, k, v, table=None, page_size: int = 0):
    """Prefill-time cache write of rows [0, S_p) into one layer's entry.

    k/v [B, S_p, Hkv, D] fp; quantizes on the way in for the int8 layout
    (the commit-path fusion of DESIGN.md §10 — the cache never holds fp
    rows).  With ``table`` (paged, DESIGN.md §12) the rows scatter through
    the block table instead of landing at slice [0, S_p) of a dense row.
    """
    if table is not None:
        z = jnp.zeros((k.shape[0],), jnp.int32)

        def wr(c, rows):
            return P.scatter_rows(c, table, rows, z, page_size)
    else:
        def wr(c, rows):
            return jax.lax.dynamic_update_slice(
                c, rows.astype(c.dtype), (0,) * c.ndim)
    if "k_scale" in entry:
        kq, ks = Q.quantize_rows(k)
        vq, vs = Q.quantize_rows(v)
        return {"k": wr(entry["k"], kq), "v": wr(entry["v"], vq),
                "k_scale": wr(entry["k_scale"], ks),
                "v_scale": wr(entry["v_scale"], vs)}
    return {"k": wr(entry["k"], k), "v": wr(entry["v"], v)}


def _read_cache(entry, dtype, table=None):
    """fp view of one layer's cached k/v -> ([B, S, Hkv, D], [B, S, Hkv, D])
    in ``dtype``.  Dequantizes the int8 layout (XLA path; the Pallas kernel
    dequantizes per KV block in VMEM instead — DESIGN.md §10).  With
    ``table`` the view is gathered from the paged pool first (S =
    max_blocks * page_size; the kernel path never materialises it —
    DESIGN.md §12)."""
    if table is not None:
        entry = {n: P.gather_cache(entry[n], table)
                 for n in ("k", "v", "k_scale", "v_scale") if n in entry}
    if "k_scale" in entry:
        return (Q.dequantize(entry["k"], entry["k_scale"], dtype),
                Q.dequantize(entry["v"], entry["v_scale"], dtype))
    return entry["k"].astype(dtype), entry["v"].astype(dtype)


def _update_rows(cache_arr, rows, starts):
    """Per-batch dynamic row write: cache [B,S,...], rows [B,T,...], starts [B].

    Formulated as (gather from the small T-dim) + elementwise select instead
    of a scatter, so the SPMD partitioner keeps the seq-sharded cache local —
    a vmapped dynamic_update_slice lowers to a scatter that forces a full
    cache all-gather (measured: 36 GiB/device on granite-8b decode_32k).
    """
    B, S = cache_arr.shape[:2]
    T = rows.shape[1]
    s_idx = jnp.arange(S)
    rel = s_idx[None, :] - starts[:, None]                     # [B, S]
    valid = (rel >= 0) & (rel < T)
    relc = jnp.clip(rel, 0, T - 1)
    idx = relc.reshape(relc.shape + (1,) * (cache_arr.ndim - 2))
    vals = jnp.take_along_axis(rows.astype(cache_arr.dtype), idx, axis=1)
    vmask = valid.reshape(valid.shape + (1,) * (cache_arr.ndim - 2))
    return jnp.where(vmask, vals, cache_arr)


def decode(params, cfg: ModelConfig, cache, tokens, lengths, tree_mask, depths,
           use_kernel: bool = False, deferred: bool = False):
    """One static speculative step over T tree/chain tokens.

    tokens [B, T]; lengths [B]; tree_mask [T, T] bool; depths [T] int32.
    Returns (hidden [B, T, d], spec_cache) where spec_cache holds written KV
    rows (attn) and per-prefix states (ssm) — consumed by ``commit``.
    ``deferred=True`` skips the per-step tree-row cache write (attention runs
    as cache-sweep ⊕ in-flight block); commit performs the only write.
    """
    B, T = tokens.shape
    cache, pages = split_pages(cache)
    table = None if pages is None else pages["table"]
    x = embed_tokens(params, cfg, tokens)
    S_max = cache_max_len(cache, table=table)
    masks = None
    if S_max and not (use_kernel or deferred):  # pure-SSM stacks have no attention cache
        masks = jax.vmap(lambda l: L.decode_mask(tree_mask, l, T, S_max))(lengths)

    def body(h, xs):
        unit_p, cache_u = xs
        new_cache = {}
        for i, (mix, ffn) in enumerate(unit_structure(cfg)):
            p = unit_p[f"pos{i}"]
            hh = L.apply_norm(p["norm1"], h, cfg)
            if mix == "attn":
                # the returned entry adds k_new/v_new (in-flight tree rows) —
                # commit gathers path rows from these small tensors, never
                # from the seq-sharded cache
                y, new_cache[f"pos{i}"] = attention_decode_batched(
                    p["attn"], hh, cfg, cache_u[f"pos{i}"], lengths, masks,
                    tree_mask, depths, use_kernel, deferred, table=table)
            else:
                ent = cache_u[f"pos{i}"]
                y, (cxs, cbcs, ssts) = S.mamba2_decode(
                    p["ssm"], hh, cfg, ent["conv_x"], ent["conv_bc"],
                    ent["ssm"])
                # per-prefix advanced states + the speculation-root
                # checkpoint: commit's rollback select (DESIGN.md §17)
                new_cache[f"pos{i}"] = {
                    "conv_x": cxs, "conv_bc": cbcs, "ssm": ssts,
                    "conv_x" + SSM_CKPT: ent["conv_x"],
                    "conv_bc" + SSM_CKPT: ent["conv_bc"],
                    "ssm" + SSM_CKPT: ent["ssm"],
                }
            h = h + y
            if ffn != "none":
                hh = L.apply_norm(p["norm2"], h, cfg)
                y = L.moe(p["ffn"], hh, cfg)[0] if ffn == "moe" else L.mlp(p["ffn"], hh, cfg)
                h = h + y
        return h, new_cache

    x, spec_cache = jax.lax.scan(body, x, (params["units"], cache))
    if pages is not None:
        spec_cache[PAGES_KEY] = pages
    x = L.apply_norm(params["final_norm"], x, cfg)
    return x, spec_cache


def attention_decode_batched(p, x, cfg, entry, lengths, masks, tree_mask,
                             depths, use_kernel=False, deferred=False,
                             table=None):
    """attention_decode with per-batch lengths (vmapped writes/masks).

    ``entry`` is one layer's cache dict: k/v [B, S, Hkv, D] (plus k_scale/
    v_scale [B, S, Hkv, 1] f32 under the int8 layout, DESIGN.md §10), or
    pool-form k/v [n_blocks, page_size, Hkv, D] with ``table``
    [B, max_blocks] under the paged layout (DESIGN.md §12).
    Returns (y, new_entry) where new_entry carries the (possibly updated)
    cache leaves plus in-flight tree rows k_new/v_new [B, T, Hkv, D] fp —
    the in-flight rows are per-slot under every layout.

    Int8 consistency rule: the in-flight rows that verification attends over
    are fake-quantized (quantize -> dequantize), so they are bit-equal to
    what every later sweep reads back from the committed cache — greedy
    losslessness (spec == AR) survives quantization (DESIGN.md §10).
    The paged layout moves bytes, not values, so the same argument carries
    over verbatim: paged decode is token-identical to dense (DESIGN.md §12).
    """
    import math as _m
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    scale = 1.0 / _m.sqrt(hd)
    quantized = "k_scale" in entry
    # fused write side (DESIGN.md §15): qkv projection + rope + tree-row
    # cache write in one kernel launch.  fp caches only — the int8 hop
    # needs the scale cache and keeps the unfused projection; deferred mode
    # skips the tree-row write entirely, so there is nothing to fuse.
    fused = (use_kernel and cfg.verify_fusion and not deferred
             and not quantized)
    if fused:
        from repro.kernels import cache_update as CU
        cos = sin = None
        if cfg.use_rope:
            positions = lengths[:, None] + depths[None, :]
            cos, sin = L.rope_cos_sin(positions, hd, cfg.rope_theta)
        q, k, v, new_k, new_v = CU.fused_qkv_rope_commit(
            x, p, lengths, entry["k"], entry["v"], cos=cos, sin=sin,
            table=table)
        new_entry = dict(entry)
        new_entry["k"], new_entry["v"] = new_k, new_v
        from repro.kernels.ops import tree_attention
        out = tree_attention(q, new_k, new_v, tree_mask, lengths, scale,
                             k_tree=k, v_tree=v, block_tables=table)
        y = L.tp_reduce(
            jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), cfg)
        new_entry["k_new"], new_entry["v_new"] = k, v
        return y, new_entry
    q, k, v = L._project_qkv(p, x, cfg)
    if cfg.use_rope:
        positions = lengths[:, None] + depths[None, :]
        cos, sin = L.rope_cos_sin(positions, hd, cfg.rope_theta)
        q = L.apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
        k = L.apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])
    if quantized:
        kq, ks = Q.quantize_rows(k)
        vq, vs = Q.quantize_rows(v)
        k = Q.dequantize(kq, ks, k.dtype)
        v = Q.dequantize(vq, vs, v.dtype)
    if table is not None:
        def upd(c, rows):
            return P.scatter_rows(c, table, rows, lengths, cfg.page_size)
    else:
        upd = functools.partial(_update_rows, starts=lengths)
    new_entry = dict(entry)
    if deferred:
        # deferred write (DESIGN.md §6): no tree-row write this step — one
        # full cache pass saved; the only cache write left is commit's
        ck, cv = _read_cache(entry, q.dtype, table=table)
        out = L.gqa_two_part(q, ck, cv, k, v, lengths, tree_mask, scale)
    else:
        if quantized:
            new_entry["k"] = upd(entry["k"], kq)
            new_entry["v"] = upd(entry["v"], vq)
            new_entry["k_scale"] = upd(entry["k_scale"], ks)
            new_entry["v_scale"] = upd(entry["v_scale"], vs)
        else:
            new_entry["k"] = upd(entry["k"], k)
            new_entry["v"] = upd(entry["v"], v)
        if use_kernel:
            from repro.kernels.ops import tree_attention
            out = tree_attention(q, new_entry["k"], new_entry["v"], tree_mask,
                                 lengths, scale,
                                 k_scale=new_entry.get("k_scale"),
                                 v_scale=new_entry.get("v_scale"),
                                 k_tree=k, v_tree=v, block_tables=table)
        else:
            ck, cv = _read_cache(new_entry, q.dtype, table=table)
            out = L._gqa_scores_to_out(q, ck, cv, masks, scale)
    y = L.tp_reduce(
        jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), cfg)
    new_entry["k_new"], new_entry["v_new"] = k, v
    return y, new_entry


def cache_max_len(cache, table=None):
    """Logical per-slot capacity in rows.  Dense: the S axis.  Paged: the
    table's reach, max_blocks * page_size (callers that hold a full paged
    cache can pass it directly — the table is found under ``_pages``)."""
    if table is None and PAGES_KEY in cache:
        table = cache[PAGES_KEY]["table"]
    for pos, entry in cache.items():
        if pos != PAGES_KEY and "k" in entry:
            # dense [.., B, S, H, D] -> S; paged [.., nb, ps, H, D] -> ps
            per_block_or_s = entry["k"].shape[-3]
            if table is not None:
                return table.shape[1] * per_block_or_s
            return per_block_or_s
    return 0


def _commit_attn_entry(entry, lengths, path_slots, table=None,
                       page_size: int = 0):
    """Commit one attention layer: gather best-path rows from the small
    in-flight tensors and write them back at [len, len+K1).

    entry: k/v [nu, B, S, Hkv, D] cache + k_new/v_new [nu, B, T, Hkv, D] fp
    (+ scales under int8); under the paged layout k/v are pools
    [nu, n_blocks, page_size, Hkv, D] and the write scatters through
    ``table`` [B, max_blocks] — same physical block index in every unit's
    pool (DESIGN.md §12).  For the int8 layout the gathered fp rows are
    re-quantized at the write; quantization is deterministic and idempotent
    on fake-quantized values (the max-|x| element always lands on ±127), so
    the committed bytes equal the values verification attended over
    (DESIGN.md §10).
    """
    idx = path_slots[None, :, :, None, None]
    if table is not None:
        def upd(c, rows, lens):
            return P.scatter_rows_stacked(c, table, rows, lens, page_size)
    else:
        upd = jax.vmap(_update_rows, in_axes=(0, 0, None))
    quantized = "k_scale" in entry
    out = {}
    for name in ("k", "v"):
        rows = jnp.take_along_axis(entry[name + "_new"], idx, axis=2)  # [nu,B,K1,H,D]
        if quantized:
            qrows, srows = Q.quantize_rows(rows)
            out[name] = upd(entry[name], qrows, lengths)
            out[name + "_scale"] = upd(entry[name + "_scale"], srows, lengths)
        else:
            out[name] = upd(entry[name], rows, lengths)
    return out


def commit(cfg: ModelConfig, spec_cache, lengths, path_slots, acc, active=None):
    """Zero-copy compaction: keep exactly the accepted prefix.

    path_slots [B, K+1]: tree-node slots of the best path (0..T-1);
    acc [B] in [1, K+1].  Attn: gather best-path KV rows and write them back
    at [len, len+K+1) (rows past ``acc`` are dead and will be overwritten).
    SSM: select the state after ``acc`` tokens of the chain, from the
    per-prefix scan states plus the speculation-root checkpoint stashed by
    ``decode`` (DESIGN.md §17).

    ``active`` [B] bool (optional) is the serving scheduler's masked-commit
    path (DESIGN.md §9): rows whose slot is empty/finished do not advance
    ``lengths``, so idle slots stay frozen inside the shared static step.
    Their (dead) attention row writes still happen — under the dense layout
    admission replaces the whole slot row, and under the paged layout an
    idle slot's zeroed table sinks them into the reserved trash block
    (DESIGN.md §12) — so nothing stale is ever read.  SSM recurrent state
    has no dead-write sink, so inactive rows instead *restore* the
    speculation-root checkpoint (effective acc = 0), which is what lets
    SSM/hybrid families share the step with chunked prefill and idle slots
    (DESIGN.md §17).
    Returns (cache, new_lengths).
    """
    spec_cache, pages = split_pages(spec_cache)
    table = None if pages is None else pages["table"]
    new_cache = {}
    for pos, entry in spec_cache.items():
        if "k" in entry:
            new_cache[pos] = _commit_attn_entry(entry, lengths, path_slots,
                                                table=table,
                                                page_size=cfg.page_size)
        else:
            # checkpointed SSM rollback (DESIGN.md §17): prepend the
            # speculation-root snapshot at chain index 0 and select with the
            # *effective* accepted length — rows masked out of this step
            # (acc forced to 0) restore the root state bitwise instead of
            # absorbing the chain's dead recurrence writes
            eff = acc if active is None else jnp.where(active, acc, 0)

            def sel(name, st):  # [nu, B, T, ...] -> [nu, B, ...]
                root = entry[name + SSM_CKPT].astype(st.dtype)
                full = jnp.concatenate([root[:, :, None], st], axis=2)
                idx = eff[None, :, None]
                idx = idx.reshape((1, -1, 1) + (1,) * (st.ndim - 3))
                return jnp.take_along_axis(full, idx, axis=2)[:, :, 0]
            new_cache[pos] = {k: sel(k, v) for k, v in entry.items()
                              if not k.endswith(SSM_CKPT)}
    if pages is not None:
        new_cache[PAGES_KEY] = pages
    adv = acc if active is None else jnp.where(active, acc, 0)
    return new_cache, lengths + adv
