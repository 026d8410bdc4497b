"""jit'd wrapper around the Pallas flash-decode kernel: full tree-attention
semantics = (cache sweep via kernel) ⊕ (tiny tree block) merged exactly via
partial-softmax stats.

Accepts both cache dtypes (DESIGN.md §10): fp k/v, or int8 k/v with
per-head-per-row f32 scales — and both cache layouts (DESIGN.md §12):
dense per-slot rows, or the paged block pool addressed through per-slot
``block_tables``.  On the CPU backend the kernel runs in interpret mode
(tests; ``kernels.platform``); the jnp tree block and the merge are
backend-agnostic.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import quant as Q
from repro.kernels.platform import interpret_default
from repro.kernels.tree_attention import flash_decode, unembed_verify_stats


def verify_stats(hidden, w, candidates, tmax, *, block_v=None,
                 interpret: bool | None = None):
    """Fused unembed + verify-statistics epilogue (DESIGN.md §15).

    hidden [B, T, d]; w [d, V] lm-head weight (cast to hidden.dtype like
    ``models.transformer.unembed``); candidates [B, T] int32; tmax [B] f32
    pre-clamped warp temperatures.  Returns (argm, m, l, cand_w) — see
    ``kernels.tree_attention.unembed_verify_stats``.  On the CPU backend
    the kernel runs in interpret mode (tests; ``kernels.platform``)."""
    if interpret is None:
        interpret = interpret_default()
    return unembed_verify_stats(hidden, w, candidates, tmax,
                                block_v=block_v, interpret=interpret)


def _pick_block(S: int):
    for bs in (512, 256, 128):
        if S % bs == 0:
            return bs
    return None


def tree_attention(q, k, v, tree_mask, lengths, scale, *,
                   k_scale=None, v_scale=None, k_tree=None, v_tree=None,
                   block_tables=None, block_s: int | None = None,
                   interpret: bool | None = None):
    """Tree-decode attention over a committed cache plus T in-flight rows.

    q [B, T, Hq, D] f32/bf16; k/v [B, S, Hkv, D] — fp, or int8 with
    ``k_scale``/``v_scale`` [B, S, Hkv, 1] f32 (the int8 cache layout,
    DESIGN.md §10); tree rows already written at [lengths, lengths+T).
    tree_mask [T, T] bool; lengths [B] int32 or scalar.  Pass
    ``k_tree``/``v_tree`` [B, T, Hkv, D] fp (the in-flight tree rows —
    fake-quantized by the caller under int8) to skip the gather from a
    potentially seq-sharded cache.  Returns [B, T, Hq, D] in q.dtype.

    Paged cache (DESIGN.md §12): pass ``block_tables`` [B, max_blocks]
    int32 with pool-form k/v [n_blocks, page_size, Hkv, D] (scales
    [n_blocks, page_size, Hkv, 1]); ``k_tree``/``v_tree`` are then
    required — the in-flight rows live outside the pool, so there is no
    per-slot array to gather them from.
    """
    B, T, Hq, D = q.shape
    paged = block_tables is not None
    if paged:
        assert k_tree is not None, "paged tree_attention requires k_tree/v_tree"
        S, Hkv = block_tables.shape[1] * k.shape[1], k.shape[2]
    else:
        S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    quantized = k.dtype == jnp.int8
    if interpret is None:
        interpret = interpret_default()
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    # tiny/odd caches fall through to flash_decode's pad/clamp path
    bs = None if paged else (block_s or _pick_block(S) or 128)

    # fold q: [B,T,Hq,D] -> [B,Hkv,R,D], row r = g*T_pad + t
    T_pad = T
    while (G * T_pad) % 8:
        T_pad += 1
    qp = jnp.pad(q, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
    qf = qp.reshape(B, T_pad, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(B, Hkv, G * T_pad, D) * jnp.asarray(scale, q.dtype)
    # dense [B,S,Hkv,D] -> [B,Hkv,S,D]; pool [nb,ps,Hkv,D] -> [nb,Hkv,ps,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kst = k_scale.transpose(0, 2, 1, 3) if quantized else None
    vst = v_scale.transpose(0, 2, 1, 3) if quantized else None

    fd_kw = ({"block_tables": block_tables} if paged else {"block_s": bs})
    acc1, m1, l1 = flash_decode(qf, kt, vt, lengths, k_scale=kst, v_scale=vst,
                                interpret=interpret, **fd_kw)  # [B,Hkv,R,D] f32

    # --- tree block (tiny) --------------------------------------------------
    if k_tree is None:
        idx = (lengths[:, None] + jnp.arange(T))[:, :, None, None]
        k_tree = jnp.take_along_axis(k, idx, axis=1)        # [B,T,Hkv,D]
        v_tree = jnp.take_along_axis(v, idx, axis=1)
        if quantized:
            ks_tree = jnp.take_along_axis(k_scale, idx, axis=1)
            vs_tree = jnp.take_along_axis(v_scale, idx, axis=1)
            k_tree = Q.dequantize(k_tree, ks_tree, q.dtype)
            v_tree = Q.dequantize(v_tree, vs_tree, q.dtype)
    scores2 = jnp.einsum("bhrd,bthd->bhrt", qf, k_tree.astype(qf.dtype)).astype(jnp.float32)
    # row r sees tree col t' iff tree_mask[r % T_pad, t'] (pad rows: self only)
    row_mask = jnp.zeros((T_pad, T), bool).at[:T, :].set(tree_mask)
    row_mask = jnp.tile(row_mask, (G, 1))                   # [R, T]
    scores2 = jnp.where(row_mask[None, None], scores2, -1e30)
    m2 = jnp.max(scores2, axis=-1, keepdims=True)
    m2 = jnp.maximum(m2, -1e30)                             # pad rows: all masked
    p2 = jnp.exp(scores2 - m2)
    p2 = jnp.where(row_mask[None, None], p2, 0.0)
    l2 = jnp.sum(p2, axis=-1, keepdims=True)
    acc2 = jnp.einsum("bhrt,bthd->bhrd", p2.astype(qf.dtype),
                      v_tree.astype(qf.dtype)).astype(jnp.float32)

    # --- exact merge --------------------------------------------------------
    m = jnp.maximum(m1, m2)
    a1, a2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    out = (acc1 * a1 + acc2 * a2) / jnp.maximum(l1 * a1 + l2 * a2, 1e-30)

    out = out.reshape(B, Hkv, G, T_pad, D).transpose(0, 3, 1, 2, 4)
    return out[:, :T].reshape(B, T, Hq, D).astype(q.dtype)
