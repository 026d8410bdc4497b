"""Plain float32 forward of a dense GQA decoder.

Pre-norm decoder layers: RMSNorm, grouped-query attention with half-rotation
RoPE and a causal mask, residual add, RMSNorm, SwiGLU MLP
(``wo(wi(x) * silu(wg(x)))``), residual add; a final RMSNorm and an untied lm
head.  Everything in float32 at ``highest`` matmul precision, computed layer
by layer over a batch of whole sequences, with each layer's weights redrawn
from the seed (``bench.weights``): nothing is taken from the program.

``lowp=True`` is the benchmark's control: every matmul operand (weights,
activations, q, k, v) rounded to float8 e4m3 with a per-channel scale, the
step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W

F8_MAX = 448.0


def fake_fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one absmax scale per slice along
    ``axis`` (the contraction axis), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, H, D], pos [S] -> half-rotation RoPE."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(conf, lowp, w, h):
    """h [N, S, d] f32 -> h after one decoder layer."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if lowp:
        w = {k: (fake_fp8(v, 0) if not k.startswith("norm") else v)
             for k, v in w.items()}
        w["wo"] = fake_fp8(w["wo"].reshape(hq * hd, -1), 0).reshape(
            w["wo"].shape)
    act = (lambda x: fake_fp8(x, -1)) if lowp else (lambda x: x)

    def attend(x):                                   # x [S, d]
        S = x.shape[0]
        pos = jnp.arange(S)
        q = _rope(jnp.einsum("sd,dhk->shk", x, w["wq"]), pos, theta)
        k = _rope(jnp.einsum("sd,dhk->shk", x, w["wk"]), pos, theta)
        v = jnp.einsum("sd,dhk->shk", x, w["wv"])
        q, k, v = act(q), act(k), act(v)
        q = q.reshape(S, hkv, hq // hkv, hd)
        s = jnp.einsum("sgrk,tgk->grst", q, k) / jnp.sqrt(jnp.float32(hd))
        causal = pos[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("grst,tgk->sgrk", p, v).reshape(S, hq, hd)
        return jnp.einsum("shk,hkd->sd", act(o), w["wo"])

    x = act(_rms(h, w["norm1"], eps))
    h = h + jax.lax.map(attend, x)
    x = act(_rms(h, w["norm2"], eps))
    u = act(jnp.einsum("nsd,df->nsf", x, w["wi"])
            * jax.nn.silu(jnp.einsum("nsd,df->nsf", x, w["wg"])))
    return h + jnp.einsum("nsf,fd->nsd", u, w["wo_ff"])


def logits_at(conf: dict, seed: int, tokens, rows, cols, lowp: bool = False):
    """Reference logits [P, V] float32 at positions (rows[i], cols[i]) of
    ``tokens`` [N, S] int32 (each row one whole sequence from position 0)."""
    key = W.base_key(seed)
    g = jax.jit(functools.partial(W.global_weights, conf))(key)
    with jax.default_matmul_precision("highest"):
        h = jax.jit(lambda e, t: e.astype(jnp.float32)[t])(g["embed"], tokens)
        draw = jax.jit(functools.partial(W.layer_weights, conf))
        step = jax.jit(functools.partial(_layer, conf, lowp),
                       donate_argnums=1)
        for layer in range(conf["num_hidden_layers"]):
            h = step(draw(key, layer), h)

        @jax.jit
        def head(h, norm, lm, rows, cols):
            x = _rms(h[rows, cols], norm.astype(jnp.float32),
                     conf["rms_norm_eps"])
            lm = lm.astype(jnp.float32)
            if lowp:
                x, lm = fake_fp8(x, -1), fake_fp8(lm, 0)
            return x @ lm

        return head(h, g["final_norm"], g["lm_head"], rows, cols)
