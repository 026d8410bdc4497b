"""Seeded random weights of a dense GQA decoder, made by the benchmark.

The program under test and the plain reference both take their weights from
here, so the reference never reads an array the program made.  Every leaf is
a function of (seed, layer, leaf name) alone:

    bits  = threefry bits of fold_in(fold_in(fold_in(key(seed), hi), layer), leaf)
    value = bf16(f32(int24(bits) - 2^23) * std * sqrt(3) / 2^23)

uniform with the leaf's standard deviation.  Only integer arithmetic, one
f32 multiply and one cast round (norm gains: exact dyadic values), so the
whole-model init on the device and a per-layer redraw for the reference
give the same bits however XLA fuses them.

Standard deviations: fan-in scaling (1/sqrt(d) into the projections and the
lm head, 1/sqrt(H*hd) into the attention output, 1/sqrt(d_ff) into the MLP
output), unit embeddings, norm gains 1 + k/256, |k| <= 32.  Attention logits stay O(1),
so two correct bf16 paths stay comparable at depth (a fan-in taken from the
head count instead saturates every softmax at these widths).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GLOBAL_LAYER = -1          # layer index used for embed / final norm / lm head


def base_key(seed: int):
    """PRNG key of any non-negative seed (more than 32 bits allowed)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def layer_shapes(conf: dict) -> dict:
    """{leaf: (shape, std, dtype)} of one decoder layer."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    wdt = jnp.dtype(conf["torch_dtype"])
    return {
        "norm1": ((d,), None, jnp.float32),
        "wq": ((d, h, hd), d ** -0.5, wdt),
        "wk": ((d, hkv, hd), d ** -0.5, wdt),
        "wv": ((d, hkv, hd), d ** -0.5, wdt),
        "wo": ((h, hd, d), (h * hd) ** -0.5, wdt),
        "norm2": ((d,), None, jnp.float32),
        "wi": ((d, f), d ** -0.5, wdt),
        "wg": ((d, f), d ** -0.5, wdt),
        "wo_ff": ((f, d), f ** -0.5, wdt),
    }


def global_shapes(conf: dict) -> dict:
    d, v = conf["hidden_size"], conf["vocab_size"]
    wdt = jnp.dtype(conf["torch_dtype"])
    return {"embed": ((v, d), 1.0, wdt),
            "final_norm": ((d,), None, jnp.float32),
            "lm_head": ((d, v), d ** -0.5, wdt)}


def draw(key, shape, std, dtype, offset=None):
    """Uniform values of standard deviation ``std``; with ``offset``, the
    offset plus k / 256 for k uniform in [-32, 32) instead (std 0.072),
    every value exact in float32 so no fused multiply-add can change it."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    centred = (bits >> 8).astype(jnp.int32) - (1 << 23)
    if offset is not None:
        k = (centred >> 18).astype(jnp.float32)
        return (jnp.float32(offset) + k * jnp.float32(2 ** -8)).astype(dtype)
    x = centred.astype(jnp.float32) * jnp.float32(std * math.sqrt(3) / (1 << 23))
    return x.astype(dtype)


def _leaf_key(key, layer: int, j: int):
    return jax.random.fold_in(jax.random.fold_in(key, layer + 1), j)


def layer_weights(conf: dict, key, layer):
    """One layer's leaves, {name: array}; ``layer`` may be traced."""
    out = {}
    for j, (name, (shape, std, dt)) in enumerate(layer_shapes(conf).items()):
        norm = name.startswith("norm")
        out[name] = draw(_leaf_key(key, layer, j), shape, std, dt,
                         offset=1.0 if norm else None)
    return out


def global_weights(conf: dict, key):
    out = {}
    for j, (name, (shape, std, dt)) in enumerate(global_shapes(conf).items()):
        out[name] = draw(_leaf_key(key, GLOBAL_LAYER, j), shape, std, dt,
                         offset=1.0 if name == "final_norm" else None)
    return out


def program_tree(conf: dict, seed: int, out_shardings=None):
    """The whole model in the serving program's parameter layout (layers
    stacked on a leading axis), in one jitted call, placed by
    ``out_shardings`` (default: the default device).  Layers are drawn one
    at a time (``lax.map``), so the init holds one layer of scratch."""
    n = conf["num_hidden_layers"]

    def init(key):
        layers = jax.lax.map(lambda l: layer_weights(conf, key, l),
                             jnp.arange(n))
        g = global_weights(conf, key)
        unit = {"norm1": {"w": layers["norm1"]},
                "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
                "norm2": {"w": layers["norm2"]},
                "ffn": {"wi": layers["wi"], "wg": layers["wg"],
                        "wo": layers["wo_ff"]}}
        return {"embed": g["embed"], "units": {"pos0": unit},
                "final_norm": {"w": g["final_norm"]}, "lm_head": g["lm_head"]}

    return jax.jit(init, out_shardings=out_shardings)(base_key(seed))
