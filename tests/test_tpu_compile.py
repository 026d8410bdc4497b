"""Compile-only guards for the chip path: the Pallas kernels of the serving
step at openpangu-7b's published widths (d_model 4096, 32/8 heads of 128,
vocab 153,376), compiled for a described TPU v5e with no chip attached.

Nothing runs, so these say nothing about results or speed; they catch what
interpret mode cannot — block shapes the TPU lowering refuses, VMEM
overruns, layouts that force a copy — before any chip time is spent.  The
topology is described inside a module fixture (libtpu may be loaded by one
process at a time), and the persistent compilation cache is off around the
tests: a described-topology executable cannot be read back without a chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as KO
from repro.kernels.cache_update import (commit_rows, commit_rows_paged,
                                        fused_qkv_rope_commit)
from repro.kernels.tree_attention import flash_decode, unembed_verify_stats

D_MODEL, HQ, HKV, HD, VOCAB = 4096, 32, 8, 128, 153376
B, T, S, PAGE = 4, 64, 512, 64          # 4 slots, the 64-node Medusa tree
R = (HQ // HKV) * T                      # folded query rows per kv head
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    return compiled


@pytest.mark.parametrize("layout", ["dense-bf16", "paged-bf16", "dense-int8"])
def test_flash_decode_compiles(one_chip, layout):
    n_blocks = B * S // PAGE + 1
    q = ((B, HKV, R, HD), BF16)
    lens = ((B,), jnp.int32)
    if layout == "paged-bf16":
        kv = ((n_blocks, HKV, PAGE, HD), BF16)
        tables = ((B, S // PAGE), jnp.int32)
        _compile(one_chip, lambda q, k, v, l, t: flash_decode(
            q, k, v, l, block_tables=t, interpret=False),
            q, kv, kv, lens, tables)
    elif layout == "dense-int8":
        kv, sc = ((B, HKV, S, HD), jnp.int8), ((B, HKV, S, 1), jnp.float32)
        _compile(one_chip, lambda q, k, v, ks, vs, l: flash_decode(
            q, k, v, l, k_scale=ks, v_scale=vs, interpret=False),
            q, kv, kv, sc, sc, lens)
    else:
        kv = ((B, HKV, S, HD), BF16)
        _compile(one_chip, lambda q, k, v, l: flash_decode(
            q, k, v, l, interpret=False), q, kv, kv, lens)


def test_tree_attention_compiles(one_chip):
    """The serving wrapper: kernel sweep plus the jnp tree block, T=64."""
    kv, tree = ((B, S, HKV, HD), BF16), ((B, T, HKV, HD), BF16)
    _compile(one_chip, lambda q, k, v, kt, vt, l: KO.tree_attention(
        q, k, v, jnp.tril(jnp.ones((T, T), bool)), l, HD ** -0.5,
        k_tree=kt, v_tree=vt, interpret=False),
        ((B, T, HQ, HD), BF16), kv, kv, tree, tree, ((B,), jnp.int32))


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_commit_rows_compiles(one_chip, layout):
    rows, lens = ((B, 5, HKV, HD), BF16), ((B,), jnp.int32)
    if layout == "paged":
        _compile(one_chip, lambda pool, t, r, l: commit_rows_paged(
            pool, t, r, l, interpret=False),
            ((B * S // PAGE + 1, PAGE, HKV, HD), BF16),
            ((B, S // PAGE), jnp.int32), rows, lens)
    else:
        _compile(one_chip, lambda c, r, l: commit_rows(
            c, r, l, interpret=False), ((B, S, HKV, HD), BF16), rows, lens)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_fused_qkv_rope_commit_compiles(one_chip, layout):
    def fn(x, wq, wk, wv, l, kc, vc, cos, sin, *table):
        return fused_qkv_rope_commit(
            x, {"wq": wq, "wk": wk, "wv": wv}, l, kc, vc, cos=cos, sin=sin,
            table=table[0] if table else None, interpret=False)

    cache = (((B * S // PAGE + 1, PAGE, HKV, HD), BF16) if layout == "paged"
             else ((B, S, HKV, HD), BF16))
    rope = ((B, T, HD // 2), jnp.float32)
    extra = [((B, S // PAGE), jnp.int32)] if layout == "paged" else []
    _compile(one_chip, fn, ((B, T, D_MODEL), BF16),
             ((D_MODEL, HQ, HD), BF16), ((D_MODEL, HKV, HD), BF16),
             ((D_MODEL, HKV, HD), BF16), ((B,), jnp.int32), cache, cache,
             rope, rope, *extra)


def test_unembed_verify_stats_compiles_without_head_copy(one_chip):
    """B=4, T=64 over the full 153,376-column head: the kernel reads the
    head where it lies (no pad, no relayout), so scratch stays far below
    the head's 1.26 GB."""
    compiled = _compile(
        one_chip, lambda h, w, c, t: unembed_verify_stats(
            h, w, c, t, interpret=False),
        ((B, T, D_MODEL), BF16), ((D_MODEL, VOCAB), BF16),
        ((B, T), jnp.int32), ((B,), jnp.float32))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 << 20, f"{temp} bytes of scratch: the lm head is copied"
