"""Compile-only guards for the chip path: the Pallas kernels of the serving
step at openpangu-7b's published widths (d_model 4096, 32/8 heads of 128,
vocab 153,376), compiled for a described TPU v5e with no chip attached, and
the TP=4 engine's ``jit_tp_generate`` over a described v5e 2x2, whose
row-parallel psums the benchmark's ``bench/counts/all_reduce.py`` matches.

Nothing runs, so these say nothing about results or speed; they catch what
interpret mode cannot — block shapes the TPU lowering refuses, VMEM
overruns, layouts that force a copy — before any chip time is spent.  The
topology is described inside a module fixture (libtpu may be loaded by one
process at a time), and the persistent compilation cache is off around the
tests: a described-topology executable cannot be read back without a chip.
"""
import re
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import ops as KO
from repro.kernels.cache_update import (commit_rows, commit_rows_paged,
                                        fused_qkv_rope_commit)
from repro.kernels.tree_attention import (flash_decode, unembed_verify_stats,
                                         verify_stats_plan)

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


@pytest.mark.parametrize("slots", [4, 8])
def test_unembed_verify_stats_compiles_without_head_copy(one_chip, slots):
    """4 and 8 slots x T=64 over the full 153,376-column head, every slot
    in one group (one head pass) within the kernel's VMEM limit: the
    kernel reads the head where it lies (no pad, no relayout), so scratch
    stays far below the head's 1.26 GB."""
    assert verify_stats_plan(slots, T, D_MODEL, 512, BF16)[0] == slots
    compiled = _compile(
        one_chip, lambda h, w, c, t: unembed_verify_stats(
            h, w, c, t, interpret=False),
        ((slots, T, D_MODEL), BF16), ((D_MODEL, VOCAB), BF16),
        ((slots, T), jnp.int32), ((slots,), jnp.float32))
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 << 20, f"{temp} bytes of scratch: the lm head is copied"


@pytest.mark.parametrize("slots", [8, 16])
def test_medusa_topk_compiles_without_sort(one_chip, slots):
    """The four Medusa heads at published widths ([4, slots, 4096] head
    hidden states into [4, 4096, 153376] projections) and the
    ``medusa_63`` tree's k = 10, at the one-chip cells' 8 slots and the
    TP cell's 16: the top-k is reduces over the vocabulary, with no sort
    and no TopK custom call."""
    from repro.core import medusa as M

    def sds(s):
        return jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)

    mp = {"w1": sds((4, D_MODEL, D_MODEL)), "b1": sds((4, D_MODEL)),
          "lm": sds((4, D_MODEL, VOCAB))}
    text = jax.jit(M.medusa_topk, static_argnums=2).lower(
        mp, sds((slots, D_MODEL)), 10).compile().as_text()
    assert " sort(" not in text
    assert 'custom_call_target="TopK"' not in text


@pytest.fixture(scope="module")
def mesh_2x2(topo, one_chip):      # one_chip: the compilation cache is off
    return Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model"))


def test_tp4_generate_psums_are_what_the_benchmark_matches(mesh_2x2,
                                                           monkeypatch):
    """``jit_tp_generate`` of ``pangu34-tp4.offline-batch`` cut to 2 layers
    (its 16 slots, 1024-token bucket, 1280 positions, 64-node tree):
    ``MATCH`` finds the psum after attention wo and after the MLP
    down-projection in the layer loop of the decode step, and no other
    all-reduce: not the prefill's psums of the same exchange."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import manifest
    from bench.model import program_config
    from repro.core import medusa as M
    from repro.distributed import profiles
    from repro.distributed.sharding import split_params
    from repro.distributed.tp import build_tp_engine
    from repro.launch.serve import weight_shapes
    from repro.models import api as model_api

    cell = manifest.cell("pangu34-tp4.offline-batch")
    conf = dict(cell.config, num_hidden_layers=2)
    srv = cell.mix["server"]
    slots, bucket = srv["slots"], max(srv["prompt_buckets"])
    cfg = program_config(conf)
    tpe = build_tp_engine(cfg, mesh_2x2, conf["serving"]["proposer"],
                          accept=conf["serving"]["accept"])
    shapes, axes = weight_shapes(cfg)

    def sds(s, sharding):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    params = jax.tree.map(sds, shapes, profiles.to_named(
        tpe.param_specs(shapes, axes), mesh_2x2))
    rep = NamedSharding(mesh_2x2, P())
    pp = jax.tree.map(lambda s: sds(s, rep), jax.eval_shape(
        lambda k: split_params(M.init_medusa(k, cfg, tpe.tb.K,
                                             dtype=cfg.dtype))[0],
        jax.random.PRNGKey(0)))
    cache = model_api.init_cache(cfg, slots, srv["max_len"], abstract=True)
    cache = jax.tree.map(sds, cache, profiles.to_named(
        profiles.tp_cache_pspecs(cache, cfg, mesh_2x2, "model"), mesh_2x2))
    toks = jax.ShapeDtypeStruct((slots, bucket), jnp.int32, sharding=rep)
    plens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep)
    built = {}

    def capture(name, build):       # the jitted program, left unrun
        built[name] = build()
        return lambda *a: (None, None, types.SimpleNamespace(steps=0))

    monkeypatch.setattr(tpe, "_cached", capture)
    tpe.generate(params, pp, toks, plens, cache, 4)
    text = built[("generate", 4)].lower(params, pp, toks, plens, cache, None,
                                        None).compile().as_text()
    assert text.startswith("HloModule jit_tp_generate")
    match = re.compile(manifest.counts("all_reduce").MATCH)
    ops = [(re.search(r"= (\S+\[[\d,]*\])", line).group(1),
            re.search(r'op_name="([^"]*)"', line), bool(match.search(line)))
           for line in text.splitlines() if " all-reduce(" in line
           or " all-reduce-start(" in line]
    matched = [(shape, name) for shape, name, hit in ops if hit]
    assert all(name and name.group(1).endswith("/psum")
               for _, name in matched), matched
    d, tree = cfg.d_model, tpe.dtree.T
    step, prefill = f"bf16[{slots},{tree},{d}]", f"bf16[{slots},{bucket},{d}]"
    # one layer-loop body each: the step's two psums matched, the prefill's
    # two of the same exchange over the prompt bucket not
    assert [shape for shape, _ in matched] == [step] * 2
    assert sorted(shape for shape, _, _ in ops
                  if shape in (step, prefill)) == [prefill] * 2 + [step] * 2
