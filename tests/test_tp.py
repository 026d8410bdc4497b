"""Tensor-parallel speculative decode (DESIGN.md §18): cache PartitionSpec
trees across layouts, TP engine construction guards, the ngram matcher
automaton, and the sharded==single-device token-identity matrix on a forced
8-device host mesh."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import get_config
from repro.core.proposers import NgramProposer
from repro.distributed import profiles
from repro.models.api import get_model


class FakeMesh:
    shape = {"data": 2, "model": 2}


def _cfg(**kw):
    return dataclasses.replace(get_config("qwen1.5-0.5b", reduced=True), **kw)


def _abstract_cache(cfg, B=2, S=64):
    nb = (B * S) // cfg.page_size if cfg.paged else None
    return get_model(cfg).init_cache(cfg, B, S, n_blocks=nb, abstract=True)


SHAPE = dataclasses.replace(SHAPES["decode_32k"], seq_len=64, global_batch=8)


# --------------------------------------------------------- cache spec trees

def test_cache_pspecs_dense_layout():
    """Dense decode branch: flash-decoding KV-seq parallelism — k/v (and
    int8 scales) shard seq over "model", batch over "data"."""
    cfg = _cfg()
    specs = profiles.cache_pspecs(_abstract_cache(cfg), cfg, SHAPE,
                                  FakeMesh(), False)
    unit = specs["pos0"]
    assert unit["k"] == P(None, ("data",), "model", None, None)
    assert unit["v"] == P(None, ("data",), "model", None, None)


def test_cache_pspecs_paged_pool_shards_heads():
    """Paged branch (the §18 fix): pool-form k/v leaves [nu, nb, ps, Hkv,
    hd] shard their kv-head axis over "model" instead of replicating; the
    block table stays replicated."""
    cfg = _cfg(cache_layout="paged", page_size=16)
    specs = profiles.cache_pspecs(_abstract_cache(cfg), cfg, SHAPE,
                                  FakeMesh(), False)
    unit = specs["pos0"]
    assert unit["k"] == P(None, None, None, "model", None)
    assert unit["v"] == P(None, None, None, "model", None)
    assert specs["_pages"]["table"] == P(None, None)


def test_cache_pspecs_paged_int8_scales_ride_along():
    cfg = _cfg(cache_layout="paged", page_size=16, cache_dtype="int8")
    specs = profiles.cache_pspecs(_abstract_cache(cfg), cfg, SHAPE,
                                  FakeMesh(), False)
    unit = specs["pos0"]
    for leaf in ("k", "v", "k_scale", "v_scale"):
        assert unit[leaf] == P(None, None, None, "model", None), leaf


def test_cache_pspecs_paged_indivisible_heads_replicate():
    """4 kv heads on an 8-way model axis: the divisibility guard demotes
    the pool leaves to replicated instead of producing an invalid spec."""
    class WideMesh:
        shape = {"data": 1, "model": 8}
    cfg = _cfg(cache_layout="paged", page_size=16)   # reduced: Hkv == 4
    specs = profiles.cache_pspecs(_abstract_cache(cfg), cfg, SHAPE,
                                  WideMesh(), False)
    assert specs["pos0"]["k"] == P(None, None, None, None, None)


def test_tp_cache_pspecs_both_layouts():
    """The TP tree shards the head axis on BOTH layouts (the shard_map
    body is head-local either way); paged agrees with cache_pspecs
    leaf-for-leaf, dense deliberately differs from its flash-decoding
    spec."""
    dense = _cfg()
    specs = profiles.tp_cache_pspecs(_abstract_cache(dense), dense,
                                     FakeMesh())
    assert specs["pos0"]["k"] == P(None, None, None, "model", None)
    paged = _cfg(cache_layout="paged", page_size=16, cache_dtype="int8")
    ab = _abstract_cache(paged)
    tp_specs = profiles.tp_cache_pspecs(ab, paged, FakeMesh())
    legacy = profiles.cache_pspecs(ab, paged, SHAPE, FakeMesh(), False)
    assert jax.tree.map(lambda a, b: a == b, tp_specs, legacy,
                        is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.map(lambda _: True, tp_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------ construction guards

def _mesh2():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from repro.distributed.tp import make_tp_mesh
    return make_tp_mesh(2)


@pytest.mark.parametrize("bad, msg", [
    (dict(family="moe", num_experts=4), "dense family"),
    (dict(tie_embeddings=True), "lm_head"),
    (dict(verify_fusion=True), "verify_fusion"),
    (dict(num_heads=3, num_kv_heads=1, head_dim=16), "divide"),
    (dict(tp_axis="model"), "global config"),
])
def test_build_tp_engine_rejects(bad, msg):
    from repro.distributed.tp import TPSpecEngine, _validate
    with pytest.raises(ValueError, match=msg):
        _validate(_cfg(**bad), "medusa", 2)


def test_build_tp_engine_rejects_draft_proposer():
    from repro.distributed.tp import _validate
    with pytest.raises(ValueError, match="proposer"):
        _validate(_cfg(), "draft", 2)


def test_tp_engine_local_cfg_and_param_specs():
    """The local config halves heads/kv-heads and pins head_dim; param
    specs shard wq on heads, lm_head on vocab, and force the embedding
    replicated (token-id take)."""
    mesh = _mesh2()
    from repro.distributed.sharding import split_params
    from repro.distributed.tp import build_tp_engine
    cfg = _cfg()
    tpe = build_tp_engine(cfg, mesh, "medusa")
    assert tpe.local_cfg.num_heads == cfg.num_heads // 2
    assert tpe.local_cfg.num_kv_heads == cfg.num_kv_heads // 2
    assert tpe.local_cfg.head_dim == cfg.resolved_head_dim
    assert tpe.local_cfg.tp_axis == "model"
    assert tpe.local_cfg.vocab_size == cfg.vocab_size   # global on purpose
    params, axes = split_params(
        get_model(cfg).init_params(jax.random.PRNGKey(0), cfg))
    tpe.shard_params(params, axes)
    sp = tpe._pspecs
    assert sp["embed"] == P()
    assert sp["lm_head"] == P(None, "model")
    assert sp["units"]["pos0"]["attn"]["wq"] == P(None, None, "model", None)
    assert sp["units"]["pos0"]["ffn"]["wi"] == P(None, None, "model")


def test_tp_engine_requires_shard_params_first():
    mesh = _mesh2()
    from repro.distributed.tp import build_tp_engine
    tpe = build_tp_engine(_cfg(), mesh, "ngram")
    with pytest.raises(RuntimeError, match="shard_params"):
        tpe.prefill(None, None, None, None, {})


def test_tp_engine_stats_before_any_call():
    from repro.distributed.tp import build_tp_engine
    cfg = _cfg()
    tpe = build_tp_engine(cfg, FakeMesh(), "ngram")
    assert tpe.stats == {"prefill_calls": 0, "spec_step_calls": 0,
                         "generate_calls": 0, "generate_steps": 0,
                         "psums_per_step": 2 * cfg.num_layers}


def test_serve_parses_tp_with_profile():
    """``--tp N --profile DIR`` is one launch: the sharded path under the
    profiler (the trace itself: tests/bench/test_bench_tp4.py)."""
    from repro.launch.serve import build_parser
    args = build_parser().parse_args(["--tp", "4", "--profile", "/tmp/t",
                                      "--mesh-shape", "1x4"])
    assert (args.tp, args.profile, args.mesh_shape) == (4, "/tmp/t", "1x4")
    args = build_parser().parse_args([])
    assert (args.tp, args.profile) == (0, "")


# ------------------------------------------------------ ngram matcher index

def _primed(matcher, rng, B=3, cap=96):
    cfg = _cfg()
    prop = NgramProposer(cfg, gamma=4, max_n=3, min_n=1, matcher=matcher)
    hl = rng.integers(6, cap - 10, B)
    tokens = jnp.asarray(rng.integers(2, 9, (B, cap - 10)), jnp.int32)
    base = jnp.asarray(rng.integers(2, 9, B), jnp.int32)
    state = prop.prime(None, prop.init_state(B, cap), tokens, None,
                       jnp.asarray(hl, jnp.int32), None, base)
    return prop, state, base


def _match(prop, state):
    if "tab" in state:
        return prop._match_tab(state["tab"], state["hist"], state["hlen"])
    return prop._match_scan(state["hist"], state["hlen"])


def test_ngram_automaton_matches_scan_after_prime():
    """Small-vocab histories (dense with repeats) — the automaton must find
    the scan's window: same found mask, same continuation start."""
    for seed in range(12):   # identical inputs for both matchers
        r1, r2 = np.random.default_rng(seed), np.random.default_rng(seed)
        scan, s1, _ = _primed("scan", r1)
        auto, s2, _ = _primed("automaton", r2)
        f1, c1 = _match(scan, s1)
        f2, c2 = _match(auto, s2)
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
        np.testing.assert_array_equal(np.asarray(c1) * np.asarray(f1),
                                      np.asarray(c2) * np.asarray(f2))


def test_ngram_automaton_incremental_observe_matches_scan():
    """The ≤K1-window incremental insert must leave the index equivalent
    to a full rebuild: commit fake verdicts, re-compare matchers."""
    K1 = 5
    for seed in range(6):
        r1, r2 = np.random.default_rng(seed), np.random.default_rng(seed)
        scan, s1, _ = _primed("scan", r1)
        auto, s2, _ = _primed("automaton", r2)
        rv = np.random.default_rng(100 + seed)
        for _ in range(4):
            vd = type("Vd", (), dict(
                path_tokens=jnp.asarray(rv.integers(2, 9, (3, K1)), jnp.int32),
                acc=jnp.asarray(rv.integers(1, K1 + 1, 3), jnp.int32),
                next_token=jnp.asarray(rv.integers(2, 9, 3), jnp.int32)))
            s1 = scan.observe(None, s1, vd, None, None)
            s2 = auto.observe(None, s2, vd, None, None)
            np.testing.assert_array_equal(np.asarray(s1["hist"]),
                                          np.asarray(s2["hist"]))
            f1, c1 = _match(scan, s1)
            f2, c2 = _match(auto, s2)
            np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
            np.testing.assert_array_equal(np.asarray(c1) * np.asarray(f1),
                                          np.asarray(c2) * np.asarray(f2))


def test_ngram_auto_threshold_and_reset():
    cfg = _cfg()
    auto = NgramProposer(cfg, matcher="auto")
    assert "tab" not in auto.init_state(2, auto.AUTO_THRESHOLD - 1)
    big = auto.init_state(2, auto.AUTO_THRESHOLD)
    assert "tab" in big and big["tab"].shape == (2, 3, auto.nb)
    # reset_rows zeroing == empty index (0 is the empty-bucket sentinel)
    prop = NgramProposer(cfg, matcher="automaton")
    st = prop.prime(None, prop.init_state(2, 64),
                    jnp.asarray(np.tile([3, 4, 5], 10)[None, :].repeat(2, 0),
                                jnp.int32),
                    None, jnp.asarray([30, 30], jnp.int32), None,
                    jnp.asarray([3, 3], jnp.int32))
    found, _ = _match(prop, st)
    assert bool(found[0])
    st = prop.reset_rows(st, jnp.asarray([False, True]))
    found, _ = _match(prop, st)
    assert not bool(found[0]) and bool(found[1])


def test_ngram_matcher_validation():
    with pytest.raises(ValueError, match="matcher"):
        NgramProposer(_cfg(), matcher="bloom")


# --------------------------------------------- sharded == single-device

_MATRIX_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_config
from repro.configs.base import SamplingParams
from repro.core import medusa as M
from repro.core.engine import build_engine
from repro.distributed.sharding import split_params
from repro.distributed.tp import build_tp_engine, make_tp_mesh
from repro.models.api import get_model, init_cache

base = get_config("qwen1.5-0.5b", reduced=True)
mesh = make_tp_mesh(2)
B, S, NEW, PS = 2, 64, 12, 16

def run(tag, cfg, proposer, accept):
    sampling = SamplingParams(temperature=0.0) if accept == "sample" else None
    model = get_model(cfg)
    params, axes = split_params(model.init_params(jax.random.PRNGKey(0), cfg))
    ref = build_engine(cfg, proposer, accept=accept, sampling=sampling)
    pp = None
    if proposer == "medusa":
        pp, _ = split_params(M.init_medusa(jax.random.PRNGKey(1), cfg,
                                           ref.dtree.K))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (B, S)), jnp.int32)
    plens = jnp.asarray([S, S - 7], jnp.int32)
    nb = (B * (S + NEW + 32)) // PS if cfg.paged else None
    smax = S + NEW + 16
    key = jax.random.PRNGKey(7)
    out_r, n_r, _ = ref.generate(params, pp, toks, plens,
                                 init_cache(cfg, B, smax, n_blocks=nb), NEW,
                                 key=key)
    tpe = build_tp_engine(cfg, mesh, proposer, accept=accept,
                          sampling=sampling)
    sp = tpe.shard_params(params, axes)
    out_t, n_t, _ = tpe.generate(sp, tpe.replicate(pp), tpe.replicate(toks),
                                 tpe.replicate(plens),
                                 tpe.init_cache(B, smax, n_blocks=nb), NEW,
                                 key=tpe.replicate(key))
    np.testing.assert_array_equal(np.asarray(n_r), np.asarray(n_t),
                                  err_msg=tag)
    for b in range(B):
        np.testing.assert_array_equal(np.asarray(out_r)[b, :int(n_r[b])],
                                      np.asarray(out_t)[b, :int(n_t[b])],
                                      err_msg=tag)
    print(tag, "ok")

paged = dataclasses.replace(base, cache_layout="paged", page_size=PS)
pagedq = dataclasses.replace(paged, cache_dtype="int8")
denseq = dataclasses.replace(base, cache_dtype="int8")
ACCEPT = __ACCEPT__
for proposer in ("medusa", "ngram"):
    for lname, cfg in (("dense", base), ("paged", paged),
                       ("dense-int8", denseq), ("paged-int8", pagedq)):
        run(f"{proposer}/{lname}/{ACCEPT}", cfg, proposer, ACCEPT)
print("TP_MATRIX_OK")
"""


def _run_matrix(accept: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    code = _MATRIX_CODE.replace("__ACCEPT__", repr(accept))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert "TP_MATRIX_OK" in out.stdout, \
        out.stdout[-1000:] + out.stderr[-2000:]


@pytest.mark.slow
def test_tp_identity_matrix_greedy():
    """{medusa,ngram} x {dense,paged} x {fp,int8} at tp=2 on the forced
    8-device host mesh: greedy sharded generate must be token-identical to
    the single-device engine."""
    _run_matrix("greedy")


@pytest.mark.slow
def test_tp_identity_matrix_sample_t0():
    """Same matrix under accept=sample at temperature 0 (the t_zero
    one-hot path exercises the §18 cross-shard verify-stats epilogue)."""
    _run_matrix("sample")
