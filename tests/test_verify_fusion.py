"""Fused in-kernel verification (DESIGN.md §15): the decode epilogue that
computes acceptance from in-VMEM statistics must be a drop-in for the
unfused reference.

Four layers of evidence:

* unit: the ``verify_stats`` kernel reproduces the reference statistics
  bitwise in the default single-V-block regime (and within float noise
  across blocks);
* walk differential: every stats-fed verification walk (greedy, tree,
  chain) is Verdict-identical to its logits-fed sibling under a shared
  key, across temperatures including the temp->0 collapse;
* engine differential: fused and unfused engines are token-identical for
  every completion across {medusa, draft, ngram} x {dense, paged} x
  {fp, int8} x {greedy, sample}, plus the Pallas kernel path that also
  fuses qkv+rope+commit; at temperature > 0 the fused engine passes the
  same TVD gate against the sampled AR oracle as the unfused suite;
* property fuzzing (``_hypothesis_stub``): random tree shapes and
  adversarial logits — exact argmax ties, near-one-hot rows, temp->0 —
  preserve the walk invariants (root-connected accepted path, candidates
  along the path, deterministic draws) on both ref and kernel stats.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

from benchmarks.common import max_marginal_tvd as _max_marginal_tvd
from repro.configs.base import SamplingParams
from repro.configs.registry import get_config
from repro.core import medusa as M
from repro.core import verify as V
from repro.core.engine import ar_generate_sampled, build_engine
from repro.core.tree import cartesian_tree, chain_tree
from repro.distributed.sharding import split_params
from repro.kernels import ops as KO
from repro.kernels import ref as KR
from repro.kernels import tree_attention as TA
from repro.models.api import get_model


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_cache():
    # This module runs near the end of the suite; drop the hundreds of
    # executables accumulated by earlier modules before compiling the large
    # verify/engine graphs here (XLA has segfaulted in backend_compile under
    # that pressure on the CI container — standalone runs are unaffected).
    jax.clear_caches()
    yield


# ------------------------------------------------------- unit: stats kernel

def test_verify_stats_kernel_matches_ref_single_block(rng):
    """Default regime (V <= 4096, one V-block): bitwise-equal statistics."""
    B, T, d, Vc = 3, 6, 16, 256
    hidden = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, Vc)), jnp.float32) * 0.3
    cand = jnp.asarray(rng.integers(0, Vc, (B, T)), jnp.int32)
    tmax = jnp.asarray([1.0, 0.7, 1e-6], jnp.float32)
    ref = KR.verify_stats_ref(hidden, w, cand, tmax)
    out = KO.verify_stats(hidden, w, cand, tmax, interpret=True)
    for r, o, name in zip(ref, out, ("argm", "m", "l", "cand_w")):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o), name)


def test_verify_stats_kernel_multi_block_close(rng):
    """Forced multi-block V sweep: argmax/cand_w stay exact (first-wins
    cross-block merge), the online log-sum-exp accumulates ~1 ulp."""
    B, T, d, Vc = 2, 4, 8, 512
    hidden = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, Vc)), jnp.float32) * 0.3
    cand = jnp.asarray(rng.integers(0, Vc, (B, T)), jnp.int32)
    tmax = jnp.ones((B,), jnp.float32)
    argm, m, l, cand_w = KR.verify_stats_ref(hidden, w, cand, tmax)
    out = KO.verify_stats(hidden, w, cand, tmax, block_v=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(argm), np.asarray(out[0]))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(out[1]))
    np.testing.assert_allclose(np.asarray(l), np.asarray(out[2]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cand_w), np.asarray(out[3]))


def test_verify_stats_kernel_ragged_vocab_major(rng):
    """V no multiple of the block (or of 128) with a 128-aligned d: the
    kernel sweeps the head's [V, d] transpose in cdiv(V, block_v) blocks
    and masks the ragged tail — same statistics as the oracle (exact
    argmax, f32-rounding-close values)."""
    B, T, d, Vc = 2, 4, 128, 300
    hidden = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, Vc)), jnp.float32) * 0.3
    cand = jnp.asarray(rng.integers(0, Vc, (B, T)), jnp.int32)
    tmax = jnp.asarray([1.0, 0.7], jnp.float32)
    argm, m, l, cand_w = KR.verify_stats_ref(hidden, w, cand, tmax)
    out = KO.verify_stats(hidden, w, cand, tmax, block_v=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(argm), np.asarray(out[0]))
    np.testing.assert_allclose(np.asarray(m), np.asarray(out[1]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(out[2]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cand_w), np.asarray(out[3]),
                               rtol=1e-5, atol=1e-6)


# (B, T, d, V, block_v, tmax, group): each case runs the kernel with every
# slot in its own group (one head pass per slot) and with `group` slots
# folded into the rows of one matmul.
GROUPING_CASES = {
    "batch_not_multiple_of_group": (3, 8, 16, 256, None, [1.0, 1.0, 1.0], 2),
    "tree_padded_6_to_8": (4, 6, 16, 256, None, [1.0] * 4, 4),
    "ragged_vocab_major": (2, 4, 128, 300, 128, [1.0, 0.7], 2),
    "per_slot_temperatures": (3, 8, 16, 512, 128, [1.0, 0.7, 1e-6], 3),
}


@pytest.mark.parametrize("case", sorted(GROUPING_CASES))
def test_verify_stats_grouping_is_bitwise_neutral(rng, case):
    """Folding G slots into the kernel's rows changes how often the head
    is read, not a statistic: argm, m and cand_w are bitwise those of one
    slot per group, l within the online rescale's 1 ulp."""
    B, T, d, Vc, block_v, temps, group = GROUPING_CASES[case]
    hidden = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, Vc)), jnp.float32) * 0.3
    cand = jnp.asarray(rng.integers(0, Vc, (B, T)), jnp.int32)
    tmax = jnp.asarray(temps, jnp.float32)
    Tp = T + (-T % 8)
    plans = [TA.verify_stats_plan(B, Tp, d, block_v or Vc, jnp.float32,
                                  max_group=g)[0] for g in (1, group)]
    assert plans == [1, group]
    one, grouped = (TA.unembed_verify_stats(
        hidden, w, cand, tmax, block_v=block_v, max_group=g, interpret=True)
        for g in (1, group))
    for i, name in ((0, "argm"), (1, "m"), (3, "cand_w")):
        np.testing.assert_array_equal(np.asarray(one[i]),
                                      np.asarray(grouped[i]), name)
    np.testing.assert_array_max_ulp(np.asarray(one[2]),
                                    np.asarray(grouped[2]), maxulp=1)
    assert one[0].shape == (B, T) and one[3].shape == (B, T, T)


@pytest.mark.parametrize("B", [4, 8])
def test_verify_stats_plan_one_head_pass_at_cell_shapes(B):
    """openpangu-7b's decode step (B slots x a 64-node tree, d 4096,
    V 153,376, bf16): every slot fits one group, so one head pass."""
    G, block_v, need = TA.verify_stats_plan(B, 64, 4096, 512, jnp.bfloat16)
    assert (G, block_v) == (B, 512)
    assert need <= TA.VERIFY_VMEM_LIMIT
    assert TA.lm_head_passes(B, 64, 4096, 153376, jnp.bfloat16) == 1


def test_verify_stats_plan_splits_when_vmem_is_short():
    """32 slots do not fit one group: the plan splits them into even
    groups that fit, and G = 1 stays reachable."""
    G, _, need = TA.verify_stats_plan(32, 64, 4096, 512, jnp.bfloat16)
    assert 1 < G < 32 and 32 % G == 0
    assert need <= TA.VERIFY_VMEM_LIMIT
    assert TA.lm_head_passes(32, 64, 4096, 153376, jnp.bfloat16) == 32 // G
    assert TA.verify_stats_plan(32, 64, 4096, 512, jnp.bfloat16,
                                max_group=1)[0] == 1


# ----------------------------------------------- walk differential (no E2E)

def _stats_and_logits(rng, B, T, Vc, temp):
    """Adversary-free random stats: logits via an identity unembed so the
    stats path sees exactly the same values as the logits path."""
    logits = jnp.asarray(rng.standard_normal((B, T, Vc)), jnp.float32) * 2
    eye = jnp.eye(Vc, dtype=jnp.float32)
    tmax = jnp.full((B,), max(temp, 1e-6), jnp.float32)
    stats = V.VerifyStats(*KR.verify_stats_ref(logits, eye, jnp.zeros(
        (B, T), jnp.int32), tmax))
    return logits, eye, tmax


def _assert_verdicts_equal(a, b):
    acc = np.asarray(a.acc)
    np.testing.assert_array_equal(acc, np.asarray(b.acc))
    np.testing.assert_array_equal(np.asarray(a.next_token),
                                  np.asarray(b.next_token))
    np.testing.assert_array_equal(np.asarray(a.last_slot),
                                  np.asarray(b.last_slot))
    pa, pb = np.asarray(a.path_slots), np.asarray(b.path_slots)
    ta, tb_ = np.asarray(a.path_tokens), np.asarray(b.path_tokens)
    for i in range(acc.shape[0]):
        np.testing.assert_array_equal(pa[i, :acc[i]], pb[i, :acc[i]])
        np.testing.assert_array_equal(ta[i, :acc[i]], tb_[i, :acc[i]])


@pytest.mark.parametrize("temp", [0.0, 0.7, 1.3])
def test_tree_walk_stats_equals_logits_walk(rng, temp):
    tb = cartesian_tree((3, 2))
    dt = V.device_tree(tb)
    B, Vc = 4, 33
    for trial in range(5):
        logits = jnp.asarray(rng.standard_normal((B, dt.T, Vc)),
                             jnp.float32) * 2
        cand = jnp.asarray(rng.integers(0, Vc, (B, dt.T)), jnp.int32)
        mprob = jnp.asarray(rng.random((B, dt.K, dt.max_topk)), jnp.float32)
        tmax = jnp.full((B,), max(temp, 1e-6), jnp.float32)
        stats = V.VerifyStats(*KR.verify_stats_ref(
            logits, jnp.eye(Vc, dtype=jnp.float32), cand, tmax))
        key = jax.random.PRNGKey(100 + trial)
        ref = V.sample_verify_tree(cand, logits, mprob, dt, key,
                                   temperature=temp)
        fused = V.sample_verify_tree_stats(
            cand, stats, mprob, dt, key,
            lambda idx: logits[jnp.arange(B), idx], temperature=temp)
        _assert_verdicts_equal(ref, fused)


@pytest.mark.parametrize("temp", [0.0, 0.7, 1.3])
def test_chain_walk_stats_equals_logits_walk(rng, temp):
    gamma = 3
    dt = V.device_tree(chain_tree(gamma))
    B, Vc = 4, 33
    for trial in range(5):
        logits = jnp.asarray(rng.standard_normal((B, gamma + 1, Vc)),
                             jnp.float32) * 2
        dlog = jnp.asarray(rng.standard_normal((B, gamma, Vc)),
                           jnp.float32) * 2
        cand = jnp.asarray(rng.integers(0, Vc, (B, gamma + 1)), jnp.int32)
        tmax = jnp.full((B,), max(temp, 1e-6), jnp.float32)
        stats = V.VerifyStats(*KR.verify_stats_ref(
            logits, jnp.eye(Vc, dtype=jnp.float32), cand, tmax))
        key = jax.random.PRNGKey(200 + trial)
        ref = V.sample_verify_chain(cand, logits, dlog, dt, key,
                                    temperature=temp)
        fused = V.sample_verify_chain_stats(
            cand, stats, dlog, dt, key,
            lambda idx: logits[jnp.arange(B), idx], temperature=temp)
        _assert_verdicts_equal(ref, fused)


def test_greedy_stats_equals_greedy_verify(rng):
    tb = cartesian_tree((2, 2, 1))
    dt = V.device_tree(tb)
    B, Vc = 4, 64
    logits = jnp.asarray(rng.standard_normal((B, dt.T, Vc)), jnp.float32)
    cand = jnp.asarray(rng.integers(0, Vc, (B, dt.T)), jnp.int32)
    stats = V.VerifyStats(*KR.verify_stats_ref(
        logits, jnp.eye(Vc, dtype=jnp.float32), cand, jnp.ones((B,))))
    ref = V.greedy_verify(cand, logits, dt)
    fused = V.greedy_verify_stats(cand, stats, dt)
    for a, b in zip(ref, fused):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- engine differential

@pytest.fixture(scope="module")
def stack():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    m = get_model(cfg)
    params, _ = split_params(m.init_params(jax.random.PRNGKey(1), cfg))
    return cfg, m, params


def _proposer_params(cfg, m, proposer, eng):
    if proposer == "medusa":
        mp, _ = split_params(M.init_medusa(jax.random.PRNGKey(2), cfg,
                                           eng.tb.K))
        mp["w1"] = jax.random.normal(jax.random.PRNGKey(3), mp["w1"].shape,
                                     mp["w1"].dtype) * 0.1
        return mp
    if proposer == "draft":
        pp, _ = split_params(m.init_params(jax.random.PRNGKey(2),
                                           eng.proposer.dc))
        return pp
    return None


@pytest.mark.parametrize("layout,cdtype", [
    ("dense", ""), ("dense", "int8"), ("paged", ""), ("paged", "int8")])
@pytest.mark.parametrize("proposer,accept", [
    ("medusa", "greedy"), ("medusa", "sample"),
    ("draft", "greedy"), ("draft", "sample"),
    ("ngram", "greedy"), ("ngram", "sample")])
def test_fused_engine_token_identical(stack, proposer, accept, layout,
                                      cdtype):
    """The full §15 matrix: for every proposer x layout x cache dtype x
    verification mode, the fused engine reproduces the unfused engine's
    completions token for token (same key, same steps)."""
    cfg0, m0, params0 = stack
    cfg = dataclasses.replace(cfg0, cache_layout=layout, cache_dtype=cdtype,
                              page_size=16)
    m = get_model(cfg)
    sp = (SamplingParams(temperature=0.7) if accept == "sample" else None)
    tb = cartesian_tree((2, 2)) if proposer == "medusa" else None
    B, SP, NEW = 2, 8, 8
    toks = jax.random.randint(jax.random.PRNGKey(0), (B, SP), 0,
                              cfg.vocab_size)
    lens = jnp.full((B,), SP, jnp.int32)
    smax = SP + NEW + 16
    res = {}
    for vf in (False, True):
        eng = build_engine(cfg, proposer, tb=tb, gamma=3, accept=accept,
                           sampling=sp, verify_fusion=vf)
        pp = _proposer_params(cfg, m, proposer, eng)
        out, n_out, stats = eng.generate(params0, pp, toks, lens,
                                         m.init_cache(cfg, B, smax), NEW,
                                         key=jax.random.PRNGKey(7))
        res[vf] = (np.asarray(out), np.asarray(n_out), int(stats.steps))
    np.testing.assert_array_equal(res[False][0], res[True][0])
    np.testing.assert_array_equal(res[False][1], res[True][1])
    assert res[False][2] == res[True][2]


@pytest.mark.parametrize("accept", ["greedy", "sample"])
def test_fused_kernel_path_token_identical(stack, accept):
    """use_kernel=True additionally routes the decode step through the
    Pallas tree-attention kernel and the fused qkv+rope+commit kernel
    (fp cache): still token-identical to the unfused engine."""
    cfg, m, params = stack
    sp = (SamplingParams(temperature=0.7) if accept == "sample" else None)
    tb = cartesian_tree((2, 2))
    B, SP, NEW = 2, 8, 8
    toks = jax.random.randint(jax.random.PRNGKey(0), (B, SP), 0,
                              cfg.vocab_size)
    lens = jnp.full((B,), SP, jnp.int32)
    smax = SP + NEW + 16
    res = {}
    for vf in (False, True):
        eng = build_engine(cfg, "medusa", tb=tb, accept=accept, sampling=sp,
                           use_kernel=vf, verify_fusion=vf)
        pp = _proposer_params(cfg, m, "medusa", eng)
        out, n_out, _ = eng.generate(params, pp, toks, lens,
                                     m.init_cache(cfg, B, smax), NEW,
                                     key=jax.random.PRNGKey(7))
        res[vf] = (np.asarray(out), np.asarray(n_out))
    np.testing.assert_array_equal(res[False][0], res[True][0])
    np.testing.assert_array_equal(res[False][1], res[True][1])


def test_fused_sampled_distribution_matches_ar_sampled():
    """The §11 TVD gate survives fusion: fused sampled tree decoding on a
    tiny vocab matches the sampled AR oracle within the AR-vs-AR noise
    floor."""
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b", reduced=True),
                              vocab_size=16, num_layers=2)
    m = get_model(cfg)
    params, _ = split_params(m.init_params(jax.random.PRNGKey(1), cfg))
    tb = cartesian_tree((2, 2))
    B, SP, NEW = 1024, 4, 5
    prompt = jax.random.randint(jax.random.PRNGKey(0), (1, SP), 0,
                                cfg.vocab_size)
    toks = jnp.broadcast_to(prompt, (B, SP))
    lens = jnp.full((B,), SP, jnp.int32)
    smax = SP + NEW + tb.T + 8
    sp = SamplingParams(temperature=0.9)
    eng = build_engine(cfg, "medusa", tb=tb, accept="sample", sampling=sp,
                       verify_fusion=True)
    mp = _proposer_params(cfg, m, "medusa", eng)
    spec, n_out, _ = eng.generate(params, mp, toks, lens,
                                  m.init_cache(cfg, B, smax), NEW,
                                  key=jax.random.PRNGKey(21))
    assert (np.asarray(n_out) == NEW).all()
    ar1, _ = ar_generate_sampled(cfg, params, toks, lens,
                                 m.init_cache(cfg, B, smax), NEW,
                                 jax.random.PRNGKey(22), sp)
    ar2, _ = ar_generate_sampled(cfg, params, toks, lens,
                                 m.init_cache(cfg, B, smax), NEW,
                                 jax.random.PRNGKey(23), sp)
    floor = _max_marginal_tvd(np.asarray(ar1), np.asarray(ar2),
                              cfg.vocab_size)
    tvd = _max_marginal_tvd(np.asarray(spec), np.asarray(ar1),
                            cfg.vocab_size)
    assert tvd <= 1.5 * floor + 0.05, (tvd, floor)


# ----------------------------------------------------- construction guards

def test_fusion_rejects_typical_verify(stack):
    cfg, _, _ = stack
    with pytest.raises(ValueError):
        build_engine(cfg, "medusa", tb=cartesian_tree((2, 2)),
                     accept="typical", verify_fusion=True)


def test_fusion_rejects_truncated_sampling(stack):
    cfg, _, _ = stack
    for sp in (SamplingParams(temperature=0.7, top_k=5),
               SamplingParams(temperature=0.7, top_p=0.9)):
        with pytest.raises(ValueError):
            build_engine(cfg, "medusa", tb=cartesian_tree((2, 2)),
                         accept="sample", sampling=sp, verify_fusion=True)


def test_scheduler_rejects_per_request_top_p_under_fusion(stack):
    from repro.serving.scheduler import MedusaServer
    cfg, m, params = stack
    eng = build_engine(cfg, "medusa", tb=cartesian_tree((2, 2)),
                       accept="sample",
                       sampling=SamplingParams(temperature=0.7),
                       verify_fusion=True)
    mp = _proposer_params(cfg, m, "medusa", eng)
    srv = MedusaServer(eng, params, mp, batch_slots=2, max_len=64)
    prompt = np.arange(5, dtype=np.int32)
    with pytest.raises(ValueError):
        srv.submit(prompt, max_new=4, top_p=0.9)
    # top_p=1.0 stays accepted
    rid = srv.submit(prompt, max_new=4, top_p=1.0)
    srv.run()
    assert srv.result(rid).status == "done"


# ------------------------------------------------------ property fuzzing

def _adversarial_logits(rng, B, T, Vc):
    """Random logits with injected argmax ties, near-one-hot rows and a
    huge-scale row — the cases where fused/unfused could round apart."""
    logits = rng.standard_normal((B, T, Vc)).astype(np.float32) * 3
    logits[0, :, 1] = logits[0].max(-1)            # exact tie with the max
    logits[0, :, 0] = logits[0, :, 1]
    if B > 1:
        logits[1] = -1e9                           # near-one-hot rows
        logits[1, :, rng.integers(0, Vc)] = 0.0
    if B > 2:
        logits[2] *= 30.0                          # extreme scale
    return logits


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10**6),
       st.lists(st.integers(1, 3), min_size=1, max_size=3))
def test_fuzz_tree_walk_invariants(seed, topk):
    """Random DeviceTree shapes x adversarial logits: the stats walk equals
    the logits walk (ref AND kernel stats), the accepted path is
    root-connected through ``tb.parent`` and carries the candidate tokens,
    and draws are deterministic under a fixed key."""
    rng = np.random.default_rng(seed)
    tb = cartesian_tree(tuple(topk))
    dt = V.device_tree(tb)
    B, Vc = 3, 33
    logits = jnp.asarray(_adversarial_logits(rng, B, dt.T, Vc))
    cand = rng.integers(0, Vc, (B, dt.T)).astype(np.int32)
    cand[0] = np.asarray(jnp.argmax(logits[0], -1))   # force deep accepts
    cand = jnp.asarray(cand)
    mprob = jnp.asarray(rng.random((B, dt.K, dt.max_topk)), jnp.float32)
    eye = jnp.eye(Vc, dtype=jnp.float32)
    for temp in (1e-4, 0.9):
        tmax = jnp.full((B,), max(temp, 1e-6), jnp.float32)
        stats = V.VerifyStats(*KR.verify_stats_ref(logits, eye, cand, tmax))
        kstats = V.VerifyStats(*KO.verify_stats(logits, eye, cand, tmax,
                                                interpret=True))
        # argm/m/cand_w are bitwise; l may drift ~1 ulp on adversarial
        # inputs (online-sumexp accumulation order differs in the kernel).
        np.testing.assert_array_equal(np.asarray(stats.argm),
                                      np.asarray(kstats.argm))
        np.testing.assert_array_equal(np.asarray(stats.m),
                                      np.asarray(kstats.m))
        np.testing.assert_allclose(np.asarray(stats.l),
                                   np.asarray(kstats.l), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(stats.cand_w),
                                      np.asarray(kstats.cand_w))
        key = jax.random.PRNGKey(seed % 997)
        row_fn = lambda idx: logits[jnp.arange(B), idx]
        ref = V.sample_verify_tree(cand, logits, mprob, dt, key,
                                   temperature=temp)
        fused = V.sample_verify_tree_stats(cand, stats, mprob, dt, key,
                                           row_fn, temperature=temp)
        again = V.sample_verify_tree_stats(cand, stats, mprob, dt, key,
                                           row_fn, temperature=temp)
        _assert_verdicts_equal(ref, fused)
        _assert_verdicts_equal(fused, again)          # deterministic draws
        acc = np.asarray(fused.acc)
        slots = np.asarray(fused.path_slots)
        ptoks = np.asarray(fused.path_tokens)
        nxt = np.asarray(fused.next_token)
        cnp = np.asarray(cand)
        for b in range(B):
            assert 1 <= acc[b] <= int(tb.depths.max()) + 1
            assert slots[b, 0] == 0                   # rooted
            for i in range(1, acc[b]):                # parent-chained
                assert tb.parent[slots[b, i]] == slots[b, i - 1]
                assert ptoks[b, i] == cnp[b, slots[b, i]]
            assert 0 <= nxt[b] < Vc
        # greedy on the same stats: the bonus/resample token is always the
        # target argmax at the last accepted node (full accept included)
        g = V.greedy_verify_stats(cand, stats, dt)
        gl = np.asarray(g.last_slot)
        gn = np.asarray(g.next_token)
        am = np.asarray(stats.argm)
        for b in range(B):
            assert gn[b] == am[b, gl[b]]


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 4))
def test_fuzz_chain_walk_invariants(seed, gamma):
    """Chain-shaped fuzzing: adversarial target AND draft logits, stats
    walk == logits walk, accepted prefix carries the drafted tokens."""
    rng = np.random.default_rng(seed)
    dt = V.device_tree(chain_tree(gamma))
    B, Vc = 3, 33
    logits = jnp.asarray(_adversarial_logits(rng, B, gamma + 1, Vc))
    dlog = jnp.asarray(_adversarial_logits(rng, B, gamma, Vc))
    cand = rng.integers(0, Vc, (B, gamma + 1)).astype(np.int32)
    cand[0] = np.asarray(jnp.argmax(logits[0], -1))
    cand = jnp.asarray(cand)
    eye = jnp.eye(Vc, dtype=jnp.float32)
    for temp in (1e-4, 0.9):
        tmax = jnp.full((B,), max(temp, 1e-6), jnp.float32)
        stats = V.VerifyStats(*KR.verify_stats_ref(logits, eye, cand, tmax))
        key = jax.random.PRNGKey(seed % 991)
        ref = V.sample_verify_chain(cand, logits, dlog, dt, key,
                                    temperature=temp)
        fused = V.sample_verify_chain_stats(
            cand, stats, dlog, dt, key,
            lambda idx: logits[jnp.arange(B), idx], temperature=temp)
        _assert_verdicts_equal(ref, fused)
        acc = np.asarray(fused.acc)
        ptoks = np.asarray(fused.path_tokens)
        cnp = np.asarray(cand)
        for b in range(B):
            assert 1 <= acc[b] <= gamma + 1
            np.testing.assert_array_equal(ptoks[b, :acc[b]],
                                          cnp[b, :acc[b]])
