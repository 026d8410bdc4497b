"""Pluggable Proposer/Verifier core (DESIGN.md §13).

Three layers of protection for the refactor:

* **identity matrix** — every proposer x {dense, paged} x {fp, int8} x
  {greedy, sample@temp0} is token-identical to greedy AR (the paper's
  losslessness invariant, now quantified over the proposer seam);
* **golden tokens** — the refactored engines reproduce the *pre-refactor*
  engines' exact token streams (``tests/golden/proposer_goldens.npz``,
  captured at the commit before the refactor) for greedy, sampled and
  typical acceptance across every cache layout;
* **unit + serving coverage** — the n-gram lookup/append math on
  handcrafted histories, proposer-state merging through scheduler v2
  batched admission, and the end-to-end n-gram serve under paged cache +
  ``accept="sample"``.
"""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SamplingParams
from repro.configs.registry import get_config
from repro.core import medusa as M
from repro.core.draft_model import DraftSpecEngine
from repro.core.engine import SpecEngine, ar_generate, build_engine
from repro.core.proposers import (DraftModelProposer, NgramProposer,
                                  make_proposer)
from repro.core.tree import cartesian_tree
from repro.distributed.sharding import split_params
from repro.models.api import get_model, init_cache
from repro.serving.scheduler import SpecServer

B, SP, NEW = 2, 8, 16
GOLDEN = pathlib.Path(__file__).parent / "golden" / "proposer_goldens.npz"


@pytest.fixture(scope="module")
def stack():
    """Shared tiny stack: target params, Medusa heads, a 2-layer draft."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = get_model(cfg)
    params, _ = split_params(model.init_params(jax.random.PRNGKey(1), cfg))
    tb = cartesian_tree((3, 2))
    mp, _ = split_params(M.init_medusa(jax.random.PRNGKey(2), cfg, tb.K))
    mp["w1"] = jax.random.normal(jax.random.PRNGKey(3), mp["w1"].shape,
                                 mp["w1"].dtype) * 0.1
    dcfg = dataclasses.replace(cfg, num_layers=2, name="draft")
    dparams, _ = split_params(model.init_params(jax.random.PRNGKey(4), dcfg))
    toks = jax.random.randint(jax.random.PRNGKey(0), (B, SP), 0,
                              cfg.vocab_size)
    lens = jnp.full((B,), SP, jnp.int32)
    return cfg, model, params, tb, mp, dcfg, dparams, toks, lens


def _variant(cfg, layout, dtype):
    over = {}
    if layout == "paged":
        over.update(cache_layout="paged", page_size=8)
    if dtype == "int8":
        over.update(cache_dtype="int8")
    return dataclasses.replace(cfg, **over) if over else cfg


def _cache(c, batch, smax):
    # engine-level paged caches use the allocator-free identity table
    # (n_blocks=None); explicit n_blocks is scheduler territory (zero
    # tables, writes sunk to trash until admission maps real blocks)
    return init_cache(c, batch, smax)


# ---------------------------------------------------------------------------
# identity matrix: proposer x layout x dtype x accept  ==  greedy AR
# ---------------------------------------------------------------------------

_AR = {}


def _ar(c, params, toks, lens, smax):
    key = (c.cache_layout, c.resolved_cache_dtype)
    if key not in _AR:
        out, _ = ar_generate(c, params, toks, lens, _cache(c, B, smax), NEW)
        _AR[key] = np.asarray(out)
    return _AR[key]


@pytest.mark.parametrize("accept", ["greedy", "sample"])
@pytest.mark.parametrize("dtype", ["fp", "int8"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("kind", ["medusa", "draft", "ngram"])
def test_identity_matrix(stack, kind, layout, dtype, accept):
    """Greedy == AR, and sample@temp0 collapses to greedy == AR, for every
    proposer on every cache layout/dtype (the §13 losslessness matrix)."""
    cfg, model, params, tb, mp, dcfg, dparams, toks, lens = stack
    c = _variant(cfg, layout, dtype)
    smax = SP + NEW + tb.T + 8
    ar = _ar(c, params, toks, lens, smax)
    sampling = SamplingParams(temperature=0.0) if accept == "sample" else None
    eng = build_engine(c, kind, tb=tb if kind == "medusa" else None,
                       draft_cfg=dataclasses.replace(dcfg) if kind == "draft"
                       else None, gamma=3, accept=accept, sampling=sampling)
    pp = {"medusa": mp, "draft": dparams, "ngram": None}[kind]
    out, n_out, stats = eng.generate(params, pp, toks, lens,
                                     _cache(c, B, smax), NEW,
                                     key=jax.random.PRNGKey(11))
    np.testing.assert_array_equal(np.asarray(out), ar)
    assert (np.asarray(n_out) == NEW).all()
    assert int(stats.steps) <= NEW


# ---------------------------------------------------------------------------
# golden tokens: refactored engines == pre-refactor engines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("suffix,layout,dtype", [
    ("dense_fp", "dense", "fp"), ("dense_int8", "dense", "int8"),
    ("paged_fp", "paged", "fp"), ("paged_int8", "paged", "int8")])
def test_golden_tokens_medusa(stack, suffix, layout, dtype):
    """The generic engine + MedusaProposer reproduces the pre-refactor
    ``SpecEngine`` token for token (greedy, sampled and typical acceptance;
    goldens captured at the commit before the refactor)."""
    cfg, model, params, tb, mp, dcfg, dparams, toks, lens = stack
    g = np.load(GOLDEN)
    np.testing.assert_array_equal(np.asarray(toks), g["prompt"])
    c = _variant(cfg, layout, dtype)
    smax = SP + NEW + tb.T + 8
    key = jax.random.PRNGKey(7)
    sp = SamplingParams(temperature=0.8)
    out, _, _ = SpecEngine(c, tb).generate(params, mp, toks, lens,
                                           _cache(c, B, smax), NEW, key=key)
    np.testing.assert_array_equal(np.asarray(out),
                                  g[f"medusa_greedy_{suffix}"])
    out, _, _ = SpecEngine(c, tb, accept="sample", sampling=sp).generate(
        params, mp, toks, lens, _cache(c, B, smax), NEW, key=key)
    np.testing.assert_array_equal(np.asarray(out),
                                  g[f"medusa_sample_{suffix}"])
    out, _, _ = SpecEngine(c, tb, accept="typical", temperature=0.8).generate(
        params, mp, toks, lens, _cache(c, B, smax), NEW, key=key)
    np.testing.assert_array_equal(np.asarray(out),
                                  g[f"medusa_typical_{suffix}"])


@pytest.mark.parametrize("suffix,layout,dtype", [
    ("dense_fp", "dense", "fp"), ("dense_int8", "dense", "int8"),
    ("paged_fp", "paged", "fp"), ("paged_int8", "paged", "int8")])
def test_golden_tokens_draft(stack, suffix, layout, dtype):
    """``DraftSpecEngine`` (now a shell over the generic engine +
    ``DraftModelProposer``) reproduces the pre-refactor fused engine's
    greedy and sampled token streams — including the PRNG split order the
    sampled chain depends on."""
    cfg, model, params, tb, mp, dcfg, dparams, toks, lens = stack
    g = np.load(GOLDEN)
    c = _variant(cfg, layout, dtype)
    smax = SP + NEW + tb.T + 8
    key = jax.random.PRNGKey(7)
    out, _, _ = DraftSpecEngine(c, dcfg, gamma=3).generate(
        params, dparams, toks, lens, _cache(c, B, smax),
        init_cache(dcfg, B, smax), NEW, key=key)
    np.testing.assert_array_equal(np.asarray(out),
                                  g[f"draft_greedy_{suffix}"])
    out, _, _ = DraftSpecEngine(
        c, dcfg, gamma=3, accept="sample",
        sampling=SamplingParams(temperature=0.8)).generate(
        params, dparams, toks, lens, _cache(c, B, smax),
        init_cache(dcfg, B, smax), NEW, key=key)
    np.testing.assert_array_equal(np.asarray(out),
                                  g[f"draft_sample_{suffix}"])


# ---------------------------------------------------------------------------
# Medusa top-k: the k-pass selection == a full top-k and softmax
# ---------------------------------------------------------------------------

def _full_topk(logits, k):
    """The reference: ``lax.top_k`` over the vocabulary, and the softmax
    over it gathered at those ids."""
    x = logits.astype(jnp.float32)
    idx = jax.lax.top_k(x, k)[1]
    return idx, jnp.take_along_axis(jax.nn.softmax(x, -1), idx, -1)


@pytest.mark.parametrize("k,heads,batch,vocab,dtype", [
    (1, 1, 1, 1000, "bfloat16"), (3, 4, 8, 1000, "bfloat16"),
    (10, 4, 8, 1000, "bfloat16"), (10, 2, 1, 4099, "bfloat16"),
    (10, 4, 8, 1000, "float32")])
def test_medusa_topk_matches_full_topk(k, heads, batch, vocab, dtype):
    """Same ids as ``lax.top_k``, ties broken toward the lower id, and the
    same probabilities to within 1 ulp.  Zero resblocks and one-hot hidden
    states make each head's logits exactly rows of its ``lm``: integers in
    [-6, 6], so ties are everywhere, and one row whose maximum sits at
    scattered ids."""
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(k * 1000 + vocab)
    lm = np.round(rng.normal(size=(heads, batch, vocab)) * 2).clip(-6, 6)
    lm[0, 0, rng.choice(vocab, 2 * k, replace=False)] = 9.0
    mp = {"w1": jnp.zeros((heads, batch, batch), dt),
          "b1": jnp.zeros((heads, batch), dt), "lm": jnp.asarray(lm, dt)}
    hidden = jnp.eye(batch, dtype=dt)
    idx, probs = jax.jit(M.medusa_topk, static_argnums=2)(mp, hidden, k)
    ridx, rprobs = _full_topk(M.medusa_logits(mp, hidden), k)
    assert idx.dtype == jnp.int32 and probs.dtype == jnp.float32
    assert idx.shape == probs.shape == (heads, batch, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    ulps = np.abs(np.asarray(probs).view(np.int32)
                  - np.asarray(rprobs).view(np.int32))
    assert ulps.max() <= 1, ulps.max()


# ---------------------------------------------------------------------------
# n-gram proposer units
# ---------------------------------------------------------------------------

def test_ngram_propose_matches_longest_most_recent():
    """Longest n wins; among equal-n matches the most recent occurrence
    wins; the history's own suffix never matches itself."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    p = NgramProposer(cfg, gamma=3, max_n=2, min_n=1)
    hist = np.zeros((2, 16), np.int32)
    # row 0: [1 2 3 1 2 9 1 2] -> suffix bigram (1,2); matches at s=0 and
    # s=3 (s=6 is the suffix itself, excluded by s+n <= hlen-1); most
    # recent wins -> s=3, continuation hist[5:8] = [9, 1, 2]
    hist[0, :8] = [1, 2, 3, 1, 2, 9, 1, 2]
    # row 1: [9 8 6 5 6] -> no earlier bigram (5,6); falls back to the
    # unigram 6 at s=2 (s=4 is the suffix), continuation hist[3:6] with
    # position 5 >= hlen masked to the zero token -> [5, 6, 0]
    hist[1, :5] = [9, 8, 6, 5, 6]
    state = {"hist": jnp.asarray(hist),
             "hlen": jnp.asarray([8, 5], jnp.int32)}
    base = jnp.asarray([2, 6], jnp.int32)   # == hist[:, hlen-1]
    cand, q, _ = p.propose(None, state, base, jax.random.PRNGKey(0),
                           1.0, 0, 1.0, stochastic=False)
    np.testing.assert_array_equal(np.asarray(cand[0]), [2, 9, 1, 2])
    np.testing.assert_array_equal(np.asarray(cand[1]), [6, 5, 6, 0])
    assert q.shape == (2, 3, 1) and float(q.min()) == 1.0


def test_ngram_propose_no_match_and_short_history():
    """Rows without any match (or with history shorter than min_n + 1)
    propose the zero token — garbage that verification rejects."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    p = NgramProposer(cfg, gamma=2, max_n=3, min_n=2)
    hist = np.zeros((2, 8), np.int32)
    hist[0, :4] = [1, 2, 3, 4]        # suffix (3,4) appears once only
    hist[1, :1] = [5]                  # history of length 1 < min_n + 1
    state = {"hist": jnp.asarray(hist),
             "hlen": jnp.asarray([4, 1], jnp.int32)}
    base = jnp.asarray([4, 5], jnp.int32)
    cand, _, _ = p.propose(None, state, base, jax.random.PRNGKey(0),
                           1.0, 0, 1.0, stochastic=False)
    np.testing.assert_array_equal(np.asarray(cand),
                                  [[4, 0, 0], [5, 0, 0]])


def test_ngram_observe_appends_accepted_path():
    """observe() appends path_tokens[1:acc] + next_token (acc tokens) and
    the garbage slots beyond the claim are overwritten by the next append
    before they become readable."""
    from repro.core.verify import Verdict
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    p = NgramProposer(cfg, gamma=2)     # K1 = 3
    state = p.init_state(1, 12)
    state = p.prime(None, state, jnp.asarray([[7, 8]], jnp.int32),
                    jnp.asarray([2], jnp.int32), jnp.asarray([2], jnp.int32),
                    jnp.zeros((1, 4)), jnp.asarray([9], jnp.int32))
    np.testing.assert_array_equal(np.asarray(state["hist"][0, :3]), [7, 8, 9])
    assert int(state["hlen"][0]) == 3
    v = Verdict(acc=jnp.asarray([2], jnp.int32),
                path_slots=jnp.zeros((1, 3), jnp.int32),
                path_tokens=jnp.asarray([[9, 4, 99]], jnp.int32),
                next_token=jnp.asarray([5], jnp.int32),
                last_slot=jnp.zeros((1,), jnp.int32))
    state = p.observe(None, state, v, None, None)
    # appended: path_tokens[1] = 4, then next_token = 5
    np.testing.assert_array_equal(np.asarray(state["hist"][0, :5]),
                                  [7, 8, 9, 4, 5])
    assert int(state["hlen"][0]) == 5
    # second step overwrites the garbage 99 that landed beyond the claim
    v2 = Verdict(acc=jnp.asarray([1], jnp.int32),
                 path_slots=jnp.zeros((1, 3), jnp.int32),
                 path_tokens=jnp.asarray([[5, 88, 88]], jnp.int32),
                 next_token=jnp.asarray([6], jnp.int32),
                 last_slot=jnp.zeros((1,), jnp.int32))
    state = p.observe(None, state, v2, None, None)
    np.testing.assert_array_equal(np.asarray(state["hist"][0, :6]),
                                  [7, 8, 9, 4, 5, 6])
    assert int(state["hlen"][0]) == 6


def test_ngram_lossless_on_self_repeating_prompt():
    """A prompt built from repeated segments maximises n-gram matches
    (every suffix recurs), so lots of proposals get verified — and the
    output must still be exactly the greedy AR continuation: garbage or
    genuine, proposals can only shorten accepted paths, never change
    tokens."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = get_model(cfg)
    params, _ = split_params(model.init_params(jax.random.PRNGKey(1), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(0), (1, SP), 0,
                              cfg.vocab_size)
    lens = jnp.full((1,), SP, jnp.int32)
    ar, _ = ar_generate(cfg, params, toks, lens, init_cache(cfg, 1, 64), 12)
    big = jnp.concatenate([toks, ar[:, :8], toks, ar[:, :8]], axis=1)
    blens = jnp.full((1,), big.shape[1], jnp.int32)
    smax = big.shape[1] + 12 + 16
    ar2, _ = ar_generate(cfg, params, big, blens, init_cache(cfg, 1, smax), 12)
    eng = build_engine(cfg, "ngram", gamma=4)
    out, _, stats = eng.generate(params, None, big, blens,
                                 init_cache(cfg, 1, smax), 12)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ar2))


# ---------------------------------------------------------------------------
# construction / protocol guards
# ---------------------------------------------------------------------------

def test_make_proposer_validation():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    with pytest.raises(ValueError, match="unknown proposer"):
        make_proposer("eagle", cfg)
    with pytest.raises(ValueError, match="draft_cfg"):
        make_proposer("draft", cfg)
    with pytest.raises(ValueError, match="min_n"):
        NgramProposer(cfg, max_n=1, min_n=2)
    with pytest.raises(AssertionError):
        DraftModelProposer(cfg, dataclasses.replace(
            cfg, vocab_size=cfg.vocab_size + 1))
    with pytest.raises(ValueError, match="not both"):
        SpecEngine(cfg, tb=cartesian_tree((2,)),
                   proposer=NgramProposer(cfg))


def test_build_engine_derives_draft_sibling():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    eng = build_engine(cfg, "draft", draft_layers=2, gamma=5)
    assert isinstance(eng.proposer, DraftModelProposer)
    assert eng.proposer.dc.num_layers == 2
    assert eng.dtree.K == 5 and eng.tb.is_chain


def test_prefix_cache_rejects_suffixless_proposer():
    """The draft proposer cannot be primed from a prompt suffix, so the
    scheduler refuses to pair it with the prefix cache (DESIGN.md §13)."""
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b", reduced=True),
                              cache_layout="paged", page_size=8)
    model = get_model(cfg)
    params, _ = split_params(model.init_params(jax.random.PRNGKey(1), cfg))
    eng = build_engine(cfg, "draft", gamma=3)
    with pytest.raises(ValueError, match="primed from a prompt suffix"):
        SpecServer(eng, params, None, batch_slots=2, max_len=96,
                   prefix_cache=True)


# ---------------------------------------------------------------------------
# serving: proposer state through scheduler v2
# ---------------------------------------------------------------------------

def _serve(eng, params, pp, prompts, max_new, **kw):
    srv = SpecServer(eng, params, pp, batch_slots=2, max_len=128, **kw)
    rids = [srv.submit(p, max_new=max_new) for p in prompts]
    srv.run()
    return [srv.result(r) for r in rids], srv


def test_ngram_serves_paged_sample_end_to_end(stack):
    """The ISSUE acceptance path: NgramProposer under scheduler v2 batched
    admission, paged cache, ``accept="sample"`` — and at temperature 0 the
    sampled server reproduces the greedy server token for token."""
    cfg, model, params, tb, mp, dcfg, dparams, toks, lens = stack
    c = _variant(cfg, "paged", "fp")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, c.vocab_size, size=n).astype(np.int32)
               for n in (5, 11, 17, 8)]
    greedy, _ = _serve(build_engine(c, "ngram", gamma=3), params, None,
                       prompts, 10)
    sampled, srv = _serve(
        build_engine(c, "ngram", gamma=3, accept="sample",
                     sampling=SamplingParams(temperature=0.0)),
        params, None, prompts, 10)
    assert all(r.status == "done" for r in greedy + sampled)
    for g, s in zip(greedy, sampled):
        assert g.output == s.output
    # and against the per-prompt AR baseline
    for pr, r in zip(prompts, greedy):
        t = jnp.asarray(pr[None, :])
        ar, _ = ar_generate(c, params, t,
                            jnp.asarray([len(pr)], jnp.int32),
                            _cache(c, 1, 128), 10)
        assert r.output == list(np.asarray(ar[0]))


@pytest.mark.parametrize("kind", ["draft", "ngram"])
def test_proposer_state_survives_batched_admission(stack, kind):
    """Batched group admission merges proposer state (draft KV cache /
    n-gram history) into slots exactly like the target cache: serving
    output == single-request AR output for every request."""
    cfg, model, params, tb, mp, dcfg, dparams, toks, lens = stack
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 13, 9)]
    eng = build_engine(cfg, kind, draft_cfg=dcfg if kind == "draft" else None,
                       gamma=3)
    pp = dparams if kind == "draft" else None
    got, _ = _serve(eng, params, pp, prompts, 9, admission="batched")
    assert all(r.status == "done" for r in got)
    for pr, r in zip(prompts, got):
        t = jnp.asarray(pr[None, :])
        ar, _ = ar_generate(cfg, params, t,
                            jnp.asarray([len(pr)], jnp.int32),
                            init_cache(cfg, 1, 128), 9)
        assert r.output == list(np.asarray(ar[0]))


def test_draft_proposer_serves_paged_target(stack):
    """Regression (review finding): a paged *target* with the draft
    proposer must serve — the draft's own cache is forced dense (pool-form
    leaves have no per-slot axis for the admission merge), while the
    target cache pages normally."""
    cfg, model, params, tb, mp, dcfg, dparams, toks, lens = stack
    c = _variant(cfg, "paged", "fp")
    eng = build_engine(c, "draft", gamma=3)   # draft_cfg derived from c
    assert not eng.proposer.dc.paged          # coerced dense
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, c.vocab_size, size=n).astype(np.int32)
               for n in (7, 12)]
    dp, _ = split_params(model.init_params(jax.random.PRNGKey(4),
                                           eng.proposer.dc))
    got, _ = _serve(eng, params, dp, prompts, 8)
    assert all(r.status == "done" for r in got)
    for pr, r in zip(prompts, got):
        t = jnp.asarray(pr[None, :])
        ar, _ = ar_generate(c, params, t, jnp.asarray([len(pr)], jnp.int32),
                            _cache(c, 1, 128), 8)
        assert r.output == list(np.asarray(ar[0]))
