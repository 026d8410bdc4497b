"""The benchmark's plain float32 reference and its seeded weights, against
the program at a small size on the CPU: the program's prefill followed by
cached decode gives the reference's logits, and the whole-model init and a
per-layer redraw give the same bits."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check as C  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.references import dense_gqa as R  # noqa: E402

SEED = 2**32 + 77


def small_conf(dtype="float32", layers=2):
    conf = json.loads(
        (ROOT / "bench" / "configs" / "openpangu-7b-pp2.json").read_text())
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, intermediate_size=128, vocab_size=256,
                num_hidden_layers=layers, torch_dtype=dtype)
    return conf


def program_cfg(conf):
    from bench.model import program_config
    return program_config(conf)


def test_tree_matches_the_program_layout():
    from repro.distributed.sharding import split_params
    from repro.models.api import get_model
    conf = small_conf("bfloat16")
    cfg = program_cfg(conf)
    want = jax.eval_shape(lambda k: split_params(get_model(cfg).init_params(
        k, cfg, dtype=cfg.dtype))[0], jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: W.program_tree(conf, SEED))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_per_layer_redraw_is_bit_identical():
    conf = small_conf("bfloat16", layers=3)
    tree = W.program_tree(conf, SEED)
    key = W.base_key(SEED)
    unit = tree["units"]["pos0"]
    for layer in range(3):
        w = W.layer_weights(conf, key, layer)
        pairs = [(unit["attn"][k], w[k]) for k in ("wq", "wk", "wv", "wo")]
        pairs += [(unit["ffn"]["wi"], w["wi"]), (unit["ffn"]["wg"], w["wg"]),
                  (unit["ffn"]["wo"], w["wo_ff"]),
                  (unit["norm1"]["w"], w["norm1"])]
        for stacked, one in pairs:
            assert np.array_equal(np.asarray(stacked[layer]), np.asarray(one))
    g = W.global_weights(conf, key)
    assert np.array_equal(np.asarray(tree["lm_head"]), np.asarray(g["lm_head"]))
    other = W.program_tree(conf, SEED + 1)
    assert not np.array_equal(np.asarray(tree["embed"]),
                              np.asarray(other["embed"]))


def test_weight_statistics():
    conf = small_conf()
    w = W.layer_weights(conf, W.base_key(SEED), 0)
    assert float(jnp.std(w["wi"])) == pytest.approx(64 ** -0.5, rel=0.05)
    assert float(jnp.mean(w["norm1"])) == pytest.approx(1.0, abs=0.02)
    assert float(jnp.max(jnp.abs(w["norm1"] - 1))) <= 0.125


def test_reference_matches_prefill_then_cached_decode():
    """Prefill 12 tokens, then decode 6 tokens one at a time through the
    program's cache; the reference's full forward gives the same logits."""
    from repro.models.api import get_model, init_cache
    conf = small_conf()
    cfg = program_cfg(conf)
    model = get_model(cfg)
    params = W.program_tree(conf, SEED)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 12).astype(np.int32)
    follow = rng.integers(0, 256, 6).astype(np.int32)
    cache = init_cache(cfg, 1, 32)
    last, cache = model.prefill(params, cfg, jnp.asarray(prompt)[None],
                                jnp.asarray([12], jnp.int32), cache)
    got = [model.unembed(params, cfg, last)[0]]
    one = jnp.ones((1, 1), bool)
    zero = jnp.zeros((1,), jnp.int32)
    for i, tok in enumerate(follow[:-1]):
        hidden, spec = model.decode(params, cfg, cache,
                                    jnp.asarray([[tok]], jnp.int32),
                                    jnp.asarray([12 + i], jnp.int32), one,
                                    zero)
        cache, _ = model.commit(cfg, spec, jnp.asarray([12 + i], jnp.int32),
                                jnp.zeros((1, 1), jnp.int32),
                                jnp.ones((1,), jnp.int32))
        got.append(model.unembed(params, cfg, hidden[:, 0])[0])
    got = np.asarray(jnp.stack(got))
    seq = np.concatenate([prompt, follow[:-1]])[None]
    rows = np.zeros(6, np.int32)
    cols = np.arange(11, 17, dtype=np.int32)
    ref = np.asarray(R.logits_at(conf, SEED, seq, rows, cols))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    served = got.argmax(-1)
    assert C.gaps(ref, served).max() < 1e-4


def test_control_reads_wider_gaps_than_bf16():
    """The float8 control departs from the reference by more than a
    bfloat16 copy of the same weights does: the step below the stated
    precision is one the comparison must catch."""
    conf = small_conf("bfloat16", layers=2)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, (2, 48)).astype(np.int32)
    rows = np.repeat(np.arange(2, dtype=np.int32), 40)
    cols = np.tile(np.arange(8, 48, dtype=np.int32), 2)
    ref = np.asarray(R.logits_at(conf, SEED, tokens, rows, cols))
    low = np.asarray(R.logits_at(conf, SEED, tokens, rows, cols, lowp=True))
    ctrl = C.gaps(ref, low.argmax(-1))
    assert np.abs(low - ref).max() > 0
    assert ctrl.max() > 0


def test_pack_lines_up_served_tokens():
    prompt = np.array([5, 6, 7], np.int32)
    tokens, rows, cols, served = C.pack([(prompt, [8, 9])], 2, 8)
    assert tokens[0, :4].tolist() == [5, 6, 7, 8]
    assert cols.tolist() == [2, 3] and served.tolist() == [8, 9]
    assert rows.tolist() == [0, 0] and tokens[1].sum() == 0
    judged = C.judge({"logit_gap": 0.2, "unfinished": 0},
                     {"logit_gap": 0.1, "unfinished": 0})
    assert not C.passed(judged)
    assert C.passed(C.judge({"logit_gap": 0.05}, {"logit_gap": 0.1}))
