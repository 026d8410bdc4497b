"""The benchmark's traffic generator: seeded, the same work for every seed,
and no mix that an operation would refuse."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.traffic import generator as G  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


def _tuples(items):
    return [(it.prompt.tolist(), it.max_new, it.due_s) for it in items]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    m = G.load(mix)
    a = G.schedule(m, BIG_SEED, 153376)
    b = G.schedule(m, BIG_SEED, 153376)
    assert _tuples(a) == _tuples(b)
    c = G.schedule(m, BIG_SEED + 1, 153376)
    assert _tuples(a) != _tuples(c)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    """Sizes and gaps are one multiset permuted by the seed."""
    m = G.load(mix)
    runs = [G.schedule(m, s, 1000) for s in (1, 7, BIG_SEED)]
    for key in (lambda it: len(it.prompt), lambda it: it.max_new):
        assert len({tuple(sorted(map(key, r))) for r in runs}) == 1
    gaps = [tuple(sorted(np.round(np.diff([0.0] + [it.due_s for it in r]), 9)))
            for r in runs]
    assert len(set(gaps)) == 1


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_law(mix):
    m = G.load(mix)
    items = G.schedule(m, 3, 500)
    plens = np.array([len(it.prompt) for it in items])
    outs = np.array([it.max_new for it in items])
    assert plens.min() >= m["prompt"]["min"]
    assert plens.max() <= m["prompt"]["max"]
    assert abs(np.median(plens) - m["prompt"]["median"]) <= 1
    assert outs.min() >= m["output"]["min"]
    assert outs.max() <= m["output"]["max"]
    assert all(0 <= t < 500 for it in items for t in it.prompt)
    if m["loop"] == "open":
        due = np.array([it.due_s for it in items])
        assert np.all(np.diff(due) > 0)
        rate = len(due) / due[-1]
        assert rate == pytest.approx(m["rate_per_s"], rel=0.05)


@pytest.mark.parametrize("mix", MIXES)
def test_committed_mixes_fit_the_server(mix):
    G.check_mix(G.load(mix), tree_tokens=64)


def test_check_mix_refuses_what_would_fail():
    m = json.loads(json.dumps(G.load(MIXES[0])))
    m["server"]["max_len"] = G.longest(m) + 64
    with pytest.raises(ValueError, match="max_len"):
        G.check_mix(m, tree_tokens=64)
    m = json.loads(json.dumps(G.load(MIXES[0])))
    m["prompt"]["max"] = max(m["server"]["prompt_buckets"]) + 1
    with pytest.raises(ValueError, match="bucket"):
        G.check_mix(m, tree_tokens=64)


def test_quantile_lengths_by_hand():
    law = {"median": 100, "sigma": 0.0, "min": 1, "max": 1000}
    assert G.quantile_lengths(law, 4).tolist() == [100] * 4
    law = {"median": 100, "sigma": 1.0, "min": 50, "max": 200}
    x = G.quantile_lengths(law, 3)
    assert x.tolist() == [50, 100, 200]          # 38 and 263 clipped
    assert G.bucket_of(100, [64, 128]) == 128
