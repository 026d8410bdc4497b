"""Trace reduction and kernel counts.

The fixture is a profiler trace recorded on one TPU v5e chip: three
``bench.step_once`` spans, each running the tree-attention wrapper
(``flash_decode``, B=2, Hkv=2, 256 folded rows, 512 cached positions) and
``unembed_verify_stats`` (B=2, T=64, d=1024, V=4096), each followed by a
20 ms ``bench.wait_arrival`` sleep, all inside one ``bench.window``."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402
from bench import trace as TR  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures"
KERNELS = ("flash_decode", "unembed_verify_stats")


@pytest.fixture(scope="module")
def reduced():
    pd = TR.load(FIXTURE)
    return TR.reduce(pd, {k: manifest.counts(k).MATCH for k in KERNELS})


def test_kernels_found_by_signature(reduced):
    assert reduced["devices"] == 1
    assert reduced["kernel_calls"] == {k: 3 for k in KERNELS}
    assert 0 < reduced["kernel_s"]["flash_decode"] \
        < reduced["kernel_s"]["unembed_verify_stats"] < reduced["busy_s"]


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.0655, abs=1e-3)
    assert 0 < reduced["busy_s"] < 0.01 * reduced["window_s"]
    assert abs(reduced["skew_s"][0]) <= TR.MAX_SKEW_NS * 1e-9


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert 0 < len(ops) <= 10 and len(gaps) <= 10
    assert ops[0][0].startswith("unembed_verify_stats (")
    assert gaps["bench.wait_arrival"] > 0.9 * sum(gaps.values())
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_signatures_tell_the_kernels_apart():
    import re
    fd = re.compile(manifest.counts("flash_decode").MATCH)
    uv = re.compile(manifest.counts("unembed_verify_stats").MATCH)
    flash = ("%closed_call.39 = (f32[8,8,256,128]{3,2,1,0:T(8,128)S(1)}, "
             "f32[8,8,256,1]{3,2,1,0:T(8,128)S(1)}, f32[8,8,256,1]{3,2,1,0}) "
             "custom-call(s32[8]{0} %a), custom_call_target=\"tpu_custom_call\"")
    stats = ("%_serve_step_impl.1 = (s32[8,64,1]{2,1,0}, f32[8,64,1]{2,1,0}, "
             "f32[8,64,1]{2,1,0}, f32[8,64,64]{2,1,0}) custom-call(f32[8]{0} "
             "%b), custom_call_target=\"tpu_custom_call\"")
    fusion = "%fusion.166 = bf16[8,64,12800]{2,1,0} fusion(%x), kind=kLoop"
    assert fd.search(flash) and not uv.search(flash)
    assert uv.search(stats) and not fd.search(stats)
    assert not fd.search(fusion) and not uv.search(fusion)


def test_leaves_and_union():
    events = [(0, 10, "while"), (1, 3, "a"), (4, 9, "b"), (12, 13, "c")]
    assert [e[2] for e in TR.leaves(events)] == ["a", "b", "c"]
    assert TR.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert TR.overlap([[0, 3], [5, 6]], [[2, 5.5]]) == 1.5


def test_flash_decode_counts_by_hand():
    fd = manifest.counts("flash_decode")
    # one slot, 100 cached positions, 32 heads x 64 tree rows of 128
    flops, byts = fd.call([100], heads=32, kv_heads=8, head_dim=128,
                          tree_tokens=64)
    assert flops == 4 * 32 * 64 * 128 * 100
    kv = 2 * 8 * 128 * 2 * 100
    q = 32 * 64 * 128 * 2
    out = 32 * 64 * 130 * 4
    assert byts == kv + q + out
    # positions count, not max_len: two slots of 50 are one slot of 100
    f2, b2 = fd.call([50, 50], heads=32, kv_heads=8, head_dim=128,
                     tree_tokens=64)
    assert f2 == flops and b2 - byts == q + out


def test_unembed_counts_by_hand():
    uv = manifest.counts("unembed_verify_stats")
    flops, byts = uv.call(slots=8, tree_tokens=64, d_model=4096,
                          vocab=153376)
    assert flops == 2 * 512 * 4096 * 153376
    head = 4096 * 153376 * 2
    assert head < byts < head + 512 * (4096 * 2 + 4 * 4 + 64 * 4) + 1
    # compute-bound on a v5e: 643 GFLOP at 197 TFLOP/s is 3.3 ms, the head
    # 1.26 GB at 819 GB/s is 1.5 ms
    peaks = manifest.peaks("TPU v5 lite")
    assert flops / peaks["bf16_flops_per_s"] > byts / peaks["hbm_bytes_per_s"]


def test_backbone_counts_by_hand():
    bf = manifest.counts("backbone_forward")
    conf = {"hidden_size": 4096, "intermediate_size": 12800,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "head_dim": 128, "num_hidden_layers": 17, "vocab_size": 153376}
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 12800
    assert bf.per_token(conf, 0) == 17 * (2 * layer + 4 * 4096) \
        + 2 * 4096 * 153376
    assert bf.tokens(conf, 10, 3) == sum(bf.per_token(conf, 10 + j)
                                         for j in range(3))
