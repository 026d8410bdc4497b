"""The tensor-parallel engine at TP=4, on four host devices and on a trace
recorded on four v5e chips.

- Logits against the benchmark's plain float32 reference
  (``bench/references/dense_gqa.py``): ``prefill_logits``, then decoding
  through the sharded cache for a few steps; and the same comparison with
  the psum after each row-parallel contraction left out, which must fail.
- Tracing: the lowered ``tp_spec_step`` holds ``psums_per_step`` (2 x
  layers) exchanges of the hidden state, every program compiles under its
  ``jit_tp_*`` name, ``stats`` counts the calls, and ``serve.py --tp
  --profile`` writes a trace with the ``tp.*`` spans.
- The recorded trace ``fixtures/tp4_v5e_generate.xplane.pb``
  (``fixtures/record_tp_spans.py``): one static batch of openpangu-7b-tp4
  cut to 2 layers, 16 slots, a 1024-token bucket and 4 new tokens, TP=4 on
  a v5e 2x2; ``counts/all_reduce.py``'s ``MATCH`` (the decode steps'
  psums, not the prefill's) and both readers of the exchange on it.
"""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402
from bench import trace as TR  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" \
    / "tp4_v5e_generate.xplane.pb"

# float32 program against the float32 reference: the sharded program sums
# each row-parallel contraction as four partial sums, the reference in one,
# so the logits differ by float32 rounding only (~1e-6 at this size); 2e-4
# is the single-device comparison's tolerance (test_bench_reference.py).
TOL = 2e-4

PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, sys
root, fault, trace_dir = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path[:0] = [root, os.path.join(root, "src")]
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from bench import weights as W
from bench.model import program_config
from bench.references import dense_gqa as R
from repro.core import medusa as M
from repro.distributed import profiles
from repro.distributed.collectives import shard_map_compat
from repro.distributed.sharding import split_params
from repro.distributed.tp import build_tp_engine, make_tp_mesh
from repro.launch.serve import weight_shapes
if fault == "no_exchange":
    from repro.models import layers
    layers.tp_reduce = lambda y, cfg: y

SEED, B, P0, STEPS = 2**32 + 91, 2, 12, 4
conf = json.load(open(os.path.join(root, "bench/configs/openpangu-7b-tp4.json")))
# d = 96 differs from the tree's 64 tokens, so a [B, T, d] exchange is
# told apart from the verify epilogue's [B, T, T] reduction by shape
conf.update(hidden_size=96, num_attention_heads=8, num_key_value_heads=4,
            head_dim=16, intermediate_size=128, vocab_size=256,
            num_hidden_layers=int(os.environ.get("TP_LAYERS", "2")),
            torch_dtype="float32")
cfg = program_config(conf)
mesh = make_tp_mesh(4)
tpe = build_tp_engine(cfg, mesh, "medusa")
shapes, axes = weight_shapes(cfg)
specs = tpe.param_specs(shapes, axes)
params = tpe.shard_params(W.program_tree(conf, SEED), axes)
rng = np.random.default_rng(5)
prompt = rng.integers(0, 256, (B, P0)).astype(np.int32)
follow = rng.integers(0, 256, (B, STEPS)).astype(np.int32)
lens = tpe.replicate(jnp.full((B,), P0, jnp.int32))
out = {}

# prefill logits through the engine
pre = tpe.prefill_logits(params, tpe.replicate(jnp.asarray(prompt)), lens,
                         tpe.init_cache(B, 32))

# prefill then cached decode, through the model's own functions under the
# engine's sharding plan
local, lcfg = tpe.local, tpe.local_cfg
cache = tpe.init_cache(B, 32)
cspec = profiles.tp_cache_pspecs(cache, cfg, mesh, "model")

def prefill(params, tokens, lengths, cache):
    h, cache = local.model.prefill(params, lcfg, tokens, lengths, cache)
    return cache, local.model.unembed(params, lcfg, h)

def step(params, cache, tok, lengths):
    one = jnp.ones((1, 1), bool)
    hidden, spec = local.model.decode(params, lcfg, cache, tok[:, None],
                                      lengths, one, jnp.zeros((1,), jnp.int32))
    cache, _ = local.model.commit(lcfg, spec, lengths,
                                  jnp.zeros((B, 1), jnp.int32),
                                  jnp.ones((B,), jnp.int32))
    return cache, local.model.unembed(params, lcfg, hidden[:, 0])

sm = lambda f, ins: jax.jit(shard_map_compat(
    f, mesh=mesh, in_specs=ins, out_specs=(cspec, P()), check=False))
cache, first = sm(prefill, (specs, P(), P(), cspec))(
    params, tpe.replicate(jnp.asarray(prompt)), lens, cache)
got = [first]
stepf = sm(step, (specs, cspec, P(), P()))
for i in range(STEPS - 1):
    cache, lg = stepf(params, cache, tpe.replicate(jnp.asarray(follow[:, i])),
                      tpe.replicate(jnp.full((B,), P0 + i, jnp.int32)))
    got.append(lg)
got = np.stack([np.asarray(g) for g in got], 1)              # [B, STEPS, V]
seq = np.concatenate([prompt, follow[:, :-1]], 1)
rows = np.repeat(np.arange(B, dtype=np.int32), STEPS)
cols = np.tile(np.arange(P0 - 1, P0 - 1 + STEPS, dtype=np.int32), B)
ref = np.asarray(R.logits_at(conf, SEED, seq, rows, cols)).reshape(B, STEPS, -1)
out["prefill_err"] = float(np.abs(np.asarray(pre) - ref[:, 0]).max())
out["decode_err"] = float(np.abs(got[:, 1:] - ref[:, 1:]).max())
out["cached_prefill_err"] = float(np.abs(got[:, 0] - ref[:, 0]).max())
out["ref_scale"] = float(np.abs(ref).max())
if fault != "none":
    print(json.dumps(out))
    sys.exit(0)

# the speculative step: exchanges of the hidden state per step
pp = tpe.replicate(split_params(M.init_medusa(jax.random.PRNGKey(1), cfg,
                                              tpe.tb.K))[0])
toks = tpe.replicate(jnp.asarray(prompt))
c, lengths, base, state = tpe.prefill(params, pp, toks, lens,
                                      tpe.init_cache(B, 128))
args = (params, pp, c, lengths, base, state,
        tpe.replicate(jax.random.PRNGKey(3)))
tpe.spec_step(*args)
T, d = tpe.dtree.T, cfg.d_model

def count(jaxpr, times=1):
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "psum":
            n += times * sum(v.aval.shape == (B, T, d) for v in e.invars)
        mult = times * e.params.get("length", 1)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += count(inner, mult)
    return n

fns = tpe._fns
out["exchanges"] = count(jax.make_jaxpr(fns["spec_step"])(*args).jaxpr)
n_new = 3
tpe.generate(params, pp, toks, lens, tpe.init_cache(B, 128), n_new)
st = tpe.stats
out["stats"] = st
out["programs"] = {
    "prefill": fns["prefill"].lower(params, pp, toks, lens,
                                    tpe.init_cache(B, 128), None, None),
    "prefill_logits": fns["prefill_logits"].lower(params, toks, lens,
                                                  tpe.init_cache(B, 32)),
    "spec_step": fns["spec_step"].lower(*args),
    "generate": fns[("generate", n_new)].lower(
        params, pp, toks, lens, tpe.init_cache(B, 128), None, None)}
out["programs"] = {k: v.compile().as_text().split("\n", 1)[0][:80]
                   for k, v in out["programs"].items()}

# the launcher: --tp with --profile traces the sharded path
from repro.launch import serve
rc = serve.main(["--arch", "qwen1.5-0.5b", "--tp", "4", "--requests", "2",
                 "--slots", "2", "--max-new", "3", "--max-len", "128",
                 "--profile", trace_dir])
from jax.profiler import ProfileData
pb = sorted(__import__("pathlib").Path(trace_dir).rglob("*.xplane.pb"))[-1]
names = set()
for plane in ProfileData.from_file(str(pb)).planes:
    for line in plane.lines:
        names.update(e.name for e in line.events)
out["serve_rc"] = rc
out["trace_names"] = sorted(n for n in names if n.startswith("tp.")
                            or "tp_generate" in n)
print(json.dumps(out))
"""


def _probe(fault, tmp, layers=2):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TP_LAYERS": str(layers),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", PROBE, str(ROOT), fault,
                        str(tmp)], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _probe("none", tmp_path_factory.mktemp("tp4_trace"))


@pytest.fixture(scope="module")
def no_exchange(tmp_path_factory):
    return _probe("no_exchange", tmp_path_factory.mktemp("tp4_fault"))


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
@pytest.mark.parametrize("what", ["prefill_err", "cached_prefill_err",
                                  "decode_err"])
def test_tp4_logits_match_reference(request, fault, what):
    """TP=4 prefill logits, and logits decoded through the sharded cache,
    agree with the reference's full forward within float32 rounding; with
    the row-parallel psum left out they do not."""
    r = request.getfixturevalue("sound" if fault == "none" else "no_exchange")
    assert r["ref_scale"] > 10 * TOL
    if fault == "none":
        assert r[what] <= TOL, r
    else:
        assert r[what] > 100 * TOL, r


def test_tp_spec_step_exchanges_per_step(sound):
    """One psum of the [B, T, d] hidden state after attention wo and one
    after the MLP down-projection in every layer: the count the engine
    reports as ``psums_per_step``."""
    assert sound["exchanges"] == sound["stats"]["psums_per_step"] == 2 * 2


def test_tp_programs_compile_under_stable_names(sound):
    for name, head in sound["programs"].items():
        assert head.startswith(f"HloModule jit_tp_{name},") \
            or head.startswith(f"HloModule jit_tp_{name} "), head


def test_tp_stats_count_calls(sound):
    st = sound["stats"]
    # prefill_logits and prefill each count as a prefill
    assert st["prefill_calls"] == 2
    assert st["spec_step_calls"] == 1 and st["generate_calls"] == 1
    # 3 new tokens: the prefill's base token plus at least one a step
    assert 1 <= st["generate_steps"] <= 3


def test_serve_tp_profile_traces_the_sharded_path(sound):
    assert sound["serve_rc"] == 0
    names = sound["trace_names"]
    assert "tp.generate" in names
    assert any("tp_generate" in n and not n.startswith("tp.") for n in names)


# ------------------------------------------------- the recorded chip trace

# the recording: 2 layers, 16 slots, one 1024-token bucket, the 64-node
# tree; its batch ran 4 decode steps (the recorder's ``generate_steps``)
LAYERS, SLOTS, BUCKET, TREE, D, STEPS = 2, 16, 1024, 64, 4096, 4


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIXTURE))
    k = manifest.counts("all_reduce")
    return pd, TR.reduce(pd, {"all_reduce": k.MATCH})


def _view(reduced):
    conf = {"hidden_size": D, "serving": {"tree_tokens": TREE}}
    mix = {"server": {"slots": SLOTS}}
    return SimpleNamespace(
        trace=reduced, conf=conf, mix=mix, chips=4, counts=manifest.counts,
        _device=SimpleNamespace(device_kind="TPU v5 lite"))


def test_recorded_trace_has_the_tp_programs_and_spans(recorded):
    from bench import program_trace as PT
    pd, reduced = recorded
    assert reduced["devices"] == 4
    programs = {p for evs in PT.module_events(pd).values() for *_, p in evs}
    assert "tp_generate" in programs
    spans = {n for *_, n in TR.host_spans(pd, "tp.")}
    assert "tp.generate" in spans


def test_match_finds_every_row_parallel_psum(recorded):
    """2 x layers psums in each decode step, on every chip; the prefill's
    psums of the same exchange over the prompt bucket, and every other op
    of the trace, are not matched."""
    pd, reduced = recorded
    import re
    pat = re.compile(manifest.counts("all_reduce").MATCH)
    matched, exchanged = set(), set()
    for evs in TR.device_events(pd).values():
        for *_, name in evs:
            m = re.search(r"= (bf16\[[\d,]+\])\{[^}]*\} all-reduce\(", name)
            if m:
                exchanged.add(m.group(1))
            if pat.search(name):
                matched.add(m.group(1) if m else name)
    assert exchanged == {f"bf16[{SLOTS},{BUCKET},{D}]",
                         f"bf16[{SLOTS},{TREE},{D}]"}
    assert matched == {f"bf16[{SLOTS},{TREE},{D}]"}
    assert reduced["kernel_calls"]["all_reduce"] == STEPS * 2 * LAYERS


def test_readers_of_the_exchange_on_the_recorded_trace(recorded):
    _, reduced = recorded
    view = _view(reduced)
    share = manifest.reader("all_reduce_roofline").read(view)
    assert 0 < share <= 100
    frac = manifest.reader("collective_share.tp4").read(view)
    spent = reduced["kernel_s"]["all_reduce"]
    assert frac == pytest.approx(100 * spent / reduced["busy_s"])
    assert 0 < frac < 100
    # no trace, or no psum found: the readers stay silent
    for name in ("all_reduce_roofline", "collective_share.tp4"):
        assert manifest.reader(name).read(_view(None)) is None
        silent = {**reduced, "kernel_s": {"all_reduce": 0.0},
                  "kernel_calls": {"all_reduce": 0}}
        assert manifest.reader(name).read(_view(silent)) is None


def test_all_reduce_counts_by_hand():
    k = manifest.counts("all_reduce")
    # one decode-step psum of openpangu-7b-tp4: [16 x 64, 4096] bf16 over 4
    # chips, 3/2 of its 8 MiB sent: 12.6 MB, 63 us at 200 GB/s
    byts = k.call(16 * 64, 4096, 4)
    assert byts == 1.5 * 16 * 64 * 4096 * 2
    assert byts / k.ICI_BYTES_PER_S["TPU v5 lite"] == pytest.approx(
        62.9e-6, rel=1e-3)
    assert k.call(1024, 4096, 1) == 0
    import re
    pat = re.compile(k.MATCH)
    psum = ("%psum.34 = bf16[16,64,4096]{2,1,0:T(8,128)(2,1)S(1)} "
            "all-reduce(%fusion.465), channel_id=1")
    verify = "%all-gather.25 = f32[64,64]{1,0} all-gather(%reduce_max.52)"
    prefill = ("%psum.32 = bf16[16,1024,4096]{2,1,0:T(8,128)(2,1)} "
               "all-reduce(%convolution_bitcast_fusion.3), channel_id=1")
    small = "%all-reduce.3 = f32[16,64,64]{2,1,0} all-reduce(%x)"
    assert pat.search(psum)
    assert not any(pat.search(op) for op in (prefill, verify, small))


def test_readers_of_the_exchange_by_hand():
    """Two static batches of 2 layers, 3 decode steps each: 2 x 3 x 4 step
    psums a chip, 10 ms of them in 40 ms busy."""
    calls = 2 * 3 * 2 * LAYERS
    reduced = {"devices": 4, "busy_s": 0.040,
               "kernel_s": {"all_reduce": 0.010},
               "kernel_calls": {"all_reduce": calls}}
    k = manifest.counts("all_reduce")
    least = calls * k.call(SLOTS * TREE, D, 4) / 200e9
    view = _view(reduced)
    assert manifest.reader("all_reduce_roofline").read(view) == \
        pytest.approx(100 * least / 0.010)
    assert manifest.reader("collective_share.tp4").read(view) == \
        pytest.approx(25.0)
