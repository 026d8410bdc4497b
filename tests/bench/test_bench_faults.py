"""A whole benchmark run on the CPU at a tiny size, past the harness's look
for a chip: set-up, window, drain, reference check and metrics.  A sound run
comes out correct; a timed path broken underneath comes out not correct,
once for each fault a served cell can have:

- a step that returns its state unchanged (the KV cache and lengths the
  decode step was given, not the ones it committed);
- a token altered where it is produced (the step's committed path);
- for the tensor-parallel driver, the exchange between chips left out (the
  psum after each row-parallel contraction), on four host devices.

Half a batch left out with a mean over the rest is a training fault and does
not apply to a served model.
"""
import json
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402
from bench.run import run_cell  # noqa: E402

SEED = 2**33 + 7
E2E = [{"name": n, "unit": u} for n, u in (
    ("tokens_per_s", "tokens/s"), ("ttft_ms_p50", "ms"),
    ("tpot_ms_p95", "ms"), ("setup_s", "s"))]


def tiny_cell(loop):
    conf = json.loads(
        (ROOT / "bench" / "configs" / "openpangu-7b-pp2.json").read_text())
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, intermediate_size=128, vocab_size=256,
                num_hidden_layers=2, torch_dtype="float32")
    mix = {"loop": loop, "clients": 4, "requests": 64, "rate_per_s": 20.0,
           "drain_s": 30,
           "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
           "output": {"median": 8, "sigma": 0.4, "min": 4, "max": 12},
           "server": {"slots": 4, "max_len": 128, "prompt_buckets": [16, 32]},
           "check": {"sample_requests": 3}}
    # float32 program against the float32 reference: gaps are rounding
    limits = {"logit_gap": 1e-3, "unfinished": 0, "step_failures": 0}
    return manifest.Cell(f"tiny.{loop}", 1, conf, mix, limits, E2E, [])


def run(loop):
    return run_cell(tiny_cell(loop), SEED, 1.0, False, jax.devices()[:1],
                    t_start=time.monotonic())


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_sound_run_is_correct(loop):
    r = run(loop)
    assert r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] <= 1e-3
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    names = set(r["metrics"])
    assert {"setup_s", "tokens_per_s"} <= names
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "check"


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.core.engine import SpecEngine
    step = SpecEngine.spec_step

    def stale(self, params, pp, cache, lengths, *a, **kw):
        _, _, verdict, state = step(self, params, pp, cache, lengths, *a,
                                    **kw)
        return cache, lengths, verdict, state

    monkeypatch.setattr(SpecEngine, "spec_step", stale)
    r = run("closed")
    assert not r["correct"]
    assert r["check"]["logit_gap"]["value"] > 1e-3


def test_altered_token_is_caught(monkeypatch):
    from repro.serving.scheduler import SpecServer
    impl = SpecServer._serve_step_impl

    def altered(self, *a, **kw):
        out = impl(self, *a, **kw)
        sync = out[-1]
        return out[:-1] + (sync._replace(tokens=(sync.tokens + 1) % 256),)

    monkeypatch.setattr(SpecServer, "_serve_step_impl", altered)
    r = run("closed")
    assert not r["correct"]
    assert r["check"]["logit_gap"]["value"] > 1e-3


TP_RUN = r"""
import json, os, sys, time
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
import jax
from bench import manifest
from bench.run import run_cell
root = sys.argv[1]
conf = json.load(open(os.path.join(root, "bench/configs/openpangu-7b-tp4.json")))
conf.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
            head_dim=16, intermediate_size=128, vocab_size=256,
            num_hidden_layers=2, torch_dtype="float32")
mix = json.load(open(os.path.join(root, "bench/traffic/offline-batch.json")))
mix.update(prompt={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
           output={"median": 8, "sigma": 0.0, "min": 8, "max": 8})
mix["server"] = {"slots": 4, "max_len": 128, "prompt_buckets": [32]}
limits = {"logit_gap": 1e-3, "unfinished": 0, "step_failures": 0}
cell = manifest.Cell("tiny.tp4", 4, conf, mix, limits, [], [])
if sys.argv[2] == "no_exchange":
    from repro.models import layers
    layers.tp_reduce = lambda y, cfg: y
r = run_cell(cell, 2**33 + 9, 1.0, False, jax.devices()[:4],
             t_start=time.monotonic())
print(json.dumps({"correct": r["correct"], "count": r["device"]["count"],
                  "gap": r["check"]["logit_gap"]["value"]}))
"""


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_tp4_exchange_left_out_is_caught(fault):
    """The TP=4 path on four host devices: sound, it is correct; with the
    psum after each row-parallel contraction left out, it is not."""
    import os
    import subprocess
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", TP_RUN, str(ROOT), fault],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["count"] == 4
    assert r["correct"] == (fault == "none"), r
