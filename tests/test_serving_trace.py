"""The scheduler's own tracing: ``serve.*`` profiler spans, the named jitted
programs, the ``slot_steps`` / ``accepted_tokens`` counters and the
``admitted_at`` / ``first_token_at`` request times."""
import dataclasses
import pathlib

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import SchedulerParams
from repro.configs.registry import get_config
from repro.core import medusa as M
from repro.core.engine import SpecEngine, build_engine
from repro.distributed.sharding import split_params
from repro.models.api import get_model
from repro.serving.scheduler import SpecServer, named

SPANS = ("serve.admit", "serve.chunk", "serve.decode", "serve.sync",
         "serve.apply", "serve.reap", "serve.recover")


@pytest.fixture(scope="module")
def served():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    m = get_model(cfg)
    params, _ = split_params(m.init_params(jax.random.PRNGKey(0), cfg))
    eng = SpecEngine(cfg)
    mp, _ = split_params(M.init_medusa(jax.random.PRNGKey(1), cfg,
                                       eng.dtree.K))
    return cfg, params, eng, mp


def _prompts(cfg, rng, sizes):
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


def _host_events(log_dir):
    pb = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_spans_nest_inside_steps(served, rng, tmp_path):
    """Every serve.* span lies inside a serve.step, one serve.sync per
    decode step, and the host shows the named step and admission."""
    cfg, params, eng, mp = served
    srv = SpecServer(eng, params, mp, batch_slots=2, max_len=128,
                     max_retries=2, sched=SchedulerParams(chunk_size=16))
    for p in _prompts(cfg, rng, (5, 40, 9)):
        srv.submit(p, max_new=6)
    with jax.profiler.trace(str(tmp_path)):
        srv.run(fail_hook=lambda it: it == 3)
    assert all(r.status == "done" for r in srv.done.values())
    events = _host_events(tmp_path)
    steps = [(s, e) for s, e, n in events if n == "serve.step"]
    inner = [(s, e, n) for s, e, n in events if n in SPANS]
    assert {n for *_, n in inner} == set(SPANS)
    for s, e, n in inner:
        assert any(a <= s and e <= b for a, b in steps), n
    assert sum(n == "serve.sync" for *_, n in events) == srv.stats["steps"]
    names = {n for *_, n in events}
    assert {"PjitFunction(serve_step)", "PjitFunction(admit_group)",
            "PjitFunction(suffix_prefill)"} <= names


def test_programs_carry_stable_names(served):
    cfg, params, eng, mp = served
    srv = SpecServer(eng, params, mp, batch_slots=2, max_len=128,
                     sched=SchedulerParams(adaptive_gamma=True))
    assert srv._step_jit.__name__ == "serve_step"
    assert {f.__name__ for f in srv._step_jits} == {"serve_step"}
    assert srv._admit_jit.__name__ == "admit_group"
    assert srv._prefill_jit.__name__ == "prefill_one"
    assert srv._trim_jit.__name__ == "trim_proposer"
    text = jax.jit(named(lambda x: x + 1, "plus_one")).lower(1.0).as_text()
    assert "jit_plus_one" in text


def test_counters(served, rng):
    cfg, params, eng, mp = served
    srv = SpecServer(eng, params, mp, batch_slots=2, max_len=128)
    for p in _prompts(cfg, rng, (5, 9, 14)):
        srv.submit(p, max_new=6)
    srv.run()
    st = srv.stats
    assert 0 < st["slot_steps"] <= st["steps"] * srv.B
    assert st["accepted_tokens"] >= st["slot_steps"]
    srv.reset()
    assert srv.stats["slot_steps"] == srv.stats["accepted_tokens"] == 0


def test_lm_head_passes_one_per_step(served, rng):
    """Under verify fusion the kernel folds all 8 slots into one group, so
    the head is read once a step; an unfused server counts none."""
    cfg, params, _, mp = served
    counts = {}
    for vf in (True, False):
        eng = build_engine(cfg, "medusa", verify_fusion=vf)
        srv = SpecServer(eng, params, mp, batch_slots=8, max_len=128)
        for p in _prompts(cfg, rng, (5, 9, 14, 7, 11, 6, 8, 12)):
            srv.submit(p, max_new=4)
        srv.run()
        assert all(r.status == "done" for r in srv.done.values())
        counts[vf] = (srv.stats["lm_head_passes"], srv.stats["steps"])
    assert counts[True][0] == counts[True][1] > 0
    assert counts[False][0] == 0 < counts[False][1]


def test_accepted_tokens_equal_delivered_without_clipping(served, rng):
    """With no EOS and budgets far off, nothing is clipped: the verifier's
    raw acceptance is exactly what the requests received."""
    cfg, params, eng, mp = served
    srv = SpecServer(eng, params, mp, batch_slots=2, max_len=128)
    rids = [srv.submit(p, max_new=40) for p in _prompts(cfg, rng, (5, 9))]
    for it in range(5):
        srv.step_once(it=it)
    reqs = [srv.slots[i].request for i in range(srv.B)]
    assert {r.rid for r in reqs} == set(rids)
    assert srv.stats["slot_steps"] == 2 * srv.stats["steps"] == 10
    assert srv.stats["accepted_tokens"] == sum(len(r.output) for r in reqs)


def _assert_times(reqs):
    for r in reqs:
        assert r.status == "done", r.rid
        assert r.submitted_at <= r.admitted_at <= r.first_token_at, r.rid


def test_request_times_across_recovery(served, rng):
    cfg, params, eng, mp = served
    srv = SpecServer(eng, params, mp, batch_slots=2, max_len=128,
                     max_retries=2)
    rids = [srv.submit(p, max_new=6) for p in _prompts(cfg, rng, (5, 9, 14))]
    first_admit = {}

    def fail(it):
        for r in (s.request for s in srv.slots if s.request is not None):
            first_admit.setdefault(r.rid, r.admitted_at)
        return it == 2
    srv.run(fail_hook=fail)
    assert srv.stats["step_failures"] == 1
    reqs = [srv.result(r) for r in rids]
    _assert_times(reqs)
    assert all(srv.result(r).admitted_at == t for r, t in first_admit.items())
    assert max(r.retries for r in reqs) >= 1


def test_request_times_across_preemption(served, rng):
    """A preempted request resumes with its first admission time kept."""
    cfg, params, _, _ = served
    pcfg = dataclasses.replace(cfg, cache_layout="paged", page_size=8)
    peng = build_engine(pcfg, "ngram", gamma=4)
    srv = SpecServer(peng, params, None, batch_slots=2, max_len=64,
                     n_blocks=9, sched=SchedulerParams(preemption=True))
    rids = [srv.submit(p, max_new=24) for p in _prompts(cfg, rng, (16, 16))]
    admitted = {}
    while srv.busy:
        srv.step_once()
        for r in (s.request for s in srv.slots if s.request is not None):
            admitted.setdefault(r.rid, r.admitted_at)
    reqs = [srv.result(r) for r in rids]
    assert srv.stats["preemptions"] >= 1
    assert max(r.preemptions for r in reqs) >= 1
    _assert_times(reqs)
    assert all(r.admitted_at == admitted[r.rid] for r in reqs)
