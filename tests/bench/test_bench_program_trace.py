"""Trace reduction by the program's own names (``bench/program_trace.py``)
and the readers of the program's request times.

Two recorded traces of one TPU v5e chip: ``v5e_two_kernels`` (two unnamed
jitted programs, three calls each, no ``serve.*`` spans; see
``test_bench_trace.py``) and ``v5e_serve_spans``, an open-loop window of a
tiny served model (2 layers, width 1024, 2 slots) with the server's
``serve.*`` spans and named programs, recorded by
``fixtures/record_serve_spans.py`` and trimmed to what the reductions read
(186 KB of the 1.2 MB recorded)."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402
from bench import program_trace as PT  # noqa: E402
from bench import trace as TR  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
KERNELS = ("flash_decode", "unembed_verify_stats")


def _load(name):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(FIXTURES / f"{name}.xplane.pb"))


def _both(name):
    pd = _load(name)
    return (TR.reduce(pd, {k: manifest.counts(k).MATCH for k in KERNELS}),
            PT.reduce_program(pd))


@pytest.fixture(scope="module")
def unnamed():
    return _both("v5e_two_kernels")


@pytest.fixture(scope="module")
def served():
    return _both("v5e_serve_spans")


def test_unnamed_programs_fold_into_one_name(unnamed):
    """Two lambdas both show as ``jit__lambda(<hash>)``: without stable
    names their six runs cannot be told apart."""
    reduced, prog = unnamed
    assert prog["module_calls"] == {"_lambda": 6}
    # a program's run encloses its ops, and the device's gaps between them
    assert reduced["busy_s"] <= sum(prog["module_s"].values()) \
        < 1.05 * reduced["busy_s"]
    assert prog["program_spans"] == {}


def test_idle_is_the_same_idle(unnamed):
    """The same idle time as the benchmark's breakdown, cut at span edges:
    with no serve.* span, only the bench.* labels (and the bits between
    them) appear."""
    reduced, prog = unnamed
    idle = reduced["window_s"] - reduced["busy_s"]
    gaps = dict(prog["idle_gaps_program"])
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert set(gaps) == {"bench.wait_arrival", "bench.step_once",
                         PT.OUTSIDE}
    assert gaps["bench.wait_arrival"] > 0.9 * idle


def test_program_name():
    assert PT.program_name("jit_serve_step(10574761370798865287)") \
        == "serve_step"
    assert PT.program_name("jit__lambda(7)") == "_lambda"
    assert PT.program_name("jit_admit_group") == "admit_group"


def test_self_times_segments_and_split():
    spans = [(0, 10, "a"), (1, 3, "b"), (4, 9, "c"), (5, 6, "d"),
             (12, 13, "e")]
    assert [x[3] for x in PT.self_times(spans)] == [3, 2, 4, 1, 1]
    segs = PT.segments(spans, -5, 20)
    assert [n for *_, n in segs] == [PT.OUTSIDE, "a", "b", "a", "c", "d",
                                     "c", "a", PT.OUTSIDE, "e", PT.OUTSIDE]
    assert [(s, e) for s, e, _ in segs] == [(-5, 0), (0, 1), (1, 3), (3, 4),
                                            (4, 5), (5, 6), (6, 9), (9, 10),
                                            (10, 12), (12, 13), (13, 20)]
    got = PT.split([(-1, 0.5), (2, 4.5), (11, 12.5)], segs)
    assert dict(got) == {PT.OUTSIDE: 2.0, "a": 1.5, "b": 1.0, "c": 0.5,
                         "e": 0.5}


def test_served_programs_by_name(served):
    reduced, prog = served
    mods = prog["module_calls"]
    assert {"serve_step", "admit_group"} <= set(mods)
    assert prog["module_s"]["serve_step"] > 0
    assert prog["module_s"]["admit_group"] > 0
    assert reduced["busy_s"] <= sum(prog["module_s"].values()) \
        < 1.05 * reduced["busy_s"]
    # one SlotSync read per step; per step one flash_decode per layer and
    # one unembed_verify_stats
    steps = mods["serve_step"]
    assert prog["program_spans"]["serve.sync"]["calls"] == steps
    assert reduced["kernel_calls"]["flash_decode"] == 2 * steps
    assert reduced["kernel_calls"]["unembed_verify_stats"] == steps


def test_served_spans_inside_bench_steps(served):
    pd = _load("v5e_serve_spans")
    steps = [(s, e) for s, e, n in TR.host_spans(pd) if n == TR.STEP]
    spans = TR.host_spans(pd, PT.SPAN_PREFIX)
    assert {n for *_, n in spans} >= {"serve.step", "serve.admit",
                                      "serve.decode", "serve.sync",
                                      "serve.apply", "serve.reap"}
    for s, e, n in spans:
        assert any(a <= s and e <= b for a, b in steps), n
    _, prog = served
    for name, v in prog["program_spans"].items():
        assert 0 <= v["self_s"] <= v["s"], name


def test_served_idle_put_down_to_serve_spans(served):
    reduced, prog = served
    idle = reduced["window_s"] - reduced["busy_s"]
    gaps = dict(prog["idle_gaps_program"])
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    in_steps = dict(reduced["breakdown"]["idle_gaps"])[TR.STEP]
    named = sum(v for k, v in gaps.items() if k.startswith(PT.SPAN_PREFIX))
    assert named > 0.9 * in_steps
    # inside bench.step_once, only its gap before serve.step is unnamed
    assert gaps[TR.STEP] < 0.1 * named


# ----------------------------------------------------------- readers


def _run(tracked, start=0.0, end=10.0):
    window = SimpleNamespace(start=start, end=end, drained=end + 5,
                             tracked=tracked)
    return SimpleNamespace(window=window, trace=None)


def _tracked(due, submitted, admitted, first):
    req = SimpleNamespace(submitted_at=submitted, admitted_at=admitted,
                          first_token_at=first)
    return SimpleNamespace(due=due, req=req)


def test_request_time_readers():
    tracked = [_tracked(1.0 + i, 1.0 + i, 1.1 + i + 0.01 * i,
                        1.2 + i + 0.01 * i + 0.002 * i) for i in range(5)]
    tracked.append(_tracked(12.0, 12.0, 12.5, 12.9))    # due after the window
    run = _run(tracked)
    first = manifest.reader("admit_to_first_ms_p50.chat").read(run)
    wait = manifest.reader("admit_wait_ms_p95.chat").read(run)
    assert first == pytest.approx(100.0 + 2 * 2, rel=1e-9)   # median of i=2
    # waits 100, 110, 120, 130, 140 ms: numpy's p95 lies 0.8 of the way
    # from 130 to 140
    assert wait == pytest.approx(138.0, rel=1e-9)


def test_request_time_readers_without_program_times():
    """A program that stamps no times (one before this tracing) reads None,
    as does a window in which nothing was admitted."""
    bare = SimpleNamespace(due=1.0, req=SimpleNamespace(submitted_at=1.0))
    unadmitted = _tracked(1.0, 1.0, None, None)
    for name in ("admit_to_first_ms_p50.chat", "admit_wait_ms_p95.chat"):
        reader = manifest.reader(name)
        assert reader.read(_run([bare])) is None
        assert reader.read(_run([unadmitted])) is None
