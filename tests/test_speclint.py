"""speclint + the unified repo checks (DESIGN.md §16): every rule has a
positive and a negative fixture under ``tests/fixtures/speclint/``, inline
suppressions are honored, the shared finding schema is exact across all
three checkers, the repo tree itself lints clean (the regression guard
for the violations this gate was built on — the `_decode_step` per-field
host syncs, the unannotated donate_argnums sites), and the
``python -m tools.checks`` entrypoint gates with the right exit codes."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
FIX = ROOT / "tests" / "fixtures" / "speclint"
if str(ROOT / "tools") not in sys.path:
    sys.path.insert(0, str(ROOT / "tools"))

import check_bench_regress  # noqa: E402
import check_docs_refs  # noqa: E402
import speclint.rules  # noqa: E402,F401  (populates the registry)
from speclint.core import RULES, run_paths  # noqa: E402

SCHEMA = {"tool", "rule", "file", "line", "col", "message"}


def lint(name, rules=None):
    return run_paths([FIX / name], root=ROOT, rules=rules)


def _cli(*args):
    return subprocess.run([sys.executable, *args], cwd=ROOT,
                          capture_output=True, text=True)


# ------------------------------------------------------------ rule matrix

CASES = [
    ("trace-safety", "trace_safety_bad.py", "trace_safety_clean.py", 5),
    ("donation", "donation_bad.py", "donation_clean.py", 3),
    ("proposer-protocol", "proposer_bad.py", "proposer_clean.py", 4),
    ("pytree-axis", "pytree_axis_bad.py", "pytree_axis_clean.py", 1),
    ("ssm-rollback", "ssm_rollback_bad.py", "ssm_rollback_clean.py", 1),
    ("kernel-static-shape", "kernel_static_bad.py",
     "kernel_static_clean.py", 2),
    ("shard-specs", "shard_specs_bad.py", "shard_specs_clean.py", 4),
]


def test_every_rule_has_a_fixture_pair():
    assert set(RULES) == {c[0] for c in CASES}


@pytest.mark.parametrize("rule,bad,clean,n", CASES, ids=[c[0] for c in CASES])
def test_rule_positive_and_negative(rule, bad, clean, n):
    found = lint(bad)
    assert len(found) == n, [str(f) for f in found]
    assert {f.rule for f in found} == {rule}
    assert all(f.line > 0 and f.file.endswith(bad) for f in found)
    assert lint(clean) == [], [str(f) for f in lint(clean)]


def test_trace_safety_flags_every_sync_class():
    """One fixture exercises all four in-trace sync shapes plus the
    batched-transfer smell (the `_decode_step` bug class)."""
    msgs = "\n".join(f.message for f in lint("trace_safety_bad.py"))
    for frag in ("`int(...)`", "Python `if`", "`np.asarray`", "`.item()`",
                 "jax.device_get"):
        assert frag in msgs, frag


def test_donation_drift_names_both_sides():
    msgs = [f.message for f in lint("donation_bad.py")]
    assert any("donates (cache)" in m and "(lengths)" in m for m in msgs)


def test_inline_suppression_is_honored():
    """`# speclint: disable=trace-safety` on the flagged line silences it
    (the bad fixture proves the same construct otherwise fires)."""
    assert lint("suppressed.py") == []


def test_rule_filter_narrows_the_run():
    assert lint("trace_safety_bad.py", rules=["donation"]) == []
    assert len(lint("trace_safety_bad.py", rules=["trace-safety"])) == 5


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    assert [f.rule for f in run_paths([p], root=ROOT)] == ["parse-error"]


# ------------------------------------------------ schema + repo-tree gate

def test_finding_json_schema_is_exact():
    for f in lint("donation_bad.py"):
        j = f.to_json()
        assert set(j) == SCHEMA
        assert j["tool"] == "speclint"
        assert not pathlib.Path(j["file"]).is_absolute()


def test_repo_tree_lints_clean():
    """The standing regression guard: every true positive this gate found
    (per-field decode-step syncs, unannotated donations, unguarded
    per-slot cache maps) stays fixed, and new code joins the contract."""
    assert run_paths(None, root=ROOT) == [], \
        [str(f) for f in run_paths(None, root=ROOT)]


def test_docs_refs_shares_schema_and_is_green():
    assert check_docs_refs.collect_findings(ROOT) == []
    r = _cli("tools/check_docs_refs.py", "--json")
    out = json.loads(r.stdout)
    assert r.returncode == 0 and out["ok"] is True and out["findings"] == []


def test_bench_regress_findings_share_schema(tmp_path):
    base, cur = tmp_path / "base", tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    (base / "BENCH_sampling.json").write_text(
        json.dumps({"smoke": True, "tvd_chain_vs_ar": 0.1}))
    (cur / "BENCH_sampling.json").write_text(
        json.dumps({"smoke": True, "tvd_chain_vs_ar": 1.0}))
    findings, _ = check_bench_regress.collect_findings(cur, base)
    assert len(findings) == 1
    assert set(findings[0]) == SCHEMA
    assert findings[0]["tool"] == "bench-regress"
    # an empty current dir is a note, never a failure (pre-bench CI order)
    findings, notes = check_bench_regress.collect_findings(tmp_path, base)
    assert findings == [] and len(notes) == 1


# ------------------------------------------------------- CLI entrypoints

def test_checks_cli_green_on_repo():
    r = _cli("-m", "tools.checks")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tools.checks: clean" in r.stdout


@pytest.mark.parametrize("fixture,rc", [
    ("trace_safety_bad.py", 1), ("donation_bad.py", 1),
    ("proposer_bad.py", 1), ("pytree_axis_bad.py", 1),
    ("ssm_rollback_bad.py", 1), ("kernel_static_bad.py", 1),
    ("shard_specs_bad.py", 1),
    ("trace_safety_clean.py", 0), ("shard_specs_clean.py", 0),
    ("suppressed.py", 0),
])
def test_checks_cli_gates_fixtures(fixture, rc):
    r = _cli("-m", "tools.checks", str(FIX / fixture))
    assert r.returncode == rc, r.stdout + r.stderr


def test_checks_cli_json_mode():
    r = _cli("-m", "tools.checks", "--json", str(FIX / "donation_bad.py"))
    out = json.loads(r.stdout)
    assert r.returncode == 1 and out["ok"] is False
    assert len(out["findings"]) == 3
    assert all(set(f) == SCHEMA for f in out["findings"])


def test_speclint_cli_standalone():
    r = _cli("-m", "tools.speclint", str(FIX / "pytree_axis_bad.py"))
    assert r.returncode == 1 and "[pytree-axis]" in r.stdout
    r = _cli("-m", "tools.speclint", "--list-rules")
    assert r.returncode == 0
    for rule, *_ in CASES:
        assert rule in r.stdout


def test_named_jit_targets_are_seen_through(tmp_path):
    """``jax.jit(named(f, name))`` resolves to ``f``, as ``partial`` does:
    its donation pin is checked against ``f``'s signature and ``f`` is
    jit-reachable for the trace-safety rule."""
    src = tmp_path / "named_target.py"
    src.write_text(
        "import jax\n"
        "import numpy as np\n"
        "\n"
        "\n"
        "def named(fn, name):\n"
        "    return fn\n"
        "\n"
        "\n"
        "class S:\n"
        "    def _impl(self, params, cache, lengths):\n"
        "        return cache, np.asarray(lengths)\n"
        "\n"
        "    def build(self):\n"
        "        return jax.jit(named(self._impl, 'step'),\n"
        "                       donate_argnums=(1,))"
        "  # speclint: donates=lengths\n")
    found = run_paths([src], root=tmp_path)
    assert {f.rule for f in found} == {"donation", "trace-safety"}, \
        [str(f) for f in found]
