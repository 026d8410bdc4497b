"""The control of the ``logit_gap`` comparison at a size a test run holds:
a bfloat16 program served through the whole run, and the float8 reference
put in its place at the same positions.  The control's widest gap has to
stand at least three times above the program's, on every seed, so that a
limit between the two passes the program and fails the control.  On the
chip the same readings, at each cell's own size, set the cell's limit
(``bench/control.py``)."""
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

from bench import check as C  # noqa: E402
from bench.control import readings  # noqa: E402
from test_bench_faults import tiny_cell  # noqa: E402


def test_control_fails_where_the_program_passes():
    cell = tiny_cell("closed")
    cell.config["torch_dtype"] = "bfloat16"
    got = [readings(cell, 2**31 + s, 1.0, jax.devices()[:1])
           for s in (1, 2, 3)]
    lower = max(r["program"] for r in got)
    upper = min(r["control"] for r in got)
    assert upper > 0 and upper >= 3 * lower, got
    limit = lower + (upper - lower) / 3
    for r in got:
        assert C.passed(C.judge({"logit_gap": r["program"]},
                                {"logit_gap": limit}))
        assert not C.passed(C.judge({"logit_gap": r["control"]},
                                    {"logit_gap": limit}))
