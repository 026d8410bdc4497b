"""The comparison that decides ``correct`` for a served model.

A sample of finished requests (prompt + served tokens) is run once through
the plain reference; at every served token the reference's best logit at
that position minus its logit for the served token is that token's gap.
Greedy serving of the same model would make every gap 0 up to rounding;
the widest gap over the sample is compared with the cell's limit
(``bench/checks/<cell>.json``).

The sequences are padded to one shape per cell, (``sample_requests``,
``max_len``), so the reference compiles once and its program is cached.
"""
from __future__ import annotations

import numpy as np


def pack(samples, n_rows: int, width: int):
    """(tokens [n_rows, width], rows, cols, served) for the reference: row
    i holds prompt + served tokens but the last; position (i, p - 1 + j)
    predicts served token j."""
    tokens = np.zeros((n_rows, width), np.int32)
    rows, cols, served = [], [], []
    for i, (prompt, out) in enumerate(samples):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        p = len(prompt)
        rows += [i] * len(out)
        cols += list(range(p - 1, p - 1 + len(out)))
        served += list(out)
    return (tokens, np.asarray(rows, np.int32), np.asarray(cols, np.int32),
            np.asarray(served, np.int32))


def gaps(logits, tokens) -> np.ndarray:
    """Reference best logit minus the logit of ``tokens``, per position."""
    logits = np.asarray(logits, np.float64)
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


def served_gaps(reference, conf: dict, seed: int, samples, n_rows: int,
                width: int, lowp: bool = False):
    """(reference gaps of the served tokens, gaps of the tokens that the
    ``lowp`` control would put first — None unless ``lowp``)."""
    tokens, rows, cols, served = pack(samples, n_rows, width)
    ref = np.asarray(reference.logits_at(conf, seed, tokens, rows, cols))
    if not lowp:
        return gaps(ref, served), None
    low = np.asarray(reference.logits_at(conf, seed, tokens, rows, cols,
                                         lowp=True))
    return gaps(ref, served), gaps(ref, low.argmax(-1))


def judge(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every compared number; a number is
    within its limit when value <= limit."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def passed(judged: dict) -> bool:
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in judged.values())
