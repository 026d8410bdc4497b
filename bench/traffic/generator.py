"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<mix>.json``) gives the loop (``closed``: a fixed
number of clients, each sending its next request when the last one ends;
``open``: Poisson arrivals at a fixed rate; ``static``: batches of
``server.slots`` requests back to back), the prompt and output length
laws (lognormal by median and sigma, clipped), how many requests make the
pool, and the server's slot count, cache length and prompt buckets.

Every seed gets the same multiset of sizes and inter-arrival gaps: the
lengths are the law's quantiles at (i + 1/2) / N and the gaps the
exponential's, each list permuted by the seed.  The seed changes the order
and the token ids, not the amount of work, so runs with different seeds
differ no more than runs of one seed.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Item:
    """One request of the schedule: its prompt, its output budget and, in
    an open loop, when it is due (seconds after the window opens)."""
    prompt: np.ndarray
    max_new: int
    due_s: float


def load(name: str, root: Path = HERE) -> dict:
    return json.loads((root / f"{name}.json").read_text())


def quantile_lengths(law: dict, n: int) -> np.ndarray:
    """``n`` lengths at the law's quantiles (i + 1/2) / n, clipped."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(law["median"] * np.exp(law["sigma"] * z))
    return np.clip(x, law["min"], law["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def schedule(mix: dict, seed: int, vocab: int) -> list:
    """The mix's requests in order, made from ``seed`` alone."""
    n = int(mix["requests"])
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0xBE7C])
    plens = rng.permutation(quantile_lengths(mix["prompt"], n))
    outs = rng.permutation(quantile_lengths(mix["output"], n))
    if mix["loop"] == "open":
        due = np.cumsum(rng.permutation(
            exponential_gaps(float(mix["rate_per_s"]), n)))
    elif mix["loop"] in ("closed", "static"):
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return [Item(rng.integers(0, vocab, int(p), dtype=np.int32), int(o),
                 float(d)) for p, o, d in zip(plens, outs, due)]


def longest(mix: dict) -> int:
    """Tokens the longest request can hold: prompt + output."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])


def bucket_of(n: int, buckets) -> int:
    for b in sorted(buckets):
        if n <= b:
            return b
    raise ValueError(f"prompt of {n} tokens exceeds every bucket {buckets}")


def check_mix(mix: dict, tree_tokens: int) -> None:
    """Refuse a mix on which an operation would fail: a prompt beyond the
    largest bucket, or a request that cannot fit the cache."""
    srv = mix["server"]
    bucket_of(mix["prompt"]["max"], srv["prompt_buckets"])
    need = longest(mix) + tree_tokens + 2
    if need > srv["max_len"]:
        raise ValueError(f"max_len {srv['max_len']} < {need}: the longest "
                         "request would be refused at admission")
    if mix["loop"] == "closed" and mix["clients"] > srv["slots"]:
        raise ValueError("more clients than slots build a queue")
