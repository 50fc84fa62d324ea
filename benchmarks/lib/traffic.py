"""Traffic from a data file and a seed: one general generator.

A mix is a JSON file of parameters under `benchmarks/traffic/`; its
`generator` names one of the functions below. A pure function of
(mix, seed, seconds): the same seed gives the same inputs.

Every seed gets the same work in another order. The inter-arrival gaps and
the lengths are the distribution's own quantiles (a fixed multiset for a
given rate and window), and the seed only permutes them and draws the token
ids. Two runs with different seeds then differ by what the system does with
the order, not by how much was offered: a sampled Poisson count alone would
swing the offered load by 1/sqrt(n) from seed to seed.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float                # seconds after the window opens
    prompt: np.ndarray          # [P] int32
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """The n mid-quantiles of a log-normal, rounded and clipped to
    [lo, hi]."""
    z = np.array([NormalDist().inv_cdf(float(u)) for u in _mid_quantiles(n)])
    x = np.exp(math.log(median) + sigma * z)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gap_quantiles(n: int, rate: float) -> np.ndarray:
    """The n mid-quantiles of an exponential inter-arrival gap (Poisson
    arrivals) with mean 1/rate."""
    gaps = -np.log1p(-_mid_quantiles(n))
    gaps = gaps / gaps.mean()          # the discretisation's bias, removed
    return gaps / rate


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Open-loop Poisson arrivals at a rate fixed in the mix: `rate_per_s`,
    and `prompt` and `output` length log-normals ({median, sigma, min,
    max}). Requests due in [0, seconds)."""
    n = int(round(mix["rate_per_s"] * seconds))
    if n < 1:
        raise ValueError(f"rate {mix['rate_per_s']}/s over {seconds}s gives "
                         f"no request")
    gaps = gap_quantiles(n, mix["rate_per_s"])
    p, o = mix["prompt"], mix["output"]
    plens = lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
    olens = lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
    order = _rng(seed, 1)
    gaps = order.permutation(gaps)
    plens = order.permutation(plens)
    olens = order.permutation(olens)
    due = np.cumsum(gaps) - gaps[0]    # the first request opens the window
    due = due * (seconds / (due[-1] + gaps[0]))  # exactly n in [0, seconds)
    ids = _rng(seed, 2)
    return [Request(float(due[i]),
                    ids.integers(0, vocab, int(plens[i]), dtype=np.int32),
                    int(olens[i])) for i in range(n)]


def markov_tokens(n: int, seq_len: int, vocab: int, seed: int) -> np.ndarray:
    """Token rows with learnable structure: each token follows its
    predecessor's fixed successor with probability 0.7 (a copy of the
    program's `data/datasets.py::synthetic_tokens`), so a training loss
    falls within a window."""
    rng = _rng(seed, 4)
    base = rng.integers(0, vocab, size=(n, seq_len), dtype=np.int32)
    succ = (np.arange(vocab, dtype=np.int64) * 31 + 7) % vocab
    succ = succ.astype(np.int32)
    for t in range(1, seq_len):
        follow = rng.random((n,)) < 0.7
        base[follow, t] = succ[base[follow, t - 1]]
    return base


def train_steady(mix: dict, seed: int, seconds: float, vocab: int,
                 seq_len: int, chips: int) -> dict:
    """Back-to-back training steps: a pool of `pool_rows_per_chip` x chips
    rows that all differ, read `sequences_per_chip` x chips at a time in
    order. The pool is sized so that no row repeats inside a window."""
    rows = int(mix["pool_rows_per_chip"]) * chips
    return {"pool": markov_tokens(rows, seq_len, vocab, seed),
            "global_batch": int(mix["sequences_per_chip"]) * chips}


GENERATORS = {"open_loop": open_loop, "train_steady": train_steady}


def generate(mix: dict, seed: int, seconds: float, **shape):
    name = mix["generator"]
    if name not in GENERATORS:
        raise KeyError(f"traffic generator {name!r} is not one of "
                       f"{sorted(GENERATORS)}")
    return GENERATORS[name](mix, seed, seconds, **shape)
