"""The one general traffic generator, driven by a mix's data file.

A mix file (`bench/traffic/<name>.json`) gives:

  arrivals     "poisson": open loop at `rate_per_s`, requests due over the
               window; "backlog": `backlog_per_s` x seconds requests, all
               due when the window opens (an offline batch)
  prompt_len   {"dist": "lognormal", "median", "sigma", "min", "max"} or
  output_len   {"dist": "uniform", "min", "max"} (tokens, both ends kept)

Every seed gets the same work in another order: the lengths are the
distribution's quantiles at (i + 0.5) / n, paired by one fixed permutation,
and Poisson gaps are the exponential's quantiles; the seed shuffles the
requests and the gaps and draws the token ids (uniform over the
vocabulary).  So two seeds differ in arrival order and content, never in
the amount of work, and runs of different seeds spread no wider than runs
of one.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass
class Request:
    req_id: str
    due_s: float  # seconds after the window opens
    prompt: List[int]
    max_new: int


def _quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def count(mix: Dict[str, Any], seconds: float) -> int:
    per_s = mix["rate_per_s"] if mix["arrivals"] == "poisson" else mix["backlog_per_s"]
    return max(1, int(round(float(per_s) * seconds)))


def length_range(mix: Dict[str, Any], key: str) -> tuple:
    return int(mix[key]["min"]), int(mix[key]["max"])


def generate(mix: Dict[str, Any], seed: int, seconds: float, vocab: int) -> List[Request]:
    n = count(mix, seconds)
    plen = _quantiles(mix["prompt_len"], n)
    olen = _quantiles(mix["output_len"], n)[np.random.default_rng(0).permutation(n)]
    rng = np.random.default_rng(int(seed))
    order = rng.permutation(n)
    plen, olen = plen[order], olen[order]
    if mix["arrivals"] == "poisson":
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)  # exponential quantiles
        gaps = gaps[rng.permutation(n)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    elif mix["arrivals"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    toks = np.random.default_rng([int(seed), 1])
    return [
        Request(
            req_id=f"r{i:05d}",
            due_s=float(due[i]),
            prompt=toks.integers(0, vocab, size=int(plen[i])).tolist(),
            max_new=int(olen[i]),
        )
        for i in range(n)
    ]


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank; an infinite value (a request that
    failed or never finished) counts as the slowest."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(q * len(v)) - 1)])
