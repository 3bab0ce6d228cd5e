"""The one traffic generator: reads a mix's parameters, returns requests.

A mix file (``mixes/<name>.json``) holds only data.  Its sizes and
inter-arrival gaps are taken at the quantiles of the mix's distributions
(or, where no inverse CDF is at hand, drawn once from a generator fixed by
the mix), in an order fixed by the mix: one schedule, shaped like the
distributions, that every seed sends.  The seed draws only the prompt token
ids (and, in ``drivers/serve.py``, the weights).  So every seed does the
same work at the same times, and the spread between runs measures the
system, not the draw.

Open loop (``poisson``, ``gamma``): request i is due at a fixed offset from
the window's start, whether or not earlier ones finished.  Closed loop
(``closed``): ``clients`` clients each send their next request when the
previous one finishes; the harness sets those due times as it runs.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Item:
    rid: int
    prompt: np.ndarray       # int32 token ids
    max_new: int
    due: Optional[float]     # seconds after the window opens; None = closed
    client: int = -1


def _mids(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantile_set(spec: dict, n: int) -> np.ndarray:
    """``n`` values spread over the distribution ``spec`` by its quantiles,
    clipped to [min, max]."""
    kind = spec["kind"]
    u = _mids(n)
    if kind == "fixed":
        v = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "exponential":
        v = -np.log1p(-u) * spec["mean"]
    elif kind == "gamma":
        # no inverse CDF in the standard library: one draw, fixed by the mix
        cv = spec["cv"]
        shape = 1.0 / cv ** 2
        v = np.sort(np.random.default_rng(spec.get("set_seed", 0)).gamma(
            shape, spec["mean"] / shape, n))
    else:
        raise ValueError(f"unknown distribution kind {kind!r}")
    lo, hi = spec.get("min", -math.inf), spec.get("max", math.inf)
    return np.clip(v, lo, hi)


def _order(values: np.ndarray, fixed: int) -> np.ndarray:
    """``values`` in an order fixed by ``fixed``."""
    return np.random.default_rng(fixed).permutation(values)


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "closed":
        return int(arr["requests"])
    return max(1, int(round(arr["rate_per_s"] * seconds)))


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The requests of one run: sizes, gaps and order from the mix, token ids
    from ``seed``."""
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    n = n_requests(mix, seconds)
    def lengths(spec, fixed):
        return _order(np.rint(quantile_set(spec, n)).astype(np.int64), fixed)

    prompts = lengths(mix["prompt_len"], 1)
    outs = lengths(mix["output_len"], 2)
    if arr["kind"] == "closed":
        due = [None] * n
        client = [i % int(arr["clients"]) for i in range(n)]
    else:
        mean = 1.0 / arr["rate_per_s"]
        gap = ({"kind": "exponential", "mean": mean} if arr["kind"] == "poisson"
               else {"kind": "gamma", "mean": mean, "cv": arr["cv"],
                     "set_seed": arr.get("set_seed", 0)})
        gaps = _order(quantile_set(gap, n - 1), 3)
        # the first request is due as the window opens
        due = list(np.concatenate([[0.0], np.cumsum(gaps)]))
        client = [-1] * n
    ids = mix.get("ids", {"kind": "uniform"})
    if ids["kind"] != "uniform":
        raise ValueError(f"unknown id kind {ids['kind']!r}")
    return [Item(i, rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                 int(outs[i]), None if due[i] is None else float(due[i]),
                 client[i])
            for i in range(n)]
