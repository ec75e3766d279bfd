"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix file (``bench/traffic/<mix>.json``) names its driver and gives:

* ``arrival``: ``{"kind": "offline"}``, a queue of ``requests`` requests
  all waiting at the window's open, or ``{"kind": "poisson",
  "rate_per_s": r}``, ``ceil(r * seconds)`` requests whose gaps add up to
  the window, so that every one of them arrives inside it;
* ``prompt`` and ``output``: lognormal lengths ``{"median", "sigma",
  "min", "max", "quantum"}``, rounded up to a multiple of ``quantum`` and
  clamped to ``[min, max]``;
* ``order``: ``"seed"`` to let the seed draw the order of the lengths and
  gaps, or ``"fixed"`` (the default) for one order shared by every seed.

Every seed gets the same set of lengths and gaps: the lengths are the
distribution's quantiles at ``(i + 0.5) / n`` and the gaps the
exponential's, so each seed does the same work.  The order is shuffled,
and the seed draws the prompt tokens (and the weights).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    arrival_s: float          # scheduled arrival, from the window's open
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A NumPy generator for any whole-number seed and stream tags."""
    return np.random.default_rng(
        np.random.SeedSequence([seed & ((1 << 64) - 1), *tags]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The mix's ``n`` lengths, in ascending order."""
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _quantiles(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    q = spec.get("quantum", 1)
    up = np.ceil(raw / q) * q
    return np.clip(up, spec["min"], spec["max"]).astype(np.int64)


def arrivals(spec: dict, n: int, order: np.ndarray,
             seconds: float) -> np.ndarray:
    if spec["kind"] == "offline":
        return np.zeros(n)
    if spec["kind"] == "poisson":
        gaps = -np.log1p(-_quantiles(n))
        gaps *= seconds / gaps.sum()
        return np.concatenate([[0.0], np.cumsum(gaps[order])[:-1]])
    raise ValueError(f"unknown arrival kind {spec['kind']!r}")


ORDER_STREAM = 1


def count(mix: dict, seconds: float) -> int:
    a = mix["arrival"]
    if a["kind"] == "poisson":
        return math.ceil(a["rate_per_s"] * seconds)
    return mix["requests"]


def order_seed(mix: dict, seed: int) -> int:
    """The seed that draws the schedule's order."""
    order = mix.get("order", "fixed")
    if order not in ("fixed", "seed"):
        raise ValueError(f"unknown order {order!r}")
    return seed if order == "seed" else 0


def generate(mix: dict, seed: int, vocab: int,
             seconds: float) -> List[Request]:
    """The mix's requests for a window of ``seconds``."""
    n = count(mix, seconds)
    r = rng(order_seed(mix, seed), ORDER_STREAM)
    prompts = lengths(mix["prompt"], n)[r.permutation(n)]
    outputs = lengths(mix["output"], n)[r.permutation(n)]
    times = arrivals(mix["arrival"], n, r.permutation(n), seconds)
    tok = rng(seed, 2)
    return [Request(float(t), tok.integers(1, vocab, int(p)).astype(np.int32),
                    int(o)) for t, p, o in zip(times, prompts, outputs)]


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (q in [0, 100])."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
