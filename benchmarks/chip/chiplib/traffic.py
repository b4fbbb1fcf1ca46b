"""The one traffic generator: lengths, arrivals and prompts from a mix file
and a seed.

Every seed gets the same work in another order.  Lengths are the
distribution's quantiles at (i + 0.5) / n, and Poisson arrivals are
exponential gaps at those quantiles; the seed only permutes them and draws
the prompt tokens.  So two seeds differ in order and content, never in the
amount of work, and the spread between runs is the system's own.

A length spec is ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
or ``{"dist": "uniform", "min", "max"}``, with an optional ``round_up``:
lengths are clipped to [min, max] and then rounded up to that multiple.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream)."""
    return np.random.default_rng([seed, stream])


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """The ``n`` lengths of ``spec`` in ascending order."""
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if spec["dist"] == "lognormal":
            v = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(p))
        elif spec["dist"] == "uniform":
            v = spec["min"] + p * (spec["max"] - spec["min"])
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        v = min(max(math.ceil(v), spec["min"]), spec["max"])
        r = spec.get("round_up", 1)
        out.append(-(-v // r) * r)
    return out


def buckets(spec: dict) -> list[int]:
    """Every length ``spec`` can give: the shapes to warm up."""
    r = spec.get("round_up", 1)
    lo = -(-spec["min"] // r) * r
    hi = -(-spec["max"] // r) * r
    return list(range(lo, hi + 1, r))


def lengths(spec: dict, n: int, gen: np.random.Generator) -> list[int]:
    return [int(x) for x in gen.permutation(quantile_lengths(spec, n))]


def poisson_arrivals(rate: float, seconds: float, gen: np.random.Generator) -> list[float]:
    """round(rate * seconds) due times in [0, seconds): exponential gaps at
    their quantiles, permuted, scaled to span the window."""
    n = max(1, round(rate * seconds))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = gen.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [float(t) for t in due * (seconds * (n - 0.5) / n) / gaps.sum()]


def prompts(lens: list[int], vocab: int, gen: np.random.Generator) -> list[list[int]]:
    return [gen.integers(0, vocab, n).tolist() for n in lens]
