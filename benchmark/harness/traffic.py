"""Lengths and arrival gaps for a traffic mix: the distribution's stratified
quantiles, in an order drawn from the mix's own `schedule_seed`.

A window at these request rates holds some tens of requests, and which
request meets which moves a median by more than any change a PR makes. So
the schedule (sizes, order, due times) belongs to the traffic file and is
the same in every run; `--seed` draws the weights and every prompt's text.
"""

from __future__ import annotations

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n values at the quantiles (i + 0.5) / n of the distribution `spec`:
    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    {"dist": "uniform", "min", "max"}; whole numbers, clipped."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(p) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def sizes(traffic: dict, n: int, rng: np.random.Generator) -> list[tuple[int, int]]:
    """n (prompt tokens, output tokens) pairs, each axis permuted on its own."""
    p = rng.permutation(quantiles(traffic["prompt_tokens"], n))
    o = rng.permutation(quantiles(traffic["output_tokens"], n))
    return [(int(a), int(b)) for a, b in zip(p, o)]


def poisson_arrivals(rate: float, seconds: float, rng: np.random.Generator) -> list[float]:
    """Due times in [0, seconds): n = rate x seconds exponential gaps at
    their stratified quantiles, permuted, scaled to fill the window."""
    n = max(1, round(rate * seconds))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return [float(t) for t in np.cumsum(gaps) - gaps[0] * 0.5]
