"""Stratified draws from the length distributions a traffic file names.

``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
``{"dist": "uniform", "min": a, "max": b}``, ``{"dist": "fixed", "value": v}``
and ``{"dist": "exponential", "mean": m}``. ``n`` values sit at the quantiles
``(i + 0.5) / n`` and come back in an order shuffled by ``rng``, so every
seed draws the same multiset.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile(spec: dict, q: float) -> float:
    d = spec["dist"]
    if d == "lognormal":
        v = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(q))
    elif d == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    elif d == "fixed":
        v = spec["value"]
    elif d == "exponential":
        v = -spec["mean"] * math.log1p(-q)
    else:
        raise ValueError(f"unknown length distribution {d!r}")
    if "min" in spec:
        v = max(v, spec["min"])
    if "max" in spec:
        v = min(v, spec["max"])
    return v


def stratified(spec: dict, n: int, rng: np.random.Generator,
               integer: bool = True) -> np.ndarray:
    vals = np.array([quantile(spec, (i + 0.5) / n) for i in range(n)])
    if integer:
        vals = np.rint(vals).astype(np.int64)
    return vals[rng.permutation(n)]
