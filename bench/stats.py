"""Percentiles and the window arithmetic of the end-to-end metrics.

``percentile`` is the arithmetic of ``repro.obs.metrics.Histogram``
(linear interpolation, as ``np.quantile``), copied so that the yardstick
stays with the benchmark.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """q in [0, 100]; linear interpolation, matching np.quantile."""
    if len(samples) == 0:
        return None
    return float(np.quantile(np.asarray(samples, np.float64), q / 100.0))


@dataclasses.dataclass
class RequestStamps:
    """Host-clock stamps of one request (seconds, ``time.perf_counter``).

    ``due`` is when the schedule offered it, ``refill`` when a slot took it,
    ``tokens`` when each generated token reached the host."""

    uid: int
    due: float
    refill: Optional[float] = None
    tokens: list = dataclasses.field(default_factory=list)


def ttft_samples(reqs: Sequence[RequestStamps], t0: float, t1: float):
    """(samples_s, attempted, failed) over requests due in [t0, t1).

    A request's TTFT runs from its due time to the host receiving its first
    generated token. One with no token by ``t1`` is failed and enters the
    sample at its age ``t1 - due``."""
    out, attempted, failed = [], 0, 0
    for r in reqs:
        if not t0 <= r.due < t1:
            continue
        attempted += 1
        first = r.tokens[0] if r.tokens else None
        if first is None or first > t1:
            failed += 1
            out.append(t1 - r.due)
        else:
            out.append(first - r.due)
    return out, attempted, failed


def queue_wait_samples(reqs: Sequence[RequestStamps], t0: float, t1: float):
    """Due-to-refill seconds over requests due in [t0, t1); one not taken
    by a slot before ``t1`` enters at its age."""
    out = []
    for r in reqs:
        if not t0 <= r.due < t1:
            continue
        if r.refill is None or r.refill > t1:
            out.append(t1 - r.due)
        else:
            out.append(max(0.0, r.refill - r.due))
    return out


def itl_samples(reqs: Sequence[RequestStamps], t0: float, t1: float):
    """Every gap between consecutive tokens of one request whose later
    token reached the host inside [t0, t1]."""
    out = []
    for r in reqs:
        ts = r.tokens
        for a, b in zip(ts, ts[1:]):
            if t0 <= b <= t1:
                out.append(b - a)
    return out


def tokens_in(reqs: Sequence[RequestStamps], t0: float, t1: float) -> int:
    """Tokens that reached the host inside [t0, t1]."""
    return sum(1 for r in reqs for t in r.tokens if t0 <= t <= t1)
