"""Open-loop request schedule: independent users arriving at a fixed rate.

Arrivals are Poisson at ``rate_per_s``; prompt and output lengths follow the
distributions the traffic file names. Every seed gets the same set of gaps
and lengths, in another order: each set is drawn at the quantiles
``(i + 0.5) / n`` of its distribution and shuffled by the seed, so runs of
different seeds differ by ordering and token ids, not by how much work
they offer.
"""
from __future__ import annotations

import math

import numpy as np

from bench.seeds import host_rng
from bench.traffic.lengths import stratified

KIND = "open"


def schedule(traffic: dict, seed: int, horizon_s: float, vocab: int):
    """[(due_offset_s, prompt int32 array, max_new)] for ``horizon_s``
    seconds of arrivals."""
    rate = float(traffic["rate_per_s"])
    n = max(1, math.ceil(rate * horizon_s))
    rng = host_rng(seed, 1)
    gaps = stratified({"dist": "exponential", "mean": 1.0 / rate}, n, rng,
                      integer=False)
    prompts = stratified(traffic["prompt_tokens"], n, rng)
    outputs = stratified(traffic["output_tokens"], n, rng)
    due = np.cumsum(gaps) - gaps[0]
    tok_rng = host_rng(seed, 2)
    out = []
    for i in range(n):
        prompt = tok_rng.integers(1, vocab, int(prompts[i]), dtype=np.int32)
        out.append((float(due[i]), prompt, int(outputs[i])))
    return out
