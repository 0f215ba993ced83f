"""Closed loop of long sessions, one per slot.

``sessions`` sessions run at once; a finished one is replaced at once by a
fresh one, so the offered load follows the system. Sessions come in blocks
of ``sessions``: each block holds the same stratified set of prompt and
output lengths (see ``lengths.stratified``) in a seed-shuffled order.
"""
from __future__ import annotations

import numpy as np

from bench.seeds import host_rng
from bench.traffic.lengths import stratified

KIND = "closed"


def session_stream(traffic: dict, seed: int, vocab: int):
    """Endless iterator of (prompt int32 array, max_new)."""
    n = int(traffic["sessions"])
    rng = host_rng(seed, 1)
    tok_rng = host_rng(seed, 2)
    while True:
        prompts = stratified(traffic["prompt_tokens"], n, rng)
        outputs = stratified(traffic["output_tokens"], n, rng)
        for p, o in zip(prompts, outputs):
            yield (tok_rng.integers(1, vocab, int(p), dtype=np.int32), int(o))
