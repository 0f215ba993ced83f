"""One seed, every random draw of a run.

``--seed`` may be any whole number up to 64 bits. ``jax.random.key`` keeps
only its low 32 bits, so the key is built from both halves; below 2**32 it
equals ``jax.random.key(seed)``."""
from __future__ import annotations

import numpy as np

MASK32 = (1 << 32) - 1


def model_key(seed: int):
    """The PRNG key the configuration's weights (or data) are drawn from."""
    import jax

    s = int(seed)
    if s < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    words = np.array([(s >> 32) & MASK32, s & MASK32], np.uint32)
    return jax.random.wrap_key_data(words)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for host-side draws (traffic, sampling); separate
    ``stream`` numbers never share a sequence."""
    return np.random.default_rng([int(seed), int(stream)])
