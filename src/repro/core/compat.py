"""Small shared helpers of both kernel packages (``repro.kernels`` and
``repro.xnor``): backend dispatch and block rounding."""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
