"""Closed loop of back-to-back classifier forwards over a pool of batches.

The pool (``pool_batches`` batches of ``batch`` images) is drawn on the
device from the seed in one jitted call at set-up, as standard-normal
pixels (CIFAR-10 images after per-channel normalisation), and the window
cycles through it.
"""
from __future__ import annotations


def image_pool(traffic: dict, key):
    import jax
    import jax.numpy as jnp

    shape = (int(traffic["pool_batches"]), int(traffic["batch"]),
             *traffic["image_shape"])
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(key)
