"""Elastic scaling: re-mesh a checkpoint onto a different device count.

When a pod loses hosts (or gains them back), the job restarts with a
different device count. Parameters/optimizer state are *logical* arrays —
the checkpoint stores them unsharded (host-side), so elastic restart is:

  1. build the largest valid mesh from the surviving devices
     (:func:`best_mesh_shape`),
  2. restore the checkpoint through the template,
  3. ``jax.device_put`` each leaf with its PartitionSpec resolved against
     the *new* mesh (:func:`reshard`).

The data pipeline needs no adjustment (batches are step-indexed), and the
global batch is preserved by raising ``microbatches`` when fewer chips must
fit the same tokens (``adjust_microbatching``).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import make_mesh


def best_mesh_shape(n_devices: int, model_parallel: int,
                    axis_names=("data", "model")) -> tuple[int, ...]:
    """Largest (data, model) grid for n_devices, keeping TP if possible."""
    tp = math.gcd(n_devices, model_parallel)
    while tp > 1 and n_devices % tp:
        tp //= 2
    return (n_devices // max(tp, 1), max(tp, 1))


def make_elastic_mesh(model_parallel: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    shape = best_mesh_shape(len(devices), model_parallel)
    return make_mesh(shape, ("data", "model"), devices=devices)


def reshard(tree, pspecs, mesh: Mesh):
    """Places a host-side pytree onto ``mesh`` under ``pspecs``."""

    def put(leaf, spec):
        spec = spec if isinstance(spec, P) else P()
        # drop axes that exceed the leaf rank or don't divide its dims
        usable = []
        for i, ax in enumerate(spec):
            if ax is None:
                usable.append(None)
                continue
            size = mesh.shape[ax] if isinstance(ax, str) else \
                math.prod(mesh.shape[a] for a in ax)
            if i < leaf.ndim and leaf.shape[i] % size == 0:
                usable.append(ax)
            else:
                usable.append(None)
        return jax.device_put(leaf, NamedSharding(mesh, P(*usable)))

    return jax.tree.map(put, tree, pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def adjust_microbatching(global_batch: int, old_devices: int,
                         new_devices: int, old_microbatches: int = 1) -> int:
    """Keep the global batch (and thus the loss trajectory) constant when
    the device count shrinks: scale gradient-accumulation steps up."""
    if new_devices >= old_devices:
        return old_microbatches
    factor = -(-old_devices // new_devices)  # ceil
    return old_microbatches * factor
