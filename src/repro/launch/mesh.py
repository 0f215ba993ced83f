"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests see 1 device; only
``launch/dryrun.py`` sets the 512-placeholder-device XLA flag.
"""
from __future__ import annotations

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for in-test dry-runs (subprocess with 8 host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def chips(mesh) -> int:
    return mesh.devices.size
