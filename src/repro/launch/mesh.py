"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS *before* any jax
init; smoke tests must keep seeing 1 device).
"""

from __future__ import annotations

import jax

from repro.parallel.sharding import make_mesh as _make_mesh

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips/pod; 2 pods on the multi-pod mesh (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...]) -> jax.sharding.Mesh:
    """Elastic-runtime entry: arbitrary (pod?, data, model) shapes."""
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return _make_mesh(shape, axes)
