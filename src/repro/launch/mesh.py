"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before the first
jax initialization.
"""

from __future__ import annotations

from repro.parallel.mesh import build_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods = 512 chips as (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return build_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small host-device mesh for tests (XLA_FLAGS device_count >= d*m)."""
    return build_mesh((data, model), ("data", "model"))
