"""Mesh construction and axis conventions.

Axis convention (matching the COMET paper's MP/DP vocabulary):
  "pod"   — inter-pod data parallelism over DCN (multi-pod meshes)
  "data"  — intra-pod data parallelism over ICI
  "model" — tensor/expert parallelism (the paper's MP)

DP degree = pod * data; MP degree = model.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

DATA_AXES: Tuple[str, ...] = ("pod", "data")
MODEL_AXIS = "model"


def build_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings and
    ``with_sharding_constraint`` may name any axis (``jax.make_mesh``
    defaults to ``Explicit`` axes, which reject both)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes present in this mesh, outermost first."""
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def dp_size(mesh: Mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def mp_size(mesh: Mesh) -> int:
    return mesh.shape.get(MODEL_AXIS, 1)


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes used for FSDP-style parameter sharding: intra-pod data axis only
    (all-gathering parameters over DCN every step would be prohibitive —
    the COMET network model quantifies exactly this; see DESIGN.md)."""
    return ("data",) if "data" in mesh.axis_names else ()


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))
