"""The comparisons that decide ``correct``: gaps between the program's
readings and the reference's.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Optional


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of every leaf, by its path."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              keep: Optional[Dict[str, bool]] = None) -> Dict[str, float]:
    """Each leaf's gap between the program's and the reference's norm, over
    the larger of that leaf's reference norm and the median leaf's. A leaf
    missing on the program's side reads infinite."""
    names = [k for k in want if keep is None or keep[k]]
    floor = statistics.median(want[k] for k in names)
    return {k: abs(got.get(k, math.inf) - want[k])
            / max(want[k], floor, 1e-30) for k in names}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   keep: Optional[Dict[str, bool]] = None) -> float:
    """The largest of ``leaf_gaps``."""
    return max(leaf_gaps(got, want, keep).values())


def moving_leaves(grad_norms: Dict[str, float],
                  share: float = 1e-3) -> Dict[str, bool]:
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's; the others move under Adam by round-off alone."""
    med = statistics.median(grad_norms.values())
    return {k: v >= share * med for k, v in grad_norms.items()}


def checks(numbers: Dict[str, float], limits: dict) -> dict:
    """Each compared number beside its limit; a number with no limit is
    held to 0."""
    return {k: {"value": v, "limit": limits.get(k, 0.0)}
            for k, v in numbers.items()}
