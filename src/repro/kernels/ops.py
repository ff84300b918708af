"""jit'd public wrappers around the Pallas kernels.

The caller chooses the implementation: ``impl="pallas"`` runs the kernel
(natively on a TPU, or in Pallas' interpreter with ``interpret=True``) and
``impl="ref"`` runs the jnp oracle. Nothing here looks at the platform, so
a run on the wrong device fails instead of quietly measuring another path.

The models do not go through these wrappers: ``models.common.attention``
calls the differentiable flash kernel itself, where it is lowered for a TPU
(forward and backward on the train path; once per shard under a mesh). The
SSD scan, RMSNorm and embedding-bag kernels are on no model's path.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag as _bag_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_kernel
from repro.kernels.ssd_scan import ssd_scan as _ssd_kernel


def _check_impl(impl: str) -> None:
    if impl not in ("pallas", "ref"):
        raise ValueError(f"impl must be 'pallas' or 'ref', got {impl!r}")


@functools.partial(jax.jit, static_argnames=("causal", "impl", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, *, impl: str,
                    interpret: bool = False) -> jax.Array:
    """q: (b, h, sq, d), k/v: (b, hkv, skv, d)."""
    _check_impl(impl)
    if impl == "pallas":
        return _flash_kernel(q, k, v, causal, interpret=interpret)
    return ref.attention_ref(q, k, v, causal=causal)


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, bmat: jax.Array,
        cmat: jax.Array, *, impl: str, interpret: bool = False):
    _check_impl(impl)
    if impl == "pallas":
        return _ssd_kernel(x, dt, a, bmat, cmat, interpret=interpret)
    return ref.ssd_ref(x, dt, a, bmat, cmat)


@functools.partial(jax.jit, static_argnames=("eps", "impl", "interpret"))
def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5, *,
            impl: str, interpret: bool = False) -> jax.Array:
    _check_impl(impl)
    if impl == "pallas":
        return _rmsnorm_kernel(x, gamma, eps=eps, interpret=interpret)
    return ref.rmsnorm_ref(x, gamma, eps)


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def embedding_bag(tables: jax.Array, indices: jax.Array, *, impl: str,
                  interpret: bool = False) -> jax.Array:
    _check_impl(impl)
    if impl == "pallas":
        return _bag_kernel(tables, indices, interpret=interpret)
    return ref.embedding_bag_ref(tables, indices)
