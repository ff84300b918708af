"""GPipe-style pipeline parallelism via shard_map + ppermute.

Opt-in capability (the assigned production mesh uses DP x TP; PP becomes
profitable past ICI-domain limits — COMET's collective model quantifies the
crossover). The schedule is the classic GPipe fill-drain: M microbatches
over S stages, bubble fraction (S-1)/(M+S-1).

``gpipe`` is differentiable end-to-end: ppermute is linear, so jax.grad
produces the reversed communication schedule for the backward pass
automatically — no hand-written backward pipeline.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PIPE_AXIS = "pipe"


def gpipe(
    stage_fn: Callable,            # (stage_params, x_mb) -> y_mb
    stage_params,                  # pytree stacked on leading S axis
    x: jax.Array,                  # (M, mb, ...) microbatched input
    *,
    mesh: Mesh,
    axis: str = PIPE_AXIS,
) -> jax.Array:
    """Returns (M, mb, ...) outputs of the final stage."""
    s = mesh.shape[axis]
    m = x.shape[0]

    def body(params, xs):
        # params: leading stage axis of size 1 on each device
        local = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        state = jnp.zeros_like(stage_fn(local, xs[0]))  # activation buffer
        outs = jnp.zeros((m,) + state.shape, state.dtype)
        perm = [(i, (i + 1) % s) for i in range(s)]
        for t in range(m + s - 1):
            mb = min(t, m - 1)
            x_in = jnp.where(idx == 0, xs[mb], state)
            y = stage_fn(local, x_in)
            out_mb = t - (s - 1)
            if out_mb >= 0:
                write = jnp.where(idx == s - 1, y, outs[out_mb])
                outs = outs.at[out_mb].set(write)
            state = jax.lax.ppermute(y, axis, perm)
        # broadcast final-stage outputs to all pipe ranks
        outs = jax.lax.psum(
            jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),       # params sharded by stage, x replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
