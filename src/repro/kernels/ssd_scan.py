"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU-native adaptation of the SSD algorithm (state-space duality): each chunk
becomes three MXU GEMMs (CB^T masked "attention", state build, state apply);
the (p x n) inter-chunk state is carried in fp32 VMEM scratch across the
sequential chunk grid dimension. On GPU this recurrence needs a separate
kernel launch or grid-wide sync; the TPU sequential grid makes it a single
kernel.

All decay terms are exp of non-positive cumsums (A < 0, dt > 0), so the
kernel is numerically stable without rescaling.

Layout: x (b, h, s, p), dt (b, h, s), A (h,), Bmat/Cmat (b, h, s, n)
        -> y (b, h, s, p), final_state (b, h, p, n).

Not on the normal path, and tested in interpret mode only. The TPU compiler
refuses it natively at mamba2-780m widths (48 heads, chunk 256, compiled
for a described v5e): the ``dt`` block ``(1, 1, chunk)`` breaks the rule
that a block's last two dimensions be divisible by 8 and 128 (or span the
array), and the ``(1,)`` block of ``A`` is of the same kind. Redesign the
blocking before putting the kernel on the path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_out_ref,
                state_scr, *, chunk: int, s_valid: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)        # (Q, p)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (Q,)
    a = a_ref[0].astype(jnp.float32)           # scalar decay rate (negative)
    bm = b_ref[0, 0].astype(jnp.float32)       # (Q, n)
    cm = c_ref[0, 0].astype(jnp.float32)       # (Q, n)

    # Zero padded tail positions (dt = 0 -> identity recurrence).
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk,), 0)
    dt = jnp.where(pos < s_valid, dt, 0.0)

    dA = dt * a                                 # (Q,) <= 0
    cs = jnp.cumsum(dA)
    # L[i, j] = exp(sum_{j+1..i} dA) for i >= j else 0.
    diff = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)

    dtx = x * dt[:, None]                       # (Q, p)
    # Diagonal (within-chunk) term.
    G = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(G * L, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, p)
    # Off-diagonal: apply carried state.
    prev = state_scr[...]                       # (p, n)
    decay_in = jnp.exp(cs)                      # (Q,)
    y += decay_in[:, None] * jax.lax.dot_general(
        cm, prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (Q, n) x (p, n)^T
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # State update: S = S * exp(sum dA) + (dtx * decay_to_end)^T @ B.
    decay_out = jnp.exp(cs[-1] - cs)            # (Q,)
    new_state = prev * jnp.exp(cs[-1]) + jax.lax.dot_general(
        dtx * decay_out[:, None], bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (p, n)
    state_scr[...] = new_state

    @pl.when(ci == nc - 1)
    def _emit_state():
        st_out_ref[0, 0] = new_state


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array,
             bmat: jax.Array, cmat: jax.Array, *, chunk: int = 256,
             interpret: bool = True):
    """Returns (y: (b, h, s, p), final_state: (b, h, p, n))."""
    b, h, s, p = x.shape
    n = bmat.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
        bmat = jnp.pad(bmat, ((0, 0), (0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = x.shape[2] // chunk
    grid = (b, h, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, s_valid=s)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, bmat, cmat)
    return y[:, :, :s, :], st
