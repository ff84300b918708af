"""Pooled embedding-bag lookup — Pallas TPU kernel (DLRM hot spot).

Each grid step handles one (sample, table) pair: gathers L rows from the
table shard resident in HBM/ANY memory by dynamic index and accumulates the
pooled sum in VMEM. On TPU this becomes a sequence of DMA row fetches —
the analogue of the GPU's per-warp gather, adapted to the explicit-DMA TPU
memory hierarchy (no hardware gather on the vector unit).

tables: (T, R, E); indices: (B, T, L) int32 -> out: (B, T, E).

Not on the normal path, and tested in interpret mode only. The TPU compiler
refuses it natively (compiled for a described v5e): the index block
``(1, 1, L)`` breaks the rule that a block's last two dimensions be
divisible by 8 and 128 (or span the array). Its design also maps a whole
``(1, R, E)`` table into VMEM per grid step, so no deployment-sized table
fits; a row-DMA gather from HBM has to replace it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bag_kernel(idx_ref, table_ref, o_ref):
    lpool = idx_ref.shape[-1]

    def body(i, acc):
        row = idx_ref[0, 0, i]
        # Index the leading (blocked) dim with a length-1 dslice too: a bare
        # int here trips pallas' load discharge rule (no .shape on int).
        rows = table_ref[pl.dslice(0, 1), pl.dslice(row, 1), :]
        return acc + rows[0, 0].astype(jnp.float32)

    e = table_ref.shape[-1]
    acc = jax.lax.fori_loop(0, lpool, body,
                            jnp.zeros((e,), jnp.float32))
    o_ref[0, 0] = acc.astype(o_ref.dtype)


def embedding_bag(tables: jax.Array, indices: jax.Array, *,
                  interpret: bool = True) -> jax.Array:
    """tables: (T, R, E); indices: (B, T, L) -> (B, T, E)."""
    t, r, e = tables.shape
    b, t2, lpool = indices.shape
    assert t == t2
    grid = (b, t)
    out = pl.pallas_call(
        _bag_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, lpool), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, r, e), lambda bi, ti: (ti, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, e), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, e), tables.dtype),
        interpret=interpret,
    )(indices, tables)
    return out
