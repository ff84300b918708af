"""Flash attention, forward and backward — Pallas TPU kernels.

TPU-native adaptation: the GPU flash-attention algorithm is re-tiled for
VMEM + MXU. Query/key blocks are MXU-aligned (multiples of 128 on the
contraction dims); the softmax running statistics (m, l) and the fp32
accumulator live in VMEM scratch that persists across the sequential
kv-block grid dimension (TPU grids execute in order, unlike CUDA thread
blocks — this replaces the GPU kernel's shared-memory reduction).

GQA is handled in the index map: the kv-head block index is derived from
the query-head grid index (``h // group``), so KV is never materialized
per-query-head in HBM.

``flash_attention`` is differentiable (``jax.custom_vjp``). The forward
also writes each query row's logsumexp (fp32), and the backward is two
kernels in the FlashAttention-2 form, both recomputing P from Q, K and the
logsumexp, with D = rowsum(dO * O):

* dK/dV: a grid over kv heads and kv blocks, sequential over the query
  heads of the GQA group and the q blocks; dK and dV accumulate in fp32
  VMEM, so they are never materialized per query head. It works on the
  transposed scores (kv rows, q columns), so the per-row statistics
  broadcast along the lanes as they are stored;
* dQ: a grid over query heads and q blocks, sequential over kv blocks.

Causal kernels skip the blocks above the diagonal, and the index map of the
operand that walks sequentially is clamped to the nearest live block there,
so a skipped block costs no DMA either. Only blocks that the diagonal or the
kv padding cuts compute a mask.

Precision: bf16 (the inputs' dtype) operands into every matmul, fp32
accumulation and softmax statistics; P and dS are cast to the inputs' dtype
before their matmuls.

Layout: q (b, h, sq, d), k/v (b, hkv, skv, d) -> out (b, h, sq, d).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


class Blocks(NamedTuple):
    """(q, kv) block sizes of the forward, dQ and dK/dV kernels, each
    clipped to the sequence length."""
    q: int
    k: int
    q_dq: int
    k_dq: int
    q_dkv: int
    k_dkv: int


# From a sweep over {256, 512, 1024}^2 on one TPU v5e at (4, 9 / 3, 2048,
# 64) bf16, causal (PERF.md §6).
BLOCKS = Blocks(q=1024, k=1024, q_dq=512, k_dq=512, q_dkv=512, k_dkv=512)


def _clip(block: int, n: int) -> int:
    return min(block, max(1, n))


def _pad(x: jax.Array, block: int, axis: int = 2) -> jax.Array:
    extra = (-x.shape[axis]) % block
    if not extra:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, extra)
    return jnp.pad(x, pad)


def _last_kv(qi, bq: int, bk: int):
    """The last kv block a causal q block ``qi`` sees."""
    return (qi * bq + bq - 1) // bk


def _first_q(ki, bq: int, bk: int):
    """The first q block that sees causal kv block ``ki``."""
    return (ki * bk) // bq


def _mask(shape, row0, col0, *, causal: bool, rows_are_q: bool,
          skv_valid: int | None):
    """Where a (rows, cols) block of scores is live: key at or before the
    query (causal) and inside the unpadded keys (``skv_valid``)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    qpos, kpos = (rows, cols) if rows_are_q else (cols, rows)
    mask = None
    if skv_valid is not None:
        mask = kpos < skv_valid
    if causal:
        c = kpos <= qpos
        mask = c if mask is None else mask & c
    return mask


def _when_masked(needs_mask, body):
    """Run ``body(masked)`` with the mask only where ``needs_mask``."""
    if needs_mask is False:
        body(False)
        return

    @pl.when(needs_mask)
    def _():
        body(True)

    @pl.when(jnp.logical_not(needs_mask))
    def _():
        body(False)


def _cuts(q0, k0, bq: int, bk: int, *, causal: bool, skv: int,
          skv_pad: int):
    """Whether the block at (q0, k0) needs a mask: the diagonal crosses it,
    or it holds padded keys."""
    cut = False
    if causal:
        cut = k0 + bk - 1 > q0
    if skv_pad != skv:
        pad = k0 + bk > skv
        cut = pad if cut is False else cut | pad
    return cut


# --------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------- #

def _lanes(bk: int) -> int:
    return 128 if bk % 128 == 0 else bk


def _lane_sums(p: jax.Array) -> jax.Array:
    """(rows, bk) -> (rows, 128) partial row sums, one per lane: the
    running softmax sum adds these with no cross-lane reduction per kv
    block, and reduces across lanes once, at the end."""
    n = _lanes(p.shape[1])
    out = p[:, :n]
    for c in range(n, p.shape[1], n):
        out = out + p[:, c:c + n]
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, bq: int, bk: int,
                skv: int, skv_pad: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0, k0 = qi * bq, ki * bk

    def body(masked: bool):
        q = q_ref[0, 0]                       # (bq, d)
        k = k_ref[0, 0]                       # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_mask(s.shape, q0, k0, causal=causal,
                                rows_are_q=True, skv_valid=skv), s, NEG_INF)
        m_prev = m_scr[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + _lane_sums(p)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    live = k0 <= q0 + bq - 1 if causal else True

    @pl.when(live)
    def _run():
        _when_masked(_cuts(q0, k0, bq, bk, causal=causal, skv=skv,
                           skv_pad=skv_pad), body)

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...].sum(axis=-1, keepdims=True), 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(denom)).reshape(1, bq)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = BLOCKS.q,
                        block_k: int = BLOCKS.k,
                        interpret: bool = False):
    """q: (b, h, sq, d); k/v: (b, hkv, skv, d). Returns the output
    (b, h, sq, d) and each query row's logsumexp (b, h, sq), fp32."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert h % hkv == 0
    group = h // hkv
    bq, bk = _clip(block_q, sq), _clip(block_k, skv)
    qp = _pad(q, bq)
    kp, vp = _pad(k, bk), _pad(v, bk)
    nq, nk = qp.shape[2] // bq, kp.shape[2] // bk

    def q_map(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def row_map(bi, hi, qi, ki):
        return (bi, hi, 0, qi)

    def kv_map(bi, hi, qi, ki):
        if causal:      # a skipped block repeats the last live one: no DMA
            ki = jnp.minimum(ki, _last_kv(qi, bq, bk))
        return (bi, hi // group, ki, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / math.sqrt(d), causal=causal, bq=bq, bk=bk,
        skv=skv, skv_pad=kp.shape[2])
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map)],
        out_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                   pl.BlockSpec((1, 1, 1, bq), row_map)],
        out_shape=[jax.ShapeDtypeStruct(qp.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, qp.shape[2]), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),                 # running max
            pltpu.VMEM((bq, _lanes(bk)), jnp.float32),        # running sum
            pltpu.VMEM((bq, d), jnp.float32),                 # accumulator
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    return out[:, :, :sq], lse[:, :, 0, :sq]


# --------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------- #

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale: float, causal: bool, bq: int, bk: int,
               skv: int, skv_pad: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q0, k0 = qi * bq, ki * bk

    def body(masked: bool):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0, 0].reshape(bq)[:, None])
        if masked:
            p = jnp.where(_mask(s.shape, q0, k0, causal=causal,
                                rows_are_q=True, skv_valid=skv), p, 0.0)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0].reshape(bq)[:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    live = k0 <= q0 + bq - 1 if causal else True

    @pl.when(live)
    def _run():
        _when_masked(_cuts(q0, k0, bq, bk, causal=causal, skv=skv,
                           skv_pad=skv_pad), body)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale: float, causal: bool,
                bq: int, bk: int):
    ki, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    ng, nq = pl.num_programs(3), pl.num_programs(4)

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q0, k0 = qi * bq, ki * bk

    def body(masked: bool):
        # Transposed scores: kv rows, q columns. Padded keys need no mask
        # here: they only reach their own (discarded) rows of dK and dV.
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(st - lse_ref[0, 0])
        if masked:
            pt = jnp.where(_mask(st.shape, k0, q0, causal=causal,
                                 rows_are_q=False, skv_valid=None), pt, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0]) * scale
        dk_scr[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    live = k0 <= q0 + bq - 1 if causal else True

    @pl.when(live)
    def _run():
        _when_masked(k0 + bk - 1 > q0 if causal else False, body)

    @pl.when((g == ng - 1) & (qi == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq(q, k, v, dout, lse, delta, *, causal: bool, block_q: int,
            block_k: int, interpret: bool) -> jax.Array:
    """dQ: one program per (q head, q block), walking the kv blocks.
    ``lse`` and ``delta`` are rows, (b, h, 1, sq)."""
    b, h, sq, d = q.shape
    group, skv = h // k.shape[1], k.shape[2]
    bq, bk = _clip(block_q, sq), _clip(block_k, skv)
    qp, dop = _pad(q, bq), _pad(dout, bq)
    lsep, deltap = _pad(lse, bq, 3), _pad(delta, bq, 3)
    kp, vp = _pad(k, bk), _pad(v, bk)
    nq, nk = qp.shape[2] // bq, kp.shape[2] // bk

    def q_map(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def row_map(bi, hi, qi, ki):
        return (bi, hi, 0, qi)

    def kv_map(bi, hi, qi, ki):
        if causal:
            ki = jnp.minimum(ki, _last_kv(qi, bq, bk))
        return (bi, hi // group, ki, 0)

    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, bq=bq, bk=bk, skv=skv,
                          skv_pad=kp.shape[2]),
        grid=(b, h, nq, nk),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map)],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap)[:, :, :sq]


def _bwd_dkv(q, k, v, dout, lse, delta, *, causal: bool, block_q: int,
             block_k: int, interpret: bool):
    """dK, dV: one program per (kv head, kv block), walking the group's
    query heads and the q blocks. ``lse`` and ``delta`` as in ``_bwd_dq``."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    bq, bk = _clip(block_q, sq), _clip(block_k, skv)
    qp, dop = _pad(q, bq), _pad(dout, bq)
    lsep, deltap = _pad(lse, bq, 3), _pad(delta, bq, 3)
    kp, vp = _pad(k, bk), _pad(v, bk)
    nq, nk = qp.shape[2] // bq, kp.shape[2] // bk

    def live_q(ki, qi):     # a skipped block repeats the first live one
        return jnp.maximum(qi, _first_q(ki, bq, bk)) if causal else qi

    def q_map(bi, hi, ki, g, qi):
        return (bi, hi * group + g, live_q(ki, qi), 0)

    def row_map(bi, hi, ki, g, qi):
        return (bi, hi * group + g, 0, live_q(ki, qi))

    def k_map(bi, hi, ki, g, qi):
        return (bi, hi, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, bq=bq, bk=bk),
        grid=(b, hkv, nk, group, nq),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, bk, d), k_map),
                  pl.BlockSpec((1, 1, bk, d), k_map),
                  pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map)],
        out_specs=[pl.BlockSpec((1, 1, bk, d), k_map),
                   pl.BlockSpec((1, 1, bk, d), k_map)],
        out_shape=[jax.ShapeDtypeStruct(kp.shape, k.dtype),
                   jax.ShapeDtypeStruct(vp.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS + ("arbitrary",)),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap)
    return dk[:, :, :skv], dv[:, :, :skv]


# --------------------------------------------------------------------- #
# Differentiable entry point
# --------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, blocks: Blocks = BLOCKS,
                    interpret: bool = False) -> jax.Array:
    """q: (b, h, sq, d); k/v: (b, hkv, skv, d) -> (b, h, sq, d)."""
    return flash_attention_fwd(q, k, v, causal=causal, block_q=blocks.q,
                               block_k=blocks.k, interpret=interpret)[0]


def _vjp_fwd(q, k, v, causal, blocks, interpret):
    out, lse = flash_attention_fwd(q, k, v, causal=causal, block_q=blocks.q,
                                   block_k=blocks.k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, blocks, interpret, res, dout):
    q, k, v, out, lse = res
    lse = lse[:, :, None, :]
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    dq = _bwd_dq(q, k, v, dout, lse, delta, causal=causal,
                 block_q=blocks.q_dq, block_k=blocks.k_dq, interpret=interpret)
    dk, dv = _bwd_dkv(q, k, v, dout, lse, delta, causal=causal,
                      block_q=blocks.q_dkv, block_k=blocks.k_dkv,
                      interpret=interpret)
    return dq, dk, dv


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
