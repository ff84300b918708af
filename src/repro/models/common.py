"""Shared model layers: norms, RoPE, GQA attention (full / blockwise /
cached-decode), FFN, MoE block, embeddings.

Everything is functional JAX: parameters are nested dicts of jnp arrays,
layers are pure functions. Layer stacks use stacked parameters + lax.scan so
the lowered HLO stays O(1) in depth (compile time matters at 512 devices).

Self-attention without a cache (``attention``) runs the Pallas flash kernel
of ``repro.kernels.flash_attention``, forward and backward, when it is
lowered for a TPU (under a mesh, once per (data, model) shard inside
``shard_map``); on every other backend, and in the dry-run, it runs the
XLA path here (naive, or the pure-JAX blockwise algorithm that the kernel
implements on-chip, for long sequences).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax._src import mesh as mesh_lib
from jax.sharding import AxisType, PartitionSpec as P

from repro.kernels.flash_attention import BLOCKS, Blocks, flash_attention
from repro.parallel.mesh import DATA_AXES, MODEL_AXIS

DEFAULT_DTYPE = jnp.bfloat16

# Sequence length at/above which attention switches to the blockwise
# (flash-style) path to avoid materializing seq x seq score tensors.
BLOCKWISE_THRESHOLD = 4096
Q_BLOCK = 1024
KV_BLOCK = 1024
# Head dims the Pallas flash kernel is compiled for (tests/test_tpu_compile.py).
FLASH_HEAD_DIMS = (64, 128)


# --------------------------------------------------------------------- #
# Initializers
# --------------------------------------------------------------------- #

def dense_init(key, shape, dtype=DEFAULT_DTYPE, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=DEFAULT_DTYPE):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------- #

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------- #
# RoPE (with partial-rotary support for chatglm3's "2d RoPE")
# --------------------------------------------------------------------- #

def rope_frequencies(head_dim: int, fraction: float, theta: float,
                     positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for the rotary fraction of the head dim.

    positions: (..., seq) int32. Returns (..., seq, rot_dim//2) fp32 each."""
    rot_dim = int(head_dim * fraction)
    rot_dim -= rot_dim % 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                                / rot_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (batch, seq, heads, head_dim); cos/sin: (batch, seq, rot//2)."""
    rot = 2 * cos.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    c = cos[..., None, :].astype(x.dtype)  # broadcast over heads
    s = sin[..., None, :].astype(x.dtype)
    # interleave back
    y1 = x1 * c - x2 * s
    y2 = x2 * c + x1 * s
    y = jnp.stack([y1, y2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([y, x_pass], axis=-1) if x_pass.shape[-1] else y


# --------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------- #

def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """(b, s, kv_heads, d) -> (b, s, q_heads, d) by group broadcast."""
    kv_heads = k.shape[-2]
    if kv_heads == num_q_heads:
        return k
    reps = num_q_heads // kv_heads
    return jnp.repeat(k, reps, axis=-2)


def naive_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    q_offset: int = 0) -> jax.Array:
    """Reference attention. q: (b, sq, h, d), k/v: (b, skv, h_kv, d)."""
    b, sq, h, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        q_block: int = Q_BLOCK,
                        kv_block: int = KV_BLOCK) -> jax.Array:
    """Flash-style attention: O(seq) memory via running-max softmax.

    Outer scan over query blocks, inner scan over kv blocks. This is the
    jnp oracle of the Pallas flash kernel (same tiling, on-chip there)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    # Pad to block multiples.
    pq = (-sq) % q_block
    pk = (-skv) % kv_block
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // q_block, kp.shape[1] // kv_block
    scale = 1.0 / math.sqrt(d)

    kb = kp.reshape(b, nk, kv_block, h, d)
    vb = vp.reshape(b, nk, kv_block, h, d)

    def q_step(_, qi):
        qblk, qidx = qi  # (b, qb, h, d), scalar block index

        def kv_step(carry, ki):
            acc, m, denom = carry
            kblk, vblk, kidx = ki
            logits = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk)
            logits = logits.astype(jnp.float32) * scale
            if causal:
                qpos = qidx * q_block + jnp.arange(q_block)
                kpos = kidx * kv_block + jnp.arange(kv_block)
                mask = kpos[None, :] <= qpos[:, None]
                logits = jnp.where(mask[None, None], logits, -1e30)
            # mask kv padding
            kvalid = (kidx * kv_block + jnp.arange(kv_block)) < skv
            logits = jnp.where(kvalid[None, None, None, :], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            denom_new = denom * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(qblk.dtype), vblk)
            acc_new = acc * alpha[..., None] + pv.astype(jnp.float32)
            return (acc_new, m_new, denom_new), None

        acc0 = jnp.zeros((b, h, q_block, d), jnp.float32)
        m0 = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        denom0 = jnp.zeros((b, h, q_block), jnp.float32)
        (acc, m, denom), _ = jax.lax.scan(
            kv_step, (acc0, m0, denom0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             jnp.arange(nk)))
        out = acc / jnp.maximum(denom, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    qb = qp.reshape(b, nq, q_block, h, d).transpose(1, 0, 2, 3, 4)
    _, outs = jax.lax.scan(q_step, None, (qb, jnp.arange(nq)))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, nq * q_block, h, d)
    return out[:, :sq].transpose(0, 1, 2, 3)


def _mesh():
    """The mesh in context: the abstract one (``jax.set_mesh``, or the
    manual axes inside a ``shard_map``), else the physical one that a
    ``with mesh:`` block enters."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size <= 1:
        mesh = mesh_lib.thread_resources.env.physical_mesh
    return mesh


def data_groups(batch: int) -> int:
    """The number of data shards of the mesh in context: the product of
    its ``pod`` and ``data`` axes (``repro.parallel.mesh.DATA_AXES``), where
    it divides ``batch``; 1 off a mesh."""
    mesh = _mesh()
    n = math.prod(size for name, size in mesh.shape.items()
                  if name in DATA_AXES)
    return n if n > 1 and batch % n == 0 else 1


def _shard_spec(mesh, q: jax.Array, k: jax.Array) -> Optional[P]:
    """The spec of q, k and v, (b, s, h, d), under which the flash kernel
    runs once per shard of ``mesh``: the batch over the data axes, the
    heads over ``model``, as the projections lay them out. None where the
    mesh's devices are not TPUs (an abstract mesh that names no device
    kind may be), an axis is already manual, another axis spans more than
    one device, or the batch or either head count does not divide its
    axes."""
    if isinstance(mesh, jax.sharding.Mesh):
        kind = mesh.devices.flat[0].device_kind
    else:
        kind = mesh.abstract_device and mesh.abstract_device.device_kind
    if kind and not kind.startswith("TPU"):
        return None
    if any(t == AxisType.Manual for t in mesh.axis_types):
        return None
    if any(size > 1 and name not in DATA_AXES + (MODEL_AXIS,)
           for name, size in mesh.shape.items()):
        return None
    data = tuple(a for a in DATA_AXES if a in mesh.shape)
    model = mesh.shape.get(MODEL_AXIS, 1)
    if (q.shape[0] % math.prod(mesh.shape[a] for a in data)
            or q.shape[2] % model or k.shape[2] % model):
        return None
    return P(data or None, None,
             MODEL_AXIS if MODEL_AXIS in mesh.shape else None, None)


def _flash(q, k, v, causal: bool, blocks: Blocks = BLOCKS,
           interpret: bool = False):
    """The flash kernel on q, k and v in the models' (b, s, h, d) layout."""
    out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal, blocks, interpret)
    return out.transpose(0, 2, 1, 3)


def flash_per_shard(mesh, spec: P, causal: bool, blocks: Blocks = BLOCKS,
                    interpret: bool = False):
    """``_flash`` once per shard of q, k and v laid out over ``mesh`` by
    ``spec`` (``_shard_spec``): each device runs the kernel on its own
    sequences and heads, with no collective. ``check_vma`` is off because
    the kernel's outputs name no varying axes."""
    return jax.shard_map(
        functools.partial(_flash, causal=causal, blocks=blocks,
                          interpret=interpret),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)


def attention(q, k, v, causal=True, q_offset: int = 0):
    """q: (b, sq, h, d), k/v: (b, skv, h_kv, d).

    Self-attention (``sq == skv``, no offset) at a head dim the kernel
    tiles runs the Pallas flash kernel where it is lowered for a TPU: on
    one device as it is, under a mesh once per (data, model) shard
    (``flash_per_shard``) where ``_shard_spec`` finds one. Every other
    call, and every other backend, takes XLA's path: blockwise for long
    sequences, naive otherwise."""
    def xla(q, k, v):
        if q.shape[1] >= BLOCKWISE_THRESHOLD and q.shape[1] == k.shape[1]:
            return blockwise_attention(q, k, v, causal=causal)
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset)

    if (q.shape[1] != k.shape[1] or q_offset != 0
            or q.shape[-1] not in FLASH_HEAD_DIMS):
        return xla(q, k, v)
    tpu = functools.partial(_flash, causal=causal)
    mesh = _mesh()
    if mesh.size > 1:
        # A pallas_call left to GSPMD would run replicated on every device.
        spec = _shard_spec(mesh, q, k)
        if spec is None:
            return xla(q, k, v)
        tpu = flash_per_shard(mesh, spec, causal)
    return jax.lax.platform_dependent(q, k, v, tpu=tpu, default=xla)


# --------------------------------------------------------------------- #
# GQA attention block (params + apply, with optional KV cache)
# --------------------------------------------------------------------- #

def init_attention_params(key, d_in: int, d_out: int, num_heads: int,
                          num_kv_heads: int, head_dim: int,
                          dtype=DEFAULT_DTYPE) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d_in, num_heads * head_dim), dtype),
        "wk": dense_init(k2, (d_in, num_kv_heads * head_dim), dtype),
        "wv": dense_init(k3, (d_in, num_kv_heads * head_dim), dtype),
        "wo": dense_init(k4, (num_heads * head_dim, d_out), dtype,
                         scale=1.0 / math.sqrt(num_heads * head_dim)),
    }


def _batch_shard(t: jax.Array) -> jax.Array:
    """Constrain the leading (batch) dim over ("data", "model") — used when
    attention heads cannot shard over the model axis (see ModelConfig
    .attn_batch_shard)."""
    return jax.lax.with_sharding_constraint(
        t, P(("data", "model"), *([None] * (t.ndim - 1))))


def attention_block(
    params: dict,
    x: jax.Array,                   # (b, s, d_in)
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_fraction: float = 1.0,
    rope_theta: float = 10_000.0,
    causal: bool = True,
    positions: Optional[jax.Array] = None,
    kv_cache: Optional[dict] = None,   # {"k","v": (b, max_s, hkv, d), "pos"}
    xkv: Optional[jax.Array] = None,   # cross-attention source
    precomputed_kv: bool = False,      # kv_cache holds frozen cross K/V
    batch_shard: bool = False,         # shard batch over ("data","model")
    q_scale: float = 1.0,              # scores scale is q_scale/sqrt(head_dim)
) -> Tuple[jax.Array, Optional[dict]]:
    b, s, _ = x.shape
    src = x if xkv is None else xkv
    q = (x @ params["wq"]).reshape(b, s, num_heads, head_dim)
    k = (src @ params["wk"]).reshape(b, src.shape[1], num_kv_heads, head_dim)
    v = (src @ params["wv"]).reshape(b, src.shape[1], num_kv_heads, head_dim)
    if batch_shard and kv_cache is None:
        q, k, v = _batch_shard(q), _batch_shard(k), _batch_shard(v)

    # Cache position clock is a PER-SEQUENCE (b,) vector so continuous
    # batching can host sequences at different depths in one static batch.
    offset = None
    if kv_cache is not None and not precomputed_kv:
        offset = kv_cache["pos"]
        if offset.ndim == 0:
            offset = jnp.broadcast_to(offset, (b,))
    if rope_fraction > 0 and xkv is None and not precomputed_kv:
        base = jnp.arange(s)[None, :]
        qpos = (positions if positions is not None
                else (base + offset[:, None] if offset is not None else base))
        cos, sin = rope_frequencies(head_dim, rope_fraction, rope_theta, qpos)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if q_scale != 1.0:
        q = q * q_scale

    new_cache = None
    if kv_cache is not None and not precomputed_kv and xkv is None:
        kd = k.astype(kv_cache["k"].dtype)
        vd = v.astype(kv_cache["v"].dtype)
        if s == 1:
            # decode: per-sequence scatter at each slot's own position
            bi = jnp.arange(b)
            kc = kv_cache["k"].at[bi, offset].set(kd[:, 0])
            vc = kv_cache["v"].at[bi, offset].set(vd[:, 0])
        else:
            # prefill: fresh cache, all slots start at 0
            kc = jax.lax.dynamic_update_slice(kv_cache["k"], kd, (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(kv_cache["v"], vd, (0, 0, 0, 0))
        new_cache = {"k": kc, "v": vc, "pos": offset + s}
        # Attend over the full cache with per-sequence position masking.
        kpos = jnp.arange(kc.shape[1])                       # (S,)
        qpos = jnp.arange(s)[None, :] + offset[:, None]      # (b, s)
        mask = (kpos[None, None, :] <= qpos[:, :, None])     # (b, s, S)
        kk = _repeat_kv(kc.astype(q.dtype), num_heads)
        vv = _repeat_kv(vc.astype(q.dtype), num_heads)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
        logits = logits / math.sqrt(head_dim)
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    elif kv_cache is not None:  # cross-attention with precomputed KV cache
        kk = _repeat_kv(kv_cache["k"].astype(q.dtype), num_heads)
        vv = _repeat_kv(kv_cache["v"].astype(q.dtype), num_heads)
        out = naive_attention(q, kk, vv, causal=False)
        new_cache = kv_cache
    else:
        out = attention(q, k, v, causal=causal)
    out = out.reshape(b, s, num_heads * head_dim)
    return out @ params["wo"], new_cache


# --------------------------------------------------------------------- #
# FFN
# --------------------------------------------------------------------- #

def init_ffn_params(key, d_model: int, d_ff: int, activation: str,
                    dtype=DEFAULT_DTYPE) -> dict:
    ks = jax.random.split(key, 3)
    if activation == "swiglu":
        return {
            "wg": dense_init(ks[0], (d_model, d_ff), dtype),
            "wu": dense_init(ks[1], (d_model, d_ff), dtype),
            "wd": dense_init(ks[2], (d_ff, d_model), dtype),
        }
    return {
        "wu": dense_init(ks[0], (d_model, d_ff), dtype),
        "wd": dense_init(ks[1], (d_ff, d_model), dtype),
    }


def ffn_block(params: dict, x: jax.Array, activation: str) -> jax.Array:
    if activation == "swiglu":
        return (jax.nn.silu(x @ params["wg"]) * (x @ params["wu"])) @ params["wd"]
    return jax.nn.gelu(x @ params["wu"]) @ params["wd"]


# --------------------------------------------------------------------- #
# MoE block (capacity-based top-k routing, EP/expert-TP shardable)
# --------------------------------------------------------------------- #

def init_moe_params(key, d_model: int, d_ff: int, num_experts: int,
                    activation: str, shared_d_ff: int = 0,
                    dtype=DEFAULT_DTYPE) -> dict:
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d_model, num_experts), jnp.float32),
        "we_up": dense_init(ks[1], (num_experts, d_model, d_ff), dtype),
        "we_down": dense_init(ks[2], (num_experts, d_ff, d_model), dtype),
    }
    if activation == "swiglu":
        p["we_gate"] = dense_init(ks[3], (num_experts, d_model, d_ff), dtype)
    if shared_d_ff:
        p["shared"] = init_ffn_params(ks[4], d_model, shared_d_ff,
                                      activation, dtype)
    return p


def moe_block(params: dict, x: jax.Array, *, top_k: int,
              capacity_factor: float, activation: str,
              aux_loss_weight: float = 0.0,
              dispatch: str = "gather",
              groups: int = 1) -> Tuple[jax.Array, jax.Array, dict]:
    """MoE FFN. x: (b, s, d). Expert weights are stacked on a leading
    experts axis so the sharding rules can place them on the model axis
    (EP) or shard d_ff (expert-TP) — see parallel/sharding.py.

    dispatch="gather": capacity-based per-expert top-C token selection
    (drops overflow). The tokens are split into ``groups`` equal groups
    along the batch (GShard's groups: one per data shard, so that dispatch
    stays on the shard's chips); each expert keeps the C tokens of largest
    gate of each group, C = int(tokens per group x top_k x
    capacity_factor / experts). dispatch="dense": every expert on every
    token, weighted by the combine matrix — more FLOPs but zero dispatch
    collectives (the §Perf fix for fine-grained expert-TP MoEs).

    Returns (y, aux_loss, stats). ``stats`` holds the routing: ``topk``
    (t, top_k) each token's experts; ``kept`` (groups, e, C) each expert's
    kept tokens by their index in the group, -1 for an empty slot (gather
    only); ``dropped`` the share of top-k choices that capacity dropped;
    ``load_max`` the largest count of tokens routed to one expert in one
    group over the mean count."""
    b, s, d = x.shape
    e = params["we_up"].shape[0]
    xt = x.reshape(b * s, d)
    t = b * s
    tg = t // groups
    with jax.named_scope("router"):
        logits = (xt.astype(jnp.float32) @ params["router"])  # (t, e)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, top_k)     # (t, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        # (t, e) combine matrix with only top-k nonzero
        combine = jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], gate_idx].set(gate_vals)
        # Load-balancing aux loss (Switch-style).
        density = combine.mean(axis=0)                        # (e,)
        aux = aux_loss_weight * e * jnp.sum(density * probs.mean(axis=0))
        load = jnp.sum((combine > 0).reshape(groups, tg, e), axis=1)
        stats = {"topk": gate_idx,
                 "load_max": jnp.max(load) * e / (tg * top_k)}

    if dispatch == "dense":
        with jax.named_scope("experts"):
            cw = combine.astype(xt.dtype)                      # (t, e)
            if activation == "swiglu":
                he = jax.nn.silu(jnp.einsum("td,edf->tef", xt,
                                            params["we_gate"]))
                he = he * jnp.einsum("td,edf->tef", xt, params["we_up"])
            else:
                he = jax.nn.gelu(jnp.einsum("td,edf->tef", xt,
                                            params["we_up"]))
            y = jnp.einsum("tef,te,efd->td", he, cw, params["we_down"])
        with jax.named_scope("combine"):
            if "shared" in params:
                y = y + ffn_block(params["shared"], xt, activation)
        stats["dropped"] = jnp.zeros((), jnp.float32)
        return y.reshape(b, s, d), aux, stats
    # Per-expert capacity selection. Single-token decode steps use exact
    # capacity (= tokens per group) so serving never drops; full sequences
    # use the standard capacity factor (overflow dropped, as in
    # Switch/GShard training).
    if s == 1:
        cap = tg
    else:
        cap = max(1, int(tg * top_k * capacity_factor / e))
        cap = min(cap, tg)
    with jax.named_scope("dispatch"):
        sel_val, sel_idx = jax.lax.top_k(
            combine.reshape(groups, tg, e).transpose(0, 2, 1), cap)
        xg = xt.reshape(groups, tg, d)
        xe = jax.vmap(lambda xs, idx: xs[idx])(xg, sel_idx)  # (g, e, cap, d)
        kept = sel_val > 0
        stats["kept"] = jnp.where(kept, sel_idx, -1)
        stats["dropped"] = 1.0 - jnp.sum(kept) / (t * top_k)
    with jax.named_scope("experts"):
        if activation == "swiglu":
            he = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe,
                                        params["we_gate"]))
            he = he * jnp.einsum("gecd,edf->gecf", xe, params["we_up"])
        else:
            he = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", xe,
                                        params["we_up"]))
        ye = jnp.einsum("gecf,efd->gecd", he, params["we_down"])
    with jax.named_scope("combine"):
        ye = ye * sel_val[..., None].astype(ye.dtype)
        y = jax.vmap(lambda ys, idx: jnp.zeros((tg, d), ye.dtype).at[
            idx.reshape(-1)].add(ys.reshape(e * cap, d)))(ye, sel_idx)
        y = y.reshape(t, d)
        if "shared" in params:
            y = y + ffn_block(params["shared"], xt, activation)
    return y.reshape(b, s, d), aux, stats


# --------------------------------------------------------------------- #
# Embedding / unembedding
# --------------------------------------------------------------------- #

def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       ignore_id: int = -1) -> jax.Array:
    """Mean token NLL in fp32. logits: (..., V), targets: (...) int32.

    The gold logit is extracted with an iota-compare reduction rather than
    take_along_axis: a gather along a vocab-parallel-sharded axis would
    force GSPMD to all-gather the full logits, while the masked reduction
    partitions cleanly (each vocab shard contributes its local max/sum)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    onehot = (vocab_iota == targets[..., None].astype(jnp.int32))
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = logz - gold
    mask = (targets != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
