"""Decoder-only transformer LM: dense GQA, interleaved MoE, and VLM variants.

One implementation covers the dense family (internlm2, chatglm3, minitron,
smollm), the MoE family (llama4-maverick: interleaved MoE + shared expert;
granite: every-layer fine-grained MoE), and the VLM backbone (internvl2:
precomputed patch embeddings prepended to the token stream).

Layer trunk = lax.scan over stacked parameters; one scan step processes one
"super-block" of ``moe_every`` layers (dense models: 1 layer/step), keeping
the HLO O(1) in depth. Remat policy is a knob (see ``apply_remat``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models.common import (
    DEFAULT_DTYPE,
    attention_block,
    cross_entropy_loss,
    data_groups,
    dense_init,
    embed_init,
    ffn_block,
    init_attention_params,
    init_ffn_params,
    init_moe_params,
    moe_block,
    rms_norm,
)


def _moe_every(cfg: ModelConfig) -> int:
    return cfg.moe.moe_every if cfg.moe is not None else 1


def _n_blocks(cfg: ModelConfig) -> int:
    me = _moe_every(cfg)
    assert cfg.num_layers % me == 0
    return cfg.num_layers // me


# --------------------------------------------------------------------- #
# Params
# --------------------------------------------------------------------- #

def init_params(key, cfg: ModelConfig, dtype=DEFAULT_DTYPE) -> dict:
    hd = cfg.resolved_head_dim
    me = _moe_every(cfg)
    nb = _n_blocks(cfg)
    keys = jax.random.split(key, 8)

    def stack(init_fn, key, n):
        ks = jax.random.split(key, n)
        return jax.vmap(init_fn)(ks)

    # Dense sub-layers exist in every layer position: stack over (nb, me).
    def layer_init(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        p = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": init_attention_params(
                k1, cfg.d_model, cfg.d_model, cfg.num_heads,
                cfg.num_kv_heads, hd, dtype),
        }
        return p

    def dense_ffn_init(k):
        return init_ffn_params(k, cfg.d_model, cfg.d_ff, cfg.activation, dtype)

    params = {
        "embed": embed_init(keys[0], (cfg.padded_vocab, cfg.d_model), dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
        "layers": stack(layer_init, keys[1], cfg.num_layers),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(
            keys[2], (cfg.d_model, cfg.padded_vocab), dtype)

    if cfg.moe is not None:
        # Dense FFNs at non-MoE positions (me-1 per block).
        if me > 1:
            params["dense_ffn"] = stack(dense_ffn_init, keys[3],
                                        nb * (me - 1))

        def moe_init(k):
            return init_moe_params(
                k, cfg.d_model, cfg.moe.d_ff, cfg.moe.num_experts,
                cfg.activation,
                shared_d_ff=(cfg.moe.shared_d_ff if cfg.moe.shared_expert
                             else 0),
                dtype=dtype)

        params["moe"] = stack(moe_init, keys[4], nb)
    else:
        params["dense_ffn"] = stack(dense_ffn_init, keys[3], cfg.num_layers)
    return params


# --------------------------------------------------------------------- #
# Layer stack
# --------------------------------------------------------------------- #

def _reshape_blocks(tree, nb: int, me: int):
    """(nb*me, ...) stacked params -> (nb, me, ...)."""
    return jax.tree.map(lambda x: x.reshape((nb, me) + x.shape[1:]), tree)


def apply_remat(fn, policy: Optional[str]):
    if policy is None or policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if policy == "blocks":
        # Save the post-collective block outputs (tagged "block_out") so the
        # backward replay recomputes block-local math but NOT the Megatron
        # all-reduces — trades L*b*s*d bytes of saved activations for a third
        # of the MP collective traffic (§Perf hillclimb, EXPERIMENTS.md).
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "block_out"))
    raise ValueError(f"unknown remat policy {policy!r}")


def _residual(cfg: ModelConfig, y: jax.Array) -> jax.Array:
    """A sublayer's output as it joins the residual stream."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return checkpoint_name(y, "block_out")


def _trunk(params: dict, cfg: ModelConfig, x: jax.Array, *,
           positions: Optional[jax.Array],
           cache: Optional[dict],
           remat: Optional[str] = "dots"
           ) -> Tuple[jax.Array, jax.Array, Optional[dict], dict]:
    """Run all layers. x: (b, s, d). cache: stacked per-layer KV or None.

    Returns (x, aux, new_cache, routing): ``routing`` stacks each expert
    layer's ``moe_block`` stats over the layers (empty without experts)."""
    me = _moe_every(cfg)
    nb = _n_blocks(cfg)
    hd = cfg.resolved_head_dim
    moe_cfg = cfg.moe
    groups = data_groups(x.shape[0])
    # Scores scale attention_multiplier in place of 1/sqrt(head_dim).
    q_scale = (1.0 if cfg.attention_multiplier is None
               else cfg.attention_multiplier * hd ** 0.5)

    layer_stack = _reshape_blocks(params["layers"], nb, me)
    if moe_cfg is not None and me > 1:
        dense_stack = _reshape_blocks(params["dense_ffn"], nb, me - 1)
    elif moe_cfg is None:
        dense_stack = _reshape_blocks(params["dense_ffn"], nb, me)
    else:
        dense_stack = None

    def block(x, scanned):
        """One super-block of ``me`` layers; MoE at the last position."""
        lp = scanned["layers"]          # (me, ...) sub-stack
        aux_total = jnp.zeros((), jnp.float32)
        stats = {}
        kc_out = []
        for j in range(me):
            sub = jax.tree.map(lambda a, j=j: a[j], lp)
            kv = None
            if scanned.get("cache") is not None:
                kv = {"k": scanned["cache"]["k"][j],
                      "v": scanned["cache"]["v"][j],
                      "pos": scanned["cache"]["pos"]}
            # A sublayer's scope holds its pre-norm and its residual add,
            # so that XLA's fusions of either keep the sublayer's name.
            with jax.named_scope("attention"):
                h = rms_norm(x, sub["ln1"], cfg.norm_eps)
                attn_out, new_kv = attention_block(
                    sub["attn"], h,
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=hd, rope_fraction=cfg.rope_fraction,
                    rope_theta=cfg.rope_theta, causal=True,
                    positions=positions, kv_cache=kv,
                    batch_shard=cfg.attn_batch_shard, q_scale=q_scale)
                x = x + _residual(cfg, attn_out)
            if moe_cfg is not None and j == me - 1:
                with jax.named_scope("moe"):
                    h = rms_norm(x, sub["ln2"], cfg.norm_eps)
                    y, aux, stats = moe_block(
                        scanned["moe"], h, top_k=moe_cfg.top_k,
                        capacity_factor=moe_cfg.capacity_factor,
                        activation=cfg.activation,
                        aux_loss_weight=moe_cfg.aux_loss_weight,
                        dispatch=moe_cfg.dispatch, groups=groups)
                    aux_total = aux_total + aux
                    x = x + _residual(cfg, y)
            else:
                with jax.named_scope("ffn"):
                    fp = jax.tree.map(lambda a: a[j], scanned["dense"]) \
                        if scanned.get("dense") is not None else None
                    h = rms_norm(x, sub["ln2"], cfg.norm_eps)
                    y = ffn_block(fp, h, cfg.activation)
                    x = x + _residual(cfg, y)
            if new_kv is not None:
                kc_out.append(new_kv)
        new_cache = None
        if kc_out:
            new_cache = {"k": jnp.stack([c["k"] for c in kc_out]),
                         "v": jnp.stack([c["v"] for c in kc_out])}
        return x, aux_total, new_cache, stats

    block = apply_remat(block, remat if cache is None else None)

    def scan_body(carry, scanned):
        x, aux = carry
        x, aux_b, new_cache, stats = block(x, scanned)
        return (x, aux + aux_b), (new_cache, stats)

    scanned = {"layers": layer_stack}
    if dense_stack is not None:
        scanned["dense"] = dense_stack
    if moe_cfg is not None:
        scanned["moe"] = params["moe"]
    if cache is not None:
        # cache["k"]: (L, b, s_max, hkv, hd) -> (nb, me, ...)
        scanned["cache"] = {
            "k": cache["k"].reshape((nb, me) + cache["k"].shape[1:]),
            "v": cache["v"].reshape((nb, me) + cache["v"].shape[1:]),
            "pos": jnp.broadcast_to(cache["pos"], (nb,) + cache["pos"].shape),
        }

    (x, aux), (caches, routing) = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), scanned)
    new_cache = None
    if caches is not None and cache is not None:
        new_cache = {
            "k": caches["k"].reshape(cache["k"].shape),
            "v": caches["v"].reshape(cache["v"].shape),
            "pos": cache["pos"] + x.shape[1],
        }
    return x, aux, new_cache, routing


# --------------------------------------------------------------------- #
# Public API
# --------------------------------------------------------------------- #

def _forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
             patches: Optional[jax.Array] = None,
             cache: Optional[dict] = None,
             remat: Optional[str] = "dots"):
    """``forward``, and the trunk's routing."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    x, aux, new_cache, routing = _trunk(params, cfg, x, positions=None,
                                        cache=cache, remat=remat)
    with jax.named_scope("head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        logits = x @ head
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
    return logits, aux, new_cache, routing


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
            patches: Optional[jax.Array] = None,
            cache: Optional[dict] = None,
            remat: Optional[str] = "dots"
            ) -> Tuple[jax.Array, jax.Array, Optional[dict]]:
    """tokens: (b, s) int32; patches: (b, p, d) for VLM.

    Returns (logits, aux_loss, new_cache)."""
    return _forward(params, cfg, tokens, patches=patches, cache=cache,
                    remat=remat)[:3]


def loss(params: dict, cfg: ModelConfig, batch: dict,
         remat: Optional[str] = "dots") -> Tuple[jax.Array, dict]:
    """Mean cross-entropy plus the experts' auxiliary loss. The parts are
    ``ce`` and ``aux``; with experts also ``moe_dropped_share`` (top-k
    choices dropped by capacity, mean over layers), ``moe_load_max`` (the
    largest tokens-per-expert over the mean, over layers and groups), and
    the routing of every expert layer: ``route_topk`` (layers, tokens, k)
    and, with capacity dispatch, ``route_kept`` (layers, groups, experts,
    capacity), as ``moe_block`` gives them."""
    logits, aux, _, routing = _forward(params, cfg, batch["tokens"],
                                       patches=batch.get("patches"),
                                       remat=remat)
    n_patch = 0 if batch.get("patches") is None else batch["patches"].shape[1]
    with jax.named_scope("head"):
        ce = cross_entropy_loss(logits[:, n_patch:, :], batch["targets"])
    total = ce + aux
    parts = {"ce": ce, "aux": aux}
    if routing:
        parts.update(moe_dropped_share=jnp.mean(routing["dropped"]),
                     moe_load_max=jnp.max(routing["load_max"]),
                     route_topk=routing["topk"])
        if "kept" in routing:
            parts["route_kept"] = routing["kept"]
    return total, parts


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=DEFAULT_DTYPE) -> dict:
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, cache: dict,
            patches: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, dict]:
    logits, _, cache = forward(params, cfg, tokens, patches=patches,
                               cache=cache, remat=None)
    return logits[:, -1:, :], cache


def decode_step(params: dict, cfg: ModelConfig, cache: dict,
                tokens: jax.Array) -> Tuple[jax.Array, dict]:
    """tokens: (b, 1) — one new token per sequence."""
    logits, _, cache = forward(params, cfg, tokens, cache=cache, remat=None)
    return logits, cache
