"""Plain float32 reference of the benchmark's decoder LMs and their AdamW.

Written from the published descriptions (Llama-style decoder: pre-norm
RMSNorm, grouped-query attention with rotary positions, SwiGLU; GShard /
Switch-style top-k experts with a capacity per expert), in plain
``jax.numpy``, with every matmul at ``highest`` precision. It imports
nothing from the program; the sizes come from the configuration file and
the weights from ``weights.make`` with the run's seed.

Departures from the published models, each stated by the configuration
file (``departures``), are followed here so that the comparison measures
the program and not a design choice:

* Rotary positions rotate adjacent channel pairs (0,1), (2,3), ...; Llama's
  code pairs channel i with i + head_dim/2. With random weights the two are
  the same model up to a fixed permutation of the q and k columns.
* The softmax runs over ``padded_vocab`` rows of the tied embedding.
* Experts: the gates are the top-k softmax probabilities renormalised to
  sum to 1. Each expert keeps the ``int(tokens x k x capacity_factor /
  experts)`` tokens of largest gate and drops the rest. The auxiliary loss
  is ``coef x experts x sum_e mean_t(gate_te) x mean_t(prob_te)`` per layer.
* AdamW decays every stored leaf of two or more dimensions; the per-layer
  norm gains are stored stacked over layers and so are decayed.

The control that has to fail the output check: ``quant="fp8"`` computes
every matmul in float8, forward and backward, the step below bf16 training
(the e4m3 forward, e5m2 backward recipe of "FP8 Formats for Deep
Learning", arXiv 2209.05433): both operands rounded to float8_e4m3 and the
cotangent of its output to float8_e5m2, each with a per-tensor scale, the
products summed in float32.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    """``x`` rounded to a float8 ``dtype`` with a per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _grad_e5m2(y):
    """The identity, whose backward pass rounds the cotangent to e5m2."""
    return y


_grad_e5m2.defvjp(lambda y: (y, None),
                  lambda _, g: (_round(g, jnp.float8_e5m2),))


class Ref:
    """The reference model of one configuration file."""

    def __init__(self, c: dict, quant: Optional[str] = None):
        self.c = c
        self.quant = quant
        self.moe = "num_local_experts" in c

    # ------------------------------------------------------------------ #
    def _q(self, x):
        """Round to float8_e4m3 in the forward pass; the cotangent passes
        straight through (the matmul rounds it, ``_grad_e5m2``)."""
        if self.quant != "fp8":
            return x
        return x + jax.lax.stop_gradient(_round(x, jnp.float8_e4m3fn) - x)

    def mm(self, spec: str, a, b):
        y = jnp.einsum(spec, self._q(a), self._q(b), precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        return _grad_e5m2(y) if self.quant == "fp8" else y

    def norm(self, x, g):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.c["rms_norm_eps"]) * g

    def rope(self, x):
        """x: (s, heads, hd); rotate pairs (2i, 2i+1) by pos / theta^(2i/hd),
        the inverse frequencies computed as Llama's code computes them (an
        ulp of difference there grows with the position)."""
        s, _, hd = x.shape
        inv = 1.0 / (self.c["rope_theta"]
                     ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)

    def attention(self, lp, x):
        """One sequence. x: (s, d) normed input; causal GQA."""
        c = self.c
        s = x.shape[0]
        hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                       c["head_dim"])
        q = self.rope(self.mm("sd,dk->sk", x, lp["wq"]).reshape(s, hq, hd))
        k = self.rope(self.mm("sd,dk->sk", x, lp["wk"]).reshape(s, hkv, hd))
        v = self.mm("sd,dk->sk", x, lp["wv"]).reshape(s, hkv, hd)
        group = hq // hkv          # query head h reads kv head h // group
        q = q.reshape(s, hkv, group, hd)
        scores = self.mm("qkgd,tkd->kgqt", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = self.mm("kgqt,tkd->qkgd", p, v).reshape(s, hq * hd)
        return self.mm("sk,kd->sd", o, lp["wo"])

    def ffn(self, fp, x):
        h = jax.nn.silu(self.mm("td,df->tf", x, fp["wg"])) \
            * self.mm("td,df->tf", x, fp["wu"])
        return self.mm("tf,fd->td", h, fp["wd"])

    def experts(self, mp, x):
        """x: (t, d), all tokens of the batch. Returns (y, aux)."""
        c = self.c
        t = x.shape[0]
        e, k = c["num_local_experts"], c["num_experts_per_tok"]
        probs = jax.nn.softmax(self.mm("td,de->te", x, mp["router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
        gate = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                       * top[..., None], axis=1)                  # (t, e)
        cap = min(max(1, int(t * k * c["capacity_factor"] / e)), t)
        kth = jax.lax.top_k(gate.T, cap)[0][:, -1]                 # (e,)
        kept = jnp.where((gate >= kth[None, :]) & (gate > 0), gate, 0.0)

        def one_expert(y, w):
            we, gate_e = w
            h = jax.nn.silu(self.mm("td,df->tf", x, we["we_gate"])) \
                * self.mm("td,df->tf", x, we["we_up"])
            return y + gate_e[:, None] * self.mm("tf,fd->td", h,
                                                 we["we_down"]), None

        weights = {n: mp[n] for n in ("we_gate", "we_up", "we_down")}
        y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                            (weights, kept.T))
        aux = (c["router_aux_loss_coef"] * e
               * jnp.sum(gate.mean(0) * probs.mean(0)))
        return y, aux

    # ------------------------------------------------------------------ #
    def hidden(self, params, tokens):
        """tokens: (b, s). Final normed hidden states (b, s, d) and the
        summed auxiliary loss."""
        c = self.c
        b, s = tokens.shape
        x = params["embed"][tokens]

        def layer(x, lp):
            h = jax.vmap(lambda r: self.norm(r, lp["ln1"]))(x)
            x = x + jax.lax.map(jax.checkpoint(
                lambda r: self.attention(lp["attn"], r)), h)
            h = self.norm(x, lp["ln2"]).reshape(b * s, -1)
            if self.moe:
                y, aux = self.experts(lp["moe"], h)
            else:
                y, aux = self.ffn(lp["ffn"], h), jnp.zeros((), jnp.float32)
            return x + y.reshape(b, s, -1), aux

        stacked = {"ln1": params["layers"]["ln1"],
                   "ln2": params["layers"]["ln2"],
                   "attn": params["layers"]["attn"]}
        if self.moe:
            stacked["moe"] = params["moe"]
        else:
            stacked["ffn"] = params["dense_ffn"]
        x, aux = jax.lax.scan(jax.checkpoint(layer), x, stacked)
        return self.norm(x, params["ln_f"]), aux.sum()

    def row_logits(self, params, h):
        return self.mm("sd,vd->sv", h, params["embed"])

    def loss(self, params, batch):
        """Mean cross-entropy over all tokens, plus the auxiliary loss."""
        h, aux = self.hidden(params, batch["tokens"])

        @jax.checkpoint
        def row_nll(args):
            hr, tr = args
            logits = self.row_logits(params, hr)
            gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

        nll = jax.lax.map(row_nll, (h, batch["targets"]))
        return nll.sum() / batch["targets"].size + aux


# ---------------------------------------------------------------------- #
# AdamW, from the optimizer section of the configuration file
# ---------------------------------------------------------------------- #

def lr_at(o: dict, step):
    """Linear warm-up over ``warmup_steps``, then cosine decay to
    ``min_lr_frac`` of the peak at ``total_steps``."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / max(o["warmup_steps"], 1))
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    frac = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * frac


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "m": zeros, "v": zeros,
            "step": jnp.zeros((), jnp.int32)}


def adamw_step(o: dict, state, grads):
    """Clip by global norm, then one AdamW step. Returns (state, clipped
    gradients)."""
    step = state["step"] + 1
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(o, step)
    b1, b2 = o["b1"], o["b2"]
    b1c = 1 - b1 ** step.astype(jnp.float32)
    b2c = 1 - b2 ** step.astype(jnp.float32)

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / b1c) / (jnp.sqrt(v / b2c) + o["eps"])
        if p.ndim >= 2:
            upd = upd + o["weight_decay"] * p
        return p - lr * upd, m, v

    out = jax.tree.map(one, state["params"], grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return ({"params": pick(0), "m": pick(1), "v": pick(2), "step": step},
            grads)
