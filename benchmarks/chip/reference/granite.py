"""Plain float32 reference of granite-3.0-3b-a800m, following the program's
routing where it is given.

Adds to ``reference.transformer.Ref`` what the published model
(GraniteMoe of Hugging Face transformers) has beyond the Llama-style
decoder, each from the configuration file:

* the embedding output times ``embedding_multiplier``;
* each sublayer's output times ``residual_multiplier`` before its residual
  add;
* attention scores scaled by ``attention_multiplier`` (in place of
  1/sqrt(head_dim));
* the logits divided by ``logits_scaling``;

and the capacity per group of the configuration's ``assumed`` dispatch:
the batch's tokens are split into ``groups`` equal groups along the batch
(one per data shard), and each expert keeps at most C = int(tokens per
group x k x capacity_factor / experts) tokens of each group, by largest
gate. Experts compute only the tokens they keep. Every matmul runs at
``highest`` precision.

Routing. Without ``route`` the reference routes itself: a token's top-k
experts by its own float32 probabilities, renormalised to sum to 1, and
each expert's kept tokens by largest gate. With ``route`` (the program's
``route_topk``, (layers, tokens, k), and ``route_kept``, (layers, groups,
experts, C), each kept token by its index in its group and -1 for an empty
slot) it follows the program's selections: a token's gates are its own
float32 probabilities at the program's top-k, renormalised, and each expert
computes the tokens the program kept. The loss, gradient and update are
then continuous in the gaps between program and reference. What the
routing decided is counted apart: ``loss_and_routes`` gives, beside the
loss, the number of the followed top-k (token, expert) choices and kept
(expert, token) pairs that the reference's own routing does not make, their
total, and the reference's own routing.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from reference.transformer import Ref


class GraniteRef(Ref):
    """The reference of one granite configuration file. ``loss_and_routes``
    takes two weightings that stay 1 in a sound run, so that faults share
    its compiled program: ``expert_weight`` (experts,) on each expert's
    output and ``row_weight`` (batch,) on each sequence's loss."""

    def __init__(self, c: dict, quant: Optional[str] = None,
                 groups: int = 1):
        super().__init__(c, quant=quant)
        self.groups = groups

    def attention(self, lp, x):
        """One sequence. x: (s, d) normed input; causal GQA, scores scaled
        by ``attention_multiplier``."""
        c = self.c
        s = x.shape[0]
        hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                       c["head_dim"])
        q = self.rope(self.mm("sd,dk->sk", x, lp["wq"]).reshape(s, hq, hd))
        k = self.rope(self.mm("sd,dk->sk", x, lp["wk"]).reshape(s, hkv, hd))
        v = self.mm("sd,dk->sk", x, lp["wv"]).reshape(s, hkv, hd)
        q = q.reshape(s, hkv, hq // hkv, hd)
        scores = self.mm("qkgd,tkd->kgqt", q, k) * c["attention_multiplier"]
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = self.mm("kgqt,tkd->qkgd", p, v).reshape(s, hq * hd)
        return self.mm("sk,kd->sd", o, lp["wo"])

    def capacity(self, t: int) -> int:
        c = self.c
        tg = t // self.groups
        return min(max(1, int(tg * c["num_experts_per_tok"]
                              * c["capacity_factor"]
                              / c["num_local_experts"])), tg)

    def routed_experts(self, mp, x, topk=None, kept=None,
                       expert_weight=None):
        """x: (t, d), all tokens of the batch. ``topk`` (t, k) and ``kept``
        (groups, e, C): the routing to follow, or None to route itself.
        Returns (y, aux, own routing, (missed choices, choices))."""
        c = self.c
        t, d = x.shape
        e, k = c["num_local_experts"], c["num_experts_per_tok"]
        g, cap = self.groups, self.capacity(t)
        tg = t // g
        probs = jax.nn.softmax(self.mm("td,de->te", x, mp["router"]), axis=-1)

        def gates(idx):
            top = jnp.take_along_axis(probs, idx, axis=1)
            top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
            return jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                           * top[..., None], axis=1)              # (t, e)

        own_idx = jax.lax.top_k(probs, k)[1]
        own_gate = gates(own_idx)
        val, sel = jax.lax.top_k(own_gate.reshape(g, tg, e)
                                 .transpose(0, 2, 1), cap)        # (g, e, C)
        own_kept = jnp.where(val > 0, sel, -1)
        gate = own_gate if topk is None else gates(topk)
        kept = own_kept if kept is None else kept

        safe = jnp.maximum(kept, 0)
        xg, gg = x.reshape(g, tg, d), gate.reshape(g, tg, e)
        xe = jax.vmap(lambda xs, i: xs[i])(xg, safe)              # (g,e,C,d)
        w = jax.vmap(lambda gs, i: jnp.take_along_axis(gs.T, i, axis=1))(
            gg, safe)                                             # (g, e, C)
        w = jnp.where(kept >= 0, w, 0.0)
        if expert_weight is not None:
            w = w * expert_weight[None, :, None]
        h = jax.nn.silu(self.mm("gecd,edf->gecf", xe, mp["we_gate"])) \
            * self.mm("gecd,edf->gecf", xe, mp["we_up"])
        ye = self.mm("gecf,efd->gecd", h, mp["we_down"]) * w[..., None]
        y = jax.vmap(lambda ys, i: jnp.zeros((tg, d), ys.dtype).at[
            i.reshape(-1)].add(ys.reshape(-1, d)))(ye, safe).reshape(t, d)
        aux = (c["router_aux_loss_coef"] * e
               * jnp.sum(gate.mean(0) * probs.mean(0)))

        # The followed choices that the reference's own routing does not make.
        idx = own_idx if topk is None else topk
        own_top = jnp.sum(jax.nn.one_hot(own_idx, e, dtype=jnp.int32),
                          axis=1) > 0                             # (t, e)
        miss = jnp.sum(~jnp.take_along_axis(own_top, idx, axis=1))
        own_set = jnp.zeros((g, e, tg + 1), bool).at[
            jnp.arange(g)[:, None, None], jnp.arange(e)[None, :, None],
            jnp.where(own_kept >= 0, own_kept, tg)].set(True)
        held = jnp.take_along_axis(own_set, jnp.where(kept >= 0, kept, tg),
                                   axis=2)
        miss = miss + jnp.sum((kept >= 0) & ~held)
        total = t * k + jnp.sum(kept >= 0)
        return y, aux, (own_idx, own_kept), (miss, total)

    def hidden_routed(self, params, tokens, route=None, expert_weight=None):
        """tokens: (b, s). Final normed hidden states, the summed auxiliary
        loss, the own routing per layer and the route counts."""
        c = self.c
        b, s = tokens.shape
        rm = c["residual_multiplier"]
        x = params["embed"][tokens] * c["embedding_multiplier"]

        def layer(x, xs):
            lp, r = xs
            h = jax.vmap(lambda row: self.norm(row, lp["ln1"]))(x)
            x = x + rm * jax.vmap(lambda row: self.attention(lp["attn"],
                                                             row))(h)
            h = self.norm(x, lp["ln2"]).reshape(b * s, -1)
            y, aux, own, counts = self.routed_experts(
                lp["moe"], h, *((r["topk"], r["kept"]) if r else (None, None)),
                expert_weight=expert_weight)
            return x + rm * y.reshape(b, s, -1), (aux, own, counts)

        stacked = {"ln1": params["layers"]["ln1"],
                   "ln2": params["layers"]["ln2"],
                   "attn": params["layers"]["attn"], "moe": params["moe"]}
        x, (aux, own, counts) = jax.lax.scan(jax.checkpoint(layer), x,
                                             (stacked, route))
        return self.norm(x, params["ln_f"]), aux.sum(), own, counts

    def row_logits(self, params, h):
        return super().row_logits(params, h) / self.c["logits_scaling"]

    def loss_and_routes(self, params, batch, route=None, row_weight=None,
                        expert_weight=None):
        """Mean cross-entropy over the tokens of the rows that count plus
        the auxiliary loss, and (own routing {"topk", "kept"}, missed
        choices, choices)."""
        h, aux, own, (miss, total) = self.hidden_routed(
            params, batch["tokens"], route, expert_weight)

        @jax.checkpoint
        def row_nll(args):
            hr, tr = args
            logits = self.row_logits(params, hr)
            gold = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

        nll = jax.lax.map(row_nll, (h, batch["targets"]))
        if row_weight is None:
            loss = nll.sum() / batch["targets"].size + aux
        else:
            loss = (jnp.sum(nll * row_weight)
                    / (jnp.sum(row_weight) * batch["targets"].shape[1]) + aux)
        return loss, ({"topk": own[0], "kept": own[1]}, miss.sum(),
                      total.sum())

    def loss(self, params, batch):
        return self.loss_and_routes(params, batch)[0]

