"""Operations that the model's work needs, from the configuration file's
sizes alone: the yardstick of MFU.

The count is of the work the model requires, whatever implements it:
matmuls at 2 operations per multiply-add, the causal half of attention's
scores and values, top-k experts (not capacity slots), the tied head over
the vocabulary as run (``padded_vocab``). Recomputation, the embedding
gather, norms and softmax are not counted.
"""

from __future__ import annotations


def _attn_proj_macs(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def _ffn_macs(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    if "num_local_experts" in c:
        router = d * c["num_local_experts"]
        return router + c["num_experts_per_tok"] * 3 * d * f
    return 3 * d * f


def matmul_flops_per_token(c: dict) -> int:
    """Forward operations of every weight matmul one token needs."""
    layers = c["num_hidden_layers"] * (_attn_proj_macs(c) + _ffn_macs(c))
    head = c["hidden_size"] * c["padded_vocab"]
    return 2 * (layers + head)


def attention_flops(c: dict, context: float) -> float:
    """Forward operations of scores and values for one query that sees
    ``context`` positions, over all layers."""
    hq = c["num_attention_heads"] * c["head_dim"]
    return 2 * 2 * hq * context * c["num_hidden_layers"]


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) per token of a causal sequence:
    on average a query sees (seq_len + 1) / 2 positions."""
    fwd = matmul_flops_per_token(c) + attention_flops(c, (seq_len + 1) / 2)
    return 3.0 * fwd
