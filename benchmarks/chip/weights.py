"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, in the layout that ``repro.models.
transformer`` reads, so that the reference can make the same ones from the
same seed without taking anything from the program. Matrices are normal
with standard deviation 1/sqrt(fan-in), the output projection 1/sqrt(heads
x head size), the embedding 0.02; norm gains are 1. The router stays in
float32 whatever the dtype, as the program keeps it.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def shapes(c: dict) -> Dict[str, Tuple[Tuple[int, ...], float, bool]]:
    """Flat name -> (shape, standard deviation, always float32)."""
    d, L, hd = c["hidden_size"], c["num_hidden_layers"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    f = c["intermediate_size"]
    out = {
        "embed": ((c["padded_vocab"], d), 0.02, False),
        "ln_f": ((d,), 0.0, False),
        "layers/ln1": ((L, d), 0.0, False),
        "layers/ln2": ((L, d), 0.0, False),
        "layers/attn/wq": ((L, d, q), d ** -0.5, False),
        "layers/attn/wk": ((L, d, kv), d ** -0.5, False),
        "layers/attn/wv": ((L, d, kv), d ** -0.5, False),
        "layers/attn/wo": ((L, q, d), q ** -0.5, False),
    }
    if "num_local_experts" in c:
        e = c["num_local_experts"]
        out.update({
            "moe/router": ((L, d, e), d ** -0.5, True),
            "moe/we_gate": ((L, e, d, f), d ** -0.5, False),
            "moe/we_up": ((L, e, d, f), d ** -0.5, False),
            "moe/we_down": ((L, e, f, d), f ** -0.5, False),
        })
    else:
        out.update({
            "dense_ffn/wg": ((L, d, f), d ** -0.5, False),
            "dense_ffn/wu": ((L, d, f), d ** -0.5, False),
            "dense_ffn/wd": ((L, f, d), f ** -0.5, False),
        })
    return out


def _nest(flat: dict) -> dict:
    root: dict = {}
    for name, value in flat.items():
        node = root
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = value
    return root


def tree(c: dict, key, dtype) -> dict:
    """The parameter tree, in ``dtype`` (the router in float32); traceable,
    so that a caller can build more state in the same jitted call."""
    flat = {}
    for i, (name, (shape, std, f32)) in enumerate(sorted(shapes(c).items())):
        dt = jnp.float32 if f32 else dtype
        if std == 0.0:
            flat[name] = jnp.ones(shape, dt)
        else:
            k = jax.random.fold_in(key, i)
            flat[name] = (jax.random.normal(k, shape, jnp.float32)
                          * std).astype(dt)
    return _nest(flat)


def make(c: dict, key, dtype) -> dict:
    """``tree`` in one jitted call on the device."""
    return jax.jit(lambda k: tree(c, k, dtype))(key)
