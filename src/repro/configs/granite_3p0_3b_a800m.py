"""granite-3.0-3b-a800m — the published model: granite-moe-3b-a800m's
widths with its muP-style scalars and RMSNorm eps.
[hf:ibm-granite/granite-3.0-3b-a800m-base; hf]

The embedding output is multiplied by 12, each sublayer's output by 0.22
before its residual add, attention scores are scaled by 1/64 (not
1/sqrt(64)), and the logits are divided by 6. These values were not read
from a copy of the source's ``config.json``; the benchmark's configuration
file lists them under ``assumed``.
"""

import dataclasses

from repro.configs.granite_moe_3b_a800m import CONFIG as _GRANITE_MOE

CONFIG = dataclasses.replace(
    _GRANITE_MOE,
    arch_id="granite-3.0-3b-a800m",
    norm_eps=1e-6,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=1.0 / 64,
    logits_scaling=6.0,
)

REDUCED = CONFIG.reduced()
