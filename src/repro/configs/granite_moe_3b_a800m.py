"""granite-moe-3b-a800m — fine-grained MoE, 40 experts top-8.
[hf:ibm-granite/granite-3.0-3b-a800m-base; hf]

Granite's widths and routing with its scalars left neutral (embedding,
residual and logits multipliers 1, attention scale 1/sqrt(head_dim), RMSNorm
eps 1e-5): the MoE that the simulator's studies and the small CPU tests use.
The published model, with its scalars, is ``granite-3.0-3b-a800m``
(``granite_3p0_3b_a800m.py``).
"""

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    arch_id="granite-moe-3b-a800m",
    family="moe",
    num_layers=32,
    d_model=1536,
    num_heads=24,
    num_kv_heads=8,
    head_dim=64,
    d_ff=512,  # per-expert hidden dim (fine-grained experts)
    vocab_size=49155,
    rope_theta=10_000.0,
    tie_embeddings=True,
    activation="swiglu",
    moe=MoEConfig(
        num_experts=40,
        top_k=8,
        d_ff=512,
        moe_every=1,
        shared_expert=False,
        capacity_factor=1.5,
    ),
    source="[hf:ibm-granite/granite-3.0-3b-a800m-base; hf]",
    notes="40 experts go over the model axis (EP) wherever its size divides "
          "40: on a (2 data, 2 model) mesh each chip holds 20 experts. Where "
          "it does not (16 model ranks), each expert's d_ff=512 is sharded "
          "instead (expert-TP, 32 cols/rank). vocab padded 49155 -> 51200.",
)

REDUCED = CONFIG.reduced()
