"""Architecture registry.

``get_config("internlm2-20b")`` -> full ModelConfig
``get_config("internlm2-20b", reduced=True)`` -> CPU smoke-test variant
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exported)
    SHAPES,
    EncDecConfig,
    HybridConfig,
    ModelConfig,
    MoEConfig,
    ShapeConfig,
    SSMConfig,
    VisionStubConfig,
    pad_vocab,
)

# arch-id -> module name under repro.configs
_ARCH_MODULES: Dict[str, str] = {
    "internlm2-20b": "internlm2_20b",
    "chatglm3-6b": "chatglm3_6b",
    "minitron-8b": "minitron_8b",
    "smollm-135m": "smollm_135m",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "granite-3.0-3b-a800m": "granite_3p0_3b_a800m",
    "mamba2-780m": "mamba2_780m",
    "zamba2-2.7b": "zamba2_2p7b",
    "internvl2-76b": "internvl2_76b",
    # paper case-study models (analytical path; not dry-run archs)
    "transformer-1t": "transformer_1t",
}

# Not swept by the simulator's studies and dry-run: the paper's case-study
# model, and granite's published variant (its layer costs are those of
# granite-moe-3b-a800m).
ASSIGNED_ARCHS: List[str] = [a for a in _ARCH_MODULES
                             if a not in ("transformer-1t",
                                          "granite-3.0-3b-a800m")]


def list_configs() -> List[str]:
    """All registry arch ids (the sweep surface of ``python -m
    repro.analysis``)."""
    return sorted(_ARCH_MODULES)


def get_config(arch_id: str, reduced: bool = False) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch_id]}")
    if reduced:
        if hasattr(mod, "REDUCED"):
            return mod.REDUCED
        return mod.CONFIG.reduced()
    return mod.CONFIG


def get_dlrm_config(reduced: bool = False):
    from repro.configs import dlrm_1p2t
    return dlrm_1p2t.REDUCED if reduced else dlrm_1p2t.CONFIG


def all_cells() -> List[tuple]:
    """Every (arch_id, shape_name) cell, including documented skips.

    Returns (arch_id, shape_name, runnable: bool, skip_reason: str).
    """
    cells = []
    for arch_id in ASSIGNED_ARCHS:
        cfg = get_config(arch_id)
        runnable = set(cfg.applicable_shapes())
        for shape_name in SHAPES:
            if shape_name in runnable:
                cells.append((arch_id, shape_name, True, ""))
            else:
                cells.append((arch_id, shape_name, False,
                              "long_500k skipped: full quadratic attention at "
                              "512k context is mis-provisioned (DESIGN.md "
                              "§Arch-applicability)"))
    return cells
