"""A copy of the benchmark at tiny sizes, for runs on the CPU.

``tiny_root(tmp)`` copies the benchmark's directory into ``tmp`` and writes
a ``BENCHMARK.json`` whose cells use the real drivers, metrics and
reference, with configurations and traffic cut to a size a test can hold.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
for _p in (BENCH_DIR, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OPTIMIZER = {"name": "adamw", "lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 10,
             "total_steps": 10000, "min_lr_frac": 0.1}
TRAIN = {"params": "bfloat16", "master": True, "opt_state": "float32",
         "matmul": "default"}

DENSE = {
    "arch_id": "smollm-135m", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "padded_vocab": 2048, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": True,
    "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                "vocab_size": 256},
    "train_precision": TRAIN, "optimizer": OPTIMIZER,
}

MOE = {
    "arch_id": "granite-moe-3b-a800m", "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_local_experts": 8, "num_experts_per_tok": 2, "capacity_factor": 1.5,
    "router_aux_loss_coef": 0.01, "vocab_size": 256, "padded_vocab": 2048,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                "num_kv_heads": 2, "head_dim": 16, "d_ff": 32,
                "vocab_size": 256,
                "moe": {"num_experts": 8, "top_k": 2, "d_ff": 32}},
    "train_precision": TRAIN, "optimizer": OPTIMIZER,
}

TRAIN_MIX = {"driver": "train", "batch": 2, "seq_len": 64, "chunk_steps": 2,
             "checked_steps": 3, "trace_seconds": 1}

CELLS = {
    "dense.train": ("dense", "tiny-train"),
    "moe.train": ("moe", "tiny-train"),
}

LIMITS = {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1.0}


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_root(tmp: str, limits: dict = None) -> str:
    """A benchmark root under ``tmp``; returns its bench directory.
    ``limits`` maps a cell to its limits (LIMITS where it is left out)."""
    bench = os.path.join(tmp, "chip")
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    configs = {"dense": DENSE, "moe": MOE}
    spec["configs"] = []
    for name, c in configs.items():
        write_json(os.path.join(bench, "configs", name + ".json"), c)
        spec["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": os.path.join("chip", "configs", name + ".json")})
    write_json(os.path.join(bench, "traffic", "tiny-train.json"), TRAIN_MIX)
    spec["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for n, (c, t) in CELLS.items()]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(CELLS)
    for name in CELLS:
        write_json(os.path.join(bench, "limits", name + ".json"),
                   {"limits": (limits or {}).get(name, LIMITS)})
    write_json(os.path.join(tmp, "BENCHMARK.json"), spec)
    # Stand-in peaks, so that the readers of a CPU run have a table; no
    # number of such a run is a device number.
    peaks = os.path.join(bench, "peaks.json")
    with open(peaks) as f:
        table = json.load(f)
    table["kinds"]["cpu"] = {"bf16_flops_per_s": 1e12,
                             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    write_json(peaks, table)
    return bench
