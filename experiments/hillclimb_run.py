"""§Perf hillclimb driver: named variants per cell, before/after roofline.

Each variant = (name, hypothesis, cfg_transform, plan_transform). The sweep
is a repro.core.study StudySpec with a "variant" Axis and a custom
``evaluate`` that runs the measured dry-run frontend (lower_cell) instead of
the analytical simulator — same engine, different evaluator.

Usage: python experiments/hillclimb_run.py <arch:shape> <variant>[,<variant>...]
Results saved to experiments/hillclimb/<cell>_<variant>.json.
"""
import dataclasses
import json
import os
import sys
import time

from repro.core.study import Axis, StudySpec, run_study
from repro.launch.dryrun import HOST_DEVICES_FLAG, lower_cell

def remat_blocks(plan):
    return dataclasses.replace(plan, remat="blocks")

def remat_dots(plan):
    return dataclasses.replace(plan, remat="dots")

def micro(n):
    return lambda plan: dataclasses.replace(plan, microbatches=n)

def moe_dense(cfg):
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, dispatch="dense"))

def bshard(cfg):
    return dataclasses.replace(cfg, attn_batch_shard=True)

def cap(f):
    return lambda cfg: dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=f))

def chain(*fns):
    def t(x):
        for f in fns:
            x = f(x)
        return x
    return t

VARIANTS = {
    "baseline": (None, None),
    "remat-blocks": (None, remat_blocks),
    "remat-dots": (None, remat_dots),
    "micro8": (None, micro(8)),
    "moe-dense": (moe_dense, None),
    "moe-dense-blocks": (moe_dense, remat_blocks),
    "cap1.0": (cap(1.0), None),
    "micro16": (None, micro(16)),
    "micro16-blocks": (None, lambda p: remat_blocks(micro(16)(p))),
    "moe-dense-micro8": (moe_dense, micro(8)),
    "moe-dense-micro8-blocks": (moe_dense, lambda p: remat_blocks(micro(8)(p))),
    "blocks-micro8": (None, lambda p: remat_blocks(micro(8)(p))),
    "moe-dense-bshard": (lambda c: bshard(moe_dense(c)), None),
    "bshard": (bshard, None),
    "moe-dense-bshard-blocks": (lambda c: bshard(moe_dense(c)), remat_blocks),
    "bshard-micro16": (bshard, micro(16)),
    "bshard-blocks": (bshard, remat_blocks),
    "bshard-cap1": (lambda c: bshard(cap(1.0)(c)), None),
    "bshard-micro16-blocks": (bshard, lambda p: remat_blocks(micro(16)(p))),
    "bshard-micro4": (bshard, micro(4)),
    "blocks": (None, remat_blocks),
    "dots": (None, remat_dots),
}


def _evaluate(ctx, arch, shape):
    # Persist + report per variant as soon as it finishes: a crash in a
    # later variant must not discard earlier multi-minute dry-run results.
    variant = ctx.point["variant"]
    cfg_t, plan_t = VARIANTS[variant]
    t0 = time.monotonic()
    _, info = lower_cell(arch, shape, multi_pod=False,
                         cfg_transform=cfg_t, plan_transform=plan_t)
    info["variant"] = variant
    info["wall_s"] = time.monotonic() - t0
    tag = f"{arch}_{shape}_{variant}"
    with open(f"experiments/hillclimb/{tag}.json", "w") as f:
        json.dump(info, f, indent=1, default=str)
    print(f"{tag}: compute={info['compute_s']:.3f}s memory={info['memory_s']:.3f}s "
          f"collective={info['collective_s']:.3f}s dom={info['dominant']} "
          f"frac={info['roofline_fraction']:.3f} util={info['model_flops_util']:.3f} "
          f"[{info['wall_s']:.0f}s]", flush=True)
    return info


def main():
    os.environ["XLA_FLAGS"] = HOST_DEVICES_FLAG
    cell = sys.argv[1]                  # e.g. internlm2-20b:train_4k
    names = sys.argv[2].split(",")      # one or more variant names
    arch, shape = cell.split(":")
    unknown = [n for n in names if n not in VARIANTS]
    if unknown:
        sys.exit(f"unknown variant(s) {unknown}; available: {sorted(VARIANTS)}")
    os.makedirs("experiments/hillclimb", exist_ok=True)
    spec = StudySpec(name=f"hillclimb:{cell}",
                     axes=[Axis("variant", tuple(names))],
                     evaluate=lambda ctx: _evaluate(ctx, arch, shape))
    run_study(spec)


if __name__ == "__main__":
    main()
