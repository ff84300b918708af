"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs abstract state/batch/cache (ShapeDtypeStruct — no memory),
  3. jit-lowers the step (train_step / prefill_step / serve_step) with the
     full sharding contract from parallel/{sharding,zero}.py,
  4. .compile()s it — sharding mismatches, impossible layouts, and OOM at
     compile time all fail HERE, which is the point of the exercise,
  5. records memory_analysis / cost_analysis / per-collective bytes and the
     three roofline terms (core/hlo.py) into experiments/dryrun/*.json.

Usage:
  python -m repro.launch.dryrun --arch internlm2-20b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

``main`` gives XLA's CPU backend 512 host devices (``HOST_DEVICES_FLAG``)
before the first device query; importing this module changes nothing.
"""

import argparse
import json
import os
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_cells, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.hlo import RooflineTerms, model_flops_util
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    abstract_cache,
    abstract_params,
    input_specs,
    model_flops,
)
from repro.models import get_model
from repro.parallel import (
    batch_shardings,
    cache_shardings,
    param_shardings,
    plan_memory,
)
from repro.train.train_step import jit_train_step
from repro.train.optimizer import AdamWConfig

HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"


def _abstract_state(cfg, plan):
    from repro.models import get_model
    from repro.train.optimizer import init_state

    model = get_model(cfg)
    opt_cfg = AdamWConfig(state_dtype=plan.opt_dtype,
                          use_master=plan.use_master)

    def build():
        params = model.init_params(jax.random.PRNGKey(0), cfg,
                                   dtype=jnp.bfloat16)
        return {"params": params, "opt": init_state(params, opt_cfg)}

    return jax.eval_shape(build)


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               remat_override: Optional[str] = None,
               cfg_transform=None, plan_transform=None):
    """Lower + compile one cell. Returns (compiled, info dict).

    ``cfg_transform`` / ``plan_transform`` are the §Perf hillclimb hooks:
    they rewrite the ModelConfig / MemoryPlan for a variant before
    lowering (e.g. MoE dispatch mode, remat policy, microbatch count)."""
    cfg = get_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    tp = mesh.shape["model"]
    dp = chips // tp
    plan = plan_memory(cfg, tp=tp, dp=dp, shape=shape)
    if plan_transform is not None:
        plan = plan_transform(plan)
    if remat_override is not None:
        import dataclasses
        plan = dataclasses.replace(plan, remat=remat_override)
    model = get_model(cfg)
    batch = input_specs(cfg, shape)

    with mesh:
        if shape.kind == "train":
            state = _abstract_state(cfg, plan)
            step = jit_train_step(cfg, plan, mesh, state, batch,
                                  donate=False)
            rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
            lowered = step.lower(state, batch, rng)
        elif shape.kind == "prefill":
            params = abstract_params(cfg)
            cache = abstract_cache(cfg, shape)
            p_sh = param_shardings(cfg, params, mesh, fsdp=plan.fsdp)
            c_sh = cache_shardings(cfg, mesh, cache)
            b_sh = batch_shardings(mesh, batch, cfg)
            extras = {k: batch[k] for k in batch if k != "tokens"}

            def prefill_step(params, tokens, cache, extras):
                return model.prefill(params, cfg, tokens, cache, **extras)

            fn = jax.jit(prefill_step,
                         in_shardings=(p_sh, b_sh["tokens"], c_sh,
                                       {k: b_sh[k] for k in extras}),
                         out_shardings=(NamedSharding(mesh, P()), c_sh))
            lowered = fn.lower(params, batch["tokens"], cache, extras)
        else:  # decode -> serve_step
            params = abstract_params(cfg)
            cache = abstract_cache(cfg, shape)
            p_sh = param_shardings(cfg, params, mesh, fsdp=plan.fsdp)
            c_sh = cache_shardings(cfg, mesh, cache)
            b_sh = batch_shardings(mesh, batch, cfg)

            def serve_step(params, cache, tokens):
                return model.decode_step(params, cfg, cache, tokens)

            fn = jax.jit(serve_step,
                         in_shardings=(p_sh, c_sh, b_sh["tokens"]),
                         out_shardings=(NamedSharding(mesh, P()), c_sh))
            lowered = fn.lower(params, cache, batch["tokens"])

        t0 = time.monotonic()
        compiled = lowered.compile()
        compile_s = time.monotonic() - t0

    info = analyze(compiled, cfg, shape, chips)
    info.update({
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "zero_stage": plan.zero_stage,
        "opt_dtype": plan.opt_dtype, "remat": plan.remat,
        "microbatches": plan.microbatches,
        "compile_s": round(compile_s, 1),
    })
    return compiled, info


def analyze(compiled, cfg: ModelConfig, shape: ShapeConfig,
            chips: int) -> Dict:
    """Roofline terms + memory/cost analysis from the compiled artifact.

    Uses the trip-count-weighted HLO walk (core/hlo_analyzer) — XLA's own
    cost_analysis counts while-loop bodies once, which under-reports every
    scan-over-layers model (recorded alongside for reference)."""
    from repro.core.hlo_analyzer import analyze_hlo

    hlo = compiled.as_text()
    cost = analyze_hlo(hlo)
    coll = {k: int(v) for k, v in cost.coll.items()}
    flops = cost.flops * chips
    hbm = cost.bytes * chips
    terms = RooflineTerms(
        flops=flops, hbm_bytes=hbm,
        coll_bytes=float(sum(coll.values())) * chips,
        chips=chips, coll_breakdown=coll)
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, list):
        xla_cost = xla_cost[0]
    mf = model_flops(cfg, shape)
    info = terms.as_dict()
    info["model_flops"] = mf
    info["model_flops_util"] = model_flops_util(mf, terms)
    info["coll_breakdown"] = {k: v for k, v in coll.items() if v}
    info["xla_unweighted_flops"] = float(xla_cost.get("flops", 0.0)) * chips
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
                "output_bytes": getattr(ma, "output_size_in_bytes", 0),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
                "generated_code_bytes":
                    getattr(ma, "generated_code_size_in_bytes", 0),
            }
    except Exception:   # CPU backend may not implement it
        pass
    info["memory_analysis"] = mem
    return info


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str) -> Dict:
    tag = f"{arch}_{shape_name}_{'2x16x16' if multi_pod else '16x16'}"
    try:
        _, info = lower_cell(arch, shape_name, multi_pod)
        info["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        info = {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(info, f, indent=1, default=str)
    return info


def main():
    os.environ["XLA_FLAGS"] = HOST_DEVICES_FLAG
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch, shape_name, runnable, _ in all_cells():
            if runnable:
                cells.append((arch, shape_name))
    else:
        cells.append((args.arch, args.shape))
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch}_{shape_name}_{'2x16x16' if mp else '16x16'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("status") == "ok":
                    continue
            t0 = time.monotonic()
            info = run_cell(arch, shape_name, mp, args.out)
            status = info["status"]
            extra = ""
            if status == "ok":
                extra = (f" dom={info['dominant']}"
                         f" frac={info['roofline_fraction']:.3f}"
                         f" compile={info['compile_s']}s")
            else:
                extra = " " + info["error"][:120]
            print(f"[{time.monotonic()-t0:7.1f}s] {arch:28s}"
                  f" {shape_name:12s} {info['mesh']:8s} {status}{extra}",
                  flush=True)


if __name__ == "__main__":
    main()
