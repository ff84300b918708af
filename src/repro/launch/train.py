"""End-to-end training driver.

    python -m repro.launch.train --arch smollm-135m --steps 300 \
        --reduced --ckpt-dir /tmp/ckpt --resume auto
    python -m repro.launch.train --arch granite-3.0-3b-a800m --mesh 2,2 \
        --global-batch 8 --seq-len 2048

Without ``--mesh`` it trains on one device. ``--mesh DATA,MODEL`` trains on
a (data, model) mesh of DATA x MODEL devices: the memory plan is made for
that mesh, the state is made directly into its shardings
(``train_step.state_shardings``), the step is ``jit_train_step``, and the
data iterator places each batch by ``batch_shardings``. The full-width
config (no ``--reduced``) wants a TPU; ``dryrun.py`` lowers the same step
on the production mesh. Parameters are built in the dtype the memory plan
assumes (bf16, with an fp32 master copy in the optimizer state). The step
is compiled ahead of the first batch, so ``compile_s`` in the returned
summary is the compile alone. Auto-resume restores the latest checkpoint —
including the data-iterator cursor — and an elastic restart onto a
different device count re-shards state transparently.
"""

from __future__ import annotations

import argparse
import contextlib
import time
from typing import Dict, List, Optional

import jax

from repro.configs import get_config
from repro.data import DataConfig, DataIterator
from repro.data.pipeline import lm_batch
from repro.launch.compile_cache import use_compile_cache
from repro.parallel import build_mesh, plan_memory
from repro.parallel.sharding import batch_shardings
from repro.train import (
    AdamWConfig,
    Trainer,
    TrainerConfig,
    init_train_state,
    make_train_step,
)
from repro.train.train_step import jit_train_step, state_shardings


def main(argv: Optional[List[str]] = None) -> Dict:
    """Run the trainer; returns its summary plus ``compile_s`` and the
    logged per-step rows (``metrics_log``)."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="train on a (data, model) mesh of DATA x MODEL "
                         "devices")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--resume", default="no", choices=["no", "auto"])
    ap.add_argument("--log-interval", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    dp, tp = map(int, args.mesh.split(",")) if args.mesh else (1, 1)
    plan = plan_memory(cfg, tp=tp, dp=dp)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 1),
                          state_dtype=plan.opt_dtype,
                          use_master=plan.use_master)
    rng = jax.random.PRNGKey(args.seed)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.global_batch, seed=args.seed)
    batch_shapes = jax.eval_shape(lambda: lm_batch(data_cfg, 0))
    mesh = build_mesh((dp, tp), ("data", "model")) if args.mesh else None

    # Jitted, so that no two leaves share a buffer (an fp32 leaf's master
    # copy is the leaf itself in eager mode) and the step may donate them.
    def init(k):
        return init_train_state(cfg, plan, k, opt_cfg)

    with mesh or contextlib.nullcontext():
        if mesh is None:
            state = jax.jit(init)(rng)
            data = DataIterator(data_cfg)
            step = jax.jit(make_train_step(cfg, plan, opt_cfg),
                           donate_argnums=(0,))
        else:
            shapes = jax.eval_shape(init, rng)
            state = jax.jit(init, out_shardings=state_shardings(
                cfg, plan, shapes, mesh))(rng)
            data = DataIterator(data_cfg, shardings=batch_shardings(
                mesh, batch_shapes, cfg))
            step = jit_train_step(cfg, plan, mesh, shapes, batch_shapes,
                                  opt_cfg)
        t0 = time.monotonic()
        step_fn = step.lower(state, batch_shapes, rng).compile()
        compile_s = time.monotonic() - t0
    trainer = Trainer(step_fn, state, data, TrainerConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_interval=args.ckpt_interval, log_interval=args.log_interval,
        seed=args.seed), state_shardings=jax.tree.map(
            lambda x: x.sharding, state) if mesh else None)
    if args.resume == "auto":
        resumed = trainer.try_resume()
        print(f"resume: {'restored step ' + str(trainer.step) if resumed else 'fresh start'}")
    summary = trainer.run(rng)
    print("summary:", summary)
    return {**summary, "compile_s": compile_s,
            "metrics_log": trainer.metrics_log}


if __name__ == "__main__":
    main()
