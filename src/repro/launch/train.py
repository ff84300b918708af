"""End-to-end training driver.

    python -m repro.launch.train --arch smollm-135m --steps 300 \
        --reduced --ckpt-dir /tmp/ckpt --resume auto

It trains on one device. The full-width config (no ``--reduced``) wants a
TPU; ``dryrun.py`` lowers the same step on the production mesh.
Parameters are built in the dtype the memory plan assumes (bf16, with an
fp32 master copy in the optimizer state). The step is compiled ahead of the
first batch, so ``compile_s`` in the returned summary is the compile alone.
Auto-resume restores the latest checkpoint — including the data-iterator
cursor — and an elastic restart onto a different device count re-shards
state transparently.
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional

import jax

from repro.configs import get_config
from repro.data import DataConfig, DataIterator
from repro.data.pipeline import lm_batch
from repro.launch.compile_cache import use_compile_cache
from repro.parallel import plan_memory
from repro.train import (
    AdamWConfig,
    Trainer,
    TrainerConfig,
    init_train_state,
    make_train_step,
)


def main(argv: Optional[List[str]] = None) -> Dict:
    """Run the trainer; returns its summary plus ``compile_s`` and the
    logged per-step rows (``metrics_log``)."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
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
    plan = plan_memory(cfg, tp=1, dp=1)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 1),
                          state_dtype=plan.opt_dtype,
                          use_master=plan.use_master)
    rng = jax.random.PRNGKey(args.seed)
    state = init_train_state(cfg, plan, rng, opt_cfg)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.global_batch, seed=args.seed)
    t0 = time.monotonic()
    step_fn = jax.jit(make_train_step(cfg, plan, opt_cfg),
                      donate_argnums=(0,)).lower(
        state, jax.eval_shape(lambda: lm_batch(data_cfg, 0)), rng).compile()
    compile_s = time.monotonic() - t0
    trainer = Trainer(step_fn, state, DataIterator(data_cfg), TrainerConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_interval=args.ckpt_interval, log_interval=args.log_interval,
        seed=args.seed))
    if args.resume == "auto":
        resumed = trainer.try_resume()
        print(f"resume: {'restored step ' + str(trainer.step) if resumed else 'fresh start'}")
    summary = trainer.run(rng)
    print("summary:", summary)
    return {**summary, "compile_s": compile_s,
            "metrics_log": trainer.metrics_log}


if __name__ == "__main__":
    main()
