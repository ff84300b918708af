"""End-to-end training driver: a real LM trained for a few hundred steps
with checkpointing, auto-resume, and the synthetic-but-learnable pipeline.

Defaults to the reduced smollm config so it finishes on a laptop-class CPU;
pass ``--full`` for the real 135M configuration (same code path — on the
production mesh this is what launch/dryrun.py lowers at 4k context).

Run: PYTHONPATH=src python examples/train_lm.py [--steps 300] [--full]
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import DataConfig, DataIterator
from repro.parallel import plan_memory
from repro.train import (
    AdamWConfig,
    Trainer,
    TrainerConfig,
    init_train_state,
    make_train_step,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config("smollm-135m", reduced=not args.full)
    plan = plan_memory(cfg, tp=1, dp=1)
    print(f"training {cfg.arch_id}: {cfg.param_count()/1e6:.1f}M params, "
          f"plan: zero-{plan.zero_stage} {plan.opt_dtype} remat={plan.remat}")
    opt = AdamWConfig(lr=3e-3, warmup_steps=args.steps // 20,
                      total_steps=args.steps)
    rng = jax.random.PRNGKey(0)
    state = init_train_state(cfg, plan, rng, opt, dtype=jnp.float32)
    step_fn = jax.jit(make_train_step(cfg, plan, opt))
    data = DataIterator(DataConfig(vocab_size=cfg.vocab_size,
                                   seq_len=args.seq_len,
                                   global_batch=args.global_batch))
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(step_fn, state, data, TrainerConfig(
            total_steps=args.steps, ckpt_dir=ckpt_dir, ckpt_interval=100,
            log_interval=20))
        summary = trainer.run(rng)
    host = ", ".join(f"{p} {t:.2f} s" for p, t in summary["host_s"].items())
    print(f"\nfinal loss {summary['final_loss']:.3f} after "
          f"{summary['final_step']} steps "
          f"(host time: {host}; stragglers: {summary['straggler_steps']})")
    assert summary["final_loss"] < 7.0, "loss should drop on Markov data"


if __name__ == "__main__":
    main()
