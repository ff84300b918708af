"""Training driver: ``repro.train.Trainer`` over the step that
``make_train_step`` builds, as ``repro.launch.train`` builds it.

Set-up makes the train state from the seed in one jitted call (the weights
of ``weights.py``, bf16 parameters with the optimizer state the memory plan
picks), compiles the step with the state donated, and drives the trainer
through its first ``checked_steps`` steps on batches from
``repro.data.DataIterator``: the same object, call and feed that the window
then uses. The window runs ``Trainer.run`` in chunks of ``chunk_steps``
until ``--seconds`` have passed; every step ends in the trainer's own
``device_get`` of its metrics.

After the window the program's state is freed and the reference follows the
checked steps from the same seed and batches (``Follower``).
"""

from __future__ import annotations

import contextlib
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import compare
import counts
import harness
import traces as tr
import weights
from reference.transformer import Ref, adamw_init, adamw_step

from repro.data import DataConfig, DataIterator
from repro.data.pipeline import lm_batch
from repro.parallel import plan_memory
from repro.train import AdamWConfig, Trainer, TrainerConfig, make_train_step
from repro.train.optimizer import init_state

SPANS = ("window", "train_chunk")


class Feed:
    """The data iterator, keeping a host copy of the first batches."""

    def __init__(self, it, keep: int):
        self.it, self.keep, self.kept = it, keep, []

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self.it)
        if len(self.kept) < self.keep:
            self.kept.append(jax.device_get(batch))
        return batch


def program_parts(c: dict):
    """The program's config, memory plan and optimizer config; the plan
    has to give what the configuration file states."""
    cfg = harness.model_config(c)
    plan = plan_memory(cfg, tp=1, dp=1)
    want = c["train_precision"]
    got = {"opt_state": plan.opt_dtype, "master": plan.use_master}
    if got != {"opt_state": want["opt_state"], "master": want["master"]}:
        raise harness.BenchError(
            f"{c['name']}: the memory plan picks {got}, the configuration "
            f"file states {want}")
    o = c["optimizer"]
    opt_cfg = AdamWConfig(
        lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        min_lr_frac=o["min_lr_frac"], state_dtype=plan.opt_dtype,
        use_master=plan.use_master)
    return cfg, plan, opt_cfg


def change_norms(c: dict, params, key) -> Dict[str, float]:
    """Norm of each leaf's change from the seed's weights."""
    @jax.jit
    def diff(p, k):
        p0 = weights.tree(c, k, jnp.dtype(c["train_precision"]["params"]))
        return jax.tree.map(lambda a, b: a.astype(jnp.float32)
                            - b.astype(jnp.float32), p, p0)
    return compare.leaf_norms(diff(params, key))


class Prepared:
    """The trainer after set-up, with the program's readings of the
    checked steps and the batches they ran on."""

    def __init__(self, ctx: harness.RunContext):
        c, t = ctx.cell.config, ctx.cell.traffic
        cfg, plan, opt_cfg = program_parts(c)
        self.key = key = harness.seed_key(ctx.seed)
        dtype = jnp.dtype(c["train_precision"]["params"])

        @jax.jit
        def build(k):
            params = weights.tree(c, k, dtype)
            return {"params": params, "opt": init_state(params, opt_cfg)}

        state = build(key)
        data = DataIterator(DataConfig(
            vocab_size=c["vocab_size"], seq_len=t["seq_len"],
            global_batch=t["batch"], seed=ctx.seed & 0xFFFFFFFF))
        step = jax.jit(make_train_step(cfg, plan, opt_cfg),
                       donate_argnums=(0,)).lower(
            state, jax.eval_shape(lambda: lm_batch(data.cfg, 0)),
            key).compile()
        mem = step.memory_analysis()
        self.step_bytes = (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        self.module = step.as_text().split("\n", 1)[0].split()[1].rstrip(
            ",") if ctx.trace else None

        checked = int(t["checked_steps"])
        self.feed = Feed(data, checked)
        self.trainer = trainer = Trainer(step, state, self.feed, TrainerConfig(
            total_steps=0, log_interval=1 << 62, seed=ctx.seed & 0xFFFFFFFF))
        del state
        losses: List[float] = []
        with contextlib.redirect_stdout(sys.stderr):
            for i in range(1, checked + 1):
                trainer.cfg.total_steps = i
                losses.append(trainer.run(key)["final_loss"])
                if i == 1:
                    m = compare.leaf_norms(trainer.state["opt"]["m"])
                    grad = {k: v / (1 - opt_cfg.b1) for k, v in m.items()}
        opt = trainer.state["opt"]
        moved = change_norms(c, opt["master"] if "master" in opt
                             else trainer.state["params"], key)
        self.prog = {"losses": losses, "grad": grad, "moved": moved}

    def batches(self) -> list:
        return self.feed.kept

    def release(self) -> None:
        """Free the program's state on the device. The trainer's signal
        handlers keep the trainer itself alive, so dropping it is not
        enough."""
        self.trainer.state = None
        self.trainer.step_fn = None


def run(ctx: harness.RunContext) -> dict:
    c, t = ctx.cell.config, ctx.cell.traffic
    prep = Prepared(ctx)
    trainer, key = prep.trainer, prep.key
    setup_s = time.monotonic() - ctx.t_process

    chunk = int(t["chunk_steps"])
    seconds = min(ctx.seconds, t["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    stop = tr.capture(tempfile.mkdtemp(dir=ctx.trace_dir)) \
        if ctx.trace else None
    n0, compiles0 = trainer.step, ctx.compile_count()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr), ctx.span("window"):
        while True:
            with ctx.span("train_chunk"):
                trainer.cfg.total_steps = trainer.step + chunk
                last = trainer.run(key)
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    trace_path = stop() if stop else None
    steps = trainer.step - n0
    times = trainer.step_times[n0:]
    slowest = int(np.argmax(times))
    dev = jax.devices()[0]
    peak = max(harness.peak_bytes(dev), prep.step_bytes)
    record = {
        "kind": "train",
        "setup_s": setup_s,
        "window_s": window_s,
        "steps": steps,
        "tokens": steps * t["batch"] * t["seq_len"],
        "window_compiles": ctx.compiled_since(compiles0),
        "final_loss": last["final_loss"],
        "attempted": steps,
        "failed": 0 if np.isfinite(last["final_loss"]) else 1,
        "memory_peak_bytes": int(peak),
        "module": prep.module,
        "flops_per_token": counts.train_flops_per_token(c, t["seq_len"]),
        "peak_flops": harness.peaks_of(
            dev.device_kind, ctx.cell.bench_dir)["bf16_flops_per_s"],
        "chips": 1,
        "trace": tr.load(trace_path, SPANS) if trace_path else None,
        "notes": [f"window: step times median {float(np.median(times))!r} s, "
                  f"slowest {times[slowest]!r} s (window step {slowest})"],
    }
    prog, batches = prep.prog, prep.batches()
    prep.release()
    del prep, trainer, last
    ref = Follower(c)(key, batches)
    record["checks"] = compare.checks(numbers(prog, ref), ctx.cell.limits)
    return record


class Follower:
    """The reference through the checked steps from the seed's weights:
    each step's loss, the first clipped gradient's leaf norms, and the leaf
    norms of the change after the last step. ``quant`` computes in lower
    precision (the control); ``rows`` keeps that many rows of each batch
    (a fault). Its programs compile once, for all seeds."""

    def __init__(self, c: dict, quant: Optional[str] = None,
                 rows: Optional[int] = None):
        ref = Ref(c, quant=quant)
        o = c["optimizer"]
        self.c, self.rows = c, rows
        self.dtype = jnp.dtype(c["train_precision"]["params"])

        def norms(tree):
            return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), tree)

        def step(state, batch):
            loss, grads = jax.value_and_grad(ref.loss)(state["params"], batch)
            state, clipped = adamw_step(o, state, grads)
            return state, loss, norms(clipped)

        self.start = jax.jit(lambda p: adamw_init(jax.tree.map(
            lambda x: x.astype(jnp.float32), p)))
        self.step = jax.jit(step, donate_argnums=(0,))
        self.moved = jax.jit(lambda p, q: norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), p, q)))

    def __call__(self, key, batches) -> dict:
        p0 = weights.make(self.c, key, self.dtype)
        state = self.start(p0)
        losses, grad = [], None
        for i, b in enumerate(batches):
            b = {k: jnp.asarray(v[:self.rows] if self.rows else v)
                 for k, v in b.items()}
            state, loss, gn = self.step(state, b)
            losses.append(float(loss))
            if i == 0:
                grad = _by_path(gn)
        return {"losses": losses, "grad": grad,
                "moved": _by_path(self.moved(state["params"], p0))}


def _by_path(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """loss_gap: worst relative gap of a checked step's loss; grad_gap and
    update_gap: worst leaf's gap of the first clipped gradient's norm and
    of the change's norm (leaves the reference's gradient leaves unmoved
    are left out of the change)."""
    keep = compare.moving_leaves(ref["grad"])
    return {
        "loss_gap": max(compare.rel_gap(a, b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": compare.worst_leaf_gap(prog["grad"], ref["grad"]),
        "update_gap": compare.worst_leaf_gap(prog["moved"], ref["moved"],
                                             keep),
    }
