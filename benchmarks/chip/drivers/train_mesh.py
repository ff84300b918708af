"""Training driver on a mesh: ``repro.train.Trainer`` over ``jit_train_step``
on the (data, model) mesh that the traffic file's ``mesh`` gives, as
``repro.launch.train --mesh`` builds it.

Set-up plans memory for the mesh (``plan_memory(cfg, tp=model, dp=data)``),
makes the train state from the seed directly into ``state_shardings`` in
one jitted call (the weights of ``weights.py``), compiles the step with the
state donated, and drives the trainer through its first ``checked_steps``
steps on batches from ``repro.data.DataIterator`` placed by
``batch_shardings``, keeping each step's routing (``Trainer.last_arrays``).
The window runs as ``drivers/train.py``'s does. A traced run's record also
holds the step's scopes (``step_scopes``) and its collective instructions.

After the window the program's state is freed and the reference
(``reference/granite.py``), its state spread over the same chips, follows
the checked steps from the same seed and batches along the program's
routing (``Follower``). The output check compares ``drivers/train.py``'s
``numbers`` and ``route_gap``: the share of the program's top-k (token,
expert) choices and kept (expert, token) pairs, over the checked steps and
all layers, that the reference's own routing does not make.
"""

from __future__ import annotations

import contextlib
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

import compare
import counts
import harness
import scopes
import traces as tr
import weights
from reference.granite import GraniteRef
from reference.transformer import adamw_init, adamw_step

from repro.data import DataConfig, DataIterator
from repro.data.pipeline import lm_batch
from repro.parallel import build_mesh, plan_memory
from repro.parallel.sharding import batch_shardings
from repro.train import AdamWConfig, Trainer, TrainerConfig
from repro.train.optimizer import init_state
from repro.train.train_step import (MOE_SCOPE, SCOPES, jit_train_step,
                                    state_shardings)

train = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "train.py"))
SPANS = train.SPANS
ROUTES = ("route_topk", "route_kept")
SCALARS = ("embedding_multiplier", "residual_multiplier",
           "attention_multiplier", "logits_scaling")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")


def program_parts(c: dict, t: dict):
    """The program's config, mesh, memory plan and optimizer config. The
    plan has to give what the configuration file states, and the program's
    scalars have to be the file's."""
    cfg = harness.model_config(c)
    for key in SCALARS:
        if getattr(cfg, key, None) != c[key]:
            raise harness.BenchError(
                f"{c['name']}: the program's {key} is "
                f"{getattr(cfg, key, None)!r}, the configuration file says "
                f"{c[key]!r}")
    dp, tp = t["mesh"]["data"], t["mesh"]["model"]
    mesh = build_mesh((dp, tp), ("data", "model"))
    plan = plan_memory(cfg, tp=tp, dp=dp)
    want = c["train_precision"]
    got = {"opt_state": plan.opt_dtype, "master": plan.use_master}
    if got != {"opt_state": want["opt_state"], "master": want["master"]}:
        raise harness.BenchError(
            f"{c['name']}: the memory plan on a {dp} x {tp} mesh picks {got}, "
            f"the configuration file states {want}")
    o = c["optimizer"]
    opt_cfg = AdamWConfig(
        lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        min_lr_frac=o["min_lr_frac"], state_dtype=plan.opt_dtype,
        use_master=plan.use_master)
    return cfg, mesh, plan, opt_cfg


def step_scopes(hlo_text: str) -> Dict[str, str]:
    """``scopes.op_scopes`` over ``SCOPES`` and ``moe``, where an
    instruction with no scope of its own (a fusion the compiler made
    without an ``op_name``, such as the combine's scatter-add) takes the
    one scope that the instructions of its fused computation carry."""
    out = scopes.op_scopes(hlo_text, SCOPES + (MOE_SCOPE,))
    comps = scopes._computations(hlo_text)
    for insts in comps.values():
        for name, _, _, calls in insts:
            inner = {out.get(i[0]) for comp in calls
                     for i in comps.get(comp, [])} - {None}
            if name not in out and len(inner) == 1:
                out[name] = inner.pop()
    return out


def collective_ops(hlo_text: str) -> List[str]:
    """Instructions that are, or whose fused computation holds, an
    all-reduce, all-gather, reduce-scatter or all-to-all (async halves
    included)."""
    comps = scopes._computations(hlo_text)

    def holds(op: str, calls: List[str]) -> bool:
        base = op.removesuffix("-start").removesuffix("-done")
        return base in COLLECTIVES or any(
            holds(inner, inner_calls) for comp in calls
            for _, inner, _, inner_calls in comps.get(comp, []))

    return sorted(name for insts in comps.values()
                  for name, op, _, calls in insts if holds(op, calls))


def change_norms(c: dict, params, key) -> Dict[str, float]:
    """Norm of each leaf's change from the seed's weights, reduced where
    the leaves are."""
    @jax.jit
    def norms(p, k):
        p0 = weights.tree(c, k, jnp.dtype(c["train_precision"]["params"]))
        return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), p, p0)
    return train._by_path(norms(params, key))


class Prepared:
    """The trainer after set-up on the mesh, with the program's readings
    of the checked steps, their batches and their routing."""

    def __init__(self, ctx: harness.RunContext, step=None):
        c, t = ctx.cell.config, ctx.cell.traffic
        cfg, self.mesh, plan, opt_cfg = program_parts(c, t)
        self.groups = t["mesh"]["data"]
        self.key = key = harness.seed_key(ctx.seed)
        dtype = jnp.dtype(c["train_precision"]["params"])

        def build(k):
            params = weights.tree(c, k, dtype)
            return {"params": params, "opt": init_state(params, opt_cfg)}

        data_cfg = DataConfig(vocab_size=c["vocab_size"], seq_len=t["seq_len"],
                              global_batch=t["batch"],
                              seed=ctx.seed & 0xFFFFFFFF)
        batch_shapes = jax.eval_shape(lambda: lm_batch(data_cfg, 0))
        with self.mesh:
            shapes = jax.eval_shape(build, key)
            state = jax.jit(build, out_shardings=state_shardings(
                cfg, plan, shapes, self.mesh))(key)
            if step is None:
                traced = jit_train_step(cfg, plan, self.mesh, shapes,
                                        batch_shapes, opt_cfg).trace(
                    state, batch_shapes, key)
                missing = [k for k in ROUTES if k not in traced.out_info[1]]
                if missing:
                    raise harness.BenchError(
                        f"{c['name']}: the train step exports no {missing}; "
                        f"the output check follows the program's routing")
                step = traced.lower().compile()
        self.step = step
        mem = step.memory_analysis()
        self.step_bytes = (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        self.hlo = step.as_text() if ctx.trace else None
        self.module = self.hlo.split("\n", 1)[0].split()[1].rstrip(",") \
            if ctx.trace else None

        checked = int(t["checked_steps"])
        data = DataIterator(data_cfg, shardings=batch_shardings(
            self.mesh, batch_shapes, cfg))
        self.feed = train.Feed(data, checked)
        self.trainer = trainer = Trainer(step, state, self.feed, TrainerConfig(
            total_steps=0, log_interval=1 << 62, seed=ctx.seed & 0xFFFFFFFF))
        del state
        losses: List[float] = []
        routes: List[dict] = []
        with contextlib.redirect_stdout(sys.stderr):
            for i in range(1, checked + 1):
                trainer.cfg.total_steps = i
                losses.append(trainer.run(key)["final_loss"])
                routes.append({"topk": jax.device_get(
                    trainer.last_arrays["route_topk"]),
                    "kept": jax.device_get(trainer.last_arrays["route_kept"])})
                if i == 1:
                    m = compare.leaf_norms(trainer.state["opt"]["m"])
                    grad = {k: v / (1 - opt_cfg.b1) for k, v in m.items()}
        opt = trainer.state["opt"]
        moved = change_norms(c, opt["master"] if "master" in opt
                             else trainer.state["params"], key)
        self.prog = {"losses": losses, "grad": grad, "moved": moved,
                     "routes": routes}

    def batches(self) -> list:
        return self.feed.kept

    def release(self) -> None:
        """Free the program's state on the devices (the trainer's signal
        handlers keep the trainer itself alive)."""
        self.trainer.state = None
        self.trainer.step_fn = None
        self.trainer.last_arrays = {}


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
    devices = list(prep.mesh.devices.flat)
    peak = max(max(harness.peak_bytes(d) for d in devices), prep.step_bytes)
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
            devices[0].device_kind, ctx.cell.bench_dir)["bf16_flops_per_s"],
        "chips": len(devices),
        "trace": tr.load(trace_path, SPANS) if trace_path else None,
        "notes": [f"window: step times median {float(np.median(times))!r} s, "
                  f"slowest {times[slowest]!r} s (window step {slowest}); "
                  f"moe_dropped_share {last['final_moe_dropped_share']!r}, "
                  f"moe_load_max {last['final_moe_load_max']!r}"],
    }
    if prep.hlo is not None:
        record["op_scopes"] = step_scopes(prep.hlo)
        record["collectives"] = collective_ops(prep.hlo)
        if record["trace"] is not None:
            lo, hi = record["trace"].window()
            runs, secs = tr.program_runs(record["trace"], prep.module, lo, hi)
            by_scope = scopes.scope_self_s(record["trace"], prep.module,
                                           record["op_scopes"], lo, hi)
            per_run = len(record["trace"].ops) / max(runs, 1)
            split = {k: v * per_run for k, v in by_scope.items()}
            record["notes"].append(
                f"trace: step program {secs / max(runs, 1)!r} s per run and "
                f"device; by scope {split!r}")
    prog, batches, groups = prep.prog, prep.batches(), prep.groups
    prep.release()
    del prep, trainer, last
    ref = Follower(c, groups)(key, batches, prog["routes"])
    record["checks"] = compare.checks(numbers(prog, ref), ctx.cell.limits)
    return record


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``drivers/train.py``'s numbers and ``route_gap``."""
    return {**train.numbers(prog, ref),
            "route_gap": ref["route_missed"] / max(ref["route_total"], 1)}


def ref_spec(path: str, shape, n: int) -> P:
    """How the reference's state lies over ``n`` chips: experts over their
    experts axis, the embedding over its rows, the other stacked matrices
    over the larger of their two matrix axes; the rest is replicated."""
    spec = [None] * len(shape)
    dims = ([1] if path.endswith("['we_gate']") or path.endswith("['we_up']")
            or path.endswith("['we_down']") else [0] if path == "['embed']"
            else sorted((1, 2), key=lambda d: -shape[d]) if len(shape) == 3
            else [])
    for d in dims:
        if shape[d] % n == 0:
            spec[d] = "r"
            break
    return P(*spec)


class Follower:
    """The reference through the checked steps from the seed's weights, on
    every chip of the run: each step's loss, the first clipped gradient's
    leaf norms, the leaf norms of the change after the last step, and each
    step's own routing. With ``routes`` it follows them and counts the
    choices its own routing does not make. ``quant`` computes in lower
    precision (the control); ``row_weight`` and ``expert_weight`` weight
    each sequence's loss and each expert's output (faults; 1 in a sound
    run, with which they share one program). Its programs compile once,
    for all seeds."""

    def __init__(self, c: dict, groups: int, quant: Optional[str] = None):
        ref = GraniteRef(c, quant=quant, groups=groups)
        o = c["optimizer"]
        self.c = c
        self.dtype = jnp.dtype(c["train_precision"]["params"])
        self.mesh = jax.make_mesh((jax.device_count(),), ("r",),
                                  axis_types=(AxisType.Auto,))
        n = self.mesh.size
        shapes = jax.eval_shape(lambda: weights.tree(
            c, jax.random.PRNGKey(0), jnp.float32))
        flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
        self.sharding = jax.tree_util.tree_unflatten(tree, [
            NamedSharding(self.mesh, ref_spec(jax.tree_util.keystr(p),
                                              leaf.shape, n))
            for p, leaf in flat])
        sh = self.sharding
        state_sh = {"params": sh, "m": sh, "v": sh,
                    "step": NamedSharding(self.mesh, P())}

        def norms(tree):
            return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), tree)

        def step(state, batch, route, row_weight, expert_weight):
            (loss, (own, miss, total)), grads = jax.value_and_grad(
                ref.loss_and_routes, has_aux=True)(
                state["params"], batch, route, row_weight, expert_weight)
            state, clipped = adamw_step(o, state, grads)
            return state, loss, norms(clipped), own, miss, total

        self.make = jax.jit(lambda k: weights.tree(c, k, self.dtype),
                            out_shardings=sh)
        self.start = jax.jit(lambda p: adamw_init(jax.tree.map(
            lambda x: x.astype(jnp.float32), p)), out_shardings=state_sh)
        self.step = jax.jit(step, donate_argnums=(0,))
        self.moved = jax.jit(lambda p, q: norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), p, q)))

    def put(self, x, spec):
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def __call__(self, key, batches, routes=None, row_weight=None,
                 expert_weight=None) -> dict:
        c = self.c
        row_weight = self.put(np.ones(len(batches[0]["tokens"]), np.float32)
                              if row_weight is None else row_weight, P())
        expert_weight = self.put(
            np.ones(c["num_local_experts"], np.float32)
            if expert_weight is None else expert_weight, P())
        state = self.start(self.make(key))
        losses, grad, own_routes = [], None, []
        missed = total = 0
        for i, b in enumerate(batches):
            b = {k: self.put(v, P("r", None)) for k, v in b.items()}
            route = None if routes is None else {
                "topk": self.put(routes[i]["topk"], P(None, "r", None)),
                "kept": self.put(routes[i]["kept"], P(None, None, "r", None))}
            state, loss, gn, own, miss, tot = self.step(
                state, b, route, row_weight, expert_weight)
            losses.append(float(loss))
            own_routes.append(jax.device_get(own))
            missed, total = missed + int(miss), total + int(tot)
            if i == 0:
                grad = train._by_path(gn)
        return {"losses": losses, "grad": grad,
                "moved": train._by_path(self.moved(state["params"],
                                                   self.make(key))),
                "routes": own_routes, "route_missed": missed,
                "route_total": total}
