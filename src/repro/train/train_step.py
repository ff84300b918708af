"""The jit'd training step + its sharding contract.

``make_train_step`` binds (model, config, memory plan, optimizer config)
into a pure (state, batch, rng) -> (state, metrics) function; shardings for
every state leaf come from parallel/{sharding,zero}.py so the same function
lowers on any mesh — this is the object the multi-pod dry-run compiles.

Gradient accumulation: the memory planner sizes ``plan.microbatches`` so
remat-saved activations fit HBM; the step scans over microbatches
accumulating fp32 grads. Before the optimizer, grads are constrained to the
optimizer-state sharding (ZeRO-1's reduce-scatter — without the constraint
GSPMD all-gathers the data-sharded Adam states to full size instead).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import get_model
from repro.parallel.mesh import dp_axes
from repro.parallel.policy import MemoryPlan
from repro.parallel.sharding import batch_shardings, param_shardings
from repro.parallel.zero import opt_state_shardings
from repro.train.optimizer import AdamWConfig, apply_updates, init_state

# The ``jax.named_scope`` names that the step's sublayers carry into the
# compiled HLO's ``op_name`` metadata: the model's attention, dense FFN and
# head (``models/transformer.py``), and the optimizer update below. A trace
# of the step attributes device time to them through that metadata.
SCOPES = ("attention", "ffn", "head", "optimizer")
# Models with experts put each expert sublayer (its pre-norm, router,
# dispatch, experts, combine and residual add) under this scope in place of
# ``ffn``; ``router``, ``dispatch``, ``experts`` and ``combine`` nest in it.
MOE_SCOPE = "moe"
# The loss's parts that are not averaged over microbatches: the largest
# over them is kept, and arrays are stacked on a leading microbatch axis.
_MAX_PARTS = ("moe_load_max",)


def make_train_step(cfg: ModelConfig, plan: MemoryPlan,
                    opt_cfg: Optional[AdamWConfig] = None,
                    batch_dp_axes: Optional[Tuple[str, ...]] = None,
                    grad_shardings=None) -> Callable:
    """(state, batch, rng) -> (state, metrics). state = {params, opt}."""
    model = get_model(cfg)
    opt_cfg = opt_cfg or AdamWConfig(state_dtype=plan.opt_dtype,
                                     use_master=plan.use_master)
    m = max(1, plan.microbatches)
    acc_dtype = (jnp.bfloat16 if plan.opt_dtype == "bfloat16"
                 else jnp.float32)

    def loss_fn(params, mb):
        return model.loss(params, cfg, mb, remat=plan.remat)

    def _constrain_batch(mb):
        if not batch_dp_axes:
            return mb
        ax = batch_dp_axes if len(batch_dp_axes) > 1 else batch_dp_axes[0]
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, P(ax, *([None] * (x.ndim - 1)))), mb)

    def train_step(state, batch, rng):
        params = state["params"]
        if m <= 1:
            (loss, parts), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, _constrain_batch(batch))
            with jax.named_scope("optimizer"):
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            mbatch = jax.tree.map(
                lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]),
                batch)

            def body(carry, mb):
                acc_loss, acc_parts, acc_g = carry
                (mb_loss, parts), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, _constrain_batch(mb))
                acc_g = jax.tree.map(
                    lambda a, x: a + x.astype(acc_dtype) / m, acc_g, g)
                acc_parts = jax.tree.map(lambda a, x: a + x / m, acc_parts,
                                         {k: parts[k] for k in acc_parts})
                rest = {k: v for k, v in parts.items() if k not in acc_parts}
                return (acc_loss + mb_loss / m, acc_parts, acc_g), rest

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params)
            zero_parts = {"ce": jnp.zeros((), jnp.float32),
                          "aux": jnp.zeros((), jnp.float32)}
            (loss, parts, grads), rest = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_parts, zero_g),
                mbatch)
            for k, v in rest.items():
                parts[k] = (v if v.ndim > 1 else v.max(0) if k in _MAX_PARTS
                            else v.mean(0))
        if grad_shardings is not None:
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads, grad_shardings)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = apply_updates(
                params, grads, state["opt"], opt_cfg, rng)
        metrics = {"loss": loss, **parts, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def init_train_state(cfg: ModelConfig, plan: MemoryPlan, rng,
                     opt_cfg: Optional[AdamWConfig] = None,
                     dtype=jnp.bfloat16) -> dict:
    model = get_model(cfg)
    opt_cfg = opt_cfg or AdamWConfig(state_dtype=plan.opt_dtype,
                                     use_master=plan.use_master)
    params = model.init_params(rng, cfg, dtype=dtype)
    return {"params": params, "opt": init_state(params, opt_cfg)}


def state_shardings(cfg: ModelConfig, plan: MemoryPlan, state_shapes,
                    mesh: Mesh):
    """NamedShardings for the full train state pytree."""
    p_sh = param_shardings(cfg, state_shapes["params"], mesh, fsdp=plan.fsdp)
    opt = state_shapes["opt"]
    o_sh = {
        "m": opt_state_shardings(cfg, opt["m"], mesh, plan),
        "v": opt_state_shardings(cfg, opt["v"], mesh, plan),
        "step": NamedSharding(mesh, P()),
    }
    if "master" in opt:
        o_sh["master"] = opt_state_shardings(cfg, opt["master"], mesh, plan)
    return {"params": p_sh, "opt": o_sh}


def jit_train_step(cfg: ModelConfig, plan: MemoryPlan, mesh: Mesh,
                   state_shapes, batch_shapes,
                   opt_cfg: Optional[AdamWConfig] = None,
                   donate: bool = True):
    """pjit the step with explicit in/out shardings (dry-run entry point)."""
    st_sh = state_shardings(cfg, plan, state_shapes, mesh)
    step = make_train_step(cfg, plan, opt_cfg,
                           batch_dp_axes=dp_axes(mesh),
                           grad_shardings=st_sh["opt"]["m"])
    b_sh = batch_shardings(mesh, batch_shapes, cfg)
    rng_sh = NamedSharding(mesh, P())
    # The metrics' shardings are left to the compiler: the scalars come out
    # replicated, and the routing arrays stay where they were computed.
    return jax.jit(
        step,
        in_shardings=(st_sh, b_sh, rng_sh),
        out_shardings=(st_sh, None),
        donate_argnums=(0,) if donate else (),
    )
