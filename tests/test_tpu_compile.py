"""Compile the main path for a described, unattached TPU v5e chip.

Nothing runs: the TPU compiler that ships with JAX compiles each program
for one chip of a ``v5e:2x2`` topology and refuses what the chip would
refuse (tiling, VMEM, device memory). Shapes are smollm-135m at full
width, as ``chip_smoke.py`` runs it; one program, granite-3.0-3b-a800m's
train step, is compiled for all four chips as a (2 data, 2 model) mesh.
The topology is described inside a fixture, never at import, and the tests
skip where it cannot be described.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.models import get_model
from repro.models.common import FLASH_HEAD_DIMS
from repro.parallel import plan_memory
from repro.train import AdamWConfig, init_train_state, make_train_step
from repro.train.train_step import MOE_SCOPE, SCOPES, jit_train_step

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip"))
import scopes  # noqa: E402  (the chip benchmark's HLO reader)

ARCH = "smollm-135m"
TRAIN_BATCH, TRAIN_SEQ = 4, 2048     # chip_smoke.py's train phase
HBM_BUDGET = 14e9                    # of the chip's 16 GiB


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


def _on(sharding, build):
    """Abstract stand-ins, placed on ``sharding``, for what ``build()``
    returns; nothing is allocated."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(build))


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def train_step(one_chip, cfg):
    """The launcher's step (bf16 params, fp32 master and Adam, donated
    state) at the smoke's batch x sequence length, compiled."""
    plan = plan_memory(cfg, tp=1, dp=1)
    opt_cfg = AdamWConfig(state_dtype=plan.opt_dtype,
                          use_master=plan.use_master)
    state = _on(one_chip, lambda: init_train_state(
        cfg, plan, jax.random.PRNGKey(0), opt_cfg))
    tokens = _sds(one_chip, (TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
    rng = _sds(one_chip, (2,), jnp.uint32)
    return jax.jit(make_train_step(cfg, plan, opt_cfg),
                   donate_argnums=(0,)).lower(
        state, {"tokens": tokens, "targets": tokens}, rng).compile()


def test_train_step_fits_one_chip(train_step):
    mem = train_step.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BUDGET, (mem.argument_size_in_bytes,
                               mem.temp_size_in_bytes)


def test_train_step_matmuls_have_scopes(train_step):
    """Every top-level instruction that is or holds a matmul carries one
    of the step's scopes, so a trace puts its device time on a sublayer."""
    hlo = train_step.as_text()
    top = scopes.top_level(hlo)
    named = scopes.op_scopes(hlo, SCOPES)
    missing = sorted(n for n, matmul in top.items()
                     if matmul and n not in named)
    unnamed = sum(n not in named for n in top)
    assert any(top.values()) and not missing, (
        f"matmuls without a scope: {missing}; {unnamed} of {len(top)} "
        f"top-level instructions ({unnamed / len(top):.0%}) carry none")


def _assert_flash_under_attention(hlo: str, scope_names, scores: str):
    """The flash kernel's forward (and its replay under remat), dQ and
    dK/dV are the program's only custom calls, all under the ``attention``
    scope, and the ``scores`` shape of materialized scores never occurs."""
    named = scopes.op_scopes(hlo, scope_names)
    calls = [line.split(" = ")[0].split()[-1].lstrip("%")
             for line in hlo.splitlines()
             if " = " in line and "tpu_custom_call" in line]
    kernels = {c.split(".")[0] for c in calls}
    assert kernels == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}, calls
    assert {named.get(c) for c in calls} == {"attention"}, calls
    assert scores not in hlo


def test_train_step_attention_runs_the_kernels(train_step, cfg):
    """The step's attention runs the flash kernels and never materializes
    the (b, h, s, s) scores."""
    _assert_flash_under_attention(
        train_step.as_text(), SCOPES,
        f"[{TRAIN_BATCH},{cfg.num_heads},{TRAIN_SEQ},{TRAIN_SEQ}]")


MESH_ARCH, MESH_LAYERS = "granite-3.0-3b-a800m", 2   # 2 of 32: a quick compile
MESH_BATCH = 8                                        # 4 sequences per data shard


def test_mesh_train_step_attention_runs_the_kernels_per_shard(topo):
    """granite-3.0-3b-a800m's train step at 8 x 2048 tokens on the four
    chips as a (2 data, 2 model) mesh, as the chip benchmark's four-chip
    cell runs it (2 of its layers): attention runs the flash kernels once
    per shard, on 4 sequences and 12 of the 24 query heads, and no shard
    materializes its (4, 12, 2048, 2048) scores."""
    cfg = dataclasses.replace(get_config(MESH_ARCH), num_layers=MESH_LAYERS)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    plan = plan_memory(cfg, tp=2, dp=2)
    opt_cfg = AdamWConfig(state_dtype=plan.opt_dtype,
                          use_master=plan.use_master)
    state = jax.eval_shape(lambda: init_train_state(
        cfg, plan, jax.random.PRNGKey(0), opt_cfg))
    tokens = jax.ShapeDtypeStruct((MESH_BATCH, TRAIN_SEQ), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    with mesh:
        hlo = jit_train_step(cfg, plan, mesh, state, batch, opt_cfg).lower(
            state, batch, jax.ShapeDtypeStruct((2,), jnp.uint32)
        ).compile().as_text()
    _assert_flash_under_attention(
        hlo, SCOPES + (MOE_SCOPE,),
        f"[{MESH_BATCH // 2},{cfg.num_heads // 2},{TRAIN_SEQ},{TRAIN_SEQ}]")


def test_decode_step(one_chip, cfg):
    """The serve engine's decode step: fp32, batch 8, 1024-deep cache."""
    model = get_model(cfg)
    params = _on(one_chip, lambda: model.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.float32))
    cache = _on(one_chip, lambda: model.init_cache(cfg, 8, 1024,
                                                   dtype=jnp.float32))
    tokens = _sds(one_chip, (8, 1), jnp.int32)
    compiled = jax.jit(lambda p, c, t: model.decode_step(p, cfg, c, t)).lower(
        params, cache, tokens).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BUDGET


def test_prefill(one_chip, cfg):
    """One 512-token prompt into a fresh 1024-deep single-sequence cache."""
    model = get_model(cfg)
    params = _on(one_chip, lambda: model.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.float32))
    cache = _on(one_chip, lambda: model.init_cache(cfg, 1, 1024,
                                                   dtype=jnp.float32))
    tokens = _sds(one_chip, (1, 512), jnp.int32)
    compiled = jax.jit(lambda p, t, c: model.prefill(p, cfg, t, c)).lower(
        params, tokens, cache).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BUDGET


def test_flash_attention_native(one_chip, cfg):
    hd = cfg.resolved_head_dim
    q = _sds(one_chip, (1, cfg.num_heads, TRAIN_SEQ, hd), jnp.bfloat16)
    kv = _sds(one_chip, (1, cfg.num_kv_heads, TRAIN_SEQ, hd), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("head_dim", FLASH_HEAD_DIMS)
def test_flash_attention_bwd_native(one_chip, cfg, head_dim):
    """The backward kernels at the head dims the models send them."""
    q = _sds(one_chip, (1, cfg.num_heads, TRAIN_SEQ, head_dim), jnp.bfloat16)
    kv = _sds(one_chip, (1, cfg.num_kv_heads, TRAIN_SEQ, head_dim),
              jnp.bfloat16)

    def grads(q, k, v):
        out, back = jax.vjp(flash_attention, q, k, v)
        return back(out)

    hlo = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    assert "flash_bwd_dq" in hlo and "flash_bwd_dkv" in hlo


def test_rmsnorm_native(one_chip, cfg):
    x = _sds(one_chip, (TRAIN_BATCH * TRAIN_SEQ, cfg.d_model), jnp.bfloat16)
    g = _sds(one_chip, (cfg.d_model,), jnp.float32)
    compiled = jax.jit(lambda x, g: rmsnorm(x, g, interpret=False)).lower(
        x, g).compile()
    assert "tpu_custom_call" in compiled.as_text()
