"""The plain reference against ``repro.models`` on the CPU at reduced
sizes, in float32: logits, loss and every gradient leaf, for the dense
configuration and for the expert configuration with capacity drops."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tb

import harness
import weights
from reference.transformer import Ref, adamw_init, adamw_step

from repro.models import transformer
from repro.train.optimizer import AdamWConfig, apply_updates, init_state


def dropping_moe():
    """The tiny expert config with a capacity of 4 tokens per expert for
    an average load of 8, so that every layer drops tokens."""
    c = copy.deepcopy(tb.MOE)
    c["capacity_factor"] = 0.5
    c["program"]["moe"]["capacity_factor"] = 0.5
    c["name"] = "moe-drop"
    return c


CASES = {"dense": dict(tb.DENSE, name="dense"), "moe-drop": dropping_moe()}


def batch(c, seed=0, b=2, s=16):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, c["vocab_size"], (b, s + 1), dtype=np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "targets": jnp.asarray(toks[:, 1:])}


@pytest.fixture(params=sorted(CASES))
def case(request):
    c = CASES[request.param]
    cfg = harness.model_config(c)
    params = weights.make(c, jax.random.PRNGKey(1), jnp.float32)
    return c, cfg, params


def test_capacity_drops_tokens():
    c = CASES["moe-drop"]
    t, k, e = 2 * 16, c["num_experts_per_tok"], c["num_local_experts"]
    cap = int(t * k * c["capacity_factor"] / e)
    assert cap < t * k / e


def test_logits_agree(case):
    c, cfg, params = case
    b = batch(c)
    got, _, _ = transformer.forward(params, cfg, b["tokens"], remat=None)
    ref = Ref(c)
    h, _ = ref.hidden(params, b["tokens"])
    want = jax.vmap(lambda r: ref.row_logits(params, r))(h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_loss_and_gradients_agree(case):
    c, cfg, params = case
    b = batch(c, seed=2)
    prog = jax.value_and_grad(
        lambda p: transformer.loss(p, cfg, b, remat=None)[0])
    ref = jax.value_and_grad(lambda p: Ref(c).loss(p, b))
    (lp, gp), (lr, gr) = prog(params), ref(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=2e-3,
            atol=2e-5 * float(jnp.max(jnp.abs(y))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_adamw_step_agrees(case):
    c, _, params = case
    o = c["optimizer"]
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.5), params)
    cfg = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"],
                      grad_clip=o["grad_clip"],
                      warmup_steps=o["warmup_steps"],
                      total_steps=o["total_steps"],
                      min_lr_frac=o["min_lr_frac"], use_master=False)
    got, _, _ = apply_updates(params, grads, init_state(params, cfg), cfg)
    want, _ = adamw_step(o, adamw_init(params), grads)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6,
                                   atol=1e-9)


def test_weights_have_the_program_layout(case):
    c, cfg, params = case
    want = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    got = jax.eval_shape(lambda: weights.tree(c, jax.random.PRNGKey(0),
                                              jnp.bfloat16))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)
