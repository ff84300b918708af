"""A whole run on the CPU, past the look for a chip, with the timed path
broken underneath: the output check has to say ``correct: false`` for each
fault a training cell can have, dense and with experts, and ``true`` for
the program as it is.

The limits here are for these tiny sizes on the CPU (SOUND gives what
sound runs read); the chip's limits, set from chip readings, are in
``limits/``."""

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as tb

import run

import repro.train

# Sound runs at these sizes on the CPU, seeds 1, 2, 3, 2**31 + 7, 2**32 + 9:
# dense reads at most 5.0e-5, 1.6e-3 and 1.2e-3; with experts (its tiny
# batch routes noisily) 5.1e-4, 1.9e-2 and 6.7e-3.
LIMITS = {
    "dense.train": {"loss_gap": 2e-4, "grad_gap": 0.02, "update_gap": 0.01},
    "moe.train": {"loss_gap": 7e-4, "grad_gap": 0.05, "update_gap": 0.02},
}

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench"))
    return tmp, tb.tiny_root(tmp, limits=LIMITS)


@pytest.fixture(autouse=True)
def restore_precision():
    was = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", was)


def correct(root, cell, seconds=0.5):
    tmp, bench = root
    result = run.main(["--workload", cell, "--seed", "3", "--seconds",
                       str(seconds), "--trace", "0"], root=tmp,
                      bench_dir=bench, check_device=False,
                      compile_cache=False)
    return result["correct"]


def broken_step(monkeypatch, fault):
    real = repro.train.make_train_step

    def make(cfg, plan, opt_cfg=None, **kw):
        step = real(cfg, plan, opt_cfg, **kw)

        def train_step(state, batch, rng):
            if fault == "half_batch":
                batch = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
            new, metrics = step(state, batch, rng)
            if fault == "unchanged":
                return jax.tree.map(jnp.copy, state), metrics
            if fault == "loss":
                metrics = dict(metrics, loss=metrics["loss"] * 1.001)
            return new, metrics
        return train_step

    monkeypatch.setattr(repro.train, "make_train_step", make)


CELLS = sorted(tb.CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_training_is_correct(root, cell):
    assert correct(root, cell)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "loss"])
def test_broken_training_is_not_correct(root, monkeypatch, fault, cell):
    broken_step(monkeypatch, fault)
    assert not correct(root, cell)
