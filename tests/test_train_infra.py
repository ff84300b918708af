"""Training infrastructure: checkpointing, resume determinism, retention,
data pipeline state, straggler watchdog, the trainer's spans and phase
timers, optimizer numerics."""

import glob
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, CheckpointManager
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
from repro.train.optimizer import apply_updates, init_state
from repro.train.trainer import PHASES, SPANS

KEY = jax.random.PRNGKey(0)


def _setup(steps=10, ckpt_dir=None, interval=5):
    cfg = get_config("smollm-135m", reduced=True)
    plan = plan_memory(cfg, 1, 1)
    state = init_train_state(cfg, plan, KEY, dtype=jnp.float32)
    step_fn = jax.jit(make_train_step(cfg, plan))
    data = DataIterator(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                   global_batch=4))
    trainer = Trainer(step_fn, state, data, TrainerConfig(
        total_steps=steps, ckpt_dir=ckpt_dir, ckpt_interval=interval,
        log_interval=1000))
    return trainer


class TestCheckpointer:
    def test_roundtrip(self):
        tree = {"a": jnp.arange(6).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(3, tree, {"note": "x"})
            out, extra = ck.restore(target=tree)
            assert extra["note"] == "x"
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_crash_mid_write_ignored(self):
        """A stale .tmp dir without a .done marker must not be restored."""
        tree = {"a": jnp.ones((2,))}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(1, tree)
            os.makedirs(os.path.join(d, "step_00000002.tmp"))
            assert ck.latest_step() == 1

    def test_retention_gc(self):
        tree = {"a": jnp.ones((2,))}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, interval=1, keep=2, async_save=False)
            for s in range(1, 6):
                mgr.maybe_save(s, tree)
            steps = sorted(int(n[5:-5]) for n in os.listdir(d)
                           if n.endswith(".done"))
            assert steps == [4, 5]

    def test_async_then_wait(self):
        tree = {"a": jnp.ones((128,))}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save_async(9, tree)
            ck.wait()
            assert ck.latest_step() == 9

    def test_async_save_error_reraised_by_wait(self, monkeypatch):
        tree = {"a": jnp.ones((128,))}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)

            def fail(*args, **kwargs):
                raise OSError("disk full")

            monkeypatch.setattr(ck, "save", fail)
            ck.save_async(3, tree)
            with pytest.raises(OSError, match="disk full"):
                ck.wait()
            ck.wait()  # the error is reported once
            assert ck.latest_step() is None


class TestResume:
    def test_resume_is_bitwise_deterministic(self):
        """train(10) == train(5) + resume + train(5)."""
        with tempfile.TemporaryDirectory() as d:
            t1 = _setup(steps=10)
            t1.run()
            straight = t1.state

            t2 = _setup(steps=5, ckpt_dir=os.path.join(d, "ck"), interval=5)
            t2.run()
            t3 = _setup(steps=10, ckpt_dir=os.path.join(d, "ck"), interval=5)
            assert t3.try_resume()
            assert t3.step == 5
            t3.run()
            for a, b in zip(jax.tree.leaves(straight["params"]),
                            jax.tree.leaves(t3.state["params"])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_data_iterator_state_travels(self):
        with tempfile.TemporaryDirectory() as d:
            t = _setup(steps=7, ckpt_dir=d, interval=3)
            t.run()
            t2 = _setup(steps=9, ckpt_dir=d, interval=3)
            assert t2.try_resume()
            assert t2.data.step == t2.step


class TestWatchdog:
    def test_straggler_counted(self):
        t = _setup(steps=1)
        for _ in range(20):
            t._watchdog(0.01)
        events = []
        t.on_straggler = lambda step, ratio: events.append(ratio)
        t._watchdog(0.5)
        assert t.straggler_steps == 1
        assert events and events[0] > 3


class TestTracing:
    def test_step_spans_in_profiler_trace(self, tmp_path):
        """One ``train.step`` span per step, numbered by the step, holding
        one span of each phase."""
        from jax.profiler import ProfileData
        t = _setup(steps=2)
        t.run()                          # compiles outside the trace
        t.cfg.total_steps = 5
        with jax.profiler.trace(str(tmp_path)):
            t.run()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        spans = {name: [] for name in SPANS}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append((e.start_ns,
                                              e.start_ns + e.duration_ns,
                                              dict(e.stats)))
        steps = sorted(spans["train.step"])
        assert [s[2]["step_num"] for s in steps] == [2, 3, 4]
        for name in SPANS[1:]:
            assert len(spans[name]) == 3, name
            for (s, e, _), (ps, pe, _) in zip(sorted(spans[name]), steps):
                assert ps <= s <= e <= pe, name

    def test_phase_seconds_make_the_step_times(self):
        t = _setup(steps=4)
        first = t.run()
        assert {p: len(v) for p, v in t.phase_s.items()} == \
            {p: 4 for p in PHASES}
        assert t.step_times == [sum(t.phase_s[p][i] for p in PHASES)
                                for i in range(4)]
        assert first["host_s"] == {p: sum(v) for p, v in t.phase_s.items()}
        t.cfg.total_steps = 6
        second = t.run()                 # this run's steps alone
        assert second["host_s"] == {p: sum(v[4:])
                                    for p, v in t.phase_s.items()}
        assert "median_step_s" not in second


class TestArrayMetrics:
    def test_only_scalars_are_fetched(self):
        """Array metrics (an expert model's routing) stay on the device as
        the newest step's ``last_arrays``; the scalars become floats."""
        def step(state, batch, rng):
            return state + 1, {"loss": state * 2.0,
                               "route_topk": jnp.full((2, 3), state)}

        data = DataIterator(DataConfig(vocab_size=16, seq_len=4,
                                       global_batch=2))
        t = Trainer(jax.jit(step), jnp.zeros((), jnp.int32), data,
                    TrainerConfig(total_steps=3, log_interval=1))
        out = t.run(KEY)
        assert out["final_loss"] == 4.0 and type(out["final_loss"]) is float
        assert not any("route_topk" in k for k in out)
        assert [set(row) for row in t.metrics_log] == \
            [{"step", "time_s", "loss"}] * 3
        arr = t.last_arrays["route_topk"]
        assert set(t.last_arrays) == {"route_topk"}
        assert isinstance(arr, jax.Array) and arr.shape == (2, 3)
        assert int(arr[0, 0]) == 2          # the newest step's


class TestOptimizer:
    def test_adamw_decreases_simple_loss(self):
        w = {"w": jnp.array([2.0, -3.0])}
        cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                          weight_decay=0.0, grad_clip=0)
        st = init_state(w, cfg)
        for _ in range(50):
            g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(w)
            w, st, _ = apply_updates(w, g, st, cfg)
        assert float(jnp.abs(w["w"]).max()) < 0.5

    def test_grad_clip_bounds_update(self):
        w = {"w": jnp.zeros((4,))}
        cfg = AdamWConfig(lr=1.0, warmup_steps=0, grad_clip=1.0,
                          weight_decay=0.0)
        st = init_state(w, cfg)
        g = {"w": jnp.full((4,), 1e6)}
        _, _, metrics = apply_updates(w, g, st, cfg)
        assert float(metrics["grad_norm"]) > 1e5  # reported pre-clip

    def test_bf16_states_no_master(self):
        w = {"w": jnp.ones((8,), jnp.bfloat16)}
        cfg = AdamWConfig(state_dtype="bfloat16", use_master=False,
                          warmup_steps=0)
        st = init_state(w, cfg)
        assert "master" not in st
        assert st["m"]["w"].dtype == jnp.bfloat16
        g = {"w": jnp.ones((8,), jnp.bfloat16)}
        w2, st2, _ = apply_updates(w, g, st, cfg, rng=KEY)
        assert w2["w"].dtype == jnp.bfloat16

    def test_stochastic_rounding_unbiased(self):
        from repro.train.optimizer import _stochastic_round
        x = jnp.full((10000,), 1.0 + 2 ** -10)  # between bf16 grid points
        keys = jax.random.split(KEY, 8)
        means = [float(_stochastic_round(x, k).astype(jnp.float32).mean())
                 for k in keys]
        est = np.mean(means)
        assert abs(est - (1.0 + 2 ** -10)) < 2e-4


class TestMemoryPlanner:
    def test_small_model_zero1(self):
        plan = plan_memory(get_config("smollm-135m"), 16, 16)
        assert plan.zero_stage == 1 and plan.use_master

    def test_large_dense_fsdp(self):
        plan = plan_memory(get_config("internvl2-76b"), 16, 16)
        assert plan.zero_stage == 3

    def test_llama4_bf16_states(self):
        plan = plan_memory(get_config("llama4-maverick-400b-a17b"), 16, 16)
        assert plan.zero_stage == 3
        assert plan.opt_dtype == "bfloat16" and not plan.use_master
        assert plan.est_bytes_per_chip < 16e9

    def test_microbatching_sized_by_activations(self):
        from repro.configs.base import SHAPES
        plan = plan_memory(get_config("internlm2-20b"), 16, 16,
                           shape=SHAPES["train_4k"])
        assert plan.microbatches >= 8
        plan_small = plan_memory(get_config("smollm-135m"), 16, 16,
                                 shape=SHAPES["train_4k"])
        assert plan_small.microbatches <= plan.microbatches
