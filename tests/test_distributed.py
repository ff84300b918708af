"""Multi-device tests (8 host CPU devices via subprocess — the main pytest
process must keep seeing 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(script: str, n: int = 8) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                          capture_output=True, text=True, env=env,
                          timeout=600)


def check(proc):
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"


def test_dp_tp_grad_equivalence():
    """One train step on a (2,2) mesh == the same step on one device."""
    check(run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.parallel import build_mesh, plan_memory
        from repro.train.train_step import (jit_train_step, init_train_state,
                                            make_train_step)
        from repro.launch.specs import input_specs
        import dataclasses

        cfg = get_config("smollm-135m", reduced=True)
        plan = dataclasses.replace(plan_memory(cfg, 2, 2), microbatches=2)
        rng = jax.random.PRNGKey(0)
        state = init_train_state(cfg, plan, rng, dtype=jnp.float32)
        tokens = jax.random.randint(rng, (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "targets": tokens}
        step_rng = jax.random.PRNGKey(1)

        # single-device reference
        ref_step = jax.jit(make_train_step(cfg, plan))
        ref_state, ref_metrics = ref_step(state, batch, step_rng)

        # (2 data, 2 model) mesh
        mesh = build_mesh((2, 2), ("data", "model"))
        with mesh:
            shapes = jax.eval_shape(lambda: state)
            bshapes = jax.eval_shape(lambda: batch)
            step = jit_train_step(cfg, plan, mesh, shapes, bshapes,
                                  donate=False)
            out_state, metrics = step(state, batch, step_rng)
        np.testing.assert_allclose(float(metrics["loss"]),
                                   float(ref_metrics["loss"]),
                                   rtol=2e-4, atol=2e-4)
        for a, b in zip(jax.tree.leaves(out_state["params"]),
                        jax.tree.leaves(ref_state["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-3, atol=5e-3)
        print("OK")
        """))


def test_moe_ep_equivalence():
    """MoE forward on a (2,4) mesh (EP over model) == single device."""
    check(run_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models import get_model
        from repro.parallel import build_mesh, param_shardings, batch_shardings
        cfg = get_config("llama4-maverick-400b-a17b", reduced=True)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_experts=4, capacity_factor=4.0))
        mod = get_model(cfg)
        rng = jax.random.PRNGKey(0)
        params = mod.init_params(rng, cfg, dtype=jnp.float32)
        tokens = jax.random.randint(rng, (4, 16), 0, cfg.vocab_size)
        ref, _, _ = mod.forward(params, cfg, tokens)
        mesh = build_mesh((2, 4), ("data", "model"))
        with mesh:
            p_sh = param_shardings(cfg, params, mesh)
            fn = jax.jit(lambda p, t: mod.forward(p, cfg, t)[0],
                         in_shardings=(p_sh, None))
            out = fn(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        print("OK")
        """))


def test_mesh_launch_path_matches_one_device():
    """``launch/train.py --mesh 2,2`` (EP over model, ZeRO-1 over data,
    each data shard's tokens routed as a group) on granite's published
    variant at a reduced size == the same steps on one device with the
    same two routing groups."""
    check(run_devices("""
        import numpy as np
        import repro.models.transformer as transformer
        from repro.launch import train
        args = ["--arch", "granite-3.0-3b-a800m", "--reduced", "--steps",
                "3", "--global-batch", "4", "--seq-len", "32",
                "--log-interval", "1"]
        mesh = train.main(args + ["--mesh", "2,2"])
        transformer.data_groups = lambda b: 2
        one = train.main(args)
        for name in ("loss", "grad_norm", "moe_dropped_share"):
            a = [row[name] for row in mesh["metrics_log"]]
            b = [row[name] for row in one["metrics_log"]]
            print(name, a, b)
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6)
        print("OK")
        """, n=4))


@pytest.mark.parametrize("heads,kv_heads,per_shard", [
    (12, 4, True),      # granite's 24/8 heads on a model axis of 2, halved
    (9, 3, False),      # smollm's heads do not divide the model axis
])
def test_flash_per_shard_matches_naive(heads, kv_heads, per_shard):
    """Causal attention on a (2 data, 2 model) mesh: where the batch and
    both head counts divide the mesh, ``attention`` would run the flash
    kernel once per shard on a TPU, and that wrapper (interpret mode,
    small blocks) matches ``naive_attention`` in value and in the grads
    of q, k and v as closely as the bf16 reference does, with no
    collective in the forward; elsewhere it keeps XLA's path."""
    check(run_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax._src.mesh import AbstractDevice
        from jax.sharding import (AbstractMesh, AxisType, NamedSharding,
                                  PartitionSpec as P)
        from repro.kernels.flash_attention import Blocks
        from repro.models import common
        from repro.parallel import build_mesh

        mesh = build_mesh((2, 2), ("data", "model"))
        tpu = AbstractMesh((2, 2), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2,
                           abstract_device=AbstractDevice("TPU v5 lite", None))
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        shape = lambda h: (4, 256, h, 64)
        q32 = jax.random.normal(ks[0], shape({heads}), jnp.float32)
        k32 = jax.random.normal(ks[1], shape({kv_heads}), jnp.float32)
        v32 = jax.random.normal(ks[2], shape({kv_heads}), jnp.float32)
        do32 = jax.random.normal(ks[3], shape({heads}), jnp.float32)
        q, k, v, do = (x.astype(jnp.bfloat16) for x in (q32, k32, v32, do32))

        spec = common._shard_spec(tpu, q, k)
        with jax.sharding.use_abstract_mesh(tpu):
            staged = str(jax.make_jaxpr(common.attention)(q, k, v))
        assert ("pallas_call" in staged) == {per_shard}, staged
        with mesh:        # CPU devices: XLA's path, as before
            assert common._shard_spec(mesh, q, k) is None
            np.testing.assert_array_equal(
                jax.jit(common.attention)(q, k, v),
                jax.jit(common.naive_attention)(q, k, v))
        if not {per_shard}:
            assert spec is None, spec
        else:
            assert spec == P("data", None, "model", None), spec
            flash = common.flash_per_shard(mesh, spec, True,
                                           Blocks(128, 128, 128, 64, 64, 128),
                                           interpret=True)
            placed = [jax.device_put(x, NamedSharding(mesh, spec))
                      for x in (q, k, v)]
            hlo = jax.jit(flash).lower(*placed).compile().as_text()
            for op in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
                assert op not in hlo, op

            def vjp(f, *args):
                out, back = jax.vjp(f, *args)
                return (out,) + back(do.astype(out.dtype))

            got = jax.jit(lambda *a: vjp(flash, *a))(*placed)
            want = vjp(common.naive_attention, q32, k32, v32)
            bf16 = vjp(common.naive_attention, q, k, v)
            for name, g, w, b in zip(("out", "dq", "dk", "dv"), got, want, bf16):
                assert g.dtype == jnp.bfloat16 and g.shape == w.shape, name
                err = float(jnp.linalg.norm(g.astype(jnp.float32) - w))
                ref = float(jnp.linalg.norm(b.astype(jnp.float32) - w))
                print(name, err, ref)
                assert err <= 1.5 * ref + 1e-6, (name, err, ref)
        print("OK")
        """, n=4))


def test_zero_sharding_reduces_per_device_bytes():
    """ZeRO-1: optimizer states sharded over data -> per-device shard is
    1/dp of the full tensor."""
    check(run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.parallel import build_mesh, plan_memory
        from repro.train.train_step import init_train_state, state_shardings
        cfg = get_config("smollm-135m", reduced=True)
        plan = plan_memory(cfg, 2, 4)
        mesh = build_mesh((4, 2), ("data", "model"))
        rng = jax.random.PRNGKey(0)
        state = init_train_state(cfg, plan, rng, dtype=jnp.float32)
        sh = state_shardings(cfg, plan, jax.eval_shape(lambda: state), mesh)
        m_sh = sh["opt"]["m"]["layers"]["attn"]["wq"]
        m = state["opt"]["m"]["layers"]["attn"]["wq"]
        placed = jax.device_put(m, m_sh)
        shard_bytes = placed.addressable_shards[0].data.nbytes
        assert shard_bytes <= m.nbytes // 4 + 1024, (shard_bytes, m.nbytes)
        print("OK")
        """))


def test_gpipe_matches_sequential():
    check(run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel import build_mesh
        from repro.parallel.pipeline import gpipe
        mesh = build_mesh((4,), ("pipe",))
        def stage(p, x):
            return jnp.tanh(x @ p["w"])
        S, M, mb, d = 4, 8, 2, 16
        key = jax.random.PRNGKey(0)
        ws = {"w": jax.random.normal(key, (S, d, d)) * 0.5}
        x = jax.random.normal(key, (M, mb, d))
        y = gpipe(stage, ws, x, mesh=mesh)
        ref = x
        for i in range(S):
            ref = jax.vmap(lambda xm: stage({"w": ws["w"][i]}, xm))(ref)
        np.testing.assert_allclose(y, ref, atol=1e-5)
        # differentiability: grads flow through ppermute
        def loss(ws):
            return gpipe(stage, ws, x, mesh=mesh).sum()
        g = jax.grad(loss)(ws)
        assert np.isfinite(np.asarray(g["w"])).all()
        assert float(np.abs(np.asarray(g["w"])).sum()) > 0
        print("OK")
        """))


def test_compressed_psum_accuracy():
    check(run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel import build_mesh
        from repro.parallel.compression import compressed_psum
        mesh = build_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (8, 64))
        def red(x):
            s, e = compressed_psum(x, "data")
            return s
        out = jax.shard_map(red, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"))(g)
        ref = jnp.broadcast_to(g.sum(0, keepdims=True), g.shape)
        rel = float(jnp.max(jnp.abs(out - ref))) / float(jnp.max(jnp.abs(ref)))
        assert rel < 0.05, rel
        # error feedback: repeated reductions with feedback converge
        err = jnp.zeros_like(g)
        print("OK")
        """))


def test_elastic_reshard_restore():
    """Save on a (2,2) mesh, restore onto (4,1) — state identical."""
    check(run_devices("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.configs import get_config
        from repro.parallel import build_mesh, plan_memory
        from repro.train.train_step import init_train_state, state_shardings
        from repro.checkpoint import Checkpointer
        cfg = get_config("smollm-135m", reduced=True)
        plan = plan_memory(cfg, 2, 2)
        rng = jax.random.PRNGKey(0)
        state = init_train_state(cfg, plan, rng, dtype=jnp.float32)
        mesh_a = build_mesh((2, 2), ("data", "model"))
        sh_a = state_shardings(cfg, plan, jax.eval_shape(lambda: state), mesh_a)
        state_a = jax.device_put(state, sh_a)
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(7, state_a, {"step": 7})
            mesh_b = build_mesh((4, 1), ("data", "model"))
            sh_b = state_shardings(cfg, plan, jax.eval_shape(lambda: state), mesh_b)
            restored, extra = ck.restore(target=state, shardings=sh_b)
            assert extra["step"] == 7
            for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
        """))


def test_multipod_mesh_axes():
    """pod axis present and shardable on a small 3-axis mesh."""
    check(run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel import build_mesh, dp_axes, dp_size, mp_size
        mesh = build_mesh((2, 2, 2), ("pod", "data", "model"))
        assert dp_axes(mesh) == ("pod", "data")
        assert dp_size(mesh) == 4 and mp_size(mesh) == 2
        x = jnp.arange(8.0).reshape(8, 1)
        sh = NamedSharding(mesh, P(("pod", "data"), None))
        y = jax.device_put(x, sh)
        assert y.addressable_shards[0].data.shape == (2, 1)
        print("OK")
        """))
