"""granite-3.0-3b-a800m at a tiny size on the CPU: the program against
``reference/granite.py`` (logits, loss, gradients, with one routing group
and two), the reference following a routing against its own, the mesh
driver's output check (sound, its control and three faults), its refusal
of a program that exports no routing, and the readers of its two metrics
on a hand-made trace."""

import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tb

import calibrate_mesh
import harness
import run
import traces
import weights
from reference.granite import GraniteRef

import repro.models.transformer as transformer

GRANITE = {
    "arch_id": "granite-3.0-3b-a800m", "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_local_experts": 8, "num_experts_per_tok": 2, "capacity_factor": 1.5,
    "router_aux_loss_coef": 0.01, "vocab_size": 256, "padded_vocab": 2048,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 6.0,
    "program": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                "num_kv_heads": 2, "head_dim": 16, "d_ff": 32,
                "vocab_size": 256,
                "moe": {"num_experts": 8, "top_k": 2, "d_ff": 32}},
    "train_precision": tb.TRAIN, "optimizer": tb.OPTIMIZER,
    "name": "granite",
}
MIX = {"driver": "train_mesh", "mesh": {"data": 1, "model": 1}, "batch": 4,
       "seq_len": 64, "chunk_steps": 2, "checked_steps": 3,
       "trace_seconds": 1}
CELL = "granite.mesh"
# Sound readings at these sizes on the CPU, seeds 1, 2, 3, 2**31 + 7,
# 2**33 + 9: at most 1.6e-5, 3.6e-3, 1.4e-3 and 3.3e-3; the control reads
# at least 2.4e-2 of route_gap.
LIMITS = {"loss_gap": 2e-4, "grad_gap": 0.01, "update_gap": 0.004,
          "route_gap": 0.01}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench"))
    bench = tb.tiny_root(tmp)
    config = {k: v for k, v in GRANITE.items() if k != "name"}
    tb.write_json(os.path.join(bench, "configs", "granite.json"), config)
    tb.write_json(os.path.join(bench, "traffic", "tiny-mesh.json"), MIX)
    tb.write_json(os.path.join(bench, "limits", CELL + ".json"),
                  {"limits": LIMITS})
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "granite", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "chip/configs/granite.json"})
    spec["workloads"].append({"name": CELL, "config": "granite",
                              "traffic": "tiny-mesh", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    tb.write_json(path, spec)
    return tmp, bench


@pytest.fixture(autouse=True)
def restore_precision():
    was = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", was)


def batch(seed=0, b=4, s=16):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, GRANITE["vocab_size"], (b, s + 1), dtype=np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "targets": jnp.asarray(toks[:, 1:])}


@pytest.fixture(scope="module")
def model():
    cfg = harness.model_config(GRANITE)
    params = weights.make(GRANITE, jax.random.PRNGKey(1), jnp.float32)
    return cfg, params


def test_scalars_are_granites(model):
    cfg, _ = model
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling,
            cfg.norm_eps) == (12.0, 0.22, 1 / 64, 6.0, 1e-6)


@pytest.mark.parametrize("groups", [1, 2])
def test_program_matches_reference(model, monkeypatch, groups):
    """Logits, loss and every gradient leaf in float32, each data shard's
    tokens routed as a group of its own."""
    cfg, params = model
    monkeypatch.setattr(transformer, "data_groups", lambda b: groups)
    jax.config.update("jax_default_matmul_precision", "highest")
    b = batch(seed=groups)
    ref = GraniteRef(GRANITE, groups=groups)
    got, _, _ = transformer.forward(params, cfg, b["tokens"], remat=None)
    h, _, _, _ = ref.hidden_routed(params, b["tokens"])
    want = jax.vmap(lambda r: ref.row_logits(params, r))(h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    prog = jax.value_and_grad(
        lambda p: transformer.loss(p, cfg, b, remat=None)[0])
    (lp, gp) = prog(params)
    lr, gr = jax.value_and_grad(ref.loss)(params, b)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=2e-3,
            atol=2e-5 * float(jnp.max(jnp.abs(y))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("groups", [1, 2])
def test_program_exports_the_routing_it_computed(model, monkeypatch,
                                                 groups):
    cfg, params = model
    monkeypatch.setattr(transformer, "data_groups", lambda b: groups)
    b = batch(seed=3)
    _, parts = transformer.loss(params, cfg, b, remat=None)
    t, e, k = 4 * 16, 8, 2
    cap = int(t // groups * k * 1.5 / e)
    assert parts["route_topk"].shape == (2, t, k)
    assert parts["route_kept"].shape == (2, groups, e, cap)
    kept = np.asarray(parts["route_kept"])
    topk = np.asarray(parts["route_topk"])
    # Every kept (expert, token) pair is one of the token's top-k choices.
    for layer, g, x in zip(*np.nonzero(kept.max(-1) >= 0)):
        toks = kept[layer, g, x][kept[layer, g, x] >= 0] + g * (t // groups)
        assert (topk[layer, toks] == x).any(axis=1).all()
    dropped = 1 - (kept >= 0).sum(axis=(1, 2, 3)) / (t * k)
    assert float(parts["moe_dropped_share"]) == pytest.approx(dropped.mean())
    assert float(parts["moe_load_max"]) >= 1.0


def test_following_its_own_routing_changes_nothing(model):
    _, params = model
    ref = GraniteRef(GRANITE, groups=2)
    b = batch(seed=4)
    grad = jax.value_and_grad(ref.loss_and_routes, has_aux=True)
    (loss, (own, miss, total)), g = grad(params, b)
    (forced, (_, fmiss, ftotal)), fg = grad(params, b, own)
    assert int(miss) == int(fmiss) == 0 and int(ftotal) == int(total)
    assert float(forced) == float(loss)
    for x, y in zip(jax.tree.leaves(fg), jax.tree.leaves(g)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_following_the_programs_routing(model):
    """The float32 program's routing is the reference's own at this size:
    followed, it gives what the reference gives unforced."""
    cfg, params = model
    jax.config.update("jax_default_matmul_precision", "highest")
    b = batch(seed=5)
    _, parts = transformer.loss(params, cfg, b, remat=None)
    route = {"topk": parts["route_topk"], "kept": parts["route_kept"]}
    ref = GraniteRef(GRANITE)
    loss, (_, miss, _) = ref.loss_and_routes(params, b, route)
    assert int(miss) == 0
    assert float(loss) == pytest.approx(float(ref.loss(params, b)),
                                        rel=1e-6)


def test_a_changed_routing_is_counted(model):
    _, params = model
    ref = GraniteRef(GRANITE)
    b = batch(seed=6)
    _, (own, _, total) = ref.loss_and_routes(params, b)
    topk = np.asarray(own["topk"]).copy()
    topk[0, :3, 0] = (topk[0, :3, 1] + 1) % 8     # three new choices
    topk[0, :3, 0] = np.where(topk[0, :3, 0] == topk[0, :3, 1],
                              (topk[0, :3, 0] + 1) % 8, topk[0, :3, 0])
    route = {"topk": jnp.asarray(topk), "kept": own["kept"]}
    _, (_, miss, total2) = ref.loss_and_routes(params, b, route)
    assert int(total2) == int(total)
    assert 1 <= int(miss) <= 3


def correct(root, seed=3, trace=0):
    tmp, bench = root
    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.5", "--trace", str(trace)], root=tmp,
                    bench_dir=bench, check_device=False,
                    compile_cache=False)["correct"]


def test_sound_training_is_correct(root):
    assert correct(root)


def test_half_the_experts_is_not_correct(root, monkeypatch):
    """A step whose expert layers lose the second half of the experts'
    outputs, as a mesh that leaves out the model axis's sum would."""
    real = transformer.moe_block

    def half(params, x, **kw):
        e = params["we_down"].shape[0]
        params = dict(params, we_down=params["we_down"].at[e // 2:].set(0))
        return real(params, x, **kw)

    monkeypatch.setattr(transformer, "moe_block", half)
    assert not correct(root)


@pytest.fixture(scope="module")
def readings(root):
    tmp, bench = root
    cell = harness.load_cell(CELL, tmp, bench)
    driver = cell.module("drivers", "train_mesh")
    sound = driver.Follower(cell.config, 1)
    control = driver.Follower(cell.config, 1, quant="fp8")
    return [calibrate_mesh.readings(cell, driver, seed, sound, control)[1]
            for seed in (1, 2**33 + 9)]


@pytest.mark.parametrize("kind", ["control", "half_batch", "experts_half",
                                  "unchanged"])
def test_control_and_faults_fail_where_the_program_passes(readings, kind):
    for out in readings:
        assert all(out["program"][k] <= v for k, v in LIMITS.items()), out
        assert any(out[kind][k] > v for k, v in LIMITS.items()), out[kind]


def test_a_program_without_routing_is_refused_before_compiling(
        root, monkeypatch):
    real = transformer.loss

    def loss(*args, **kw):
        total, parts = real(*args, **kw)
        return total, {k: v for k, v in parts.items()
                       if not k.startswith("route_")}

    monkeypatch.setattr(transformer, "loss", loss)
    tmp, bench = root
    cell = harness.load_cell(CELL, tmp, bench)
    ctx = harness.RunContext(cell=cell, seed=1, seconds=0.0, trace=False,
                             t_process=time.monotonic(),
                             compiles=harness.CompileCounter())
    driver = cell.module("drivers", "train_mesh")
    with pytest.raises(harness.BenchError, match="exports no"):
        driver.Prepared(ctx)
    assert ctx.compile_count() <= 1     # the state's build, not the step


def hand_made():
    """One device, the step program running twice in [0, 10] and
    [10, 20]: per run an MoE fusion of 2, an MoE all-reduce of 1, an
    attention all-gather of 0.5 and an unscoped op of 1."""
    ops, runs = [], []
    for t0 in (0.0, 10.0):
        runs.append(("jit_step(1)", t0, t0 + 10))
        ops += [("%fusion.1 = bf16[] fusion()", t0, t0 + 2),
                ("%all-reduce.3 = bf16[] all-reduce()", t0 + 2, t0 + 3),
                ("%all-gather.4 = bf16[] all-gather()", t0 + 3, t0 + 3.5),
                ("%copy.9 = bf16[] copy()", t0 + 4, t0 + 5)]
    trace = traces.Trace(ops={"/device:TPU:0": ops},
                         modules={"/device:TPU:0": runs},
                         spans=[("window", 0.0, 20.0)])
    return {"kind": "train", "trace": trace, "module": "jit_step",
            "op_scopes": {"fusion.1": "moe", "all-reduce.3": "moe",
                          "all-gather.4": "attention"},
            "collectives": ["all-gather.4", "all-reduce.3"]}


def test_moe_metrics_on_a_hand_made_trace(root):
    _, bench = root
    record = hand_made()

    def read(name, rec):
        return harness.load_module(os.path.join(
            bench, "metrics", name + ".py")).read(rec)

    assert read("train_step.moe_ms", record) == pytest.approx(3e3)
    assert read("train_step.moe_collective_ms", record) == pytest.approx(1e3)
    bare = copy.copy(record)
    bare["trace"] = None
    assert read("train_step.moe_ms", bare) is None
    assert read("train_step.moe_collective_ms", bare) is None


HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[]}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b)
}

%fused_gather (p: f32[4]) -> f32[8] {
  %p = f32[4]{0} parameter(0)
  ROOT %all-gather.1 = f32[8]{0} all-gather(%p), dimensions={0}
}

ENTRY %main (x: f32[4]) -> f32[8] {
  %x = f32[4]{0} parameter(0)
  %all-reduce-start.2 = f32[4]{0} all-reduce-start(%x), to_apply=%sum
  %all-reduce-done.2 = f32[4]{0} all-reduce-done(%all-reduce-start.2)
  %copy.3 = f32[4]{0} copy(%all-reduce-done.2)
  ROOT %fusion.4 = f32[8]{0} fusion(%copy.3), kind=kLoop, \
calls=%fused_gather
}
"""


def test_collectives_of_the_hlo(root):
    _, bench = root
    driver = harness.load_module(os.path.join(bench, "drivers",
                                              "train_mesh.py"))
    assert driver.collective_ops(HLO) == [
        "all-gather.1", "all-reduce-done.2", "all-reduce-start.2",
        "fusion.4"]


FUSED = """\
HloModule jit_step, entry_computation_layout={()->f32[4]}

%fused_add (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%p, %p), \
metadata={op_name="jit(step)/moe/combine/add"}
}

%fused_mixed (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  %mul.2 = f32[4]{0} multiply(%q, %q), \
metadata={op_name="jit(step)/moe/combine/mul"}
  ROOT %sub.3 = f32[4]{0} subtract(%mul.2, %q), \
metadata={op_name="jit(step)/attention/sub"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_add
  ROOT %fusion.8 = f32[4]{0} fusion(%fusion.7), kind=kLoop, \
calls=%fused_mixed
}
"""


def test_a_fusion_without_a_scope_takes_its_bodys(root):
    _, bench = root
    driver = harness.load_module(os.path.join(bench, "drivers",
                                              "train_mesh.py"))
    got = driver.step_scopes(FUSED)
    assert got["fusion.7"] == "moe" and got["add.1"] == "moe"
    assert "fusion.8" not in got            # its body holds two scopes
