"""Device time per sublayer and idle time per host phase: the program's
scopes reach the tiny step's compiled HLO, its spans reach the trace of a
CPU window, and the reductions give known answers on hand-made traces."""

import time

import pytest

import chipbench_tiny as tb

import harness
import scopes
import traces
from repro.train.train_step import SCOPES
from repro.train.trainer import PHASES, SPANS

PHASE_SPANS = [f"train.{p}" for p in PHASES]


@pytest.fixture(scope="module")
def measured(tmp_path_factory):
    """The dense tiny cell's set-up and a half-second traced window."""
    tmp = str(tmp_path_factory.mktemp("bench"))
    bench = tb.tiny_root(tmp)
    cell = harness.load_cell("dense.train", tmp, bench)
    ctx = harness.RunContext(cell=cell, seed=2**33 + 5, seconds=0.5,
                             trace=True, t_process=time.monotonic(),
                             trace_dir=tmp)
    return scopes.measure(ctx, cell.module("drivers", "train"))


def test_every_matmul_of_the_step_has_a_scope(measured):
    _, hlo, _, _ = measured
    top = scopes.top_level(hlo)
    matmuls = {n for n, matmul in top.items() if matmul}
    got = scopes.op_scopes(hlo, SCOPES)
    assert matmuls and len(top) > len(matmuls)
    assert {n: got.get(n) for n in matmuls if got.get(n) not in SCOPES} == {}
    assert set(got.values()) == set(SCOPES)


def test_the_window_holds_one_span_per_phase_per_step(measured):
    trace, _, _, phase_s = measured
    count = {name: sum(s[0] == name for s in trace.spans) for name in SPANS}
    steps = len(phase_s["input"])
    assert steps > 0 and count == {name: steps for name in SPANS}
    assert {p: len(t) for p, t in phase_s.items()} == \
        {p: steps for p in PHASES}


def test_a_device_plane_of_the_steps_own_ops(measured):
    """A stand-in device plane for the CPU window: during each step's sync
    span the step program runs each scoped instruction of its HLO in turn,
    and the device is idle the rest of the time."""
    trace, hlo, module, _ = measured
    names = sorted(scopes.op_scopes(hlo, SCOPES))
    ops, runs = [], []
    for name, s, e in trace.spans:
        if name != "train.sync":
            continue
        runs.append((f"{module}(3)", s, e))
        dt = (e - s) / len(names)
        ops += [(f"%{n} = f32[] fusion()", s + i * dt, s + (i + 1) * dt)
                for i, n in enumerate(names)]
    stand_in = traces.Trace(ops={"/device:TPU:0": ops},
                            modules={"/device:TPU:0": runs},
                            spans=trace.spans)
    out = scopes.attribution(stand_in, hlo, module, SCOPES, PHASE_SPANS)
    per_scope = out["scope_s_per_run"]
    assert set(per_scope) == set(SCOPES) and min(per_scope.values()) > 0
    assert sum(per_scope.values()) == pytest.approx(out["program_s_per_run"])
    lo, hi = stand_in.window()
    idle = out["idle_by_phase_s"]
    assert idle["train.sync"] == idle["in_program"] == 0
    for name in ("train.input", "train.dispatch"):
        assert idle[name] == pytest.approx(sum(
            e - s for n, s, e in stand_in.spans if n == name))
    assert sum(idle.values()) <= out["idle_s"] + 1e-9


def test_scope_of_unwraps_transforms():
    assert scopes.scope_of("jit(step)/transpose(jvp())/while/body/"
                           "checkpoint/attention/dot_general",
                           SCOPES) == "attention"
    assert scopes.scope_of("jit(step)/transpose(jvp(head))/dot_general",
                           SCOPES) == "head"
    assert scopes.scope_of("jit(step)/optimizer/head/add", SCOPES) == "head"
    assert scopes.scope_of("jit(step)/jvp(jit(_take))/gather",
                           SCOPES) is None
    assert scopes.scope_of("", SCOPES) is None


HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[4,4]) -> f32[4,4] {
  %param_0 = f32[4,4]{1,0} parameter(0)
  ROOT %dot.1 = f32[4,4]{1,0} dot(%param_0, %param_0), \
metadata={op_name="jit(step)/jvp()/ffn/dot_general"}
}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b)
}

ENTRY %main (p: f32[4,4]) -> f32[] {
  %p = f32[4,4]{1,0} parameter(0)
  %fusion.1 = f32[4,4]{1,0} fusion(%p), kind=kOutput, \
calls=%fused_computation, metadata={op_name="jit(step)/jvp()/ffn/add"}
  %constant.1 = f32[] constant(0)
  ROOT %reduce.1 = f32[] reduce(%fusion.1, %constant.1), dimensions={0,1}, \
to_apply=%region_0, metadata={op_name="jit(step)/optimizer/reduce_sum"}
}
"""


def test_hlo_text_reductions():
    assert scopes.op_scopes(HLO, SCOPES) == {
        "dot.1": "ffn", "fusion.1": "ffn", "reduce.1": "optimizer"}
    # Top level: the entry's four; the fusion holds the matmul.
    assert scopes.top_level(HLO) == {"p": False, "fusion.1": True,
                                     "constant.1": False, "reduce.1": False}


def hand_made():
    """One device. The step program runs twice, [1, 4] and [6, 8]; inside
    the first run no op runs in [2, 2.5]. Another program runs an eager op
    in [4.6, 4.8]. Host spans: input [0, 1] and [4.5, 5.5], dispatch
    [5.5, 6.2], sync [6.2, 8.5]."""
    ops = [("%while.1 = () while()", 1.0, 2.0),
           ("%fusion.1 = f32[] fusion()", 1.0, 1.5),      # attention
           ("%fusion.2 = f32[] fusion()", 2.5, 3.0),      # ffn
           ("%copy.1 = f32[] copy()", 3.0, 4.0),          # no scope
           ("%fusion.9 = f32[] fusion()", 4.6, 4.8),      # not the step's
           ("%fusion.1 = f32[] fusion()", 6.0, 7.5),      # attention
           ("%fusion.3 = f32[] fusion()", 7.5, 8.0)]      # optimizer
    runs = [("jit_step(7)", 1.0, 4.0), ("jit_other(8)", 4.6, 4.8),
            ("jit_step(7)", 6.0, 8.0)]
    spans = [("window", 0.0, 9.0), ("train.input", 0.0, 1.0),
             ("train.input", 4.5, 5.5), ("train.dispatch", 5.5, 6.2),
             ("train.sync", 6.2, 8.5)]
    return traces.Trace(ops={"/device:TPU:0": ops},
                        modules={"/device:TPU:0": runs}, spans=spans)


def test_scope_self_s_on_a_hand_made_trace():
    t = hand_made()
    names = {"fusion.1": "attention", "fusion.2": "ffn",
             "fusion.3": "optimizer", "fusion.9": "head"}
    got = scopes.scope_self_s(t, "jit_step", names, 0.0, 9.0)
    # The while's own time is its 1 s less the 0.5 s of the op inside it.
    assert got == pytest.approx({"attention": 2.0, "ffn": 0.5,
                                 "optimizer": 0.5, "none": 1.5})
    # Only runs that started in [lo, hi] count.
    assert scopes.scope_self_s(t, "jit_step", names, 5.0, 9.0) == \
        pytest.approx({"attention": 1.5, "optimizer": 0.5})


def test_idle_in_span_on_a_hand_made_trace():
    t = hand_made()
    lo, hi = t.window()
    # Idle: [0, 1], [2, 2.5] (in a run), [4, 4.6], [4.8, 6], [8, 9].
    idle = {name: scopes.idle_in_span(t, lo, hi, name, "jit_step")
            for name in PHASE_SPANS}
    assert idle == pytest.approx({"train.input": 1.0 + 0.1 + 0.7,
                                  "train.dispatch": 0.5,
                                  "train.sync": 0.5})
    assert scopes.idle_in_program(t, lo, hi, "jit_step") == \
        pytest.approx(0.5)
    out = scopes.attribution(t, "", "jit_step", SCOPES, PHASE_SPANS)
    assert out["idle_s"] == pytest.approx(4.3)
    # What no span holds: [4, 4.5] and [8.5, 9].
    assert out["idle_s"] - sum(out["idle_by_phase_s"].values()) == \
        pytest.approx(1.0)
    assert out["program_runs"] == 2
    assert out["program_s_per_run"] == pytest.approx(2.5)
    assert out["scope_s_per_run"] == pytest.approx({"none": 2.25})
