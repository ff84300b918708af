"""The reduction from a profiler trace to busy time, program time and the
breakdown, on a small trace recorded on a TPU v5e (a bf16 2048 x 2048
matmul run five times under the span ``probe_span``) and on hand-made
intervals."""

import os

import pytest

import chipbench_tiny as tb

import traces

TRACE = os.path.join(tb.BENCH_DIR, "tests", "data", "tpu_matmul.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return traces.load(TRACE, ["probe_span"])


def test_recorded_trace_planes(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    runs = recorded.modules["/device:TPU:0"]
    assert len(runs) == 5
    assert {traces.module_name(n) for n, _, _ in runs} == {"jit__lambda"}
    assert [s[0] for s in recorded.spans] == ["probe_span"]


def test_recorded_busy_and_program_time(recorded):
    lo, hi = -1.0, 1.0       # the whole trace
    runs, secs = traces.program_runs(recorded, "jit__lambda", lo, hi)
    assert runs == 5
    # Each run is one 2048^3 matmul: about 90 us on the chip.
    assert secs / runs == pytest.approx(90e-6, rel=0.05)
    busy = traces.busy_s(recorded, lo, hi)
    assert busy == pytest.approx(secs, rel=0.01)   # ops fill each run
    top = traces.top_ops(recorded, lo, hi)
    assert top[0][0] == "convolution_reduce_fusion bf16[]"
    assert top[0][1] == pytest.approx(secs, rel=0.01)


def test_recorded_window_and_gaps(recorded):
    lo, hi = recorded.window("probe_span")
    assert 0 < hi - lo < 0.01
    gaps = traces.idle_gaps(recorded, lo, hi)
    assert gaps and all(name == "probe_span" for name, _ in gaps)
    busy = traces.busy_s(recorded, lo, hi)
    # The gaps and the busy time fill the window.
    all_gaps = traces.idle_gaps(recorded, lo, hi, n=1000)
    assert busy + sum(g for _, g in all_gaps) == pytest.approx(hi - lo)


def test_merge_clips_and_joins():
    pieces = traces.merge([(0, 2), (1, 3), (5, 6), (7, 9)], 0.5, 8)
    assert pieces == [(0.5, 3), (5, 6), (7, 8)]


def test_nested_ops_count_their_own_time():
    ops = [("%while.1 = ()", 0.0, 10.0), ("%fusion.1 = ()", 1.0, 4.0),
           ("%fusion.2 = ()", 5.0, 6.0), ("%copy.1 = ()", 12.0, 13.0)]
    own = {n: t for n, _, _, t in traces.self_times(ops)}
    assert own == {"%while.1 = ()": 6.0, "%fusion.1 = ()": 3.0,
                   "%fusion.2 = ()": 1.0, "%copy.1 = ()": 1.0}
    t = traces.Trace(ops={"/device:TPU:0": ops}, modules={}, spans=[])
    assert traces.top_ops(t, 0.0, 20.0)[0] == ["while.1 ()", 6.0]
    assert traces.busy_s(t, 0.0, 20.0) == 11.0


def synthetic():
    return traces.Trace(
        ops={"/device:TPU:0": [("%a = f32[] add", 1.0, 2.0),
                               ("%b = f32[] mul", 1.5, 2.5),
                               ("%a = f32[] add", 4.0, 5.0)],
             "/device:TPU:1": [("%a = f32[] add", 1.0, 3.0)]},
        modules={"/device:TPU:0": [("jit_step(1)", 1.0, 2.5),
                                   ("jit_other(2)", 4.0, 5.0)]},
        spans=[("window", 0.0, 6.0), ("tick", 2.4, 4.1),
               ("wait", 5.0, 6.0)])


def test_synthetic_reductions():
    t = synthetic()
    lo, hi = t.window()
    assert (lo, hi) == (0.0, 6.0)
    # Device 0 is busy 1.5 + 1.0 s, device 1 2.0 s: 2.25 s on average.
    assert traces.busy_s(t, lo, hi) == pytest.approx(2.25)
    assert traces.program_runs(t, "jit_step", lo, hi) == (1, 1.5)
    assert traces.top_ops(t, lo, hi)[0] == ["a f32[]", pytest.approx(2.0)]
    gaps = traces.idle_gaps(t, lo, hi)
    assert gaps[0] == ["tick", pytest.approx(1.5)]
    assert [g[0] for g in gaps] == ["tick", "none", "wait"]


def test_no_device_reads_no_busy_time():
    t = traces.Trace(ops={}, modules={}, spans=[("window", 0.0, 1.0)])
    assert traces.busy_s(t, 0.0, 1.0) == 0.0
    assert traces.idle_gaps(t, 0.0, 1.0) == []
