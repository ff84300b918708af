"""The training control at a size a test can hold: the reference with
float8 matmul operands, put in the program's place, reads above what the
program reads and fails the tiny limits that the program passes. (On the
chip the same comparison is read at the cells' own sizes by
``calibrate.py``; the limits in ``limits/`` come from those readings.)"""

import time

import pytest

import chipbench_tiny as tb
import test_chipbench_faults as faults

import harness


@pytest.fixture(scope="module")
def dense_cell(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench"))
    bench = tb.tiny_root(tmp, limits=faults.LIMITS)
    return harness.load_cell("dense.train", tmp, bench)


def readings(cell, seed):
    driver = cell.module("drivers", "train")
    ctx = harness.RunContext(cell=cell, seed=seed, seconds=0.0, trace=False,
                             t_process=time.monotonic())
    prep = driver.Prepared(ctx)
    prog, batches, key = prep.prog, prep.batches(), prep.key
    prep.release()
    del prep
    ref = driver.Follower(cell.config)(key, batches)
    ctrl = driver.Follower(cell.config, quant="fp8")(key, batches)
    return driver.numbers(prog, ref), driver.numbers(ctrl, ref)


@pytest.mark.parametrize("seed", [1, 2**32 + 9])
def test_control_fails_where_the_program_passes(dense_cell, seed):
    prog, ctrl = readings(dense_cell, seed)
    limits = dense_cell.limits
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl
    assert ctrl["loss_gap"] > 3 * prog["loss_gap"]
    assert ctrl["grad_gap"] > 3 * prog["grad_gap"]


def test_leaf_gaps_are_taken_over_the_larger_of_leaf_and_median():
    import compare
    want = {"a": 1.0, "b": 2.0, "c": 4.0, "router": 0.1}
    got = {"a": 1.001, "b": 2.002, "c": 4.004, "router": 0.13}
    # The router's gap is taken over the median leaf's norm (1.5).
    assert compare.leaf_gaps(got, want)["router"] == pytest.approx(0.02)
    assert compare.worst_leaf_gap(got, want) == pytest.approx(0.02)
    assert compare.worst_leaf_gap({}, want) == float("inf")
