"""CPU rehearsal of the training driver at tiny sizes, dense and with
experts, for about a second each: the record of a run holds what every
metric of its cell reads."""

import json
import time

import jax
import pytest

import chipbench_tiny as tb

import harness
import run
import traces


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench"))
    return tmp, tb.tiny_root(tmp)


@pytest.fixture(autouse=True)
def restore_precision():
    was = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", was)


def device_trace(record, lo, hi):
    """A stand-in device plane for a CPU run: the program ran back to back
    through the traced window, so the device readers have runs to read."""
    module = record["module"] or "jit_step"
    n = 4
    step = (hi - lo) / n
    runs = [(f"{module}(7)", lo + i * step, lo + (i + 0.8) * step)
            for i in range(n)]
    trace = record["trace"]
    return traces.Trace(ops={"/device:TPU:0": [("%fusion.1 = f32[] fusion",
                                                s, e) for _, s, e in runs]},
                        modules={"/device:TPU:0": runs}, spans=trace.spans)


@pytest.mark.parametrize("cell", sorted(tb.CELLS))
@pytest.mark.parametrize("traced", [False, True])
def test_record_feeds_every_metric(root, cell, traced):
    tmp, bench = root
    c = harness.load_cell(cell, tmp, bench)
    driver = c.module("drivers", c.traffic["driver"])
    ctx = harness.RunContext(cell=c, seed=2**31 + 7, seconds=1.0,
                             trace=traced, t_process=time.monotonic(),
                             compiles=harness.CompileCounter(),
                             trace_dir=tmp)
    record = driver.run(ctx)
    assert record["attempted"] > 0 and record["failed"] == 0
    assert record["setup_s"] > 0
    assert set(record["checks"]) and all(
        {"value", "limit"} == set(v) for v in record["checks"].values())
    specs = c.per_layer if traced else c.end_to_end
    if traced:
        assert record["trace"] is not None and record["module"]
        record["trace"] = device_trace(record, *record["trace"].window())
    for spec in specs:
        value = c.module("metrics", spec["name"]).read(record)
        assert value is not None and value == value, spec["name"]
        if spec["unit"] == "%":
            assert 0 <= value <= 100, spec["name"]
    names = {s["name"] for s in specs}
    assert "setup_s" in names or traced


def test_result_line(root, capsys):
    tmp, bench = root
    result = run.main(["--workload", "dense.train", "--seed", "5",
                       "--seconds", "0.5", "--trace", "0"], root=tmp,
                      bench_dir=bench, check_device=False,
                      compile_cache=False)
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert last["correct"] == result["correct"] is True
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert last["device"]["platform"] == jax.devices()[0].platform
    err = out.err.strip().splitlines()
    assert list(last["checks"]) == ["loss_gap", "grad_gap", "update_gap"]
    assert err[-3:] == [f"check {k}: {v['value']!r} limit {v['limit']!r}"
                        for k, v in last["checks"].items()]
