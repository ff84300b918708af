"""The benchmark is driven by data: ``BENCHMARK.json`` keeps to its
contract, every name it gives has its file, a new configuration, mix,
driver and metric are added as files without editing one, and the command
refuses a device that is not a TPU or whose kind has no peaks."""

import json
import os
import re
import subprocess
import sys

import pytest

import chipbench_tiny as tb

import harness
import run

SPEC_PATH = os.path.join(tb.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(SPEC_PATH) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmarks/chip"]
    assert spec["command"] == ["python3", "benchmarks/chip/run.py"]
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51


def test_names_units_and_keys(spec):
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in spec[group]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(tb.ROOT, c["file"]))
        assert c["file"].startswith("benchmarks/chip/")
        assert all(NAME.match(k) for k in c["reduced"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}


def test_every_cell_reports_what_it_must(spec):
    cells = {w["name"]: w for w in spec["workloads"]}
    assert all(w["chips"] in (1, 4) for w in cells.values())
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    for name in cells:
        e2e = [m["name"] for m in spec["end_to_end"]
               if harness.applies(m, name)]
        layer = [m for m in spec["per_layer"] if harness.applies(m, name)]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, name
        for m in layer:
            assert m["moves"] in e2e, (name, m["name"])


def test_every_name_has_its_file(spec):
    bench = tb.BENCH_DIR
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert os.path.isfile(os.path.join(bench, "drivers",
                                           cell.traffic["driver"] + ".py"))
        if cell.traffic["driver"] == "train":
            assert set(cell.limits) == {"loss_gap", "grad_gap",
                                        "update_gap"}
        assert cell.limits, w["name"]
        harness.model_config(cell.config)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(harness.load_module(
            os.path.join(bench, "metrics", m["name"] + ".py")), "read")


DUMMY_DRIVER = '''
def run(ctx):
    return {"kind": "dummy", "setup_s": 0.25, "attempted": 3, "failed": 0,
            "memory_peak_bytes": 0, "window_compiles": [],
            "rate": ctx.cell.traffic["rate"] * ctx.cell.config["scale"],
            "checks": {"exact": {"value": 0.0, "limit": 0.0}}}
'''

DUMMY_METRIC = '''
def read(record):
    return record.get("rate")
'''


def test_a_cell_is_added_by_files_alone(tmp_path, capsys):
    tmp = str(tmp_path)
    bench = tb.tiny_root(tmp)
    tb.write_json(os.path.join(bench, "configs", "toy.json"), {"scale": 2})
    tb.write_json(os.path.join(bench, "traffic", "toy-mix.json"),
                  {"driver": "toy", "rate": 1.5})
    tb.write_json(os.path.join(bench, "limits", "toy.toy-mix.json"),
                  {"limits": {"exact": 0.0}})
    with open(os.path.join(bench, "drivers", "toy.py"), "w") as f:
        f.write(DUMMY_DRIVER)
    with open(os.path.join(bench, "metrics", "toy_rate.py"), "w") as f:
        f.write(DUMMY_METRIC)
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy", "source": "test", "reduced": [],
                            "why": "test", "file": "chip/configs/toy.json"})
    spec["workloads"].append({"name": "toy.toy-mix", "config": "toy",
                              "traffic": "toy-mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "toy_rate", "unit": "1/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["toy.toy-mix"]})
    tb.write_json(path, spec)
    result = run.main(["--workload", "toy.toy-mix", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=tmp,
                      bench_dir=bench, check_device=False,
                      compile_cache=False)
    assert result["correct"] is True
    assert result["metrics"] == {"toy_rate": {"value": 3.0, "unit": "1/s"},
                                 "setup_s": {"value": 0.25, "unit": "s"}}


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tb.BENCH_DIR, "run.py"), "--workload",
         "smollm-135m.train-4x2048", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


class FakeTPU:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [FakeTPU("TPU v99")])
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.require_chips(1)
    monkeypatch.setattr(jax, "devices", lambda: [FakeTPU("TPU v5 lite")])
    assert len(harness.require_chips(1)) == 1
    with pytest.raises(harness.BenchError, match="needs 4 chips"):
        harness.require_chips(4)


def test_seeds_wider_than_32_bits_differ():
    import jax
    keys = {tuple(jax.device_get(harness.seed_key(s)).tolist())
            for s in (5, 5 + 2**32, 2**40, 2**31 + 5)}
    assert len(keys) == 4
