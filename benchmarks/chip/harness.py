"""Plumbing of the chip benchmark: cells, files found by name, the device.

Everything that belongs to one configuration, traffic mix, driver or metric
sits in a file of its own, found by the name that ``BENCHMARK.json`` gives:

    <bench>/configs/<config>.json   (the ``file`` of the config entry)
    <bench>/traffic/<traffic>.json  names its ``driver`` and its parameters
    <bench>/drivers/<driver>.py     ``run(ctx) -> record``
    <bench>/metrics/<metric>.py     ``read(record, trace) -> float | None``
    <bench>/limits/<workload>.json  the limits of the output check

No list of names is kept in code, so a cell, a mix or a metric is added by
adding files and entries.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
SRC_DIR = os.path.join(REPO_ROOT, "src")

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A cell, file or device that the benchmark cannot run on."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import a file by path; the file name may hold dots and dashes."""
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {path}")
    name = "chipbench_" + os.path.relpath(path, REPO_ROOT).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic file
    limits: dict            # {check name: limit}
    end_to_end: List[dict]  # BENCHMARK.json metric entries of this cell
    per_layer: List[dict]
    bench_dir: str

    def module(self, kind: str, name: str) -> ModuleType:
        return load_module(os.path.join(self.bench_dir, kind, name + ".py"))


def load_cell(workload: str, root: str = REPO_ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    config["name"] = w["config"]
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    traffic["name"] = w["traffic"]
    limits_path = os.path.join(bench_dir, "limits", workload + ".json")
    limits = load_json(limits_path)["limits"] if os.path.isfile(
        limits_path) else {}
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
        bench_dir=bench_dir)


def peaks_of(kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))["kinds"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"(have {sorted(table)})")
    return table[kind]


def require_chips(chips: int, bench_dir: str = BENCH_DIR):
    """The devices of the run: TPUs, at least ``chips``, with known peaks."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    peaks_of(dev.device_kind, bench_dir)
    return devices[:chips]


def peak_bytes(device) -> int:
    """The device's own peak of bytes in use, where it reports one."""
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def use_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.launch.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``.jax_cache`` at the root of the checkout), keeping every
    program there: the weights' and the reference's small programs compile
    in less than the second that JAX asks by default."""
    if SRC_DIR not in sys.path:
        sys.path.insert(0, SRC_DIR)
    from repro.launch.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


class CompileCounter:
    """Counts the programs that JAX compiles or loads from its cache."""

    def __init__(self):
        import jax
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "")))


@dataclasses.dataclass
class RunContext:
    """What a driver is given: the cell, the run's arguments, the clock."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                    # time.monotonic() at process start
    compiles: Optional[CompileCounter] = None
    trace_dir: Optional[str] = None

    def span(self, name: str):
        """A host span in the profiler's trace; nothing when not tracing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def compile_count(self) -> int:
        return self.compiles.count if self.compiles is not None else 0

    def compiled_since(self, count: int) -> List[str]:
        """Names of the programs compiled or loaded since ``count``."""
        return self.compiles.names[count:] if self.compiles is not None \
            else []


def model_config(config: dict):
    """The program's ModelConfig for a configuration file.

    The file names the repository's ``arch_id`` and, under ``program``,
    the fields set to cut it (``moe`` as a dict of its fields); every
    width the file states is checked
    against the program's config, so that the two cannot drift apart."""
    if SRC_DIR not in sys.path:
        sys.path.insert(0, SRC_DIR)
    from repro.configs import get_config
    cfg = get_config(config["arch_id"])
    fields = dict(config.get("program", {}))
    if isinstance(fields.get("moe"), dict):
        fields["moe"] = dataclasses.replace(cfg.moe, **fields["moe"])
    cfg = dataclasses.replace(cfg, **fields)
    want = {
        "d_model": config["hidden_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "resolved_head_dim": config["head_dim"],
        "vocab_size": config["vocab_size"],
        "padded_vocab": config["padded_vocab"],
        "norm_eps": config["rms_norm_eps"],
        "rope_theta": config["rope_theta"],
        "tie_embeddings": config["tie_word_embeddings"],
    }
    if "num_local_experts" in config:
        want.update({
            "moe.num_experts": config["num_local_experts"],
            "moe.top_k": config["num_experts_per_tok"],
            "moe.d_ff": config["intermediate_size"],
            "moe.capacity_factor": config["capacity_factor"],
            "moe.aux_loss_weight": config["router_aux_loss_coef"],
        })
    else:
        want["d_ff"] = config["intermediate_size"]
    for key, value in want.items():
        obj = cfg
        for part in key.split("."):
            obj = getattr(obj, part)
        if obj != value:
            raise BenchError(f"{config['name']}: the program's {key} is "
                             f"{obj!r}, the configuration file says {value!r}")
    return cfg


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]) -> None:
    """Print the compared numbers as the last lines of standard error, and
    the result as the last line of standard output, checks last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)
