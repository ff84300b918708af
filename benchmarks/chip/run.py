"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs only on TPUs: on any other platform, with fewer chips than the cell
asks, or on a device kind missing from ``peaks.json`` it exits non-zero and
prints no result. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number the output check compared, with its limit. The same numbers are the
last lines of standard error.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, so only a cell's first run there compiles.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT, bench_dir: str = BENCH_DIR,
         check_device: bool = True, compile_cache: bool = True) -> dict:
    """Run the cell; returns the result that was printed. ``root`` holds
    ``BENCHMARK.json``. Tests on the CPU pass ``check_device=False``, which
    skips the look for a TPU, and ``compile_cache=False``."""
    args = parse(argv)
    for path in (bench_dir, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness
    if compile_cache:
        harness.use_compile_cache()
    cell = harness.load_cell(args.workload, root, bench_dir)
    if check_device:
        devices = harness.require_chips(cell.chips, bench_dir)
    else:
        import jax
        devices = jax.devices()[:cell.chips]
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    driver = cell.module("drivers", cell.traffic["driver"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    ctx = harness.RunContext(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_process=T_PROCESS,
        compiles=harness.CompileCounter(), trace_dir=trace_dir)
    try:
        record = driver.run(ctx)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    specs = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for spec in specs:
        value = cell.module("metrics", spec["name"]).read(record)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {
        "correct": all(c["value"] <= c["limit"]
                       for c in record["checks"].values()),
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metrics,
        "device": device,
    }
    trace = record.get("trace")
    if trace is not None:
        import traces
        lo, hi = trace.window()
        device["busy_s"] = traces.busy_s(trace, lo, hi)
        device["window_s"] = hi - lo
        result["breakdown"] = {"device_ops": traces.top_ops(trace, lo, hi),
                               "idle_gaps": traces.idle_gaps(trace, lo, hi)}
    names = record["window_compiles"]
    print(f"window: compiles={len(names)} {sorted(set(names))} "
          f"setup_s={record['setup_s']!r}", file=sys.stderr, flush=True)
    for line in record.get("notes", []):
        print(line, file=sys.stderr, flush=True)
    harness.emit(result, record["checks"])
    return result


if __name__ == "__main__":
    try:
        main()
    except Exception as exc:  # noqa: BLE001 - report and exit non-zero
        import traceback
        traceback.print_exc()
        sys.exit(f"chip benchmark: {type(exc).__name__}: {exc}")
