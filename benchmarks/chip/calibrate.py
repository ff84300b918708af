"""Readings that the output check's limits are set from, taken on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3]

For each seed, in one process: the set-up and checked steps of a run
(``train.Prepared``) and the reference (``train.Follower``); for the
control seeds also the control (the reference computed in float8, put in
the program's place) and the fault "half of the batch left out" (the
reference on half the rows, in the program's place), each read against
the reference as a run reads the program. Every reading gives each step's
loss gap and each leaf's gap. One JSON line per seed goes to standard
output. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def ints(text: str):
    return [int(x) for x in text.split(",") if x]


def reading(driver, compare, got: dict, want: dict) -> dict:
    """The compared numbers, each checked step's loss gap, and every leaf's
    gap of the gradient and of the change."""
    keep = compare.moving_leaves(want["grad"])
    out = driver.numbers(got, want)
    out["step_loss_gaps"] = [compare.rel_gap(a, b) for a, b in
                             zip(got["losses"], want["losses"])]
    out["grad_leaves"] = compare.leaf_gaps(got["grad"], want["grad"])
    out["update_leaves"] = compare.leaf_gaps(got["moved"], want["moved"],
                                             keep)
    return out


def calibrate(cell, driver, seeds, control_seeds) -> None:
    import compare
    import harness
    c, t = cell.config, cell.traffic
    ref = driver.Follower(c)
    others = {"control": driver.Follower(c, quant="fp8"),
              "half_batch": driver.Follower(c, rows=t["batch"] // 2)}
    for seed in seeds:
        ctx = harness.RunContext(cell=cell, seed=seed, seconds=0.0,
                                 trace=False, t_process=time.monotonic())
        prep = driver.Prepared(ctx)
        prog, batches, key = prep.prog, prep.batches(), prep.key
        prep.release()
        del prep
        want = ref(key, batches)
        out = {"seed": seed,
               "program": reading(driver, compare, prog, want),
               "losses": prog["losses"], "ref_losses": want["losses"]}
        if seed in control_seeds:
            for name, follower in others.items():
                out[name] = reading(driver, compare, follower(key, batches),
                                    want)
        print(json.dumps(out), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--control-seeds", type=ints, default=[])
    args = ap.parse_args(argv)
    for path in (BENCH_DIR, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    driver = cell.module("drivers", cell.traffic["driver"])
    calibrate(cell, driver, args.seeds, set(args.control_seeds))


if __name__ == "__main__":
    main()
