"""Readings that a mesh training cell's output-check limits are set from,
taken on the chip.

    python3 benchmarks/chip/calibrate_mesh.py --workload <cell> \
        --seeds 1,2,3 [--stop-after-s 600]

In one process, with the train step compiled once: for each seed the
set-up and checked steps of a run (``train_mesh.Prepared``) and the sound
reference following the program's routing, read as a run reads them. Then,
each in the program's place and read against a reference that follows its
routing:

* ``control``: the reference computed in float8 (operands e4m3, cotangents
  e5m2), routing itself; the sound reference follows its routing;
* ``half_batch``: only the first half of the sequences count in the loss
  (on a (2 data, 2 model) mesh, a step that loses the second data shard's
  gradient);
* ``experts_half``: the second half of the experts contribute nothing (on
  that mesh experts 20-39: each chip of the model axis keeps only its own
  experts' part, the model-axis sum left out);
* ``unchanged``: the train state left as it was: no first gradient and no
  change (its loss is read at the first step only).

The three faults are the sound reference with the fault, following the
program's routing, so that their gaps are the fault's and not the
routing's. Each reading gives the compared numbers, each checked step's
loss gap and each leaf's gap. One JSON line per seed goes to standard
output; seeds after ``--stop-after-s`` seconds are left out. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

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


def first_half(n: int) -> np.ndarray:
    return (np.arange(n) < n // 2).astype(np.float32)


def readings(cell, driver, seed: int, sound, control, step=None):
    """(the step, the seed's readings)."""
    import compare
    import harness
    c, t = cell.config, cell.traffic
    ctx = harness.RunContext(cell=cell, seed=seed, seconds=0.0, trace=False,
                             t_process=time.monotonic())
    prep = driver.Prepared(ctx, step=step)
    prog, batches, key, step = prep.prog, prep.batches(), prep.key, prep.step
    prep.release()
    del prep
    want = sound(key, batches, prog["routes"])
    out = {"seed": seed, "program": reading(driver, compare, prog, want),
           "losses": prog["losses"], "ref_losses": want["losses"]}
    ctrl = control(key, batches)
    out["control"] = reading(driver, compare, ctrl,
                             sound(key, batches, ctrl["routes"]))
    out["half_batch"] = reading(driver, compare, sound(
        key, batches, prog["routes"], row_weight=first_half(t["batch"])),
        want)
    out["experts_half"] = reading(driver, compare, sound(
        key, batches, prog["routes"],
        expert_weight=first_half(c["num_local_experts"])), want)
    zeros = {k: 0.0 for k in prog["grad"]}
    out["unchanged"] = reading(driver, compare, {
        "losses": prog["losses"][:1], "grad": zeros,
        "moved": {k: 0.0 for k in prog["moved"]}}, want)
    return step, out


def calibrate(cell, driver, seeds, stop_after_s: float) -> None:
    t0 = time.monotonic()
    groups = cell.traffic["mesh"]["data"]
    sound = driver.Follower(cell.config, groups)
    control = driver.Follower(cell.config, groups, quant="fp8")
    step = None
    for seed in seeds:
        if time.monotonic() - t0 > stop_after_s:
            print(f"calibrate: stopped after {time.monotonic() - t0:.0f} s, "
                  f"before seed {seed}", file=sys.stderr, flush=True)
            break
        t1 = time.monotonic()
        step, out = readings(cell, driver, seed, sound, control, step)
        out["seconds"] = time.monotonic() - t1
        print(json.dumps(out), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--stop-after-s", type=float, default=float("inf"))
    args = ap.parse_args(argv)
    for path in (BENCH_DIR, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    driver = cell.module("drivers", cell.traffic["driver"])
    calibrate(cell, driver, args.seeds, args.stop_after_s)


if __name__ == "__main__":
    main()
