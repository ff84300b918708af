"""Device time per sublayer, and the device's idle time put down to what
the host was doing, from a trace of the training window.

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \
        [--seconds 6]

The program names its step's sublayers with ``jax.named_scope``
(``repro.train.train_step.SCOPES``), which the compiled HLO keeps in each
instruction's ``op_name``, and each step's host phases with profiler spans
(``repro.train.trainer.SPANS``). The reductions here read both:

* ``op_scopes``: each instruction's scope, from the compiled HLO's text;
* ``scope_self_s``: per scope, the self time of the ops in the runs of the
  step program;
* ``idle_in_span``: the time with no op on the device while a host span is
  open and no run of the step program is in progress (a gap inside the
  program is not the host's doing; ``idle_in_program`` gives those).

As a script it runs the cell's set-up and a traced window as the training
driver does, and prints one JSON object: the window, the step program's
device time per run split by scope, the idle time split by host phase, the
host phases' median seconds, and the longest idle gaps, each named by the
innermost span it fell in. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import traces

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))

Pieces = List[Tuple[float, float]]      # sorted, disjoint (start, end)

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = .*?\s([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%?([^\s,]+)")
_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")
MATMULS = ("dot", "convolution")


def scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The last component of ``op_name`` that is a scope name. A transform
    wraps the scope it was applied in (``transpose(jvp(head))``), so each
    component is unwrapped first."""
    for part in reversed(op_name.split("/")):
        while (m := _WRAPPED.fullmatch(part)):
            part = m.group(1)
        if part in scopes:
            return part
    return None


def _computations(hlo_text: str) -> Dict[str, list]:
    """Computation -> its instructions as (name, opcode, op_name, the
    computations it applies: a fusion's, a reduction's)."""
    comps: Dict[str, list] = {}
    current: Optional[list] = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            current = comps.setdefault(m.group(1), []) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            op_name = _OP_NAME.search(line)
            current.append((m.group(1), m.group(2),
                            op_name.group(1) if op_name else "",
                            _CALLS.findall(line)))
    return comps


def op_scopes(hlo_text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """Instruction name -> its scope, for every instruction of the HLO
    module whose ``op_name`` holds one (``scope_of``)."""
    out = {}
    for insts in _computations(hlo_text).values():
        for name, _, op_name, _ in insts:
            scope = scope_of(op_name, scopes)
            if scope is not None:
                out[name] = scope
    return out


def top_level(hlo_text: str) -> Dict[str, bool]:
    """Each top-level instruction -> whether it is or holds a matmul.
    Top-level instructions are those of computations that no instruction
    applies (the entry computation and loop bodies): the ones a device
    trace shows."""
    comps = _computations(hlo_text)
    applied = {c for insts in comps.values() for *_, calls in insts
               for c in calls}

    def is_matmul(op: str, calls: List[str]) -> bool:
        return op in MATMULS or op == "fusion" and any(
            is_matmul(inner, inner_calls) for comp in calls
            for _, inner, _, inner_calls in comps.get(comp, []))

    return {name: is_matmul(op, calls)
            for comp, insts in comps.items() if comp not in applied
            for name, op, _, calls in insts}


def op_name_of(event_name: str) -> str:
    """``%fusion.12 = f32[4,9]{...} fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].lstrip("%")


# ---------------------------------------------------------------------- #
# Intervals
# ---------------------------------------------------------------------- #

def _intersect(a: Pieces, b: Pieces) -> Pieces:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _complement(a: Pieces, lo: float, hi: float) -> Pieces:
    out, t = [], lo
    for s, e in a:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _length(a: Pieces) -> float:
    return sum(e - s for s, e in a)


def _idle_and_runs(trace: traces.Trace, lo: float, hi: float,
                   module: str):
    """Per device: (pieces of [lo, hi] with no op, pieces in which a run
    of ``module`` is in progress)."""
    for dev, ops in trace.ops.items():
        idle = _complement(traces.merge(((s, e) for _, s, e in ops),
                                        lo, hi), lo, hi)
        runs = traces.merge(
            ((s, e) for n, s, e in trace.modules.get(dev, [])
             if traces.module_name(n) == module), lo, hi)
        yield idle, runs


def idle_in_span(trace: traces.Trace, lo: float, hi: float, span: str,
                 module: str) -> float:
    """Seconds in [lo, hi] with no op on the device, the host span ``span``
    open and no run of ``module`` in progress, averaged over the
    devices."""
    if not trace.ops:
        return 0.0
    open_ = traces.merge(((s, e) for n, s, e in trace.spans if n == span),
                         lo, hi)
    per_device = [_length(_intersect(_intersect(idle, open_),
                                     _complement(runs, lo, hi)))
                  for idle, runs in _idle_and_runs(trace, lo, hi, module)]
    return sum(per_device) / len(per_device)


def idle_in_program(trace: traces.Trace, lo: float, hi: float,
                    module: str) -> float:
    """Seconds in [lo, hi] with no op on the device while a run of
    ``module`` is in progress, averaged over the devices."""
    if not trace.ops:
        return 0.0
    per_device = [_length(_intersect(idle, runs))
                  for idle, runs in _idle_and_runs(trace, lo, hi, module)]
    return sum(per_device) / len(per_device)


def scope_self_s(trace: traces.Trace, module: str, scopes: Dict[str, str],
                 lo: float, hi: float) -> Dict[str, float]:
    """Per scope, the self time of the ops inside the runs of ``module``
    that started in [lo, hi], averaged over the devices; ops of no scope
    count under ``"none"``."""
    total: Dict[str, float] = {}
    for dev, ops in trace.ops.items():
        runs = sorted((s, e) for n, s, e in trace.modules.get(dev, [])
                      if traces.module_name(n) == module and lo <= s < hi)
        starts = [s for s, _ in runs]
        for name, s, _, own in traces.self_times(ops):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s > runs[i][1]:
                continue
            key = scopes.get(op_name_of(name), "none")
            total[key] = total.get(key, 0.0) + own
    ndev = max(len(trace.ops), 1)
    return {k: v / ndev for k, v in total.items()}


# ---------------------------------------------------------------------- #
# The measurement
# ---------------------------------------------------------------------- #

def attribution(trace: traces.Trace, hlo_text: str, module: str,
                scopes: Sequence[str], phases: Sequence[str]) -> dict:
    """The window's step-program time per run by scope, and its idle time
    by host phase outside the program and inside it, in seconds."""
    lo, hi = trace.window()
    runs, program_s = traces.program_runs(trace, module, lo, hi)
    runs_per_device = runs / max(len(trace.ops), 1) or 1
    by_scope = scope_self_s(trace, module, op_scopes(hlo_text, scopes),
                            lo, hi)
    busy = traces.busy_s(trace, lo, hi)
    idle = {p: idle_in_span(trace, lo, hi, p, module) for p in phases}
    idle["in_program"] = idle_in_program(trace, lo, hi, module)
    return {
        "window_s": hi - lo,
        "busy_s": busy,
        "idle_s": hi - lo - busy,
        "program_runs": runs,
        "program_s_per_run": program_s / max(runs, 1),
        "scope_s_per_run": {k: v / runs_per_device
                            for k, v in by_scope.items()},
        "idle_by_phase_s": idle,
    }


def measure(ctx, driver) -> Tuple[traces.Trace, str, str, dict]:
    """Set-up and a traced window of the training driver: the trace with
    the program's spans, the step's HLO text and module name, and the
    trainer's host seconds per phase over the window's steps."""
    from repro.train import trainer as program
    prep = driver.Prepared(ctx)
    trainer, key = prep.trainer, prep.key
    hlo_text = trainer.step_fn.as_text()
    chunk = int(ctx.cell.traffic["chunk_steps"])
    first = len(trainer.step_times)
    stop = traces.capture(tempfile.mkdtemp(dir=ctx.trace_dir))
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr), ctx.span("window"):
        while time.perf_counter() - t0 < ctx.seconds:
            with ctx.span("train_chunk"):
                trainer.cfg.total_steps = trainer.step + chunk
                trainer.run(key)
    trace = traces.load(stop(), tuple(driver.SPANS) + program.SPANS)
    phase_s = {p: t[first:] for p, t in trainer.phase_s.items()}
    module = prep.module
    prep.release()
    return trace, hlo_text, module, phase_s


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    for path in (BENCH_DIR, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness
    from repro.train import trainer as program
    from repro.train.train_step import SCOPES
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    dev = harness.require_chips(cell.chips)[0]
    driver = cell.module("drivers", cell.traffic["driver"])
    ctx = harness.RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                             trace=True, t_process=time.monotonic(),
                             trace_dir=tempfile.mkdtemp(prefix="scopes-"))
    try:
        trace, hlo_text, module, phase_s = measure(ctx, driver)
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    out = attribution(trace, hlo_text, module, SCOPES,
                      [f"train.{p}" for p in program.PHASES])
    out["steps"] = len(phase_s["input"])
    out["phase_median_s"] = {p: statistics.median(t)
                             for p, t in phase_s.items()}
    out["idle_gaps"] = traces.idle_gaps(trace, *trace.window())
    out["workload"], out["seed"] = args.workload, args.seed
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
