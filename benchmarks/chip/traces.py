"""From a profiler trace to the numbers the per-layer metrics read.

``capture`` wraps ``jax.profiler`` with the Python tracer off (it costs the
host more than the work it watches); ``load`` reads the ``.xplane.pb`` it
wrote into plain interval lists, and the functions below reduce those:

* device ops: events of the ``XLA Ops`` line of each ``/device:TPU:n``
  plane; busy time is the union of their intervals;
* programs: events of the ``XLA Modules`` line, named ``<module>(<id>)``;
* host spans: the benchmark's own ``TraceAnnotation`` spans, on any line of
  the ``/host:CPU`` plane.

All times are in seconds on the trace's clock, which the profiler puts the
device events on too.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]     # (name, start s, end s)

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]        # device plane -> op intervals
    modules: Dict[str, List[Interval]]    # device plane -> program runs
    spans: List[Interval]                 # the benchmark's host spans

    def window(self, name: str = "window") -> Tuple[float, float]:
        """The extent of the host span ``name`` (first to last)."""
        hits = [s for s in self.spans if s[0] == name]
        if not hits:
            raise ValueError(f"no host span {name!r} in the trace")
        return min(s[1] for s in hits), max(s[2] for s in hits)


def capture(log_dir: str):
    """Start a trace into ``log_dir``; returns the function that stops it
    and gives the path of the ``.xplane.pb`` written."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)

    def stop() -> str:
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
        return files[-1]

    return stop


def load(path: str, span_names: Iterable[str]) -> Trace:
    from jax.profiler import ProfileData
    wanted = set(span_names)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                dest.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name in wanted)
    return Trace(ops=ops, modules=modules, spans=spans)


# ---------------------------------------------------------------------- #
# Reductions
# ---------------------------------------------------------------------- #

def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """The union of intervals, clipped to [lo, hi], as disjoint pieces."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which an op ran, averaged over the devices."""
    if not trace.ops:
        return 0.0
    per_device = [sum(e - s for s, e in merge(
        ((s, e) for _, s, e in ops), lo, hi)) for ops in trace.ops.values()]
    return sum(per_device) / len(per_device)


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def program_runs(trace: Trace, module: str, lo: float,
                 hi: float) -> Tuple[int, float]:
    """(runs, device seconds) of the program ``module`` that started in
    [lo, hi], summed over the devices."""
    runs, secs = 0, 0.0
    for mods in trace.modules.values():
        for name, s, e in mods:
            if lo <= s < hi and module_name(name) == module:
                runs += 1
                secs += e - s
    return runs, secs


def op_label(event_name: str) -> str:
    """``%fusion.12 = f32[4,9]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 f32[4,9]``: the op and the shape it produces."""
    name, _, rest = event_name.partition(" = ")
    shape = re.sub(r"\{[^{}]*\}", "", rest.split(" ", 1)[0])[:48]
    return f"{name.lstrip('%')} {shape}".strip()


def self_times(ops: List[Interval]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self seconds) of each op: its duration less the
    ops nested in it (a ``while`` holds its body's ops). An op that only
    overlaps the one before is not nested in it."""
    out = []
    stack: List[list] = []      # [name, start, end, child seconds]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and (stack[-1][2] <= s or e > stack[-1][2]):
            done = stack.pop()
            out.append((done[0], done[1], done[2],
                        done[2] - done[1] - done[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    out.extend((n, s, e, e - s - c) for n, s, e, c in stack)
    return out


def top_ops(trace: Trace, lo: float, hi: float,
            n: int = 10) -> List[List]:
    """The device ops with the most self time among those that started in
    [lo, hi], averaged over the devices."""
    total: Dict[str, float] = {}
    for ops in trace.ops.values():
        for name, s, _, own in self_times(ops):
            if lo <= s < hi:
                key = op_label(name)
                total[key] = total.get(key, 0.0) + own
    ndev = max(len(trace.ops), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / ndev] for k, v in ranked]


def label_at(spans: Sequence[Interval], t: float,
             skip: Sequence[str] = ("window",)) -> str:
    """The innermost benchmark span that holds time ``t``."""
    best: Optional[Interval] = None
    for sp in spans:
        if sp[0] in skip or not sp[1] <= t <= sp[2]:
            continue
        if best is None or sp[2] - sp[1] < best[2] - best[1]:
            best = sp
    return best[0] if best else "none"


def idle_gaps(trace: Trace, lo: float, hi: float,
              n: int = 10) -> List[List]:
    """The longest gaps in [lo, hi] with no op on the first device, each
    named by the host span it fell in."""
    if not trace.ops:
        return []
    first = sorted(trace.ops)[0]
    busy = merge(((s, e) for _, s, e in trace.ops[first]), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label_at(trace.spans, (s + e) / 2), e - s] for s, e in gaps[:n]]
