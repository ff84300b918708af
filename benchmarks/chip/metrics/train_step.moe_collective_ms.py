"""Device time of the expert sublayers' collectives in one train step: the
time of the all-reduce, all-gather, reduce-scatter and all-to-all ops (the
record's ``collectives``) in the ``moe`` scope (its ``op_scopes``) inside
the train-step program's runs in the traced window, per run and per device,
in ms."""

import bisect

import scopes
import traces


def read(record):
    trace = record.get("trace")
    if record["kind"] != "train" or trace is None:
        return None
    lo, hi = trace.window()
    module = record["module"]
    runs, _ = traces.program_runs(trace, module, lo, hi)
    if not runs:
        return None
    names = set(record.get("collectives", ()))
    scope = record.get("op_scopes", {})
    total = 0.0
    for dev, ops in trace.ops.items():
        spans = sorted((s, e) for n, s, e in trace.modules.get(dev, [])
                       if traces.module_name(n) == module and lo <= s < hi)
        starts = [s for s, _ in spans]
        for name, s, _, own in traces.self_times(ops):
            op = scopes.op_name_of(name)
            if op not in names or scope.get(op) != "moe":
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s <= spans[i][1]:
                total += own
    return 1e3 * total / runs
