"""Device time of the expert sublayers in one train step: the self time of
the ops of the train-step program's runs in the traced window whose
instruction lies in the ``moe`` scope (the record's ``op_scopes``), per run
and per device, in ms. Ops the record gives no scope count under none."""

import scopes
import traces


def read(record):
    trace = record.get("trace")
    if record["kind"] != "train" or trace is None:
        return None
    lo, hi = trace.window()
    runs, _ = traces.program_runs(trace, record["module"], lo, hi)
    if not runs:
        return None
    by_scope = scopes.scope_self_s(trace, record["module"],
                                   record.get("op_scopes", {}), lo, hi)
    per_device = runs / max(len(trace.ops), 1)
    return 1e3 * by_scope.get("moe", 0.0) / per_device
