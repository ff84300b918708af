"""Model FLOP utilization of training: model operations per token
(``counts.train_flops_per_token``) x tokens of the train-step runs that
started in the traced window, over (window x chips x bf16 peak), percent."""

import traces


def read(record):
    trace = record.get("trace")
    if record["kind"] != "train" or trace is None:
        return None
    lo, hi = trace.window()
    runs, _ = traces.program_runs(trace, record["module"], lo, hi)
    if runs == 0:
        return None
    tokens = runs * record["tokens"] / max(record["steps"], 1)
    return 100.0 * tokens * record["flops_per_token"] / (
        (hi - lo) * record["chips"] * record["peak_flops"])
