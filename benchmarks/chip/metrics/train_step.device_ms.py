"""Device time of one train step: the device time of the train-step
program's runs in the traced window, over their number, in ms."""

import traces


def read(record):
    trace = record.get("trace")
    if record["kind"] != "train" or trace is None:
        return None
    lo, hi = trace.window()
    runs, secs = traces.program_runs(trace, record["module"], lo, hi)
    return 1e3 * secs / runs if runs else None
