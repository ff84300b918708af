"""Share of the traced training window in which no op ran on the device:
1 - (union of device-op intervals) / window, in percent."""

import traces


def read(record):
    trace = record.get("trace")
    if record["kind"] != "train" or trace is None:
        return None
    lo, hi = trace.window()
    return 100.0 * (1.0 - traces.busy_s(trace, lo, hi) / (hi - lo))
