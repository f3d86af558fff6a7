"""Share of the traced window in which no operation ran on the device (%):
1 - (union of the op intervals) / window, averaged over the chips."""

from bench import trace


def read(record):
    tr = record["trace"]
    if tr is None or not any(tr["devices"].values()):
        return None
    return 100.0 * (1.0 - trace.busy_ns(tr) / trace.window_ns(tr))
