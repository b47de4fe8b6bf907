"""Share of the traced window in which no operation ran on the device:
1 - (union of the device operations' intervals) / window."""
from perfbench.lib import trace


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace.window_s)
