"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals) / window."""
from perfbench import trace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 1.0 - trace.busy_s(run.trace) / run.trace.window_s
