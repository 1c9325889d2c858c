"""Device time of the replan program (XLA module ``jit_replan``) per replan
of the window, in ms (trace)."""
from perfbench import trace


def read(run):
    if run.trace is None or not run.replans:
        return None
    n, seconds = trace.module_seconds(run.trace, "jit_replan")
    return seconds / run.replans * 1e3 if n else None
