"""Device time of the served model's edge program (XLA module ``jit_edge``)
per epoch of the window, in ms (trace)."""
from perfbench import trace


def read(run):
    if run.trace is None or not run.epochs:
        return None
    n, seconds = trace.module_seconds(run.trace, "jit_edge")
    return seconds / run.epochs * 1e3 if n else None
