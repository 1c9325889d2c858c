"""Device time of the planner's programs per replan, in ms (trace). The
engine's programs and the server's plan guard all lower as the XLA module
``jit_wrapped``; in a steady window only the replan program and the guard
run, so this is the replan plus its (small) guard."""
from perfbench import trace


def read(run):
    if run.trace is None or not run.replans:
        return None
    n, seconds = trace.module_seconds(run.trace, "jit_wrapped")
    return seconds / run.replans * 1e3 if n else None
