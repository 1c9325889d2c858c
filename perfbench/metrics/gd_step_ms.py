"""Device time under the solver's ``gd_iter`` scope per Li-GD iteration of
the window, in ms (trace and name stacks): one gradient step."""
from perfbench import spans


def read(run):
    if run.trace is None or not run.scopes or not run.gd_iters:
        return None
    seconds = spans.scope_seconds(run.trace, run.scopes, "gd_iter")
    return seconds / run.gd_iters * 1e3 if seconds > 0 else None
