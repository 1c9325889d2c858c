"""Device time under the solver's ``greedy_rounding`` scope per replan of
the window, in ms (trace and name stacks): the greedy subchannel scans."""
from perfbench import spans


def read(run):
    if run.trace is None or not run.scopes or not run.replans:
        return None
    seconds = spans.scope_seconds(run.trace, run.scopes, "greedy_rounding")
    return seconds / run.replans * 1e3 if seconds > 0 else None
