"""Device time under the solver's ``warm_gate`` scope per replan of the
window, in ms (trace and name stacks): the choice between the previous
epoch's optimum and the chain carry at each split."""
from perfbench import spans


def read(run):
    if run.trace is None or not run.scopes or not run.replans:
        return None
    seconds = spans.scope_seconds(run.trace, run.scopes, "warm_gate")
    return seconds / run.replans * 1e3 if seconds > 0 else None
