"""Device time under the edge program's ``edge_decode`` scope per decode
step of the window (counter ``edge_decode_steps``), in ms (trace and name
stacks): one step of every slot through every layer and the head."""
from perfbench import spans


def read(run):
    steps = run.counters.get("edge_decode_steps", 0)
    if run.trace is None or not run.scopes or not steps:
        return None
    seconds = spans.scope_seconds(run.trace, run.scopes, "edge_decode")
    return seconds / steps * 1e3 if seconds > 0 else None
