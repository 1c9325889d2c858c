"""Device time under the edge program's ``edge_prefill`` scope per 1,000
prompt tokens prefilled in the window (counter ``edge_prefill_tokens``), in
ms (trace and name stacks)."""
from perfbench import spans


def read(run):
    tokens = run.counters.get("edge_prefill_tokens", 0)
    if run.trace is None or not run.scopes or not tokens:
        return None
    seconds = spans.scope_seconds(run.trace, run.scopes, "edge_prefill")
    return seconds / tokens * 1e6 if seconds > 0 else None
