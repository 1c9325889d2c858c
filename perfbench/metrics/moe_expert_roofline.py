"""Share of their least time that the served model's expert layers reach,
in %: the least time of the window's expert work (the configuration
module's ``expert_work``: held and shared expert weights read once a call,
the FLOPs of the (token, held expert) pairs and of the shared expert) at
the chip's peaks, over the device time under the ``moe_experts`` scope
(trace, name stacks and counters)."""
from perfbench import spans, work


def read(run):
    reader = getattr(run.cell.module, "expert_work", None)
    if (reader is None or run.trace is None or not run.scopes
            or run.peak is None
            or not run.counters.get("moe_local_assignments")):
        return None
    busy = spans.scope_seconds(run.trace, run.scopes, "moe_experts")
    least = work.least_s(*reader(run.cell.config, run.counters), run.peak)
    return 100.0 * least / busy if busy > 0 else None
