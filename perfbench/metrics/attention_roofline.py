"""Share of their least time that the served model's attention cores
reach, in %: the least time of the window's attention work (the
configuration module's ``attention_work``: 4 FLOPs per query-key pair and
head dimension, each cached key and value read once a call) at the chip's
peaks, over the device time under the ``attn_window`` and ``attn_full``
scopes (trace, name stacks and counters)."""
from perfbench import spans, work


def read(run):
    reader = getattr(run.cell.module, "attention_work", None)
    if (reader is None or run.trace is None or not run.scopes
            or run.peak is None or not run.counters.get("attn_query_keys")):
        return None
    busy = sum(spans.scope_seconds(run.trace, run.scopes, s)
               for s in ("attn_window", "attn_full"))
    least = work.least_s(*reader(run.cell.config, run.counters), run.peak)
    return 100.0 * least / busy if busy > 0 else None
