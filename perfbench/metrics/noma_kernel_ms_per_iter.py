"""Device time of the cell-intra, per-AP and AP-contract kernel events per
Li-GD iteration of the window, in ms (trace)."""
from perfbench import trace, work


def read(run):
    if run.trace is None or not run.gd_iters:
        return None
    busy = sum(trace.op_seconds(run.trace, p)[1]
               for p in work.noma_kernels(run.sizes).values())
    return busy / run.gd_iters * 1e3 if busy > 0 else None
