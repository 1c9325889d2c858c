"""Share of their own least time that the NOMA kernel calls of the window
reach, in %: the sum over the cell-intra, per-AP and AP-contract kernel
events of each call's least time (perfbench.work.kernel_call) over the sum
of their device time (trace)."""
from perfbench import trace, work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    least = busy = 0.0
    for kind, pattern in work.noma_kernels(run.sizes).items():
        n, seconds = trace.op_seconds(run.trace, pattern)
        least += n * work.least_s(*work.kernel_call(kind, run.sizes), run.peak)
        busy += seconds
    return 100.0 * least / busy if busy > 0 else None
