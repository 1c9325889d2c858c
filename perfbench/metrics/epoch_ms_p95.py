"""95th percentile, nearest rank, of every epoch's wall time in the window,
in ms (host clock): the stall a replan puts on the loop."""
from perfbench.cell import percentile


def read(run):
    return percentile(run.epoch_s, 95.0) * 1e3 if run.epoch_s else None
