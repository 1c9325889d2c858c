"""Host reads of the loop per epoch of the window: the program's
``sync.<name>`` spans begun inside the window over its epochs (trace)."""
from perfbench import spans


def read(run):
    if run.trace is None or not run.epochs:
        return None
    n = spans.sync_count(run.trace)
    return n / run.epochs if n else None
