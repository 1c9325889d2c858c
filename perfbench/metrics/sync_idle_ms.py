"""Time per epoch, in ms, in which chip 0 was idle while the host was
inside a ``sync.<name>`` span: the device clock shifted onto the host's by
the middle of the offset interval the program's spans allow (trace)."""
from perfbench import spans


def read(run):
    if run.trace is None or not run.epochs:
        return None
    offset = spans.clock_offset(run.trace)
    if offset is None:
        return None
    return spans.sync_idle_s(run.trace, offset.mid) / run.epochs * 1e3
