"""Li-GD iterations per replan in the window: the difference of the
server's device iteration counter across the window (read outside it) over
the replans dispatched in it."""


def read(run):
    return run.gd_iters / run.replans if run.replans else None
