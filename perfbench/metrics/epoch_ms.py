"""Wall time of the window over the epochs completed in it, in ms (host
clock): how fresh the plan is."""


def read(run):
    return run.window_s / run.epochs * 1e3 if run.epochs else None
