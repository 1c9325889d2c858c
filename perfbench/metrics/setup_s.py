"""Seconds from process start to the first timed epoch (host clock)."""


def read(run):
    return run.setup_s
