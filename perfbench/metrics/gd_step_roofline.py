"""Share of the window the planner's gradient steps would take at the
chip's peaks, in %: GD iterations in the window times the least time of one
gradient step (a forward and a backward evaluation of Gamma_s over both
links, perfbench.work.gd_step), over the window's wall time. It bounds the
whole solver, whatever implements a step."""
from perfbench import work


def read(run):
    if run.peak is None or not run.gd_iters:
        return None
    least = work.least_s(*work.gd_step(run.sizes), run.peak)
    return 100.0 * run.gd_iters * least / run.window_s
