"""Shared setup for the paper-figure benchmarks (Sec. VI experimental set,
scaled to CPU: the paper's 1250 users / 250 subchannels Monte-Carlo is run
at reduced but proportional scale; densities and ratios follow Sec. VI.A)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import GdConfig, make_env, make_weights, planner

CFG = GdConfig(step_size=5e-3, max_iters=250)
W_T = 0.5          # equal tradeoff weights unless a figure sweeps them
N_SEEDS = 3        # Monte-Carlo channel draws per point


def mean_outcomes(n_users, n_aps, n_sub, prof, w_T=W_T, seeds=N_SEEDS,
                  methods=("ecc_noma", "ecc_oma", "device_only", "edge_only",
                           "neurosurgeon", "dnn_surgery")):
    """Average T/E per method over Monte-Carlo channel realizations."""
    acc: dict = {m: {"T": 0.0, "E": 0.0} for m in methods}
    for s in range(seeds):
        env = make_env(jax.random.PRNGKey(1000 + s), n_users, n_aps, n_sub)
        w = make_weights(env.n_users, w_T)
        res = planner.compare_all(env, prof, w, CFG)
        for m in methods:
            acc[m]["T"] += float(jnp.mean(res[m].T)) / seeds
            acc[m]["E"] += float(jnp.mean(res[m].E)) / seeds
    return acc


def emit(name: str, rows: list[tuple]):
    """Print ``name,label,value,derived`` CSV lines, one per (label,
    value, derived-annotation) row."""
    for label, val, derived in rows:
        print(f"{name},{label},{val:.6g},{derived}")
