"""The comparison that decides ``correct``: the last replan of the window
against the plain reference, number by number, each beside its limit.

What is compared, all from the replan the window ran last (its operands
and its result, copied off the device once the window has closed):

``split``          the chosen split s* against the argmin of the
                   reference's per-split utilities (exact).
``split_utility``  every split's utility (the plan's utility is that of
                   s*) against the reference's, each
                   split solved by the reference from the same start the
                   program had (the previous epoch's optimum or the chain
                   carry, which is the program's own optimum of the split
                   before), so a gap at one split does not carry on.
``sinr_gamma``     at every transmitting split s < F, the utility the
                   program reports at its own optimum against the
                   reference's Gamma_s at that same point: the SINR layer
                   (einsum or kernels) and the utility, free of any solver
                   drift.
``gradient``       at every transmitting split, the program's Adam first
                   moment (an average of the gradients its SINR layer gave)
                   against the reference's, as the worst relative L2 gap
                   over splits and variables; a (variable, split) whose
                   reference moment is under GRAD_FLOOR of the median one
                   is nought to rounding and left out.
``rounding``       the share of users whose uplink or downlink subchannel
                   differs from the reference's rounding of the program's
                   own relaxed point at s*, the one the "best" rule picks:
                   greedy where its utility is strictly lower, else argmax.
                   At s* = F no rate enters Gamma_F, the two tie, and only
                   the argmax is compared: the greedy scans then go
                   unchecked.

The control is the reference put in the program's place on inputs rounded
to bfloat16: the channel gains, the previous epoch's state and the chain
carries. It must fail at least one number.
"""
from __future__ import annotations

import numpy as np

from perfbench import reference as ref

# Gradients under this share of the median (variable, split) gradient are
# nought to rounding and left out of ``gradient``: the compute units' gradient
# at the last splits, where the edge runs a few FLOPs that float32 computes
# as a difference of two sums of ~6e8 FLOPs.
GRAD_FLOOR = 1e-3
NAMES = ("split", "split_utility", "sinr_gamma", "gradient", "rounding")
LEAVES = ("beta_up", "beta_dn", "p_up", "p_dn", "r")


def _rel(got, want) -> float:
    got, want = np.float64(got), np.float64(want)
    return float(abs(got - want) / max(abs(want), 1e-30))


def _l2_rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    den = np.sqrt(np.sum(want * want))
    num = np.sqrt(np.sum((got - want) ** 2))
    if den == 0.0:
        return 0.0 if num == 0.0 else float("inf")
    return float(num / den)


def _to_device(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.asarray, tree)


def _bf16(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda x: (jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)
                   if np.issubdtype(np.asarray(x).dtype, np.floating)
                   else jnp.asarray(x)), tree)


def _host(tree):
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


class Setting:
    """The reference's view of one configuration."""

    def __init__(self, cfg: dict):
        planner = cfg["planner"]
        self.c = ref.consts(cfg)
        self.gd = dict(planner["gd"])
        self.rho_min = planner["warm_rho_min"]
        self.decay = planner["warm_moment_decay"]
        self.prof = tuple(np.asarray(a) for a in ref.profile(cfg["model"]))
        self.limits = cfg["limits"]


def best_rounding(rounded: dict) -> str:
    """The configuration's "best" rule: the greedy rounding where its
    discrete utility is strictly lower, else the argmax (ties included)."""
    return ("greedy" if float(rounded["u_greedy"]) < float(rounded["u_argmax"])
            else "argmax")


def numbers(st: Setting, net, out: dict, solved, at_points, rounded) -> dict:
    """The compared numbers of one planner output ``out`` (host arrays:
    s, gammas, norms, m1, sub_up, sub_dn) given the reference's
    per-split solves from the same starts, its Gamma at the output's own
    points and its roundings of the output's point at s*."""
    gam = np.asarray(solved.gamma, np.float64)
    f = len(gam) - 1
    s = int(out["s"])
    nums = {
        "split": float(abs(s - int(np.argmin(gam)))),
        "split_utility": max(_rel(a, b) for a, b in zip(out["gammas"], gam)),
        "sinr_gamma": max(_rel(out["gammas"][k], at_points[k])
                          for k in range(f)),
    }
    m1 = _host(solved.m1)
    size = {(k, i): float(np.sqrt(np.sum(np.asarray(m1[k][i], np.float64) ** 2)))
            for k in LEAVES for i in range(f)}
    floor = GRAD_FLOOR * float(np.median(list(size.values())))
    nums["gradient"] = max(
        (_l2_rel(out["m1"][k][i], m1[k][i]) for (k, i), n in size.items()
         if n >= floor), default=0.0)
    subs = np.concatenate([out["sub_up"], out["sub_dn"]])
    differ = {
        kind: float(np.mean(subs != np.concatenate(
            [np.asarray(x) for x in rounded[kind]])))
        for kind in ("argmax", "greedy")}
    nums["rounding"] = differ[best_rounding(rounded)]
    for k, v in nums.items():
        if not np.isfinite(v):
            nums[k] = float("inf")
    return nums


def _net(env: dict):
    return ref.Net(g_up=env["g_up"], g_dn=env["g_dn"], ap=env["ap"])


def _solve(st: Setting, net, prev: dict, carries: dict):
    return ref.replan_by_split(net, st.prof, st.c, st.gd, st.rho_min,
                               st.decay, prev, carries)


def check(cfg: dict, env: dict, prev: dict, out: dict,
          control: bool = False) -> dict[str, dict]:
    """Compare a replan's result ``out`` with the reference run on its
    operands (``env``: g_up, g_dn, ap; ``prev``: norms, m1, m2, steps,
    gains). Returns {name: {"value", "limit"}} for the program and, with
    ``control``, also {"control." + name: ...} for the bfloat16 control."""
    import jax.numpy as jnp
    st = Setting(cfg)
    net = _net(_to_device(env))
    prev_d = _to_device(prev)
    carries = _to_device(out["norms"])
    solved = _solve(st, net, prev_d, carries)
    nums = numbers(st, net, out, solved,
                   _host(ref.gammas_at(net, st.prof, st.c, carries)),
                   _host(ref.rounding_of(net, st.prof, st.c, int(out["s"]),
                                         {k: v[int(out["s"])] for k, v in
                                          carries.items()})))
    result = {k: {"value": nums[k], "limit": st.limits[k]} for k in NAMES}
    if control:
        ctrl = control_output(st, env, prev, out)
        cn = {k: jnp.asarray(v) for k, v in ctrl["norms"].items()}
        s = int(ctrl["s"])
        cnums = numbers(
            st, net, ctrl, solved,
            _host(ref.gammas_at(net, st.prof, st.c, cn)),
            _host(ref.rounding_of(net, st.prof, st.c, s,
                                  {k: v[s] for k, v in cn.items()})))
        result.update({f"control.{k}": {"value": cnums[k],
                                        "limit": st.limits[k]}
                       for k in NAMES})
    return result


def control_output(st: Setting, env: dict, prev: dict, out: dict) -> dict:
    """The reference in the program's place on bfloat16-rounded inputs:
    the replan from the same starts, its s*, and the best of its two
    roundings at s*, all as host arrays shaped like a program output."""
    net = _net(_bf16(env))
    solved = _host(_solve(st, net, _bf16(prev), _bf16(out["norms"])))
    gam = np.asarray(solved.gamma)
    s = int(np.argmin(gam))
    rounded = _host(ref.rounding_of(
        net, st.prof, st.c, s,
        {k: _to_device(v[s]) for k, v in solved.norm.items()}))
    pick = best_rounding(rounded)
    return {"s": s, "gammas": gam, "norms": solved.norm,
            "m1": solved.m1, "sub_up": rounded[pick][0],
            "sub_dn": rounded[pick][1]}


def passed(result: dict) -> bool:
    """Every program number (not the control's) within its limit."""
    return all(v["value"] <= v["limit"] for k, v in result.items()
               if not k.startswith("control."))
