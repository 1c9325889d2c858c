"""Plain float32 reference of the Sec. VI planner, independent of the
system under test (it imports nothing from ``repro``).

It restates, in straightforward ``jax.numpy``:

* the NOMA rates, paper eqs. (5)-(10): uplink SIC at the AP (a user is
  interfered by same-cell users with a weaker own gain, plus every user of
  another cell on the subchannel), downlink SIC at the user (same-cell users
  with a stronger gain, plus the other APs' power);
* the delay and energy model and the weighted utility Gamma_s, eqs. (1)-(22);
* the model profile (per-layer FLOPs and activation bits) from the layer
  chain that the configuration's ``model`` section lists;
* one Li-GD replan step per split point: the rho gate, the better of the
  previous epoch's optimum and the chain carry, projected Adam at the
  configuration's iteration budget and stopping rules;
* the greedy and argmax subchannel roundings with the best-of pick.

Every constant comes from the configuration file. The intra-cell SIC sums
run over blocks of receivers, so no (U, U, M) tensor is ever whole.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LN2 = math.log(2.0)
ROW_BLOCK = 125          # receivers per block of the intra-cell SIC sums


# -- the model profile ------------------------------------------------------
def profile(model: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(fl (F,), w (F+1,), m_down (F+1,)) in float32 from the configuration's
    ``model`` section: FLOPs of each layer, bits uploaded when split at s
    (the raw input at s = 0, nothing at s = F) and result bits sent back
    (nothing at s = F).

    ``layers`` is the chain, one list per layer: ``["conv", out_c, k,
    stride]``, ``["conv+pool", out_c, k, stride, pool_k]``, ``["pool", k,
    stride]``, ``["fc", out_dim]``, ``["gap"]``, ``["norm"]``, ``["flatten"]``
    or ``["softmax"]``; ``input_hwc`` the input's shape, ``input_bits`` and
    ``act_bits`` the bits per input value and per activation, and
    ``result_bits`` the size of the result."""
    h, w, c = model["input_hwc"]
    act_bits = model["act_bits"]
    fl, acts = [], []
    for spec in model["layers"]:
        kind = spec[0]
        if kind in ("conv", "conv+pool"):
            out_c, k, stride = spec[1:4]
            h, w = max(1, -(-h // stride)), max(1, -(-w // stride))
            flops, c = 2.0 * k * k * c * out_c * h * w, out_c
            if kind == "conv+pool":
                pk = spec[4]
                flops += float(h * w * c * pk * pk)
                h, w = max(1, h // pk), max(1, w // pk)
        elif kind == "gap":
            flops = float(h * w * c)
            h, w = 1, 1
        elif kind == "pool":
            k, stride = spec[1:]
            flops = float(h * w * c * k * k)
            h, w = max(1, h // stride), max(1, w // stride)
        elif kind == "fc":
            flops = 2.0 * h * w * c * spec[1]
            h, w, c = 1, 1, spec[1]
        elif kind == "norm":
            flops = 2.0 * h * w * c
        elif kind == "flatten":
            flops = 0.0
        elif kind == "softmax":
            flops = 5.0 * c
        else:
            raise ValueError(kind)
        fl.append(flops)
        acts.append(h * w * c * act_bits)
    f = len(fl)
    bits = np.array([math.prod(model["input_hwc"]) * model["input_bits"]]
                    + acts, np.float64)
    bits[f] = 0.0
    m_down = np.full(f + 1, float(model["result_bits"]))
    m_down[f] = 0.0
    return (np.asarray(fl, np.float32), bits.astype(np.float32),
            m_down.astype(np.float32))


# -- the network and its rates ----------------------------------------------
class Net(NamedTuple):
    g_up: jax.Array      # (U, N, M) user -> AP gains
    g_dn: jax.Array      # (N, U, M) AP -> user gains
    ap: jax.Array        # (U,) int32 serving AP


class Consts(NamedTuple):
    """Scalars of the configuration's radio, compute and planner sections."""
    bw_up: float
    bw_dn: float
    noise_psd: float
    p_up: tuple[float, float]
    p_dn: tuple[float, float]
    r: tuple[float, float]
    beta_min: float
    c_device: float
    c_min_edge: float
    lam_exp: float
    xi_device: float
    xi_edge: float
    phi_device: float
    phi_edge: float
    w_t: float


def consts(cfg: dict) -> Consts:
    radio, comp = cfg["radio"], cfg["compute"]
    return Consts(
        bw_up=radio["bandwidth_up_hz"], bw_dn=radio["bandwidth_dn_hz"],
        noise_psd=radio["noise_psd_w_per_hz"],
        p_up=(radio["p_up_min_w"], radio["p_up_max_w"]),
        p_dn=(radio["p_dn_min_w"], radio["p_dn_max_w"]),
        r=(comp["r_min"], comp["r_max"]), beta_min=radio["beta_min"],
        c_device=comp["c_device"], c_min_edge=comp["c_min_edge"],
        lam_exp=comp["lam_exponent"], xi_device=comp["xi_device"],
        xi_edge=comp["xi_edge"], phi_device=comp["phi_device"],
        phi_edge=comp["phi_edge"], w_t=cfg["model"]["w_T"])


def _own(net: Net) -> tuple[jax.Array, jax.Array]:
    """Each user's gain to and from its own AP, (U, M) each."""
    users = jnp.arange(net.ap.shape[0])
    return net.g_up[users, net.ap, :], net.g_dn[net.ap, users, :]


def _sic_sum(own, ap, weight, stronger: bool):
    """sum_v [ap_v == ap_u] [own_v > own_u if stronger else own_v < own_u]
    * weight_v, per receiver u and subchannel m, over blocks of receivers."""
    u, m = own.shape
    pad = -u % ROW_BLOCK
    own_r = jnp.pad(own, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, m)
    ap_r = jnp.pad(ap, (0, pad), constant_values=-1).reshape(-1, ROW_BLOCK)

    def block(args):
        o, a = args                                        # (B, M), (B,)
        same = a[:, None] == ap[None, :]                   # (B, V)
        order = own[None] > o[:, None] if stronger else own[None] < o[:, None]
        mask = same[:, :, None] & order                    # (B, V, M)
        return jnp.sum(jnp.where(mask, weight[None], 0.0), axis=1)

    return jax.lax.map(block, (own_r, ap_r)).reshape(-1, m)[:u]


def rates(net: Net, c: Consts, beta_up, beta_dn, p_up, p_dn):
    """Per-user total uplink and downlink rates in bit/s, floored at 1e-9."""
    n_aps, m = net.g_dn.shape[0], net.g_up.shape[2]
    own_up, own_dn = _own(net)
    other = net.ap[None, :] != jnp.arange(n_aps)[:, None]     # (N, U)

    tx = beta_up * p_up[:, None]
    at_ap = jnp.sum(jnp.where(other[:, :, None],
                              tx[None] * jnp.swapaxes(net.g_up, 0, 1), 0.0),
                    axis=1)                                   # (N, M)
    inter = at_ap[net.ap]
    intra = _sic_sum(own_up, net.ap, tx * own_up, stronger=False)
    sinr = p_up[:, None] * own_up / (intra + inter + c.noise_psd * c.bw_up / m)
    r_up = jnp.sum(beta_up * (c.bw_up / m) * jnp.log1p(sinr) / LN2, axis=-1)

    tx = beta_dn * p_dn[:, None]
    ap_tx = jnp.zeros((n_aps, m), tx.dtype).at[net.ap].add(tx)   # (N, M)
    inter = jnp.sum(jnp.where(other[:, :, None], ap_tx[:, None] * net.g_dn,
                              0.0), axis=0)                   # (U, M)
    intra = own_dn * _sic_sum(own_dn, net.ap, tx, stronger=True)
    sinr = p_dn[:, None] * own_dn / (intra + inter + c.noise_psd * c.bw_dn / m)
    r_dn = jnp.sum(beta_dn * (c.bw_dn / m) * jnp.log1p(sinr) / LN2, axis=-1)
    return jnp.maximum(r_up, 1e-9), jnp.maximum(r_dn, 1e-9)


def physical(norm: dict, c: Consts) -> dict:
    """Normalized [0, 1] powers and compute units to Watts and units."""
    box = lambda x, lo_hi: lo_hi[0] + x * (lo_hi[1] - lo_hi[0])
    return {"beta_up": norm["beta_up"], "beta_dn": norm["beta_dn"],
            "p_up": box(norm["p_up"], c.p_up), "p_dn": box(norm["p_dn"], c.p_dn),
            "r": box(norm["r"], c.r)}


def gamma(net: Net, prof, c: Consts, s, v: dict):
    """Gamma_s = sum_i w_T T_i + w_E E_i at physical point v (eq. 22)."""
    fl, bits, m_down = prof
    prefix = jnp.concatenate([jnp.zeros((1,), fl.dtype), jnp.cumsum(fl)])
    f_dev, f_edge = prefix[s], jnp.sum(fl) - prefix[s]
    r_up, r_dn = rates(net, c, v["beta_up"], v["beta_dn"], v["p_up"],
                       v["p_dn"])
    speed = jnp.power(v["r"], c.lam_exp) * c.c_min_edge
    t_up, t_dn = bits[s] / r_up, m_down[s] / r_dn
    t = f_dev / c.c_device + f_edge / speed + t_up + t_dn
    e = (c.xi_device * c.c_device ** 2 * c.phi_device * f_dev
         + v["p_up"] * t_up + c.xi_edge * speed ** 2 * c.phi_edge * f_edge
         + v["p_dn"] * t_dn)
    return jnp.sum(c.w_t * t + (1.0 - c.w_t) * e)


# -- the solver -------------------------------------------------------------
def _simplex(y, total):
    """Euclidean projection of each row onto {x >= 0, sum x = total}."""
    m = y.shape[-1]
    desc = -jnp.sort(-y, axis=-1)
    css = jnp.cumsum(desc, axis=-1) - total
    k = jnp.arange(1, m + 1, dtype=y.dtype)
    n_pos = jnp.maximum(jnp.sum(desc - css / k > 0, axis=-1), 1)
    theta = jnp.take_along_axis(css, n_pos[..., None] - 1, axis=-1)
    return jnp.maximum(y - theta / n_pos[..., None].astype(y.dtype), 0.0)


def project(norm: dict, beta_min: float) -> dict:
    """Shares onto the simplex with floor beta_min (clamped to 1/M), the
    rest onto [0, 1]."""
    out = {}
    for k in ("beta_up", "beta_dn"):
        m = norm[k].shape[-1]
        f = jnp.minimum(jnp.asarray(beta_min, norm[k].dtype), 1.0 / m)
        out[k] = _simplex(norm[k] - f, 1.0 - m * f) + f
    for k in ("p_up", "p_dn", "r"):
        out[k] = jnp.clip(norm[k], 0.0, 1.0)
    return out


def cold_start(u: int, m: int) -> dict:
    share = jnp.full((u, m), 1.0 / m, jnp.float32)
    half = jnp.full((u,), 0.5, jnp.float32)
    return {"beta_up": share, "beta_dn": share, "p_up": half, "p_dn": half,
            "r": half}


def rho_estimate(a, b):
    """sqrt of the clipped Pearson correlation of two gain tensors, each
    scaled by its largest magnitude first."""
    a = a.reshape(-1) / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    b = b.reshape(-1) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)
    a, b = a - jnp.mean(a), b - jnp.mean(b)
    corr = jnp.sum(a * b) / jnp.maximum(
        jnp.sqrt(jnp.sum(a * a) * jnp.sum(b * b)), 1e-30)
    return jnp.sqrt(jnp.clip(corr, 0.0, 1.0))


class SplitResult(NamedTuple):
    norm: dict
    gamma: jax.Array
    m1: dict


def _norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(x * x) for x in tree.values()))


def solve_split(net: Net, prof, c: Consts, gd: dict, s, carry: dict,
                warm: dict, warm_m1: dict, warm_m2: dict, warm_steps,
                use_warm) -> SplitResult:
    """One split point of the warm replan: start from the previous epoch's
    optimum (resuming its Adam state) where the gate is open and it is no
    worse than the chain carry, else from the carry with fresh moments; then
    up to gd['max_iters'] projected Adam steps with the stopping rules."""
    lr, eps = gd["step_size"], gd["eps"]
    b1, b2 = gd["adam_b1"], gd["adam_b2"]
    f = lambda n: gamma(net, prof, c, s, physical(n, c))

    pick = use_warm & (f(warm) <= f(carry))
    x = {k: jnp.where(pick, warm[k], carry[k]) for k in carry}
    m1 = {k: jnp.where(pick, warm_m1[k], 0.0) for k in carry}
    m2 = {k: jnp.where(pick, warm_m2[k], 0.0) for k in carry}
    t0 = jnp.where(pick, warm_steps, 0).astype(jnp.int32)
    x = project(x, c.beta_min)
    g_now = f(x)
    it = jnp.int32(0)
    done = jnp.bool_(False)
    for _ in range(gd["max_iters"]):
        val, grad = jax.value_and_grad(f)(x)
        t = (t0 + it + 1).astype(jnp.float32)
        n1 = {k: b1 * m1[k] + (1 - b1) * grad[k] for k in x}
        n2 = {k: b2 * m2[k] + (1 - b2) * grad[k] ** 2 for k in x}
        step = {k: lr * (n1[k] / (1 - b1 ** t))
                / (jnp.sqrt(n2[k] / (1 - b2 ** t)) + 1e-8) for k in x}
        new = project({k: x[k] - step[k] for k in x}, c.beta_min)
        g_new = f(new)
        probe = project({k: x[k] - lr * grad[k] for k in x}, c.beta_min)
        crit = _norm({k: x[k] - probe[k] for k in x}) / lr
        change = jnp.max(jnp.stack([jnp.max(jnp.abs(new[k] - x[k]))
                                    for k in x]))
        stop = ((crit < eps) | (jnp.abs(g_new - val) < eps * jnp.maximum(
            1.0, jnp.abs(val))) | (change < eps))
        keep = lambda a, b: jax.tree.map(lambda p, q: jnp.where(done, p, q),
                                         a, b)
        x, m1, m2 = keep(x, new), keep(m1, n1), keep(m2, n2)
        g_now = jnp.where(done, g_now, g_new)
        it = jnp.where(done, it, it + 1)
        done = done | stop
    return SplitResult(norm=x, gamma=g_now, m1=m1)


# -- rounding ----------------------------------------------------------------
def greedy_up(net: Net, c: Consts, beta, p):
    """Users in index order each take the subchannel maximizing
    beta * log(1 + SINR) against the power of users already assigned."""
    own, _ = _own(net)
    noise = c.noise_psd * c.bw_up / own.shape[1]

    def step(interf, u):
        sinr = p[u] * own[u] / (interf[u] + noise)
        pick = jnp.argmax(beta[u] * jnp.log1p(sinr))
        seen_by = net.g_up[u][net.ap]                       # (U, M)
        hit = jnp.arange(own.shape[1]) == pick
        return interf + jnp.where(hit[None], p[u] * seen_by, 0.0), pick

    _, subs = jax.lax.scan(step, jnp.zeros_like(own), jnp.arange(own.shape[0]))
    return subs.astype(jnp.int32)


def greedy_dn(net: Net, c: Consts, beta, p):
    """Downlink analogue: interference at the user from the power the other
    APs already spend on each subchannel."""
    _, own = _own(net)
    n_aps, m = net.g_dn.shape[0], own.shape[1]
    noise = c.noise_psd * c.bw_dn / m

    def step(ap_tx, u):
        others = jnp.arange(n_aps) != net.ap[u]
        interf = jnp.sum(jnp.where(others[:, None], ap_tx * net.g_dn[:, u], 0.0),
                         axis=0)
        sinr = p[u] * own[u] / (interf + noise)
        pick = jnp.argmax(beta[u] * jnp.log1p(sinr))
        hit = (jnp.arange(n_aps) == net.ap[u])[:, None] & (jnp.arange(m) == pick)
        return ap_tx + jnp.where(hit, p[u], 0.0), pick

    _, subs = jax.lax.scan(step, jnp.zeros((n_aps, m), own.dtype),
                           jnp.arange(own.shape[0]))
    return subs.astype(jnp.int32)


def roundings(net: Net, prof, c: Consts, s, norm: dict) -> dict:
    """Argmax and greedy roundings of the relaxed point at split s, with the
    utility of each at the discrete subchannels."""
    v = physical(norm, c)
    m = norm["beta_up"].shape[-1]
    arg = (jnp.argmax(v["beta_up"], -1).astype(jnp.int32),
           jnp.argmax(v["beta_dn"], -1).astype(jnp.int32))
    grd = (greedy_up(net, c, v["beta_up"], v["p_up"]),
           greedy_dn(net, c, v["beta_dn"], v["p_dn"]))

    def disc(su, sd):
        hard = dict(v, beta_up=jax.nn.one_hot(su, m),
                    beta_dn=jax.nn.one_hot(sd, m))
        return gamma(net, prof, c, s, hard)

    return {"argmax": arg, "greedy": grd, "u_argmax": disc(*arg),
            "u_greedy": disc(*grd)}


# -- the replan, checked split by split -------------------------------------
@functools.partial(jax.jit, static_argnames=("c", "gd_items"))
def _split_jit(net, prof, s, carry, warm, warm_m1, warm_m2, warm_steps,
               use_warm, c, gd_items):
    return solve_split(net, prof, c, dict(gd_items), s, carry, warm, warm_m1,
                       warm_m2, warm_steps, use_warm)


@functools.partial(jax.jit, static_argnames=("c",))
def _gamma_jit(net, prof, s, norm, c):
    return gamma(net, prof, c, s, physical(norm, c))


_rounding_jit = jax.jit(roundings, static_argnames=("c",))
_rho_jit = jax.jit(rho_estimate)


def replan_by_split(net: Net, prof, c: Consts, gd: dict, warm_rho_min: float,
                    warm_moment_decay: float, prev: dict, carries: dict):
    """The replan of every split point, each started from the chain carry
    that ``carries`` supplies (split s starts from ``carries[s - 1]``, split
    0 from the cold start). ``prev`` is the previous epoch's state: norms,
    m1, m2 (leaves lead with F+1), steps (F+1,) and gains (U, N, M).

    Returns per-split norms, Gamma and first moments, stacked."""
    u, m = net.g_up.shape[0], net.g_up.shape[2]
    n_splits = prev["steps"].shape[0]
    use_warm = _rho_jit(prev["gains"], net.g_up) >= warm_rho_min
    start = project(cold_start(u, m), c.beta_min)
    gd_items = tuple(sorted(gd.items()))
    out = []
    for s in range(n_splits):
        carry = start if s == 0 else jax.tree.map(lambda x: x[s - 1], carries)
        at = lambda t: jax.tree.map(lambda x: x[s], t)
        out.append(_split_jit(
            net, prof, jnp.int32(s), carry, at(prev["norms"]),
            jax.tree.map(lambda x: warm_moment_decay * x, at(prev["m1"])),
            jax.tree.map(lambda x: warm_moment_decay * x, at(prev["m2"])),
            prev["steps"][s], use_warm, c=c, gd_items=gd_items))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *out)


def gammas_at(net: Net, prof, c: Consts, norms: dict) -> jax.Array:
    """Gamma_s at the given per-split points (leaves lead with F+1)."""
    n_splits = norms["p_up"].shape[0]
    return jnp.stack([
        _gamma_jit(net, prof, jnp.int32(s),
                   jax.tree.map(lambda x: x[s], norms), c=c)
        for s in range(n_splits)])


def rounding_of(net: Net, prof, c: Consts, s: int, norm: dict) -> dict:
    return _rounding_jit(net, prof, c, jnp.int32(s), norm)
