"""The Loop-iteration Gradient Descent (Li-GD) optimizer — paper Table I.

Design notes
------------
* The solver works in *normalized* coordinates: subchannel shares beta live on
  the probability simplex (constraint 18.e/18.f) with a small floor beta_min;
  powers and compute units are mapped to [0, 1] via their boxes (18.c/18.d).
  Normalization makes a single scalar step size meaningful across variables
  with wildly different physical scales (Watts vs compute units); it is a
  reparameterization, not a change of the optimization problem.
* Gradients come from jax.grad of the utility (paper derives them by hand in
  eqs. 23-30; autodiff computes the same derivatives exactly).
* The per-split-point solve is a lax.while_loop with the paper's stopping
  rules (Table I lines 6/9): a gradient criterion, |Gamma_{k+1}-Gamma_k| <
  eps, or max variable change < eps, capped at max_iters. The gradient
  criterion is configurable (GdConfig.stop_rule): the paper's raw ||g|| < eps
  never fires at a *constrained* optimum (the gradient does not vanish on the
  simplex/box boundary, it only becomes normal to the feasible set), so the
  default is the projected-gradient residual ||x - P(x - alpha*g)|| / alpha,
  which is zero exactly at a KKT point of the constrained problem.
* Li-GD chains split points via lax.scan, warm-starting layer s+1 from the
  optimum of layer s (Table I lines 13-16). plain_gd is the cold-start
  baseline used to validate Corollary 4 (iteration-count reduction).
* Online (cross-epoch) warm starts can resume the Adam state: gd_solve
  accepts and returns the first/second moments and the cumulative step count
  (for bias correction), so a re-plan continues the optimizer trajectory
  instead of re-biasing from zero -- without this, sign-like early Adam steps
  near the previous optimum defeat early stopping and warm starts can *lose*
  to cold starts at moderate epoch-to-epoch correlation.
* Named scopes mark the phases on a device trace (see repro.obs): gd_iter
  (the while_loop of iterations), warm_gate (the online start's two utility
  probes) and greedy_rounding (the sequential rounding scans).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.utility import utility as _utility
from repro.core.types import (
    Array,
    EccWeights,
    GdConfig,
    GdVars,
    ModelProfile,
    NetworkEnv,
    SplitPlan,
)


# --------------------------------------------------------------------------
# projections
# --------------------------------------------------------------------------
def project_simplex(y: Array, total: float = 1.0) -> Array:
    """Euclidean projection of each row of y onto {x >= 0, sum x = total}."""
    m = y.shape[-1]
    u = jnp.sort(y, axis=-1)[..., ::-1]
    css = jnp.cumsum(u, axis=-1) - total
    idx = jnp.arange(1, m + 1, dtype=y.dtype)
    cond = (u - css / idx) > 0
    rho = jnp.maximum(jnp.sum(cond, axis=-1), 1)
    theta = jnp.take_along_axis(css, rho[..., None] - 1, axis=-1) / rho[..., None].astype(y.dtype)
    return jnp.maximum(y - theta, 0.0)


def project_simplex_floor(y: Array, floor: float) -> Array:
    """Projection onto {x >= floor, sum x = 1} (rows).

    The floored simplex is nonempty only when m * floor <= 1 (Corollary 1's
    feasibility condition beta_min <= 1/M). A larger floor is clamped to 1/m
    -- the set then degenerates to the single point x = ones/m -- instead of
    silently producing sum(x) != 1 from a negative residual budget."""
    m = y.shape[-1]
    f = jnp.minimum(jnp.asarray(floor, dtype=y.dtype), 1.0 / m)
    z = project_simplex(y - f, total=1.0 - m * f)
    return z + f


def _project(norm: dict, beta_min: float) -> dict:
    return {
        "beta_up": project_simplex_floor(norm["beta_up"], beta_min),
        "beta_dn": project_simplex_floor(norm["beta_dn"], beta_min),
        "p_up": jnp.clip(norm["p_up"], 0.0, 1.0),
        "p_dn": jnp.clip(norm["p_dn"], 0.0, 1.0),
        "r": jnp.clip(norm["r"], 0.0, 1.0),
    }


def to_physical(norm: dict, env: NetworkEnv) -> GdVars:
    rc, cc = env.radio, env.comp
    return GdVars(
        beta_up=norm["beta_up"],
        beta_dn=norm["beta_dn"],
        p_up=rc.p_up_min_w + norm["p_up"] * (rc.p_up_max_w - rc.p_up_min_w),
        p_dn=rc.p_dn_min_w + norm["p_dn"] * (rc.p_dn_max_w - rc.p_dn_min_w),
        r=cc.r_min + norm["r"] * (cc.r_max - cc.r_min),
    )


def cold_init(env: NetworkEnv) -> dict:
    """Table I line 1: start mid-box / uniform simplex, no prior knowledge."""
    u, m = env.n_users, env.n_sub
    one = jnp.ones((u, m)) / m
    half = jnp.full((u,), 0.5)
    return {"beta_up": one, "beta_dn": one, "p_up": half, "p_dn": half, "r": half}


# --------------------------------------------------------------------------
# online warm-gate: epoch-to-epoch channel correlation, traced in jax
# --------------------------------------------------------------------------
def rho_estimate(prev_gains: Array, gains: Array) -> Array:
    """Estimate the epoch-to-epoch fading correlation rho from two gain
    tensors of one scenario (vmap for fleets). For the Gauss-Markov process
    corr(|h_t|^2, |h_{t+1}|^2) = rho^2, so rho_hat = sqrt(clip(corr, 0, 1)).

    Pure jnp so the estimate lives *inside* the compiled replan program: the
    warm-vs-cold gate is selected on device and dispatch never syncs to host.
    Gains are path-loss scaled (~1e-12 at paper geometry), so both tensors
    are max-normalized before the correlation -- it is scale-invariant and
    this keeps the fp32 sums far from underflow."""
    a = prev_gains.reshape(-1).astype(jnp.float32)
    b = gains.reshape(-1).astype(jnp.float32)
    a = a / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    b = b / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)
    a = a - jnp.mean(a)
    b = b - jnp.mean(b)
    denom = jnp.sqrt(jnp.sum(a * a) * jnp.sum(b * b))
    corr = jnp.sum(a * b) / jnp.maximum(denom, 1e-30)
    return jnp.sqrt(jnp.clip(corr, 0.0, 1.0))


# --------------------------------------------------------------------------
# single-split-point projected GD (Table I lines 3-12)
# --------------------------------------------------------------------------
class GdResult(NamedTuple):
    norm: dict
    gamma: Array
    iters: Array
    grad_norm: Array
    mom: tuple       # final Adam moments (m1, m2) -- zeros when optimizer="sgd"
    opt_steps: Array # () int32 cumulative optimizer steps behind `mom`
                     # (init_steps + iters; drives Adam bias correction on resume)


def _tree_norm(t) -> Array:
    leaves = jax.tree_util.tree_leaves(t)
    return jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))


def _tree_maxdiff(a, b) -> Array:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return jnp.max(jnp.stack([jnp.max(jnp.abs(x - y)) for x, y in zip(la, lb)]))


def gd_solve(
    env: NetworkEnv,
    prof: ModelProfile,
    s: Array,
    w: EccWeights,
    init_norm: dict,
    cfg: GdConfig,
    init_mom: tuple | None = None,
    init_steps: Array | None = None,
) -> GdResult:
    """Projected (Adam-)GD for one split point.

    init_mom/init_steps resume a previous solve's optimizer state (online
    warm restarts): the Adam moments keep their accumulated history and the
    bias correction continues from init_steps instead of restarting at t=1.
    """
    if cfg.stop_rule not in ("pgd", "raw"):
        raise ValueError(f"stop_rule must be 'pgd' or 'raw', got {cfg.stop_rule!r}")
    beta_min = env.radio.beta_min

    def gamma_fn(norm):
        return _utility(env, prof, s, to_physical(norm, env), w,
                        backend=cfg.sinr_backend)

    grad_fn = jax.value_and_grad(gamma_fn)
    adam = cfg.optimizer == "adam"
    steps0 = jnp.int32(0) if init_steps is None else init_steps.astype(jnp.int32)

    def cond(state):
        _, _, _, it, done = state
        return jnp.logical_and(it < cfg.max_iters, jnp.logical_not(done))

    def body(state):
        norm, mom, gamma_prev, it, _ = state
        gamma, g = grad_fn(norm)
        if adam:
            m1, m2 = mom
            m1 = jax.tree.map(lambda a, b: cfg.adam_b1 * a + (1 - cfg.adam_b1) * b, m1, g)
            m2 = jax.tree.map(lambda a, b: cfg.adam_b2 * a + (1 - cfg.adam_b2) * b * b, m2, g)
            t = (steps0 + it + 1).astype(jnp.float32)
            step = jax.tree.map(
                lambda a, b: cfg.step_size
                * (a / (1 - cfg.adam_b1**t))
                / (jnp.sqrt(b / (1 - cfg.adam_b2**t)) + 1e-8),
                m1,
                m2,
            )
            mom = (m1, m2)
        else:
            step = jax.tree.map(lambda x: cfg.step_size * x, g)
        new = _project(jax.tree.map(lambda a, b: a - b, norm, step), beta_min)
        gamma_new = gamma_fn(new)
        if cfg.stop_rule == "pgd":
            # Projected-gradient residual: the raw-gradient probe step is
            # independent of the optimizer, so Adam's rescaled steps cannot
            # mask (or fake) convergence on the constraint boundary.
            probe = new if not adam else _project(
                jax.tree.map(lambda a, b: a - cfg.step_size * b, norm, g), beta_min)
            gcrit = _tree_norm(jax.tree.map(lambda a, b: a - b, norm, probe))
            gcrit = gcrit / cfg.step_size
        else:
            gcrit = _tree_norm(g)
        done = jnp.logical_or(
            gcrit < cfg.eps,
            jnp.logical_or(
                jnp.abs(gamma_new - gamma) < cfg.eps * jnp.maximum(1.0, jnp.abs(gamma)),
                _tree_maxdiff(new, norm) < cfg.eps,
            ),
        )
        return new, mom, gamma_new, it + 1, done

    zero_mom = (
        jax.tree.map(jnp.zeros_like, init_norm),
        jax.tree.map(jnp.zeros_like, init_norm),
    )
    mom0 = zero_mom if init_mom is None else init_mom
    norm0 = _project(init_norm, beta_min)
    state0 = (norm0, mom0, gamma_fn(norm0), jnp.int32(0), jnp.bool_(False))
    with jax.named_scope("gd_iter"):
        norm, mom, gamma, it, _ = jax.lax.while_loop(cond, body, state0)
    _, g = grad_fn(norm)
    return GdResult(norm=norm, gamma=gamma, iters=it, grad_norm=_tree_norm(g),
                    mom=mom, opt_steps=steps0 + it)


# --------------------------------------------------------------------------
# split-point loop (Table I), unified over warm-start policies
# --------------------------------------------------------------------------
class LoopResult(NamedTuple):
    gammas: Array      # (F+1,)
    iters: Array       # (F+1,)
    norms: dict        # stacked per-split optima, leaves lead with (F+1, ...)
    total_iters: Array
    moms: tuple        # stacked per-split Adam moments (m1, m2), leaves (F+1, ...)
    opt_steps: Array   # (F+1,) int32 cumulative optimizer steps per split
    used_warm: Array   # (F+1,) bool: split started from the cross-epoch state


def gd_loop(
    env: NetworkEnv,
    prof: ModelProfile,
    w: EccWeights,
    cfg: GdConfig,
    *,
    chain: bool = True,
    warm: dict | None = None,
    warm_mom: tuple | None = None,
    warm_steps: Array | None = None,
    use_warm: Array | bool = True,
) -> LoopResult:
    """Solve all F+1 split points with one warm-start policy.

    chain=True,  warm=None  -- paper Li-GD (Table I lines 13-16): split s+1
                               starts from split s's optimum.
    chain=False, warm=None  -- plain GD: every split starts from cold_init
                               (the paper's 'traditional GD' baseline).
    warm=stacked norms      -- online mode (leaves lead with (F+1, ...)):
                               warm[s] is the previous *epoch's* optimum at
                               split s. Each split starts from the BETTER of
                               warm[s] and the Li-GD chain carry (split s-1's
                               fresh optimum), judged by one extra utility
                               evaluation: under high epoch-to-epoch
                               correlation the temporal start is near-optimal
                               and stops almost immediately, while a stale
                               start (channel moved) silently degrades to the
                               paper's chain -- so online mode is never worse
                               than a cold Li-GD sweep. warm_mom / warm_steps
                               resume the per-split Adam moments and
                               bias-correction step counts (from a previous
                               LoopResult.moms/opt_steps) whenever the
                               temporal start is chosen, so the optimizer
                               continues its trajectory instead of re-biasing
                               from zero; the chain start always uses fresh
                               moments, matching Table I.
    use_warm (warm mode)    -- scalar bool (traced OK; vmap it for per-member
                               fleet selection): False disables the temporal
                               starts entirely, making the solve *exactly*
                               the paper's chained Li-GD. The engine's
                               rho-adaptive selector drives this.

    The returned moms/opt_steps always carry each split's final optimizer
    state for the next epoch's resume.
    """
    splits = jnp.arange(prof.n_layers + 1, dtype=jnp.int32)
    init = cold_init(env)

    if warm is not None:
        if warm_mom is None:
            warm_mom = (jax.tree.map(jnp.zeros_like, warm),
                        jax.tree.map(jnp.zeros_like, warm))
        if warm_steps is None:
            warm_steps = jnp.zeros_like(splits)
        use_warm = jnp.asarray(use_warm, dtype=bool)
        beta_min = env.radio.beta_min

        def step(carry_norm, xs):
            s, w0, m1, m2, st0 = xs

            def gamma_at(n):
                return _utility(env, prof, s, to_physical(n, env), w,
                                backend=cfg.sinr_backend)

            with jax.named_scope("warm_gate"):
                pick_warm = jnp.logical_and(
                    use_warm, gamma_at(w0) <= gamma_at(carry_norm))
            sel = lambda a, b: jnp.where(pick_warm, a, b)
            start = jax.tree.map(sel, w0, carry_norm)
            mom0 = jax.tree.map(lambda x: jnp.where(pick_warm, x, 0.0),
                                (m1, m2))
            res = gd_solve(env, prof, s, w, start, cfg, init_mom=mom0,
                           init_steps=jnp.where(pick_warm, st0, 0))
            return res.norm, (res.gamma, res.iters, res.norm, res.mom,
                              res.opt_steps, pick_warm)

        init = _project(init, beta_min)
        _, (gammas, iters, norms, moms, opt_steps, used_warm) = jax.lax.scan(
            step, init, (splits, warm, warm_mom[0], warm_mom[1], warm_steps))
    else:
        def step(carry_norm, s):
            res = gd_solve(env, prof, s, w, carry_norm, cfg)
            return (res.norm if chain else carry_norm), (
                res.gamma, res.iters, res.norm, res.mom, res.opt_steps)

        _, (gammas, iters, norms, moms, opt_steps) = jax.lax.scan(
            step, init, splits)
        used_warm = jnp.zeros_like(splits, dtype=bool)
    return LoopResult(gammas=gammas, iters=iters, norms=norms,
                      total_iters=jnp.sum(iters), moms=moms,
                      opt_steps=opt_steps, used_warm=used_warm)


def li_gd_loop(
    env: NetworkEnv, prof: ModelProfile, w: EccWeights, cfg: GdConfig
) -> LoopResult:
    return gd_loop(env, prof, w, cfg, chain=True)


def plain_gd_loop(
    env: NetworkEnv, prof: ModelProfile, w: EccWeights, cfg: GdConfig
) -> LoopResult:
    """Cold-start GD per split point (the paper's 'traditional GD' baseline)."""
    return gd_loop(env, prof, w, cfg, chain=False)


# --------------------------------------------------------------------------
# rounding (Table I lines 17-20 + Corollary 5) and plan assembly
# --------------------------------------------------------------------------
def round_beta(beta: Array, paper_rule: bool = True) -> tuple[Array, Array, Array]:
    """Paper rule: beta > 0.5 -> 1 else 0. Returns (onehot, chosen, violations).

    When the 0.5-rule breaks constraint (18.e) (no entry > 0.5 -- possible
    since rows live on the simplex), we repair with argmax and count it."""
    if paper_rule:
        hard = (beta > 0.5).astype(beta.dtype)
        viol = jnp.sum(jnp.abs(jnp.sum(hard, axis=-1) - 1.0) > 0.5)
    else:
        viol = jnp.zeros((), beta.dtype)
    chosen = jnp.argmax(beta, axis=-1).astype(jnp.int32)
    onehot = jax.nn.one_hot(chosen, beta.shape[-1], dtype=beta.dtype)
    return onehot, chosen, viol


def greedy_round_up(env: NetworkEnv, beta: Array, p: Array) -> Array:
    """Load-aware sequential rounding (beyond-paper; see EXPERIMENTS §Perf).

    At high SINR log2(1+SINR) compresses channel differences, so the relaxed
    optimum is interior (near-uniform beta) and both the paper's 0.5-rule and
    naive argmax collapse users onto one channel. Greedy: assign users one by
    one to the subchannel maximizing their SINR given interference from the
    users already assigned."""
    own = env.own_gain_up()                          # (U, M)

    def step(assigned_interf, u):
        # assigned_interf: (U, M) interference each user would see at its AP
        sinr = p[u] * own[u] / (assigned_interf[u] + env.noise_up)
        m = jnp.argmax(beta[u] * jnp.log1p(sinr))
        # gain of user u at every other user's AP, gathered per scan step:
        # (U, M) at rest, never the full (U, U, M) pairwise tensor (the
        # analysis.NoGatherAbove rule gates the whole plan program on this).
        g_at_u = jnp.take(env.g_up, u, axis=0)[env.ap, :]
        add = p[u] * g_at_u * jax.nn.one_hot(m, env.n_sub)[None, :]
        return assigned_interf + add, m.astype(jnp.int32)

    init = jnp.zeros_like(own)
    with jax.named_scope("greedy_rounding"):
        _, subs = jax.lax.scan(step, init, jnp.arange(env.n_users))
    return subs


def greedy_round_dn(env: NetworkEnv, beta: Array, p: Array) -> Array:
    """Downlink analogue: interference at the *user* from other APs' tx."""
    own = env.own_gain_dn()                          # (U, M)
    g_all = jnp.swapaxes(env.g_dn, 0, 1)             # (U, N, M) AP->user gains
    cell = jax.nn.one_hot(env.ap, env.n_aps)         # (U, N)

    def step(ap_tx, u):
        # ap_tx: (N, M) power each AP already spends per subchannel.
        # Other-AP interference via a masked sum (no full-sum-minus-own-AP
        # subtraction: fp32-safe, matching the channel.py convention).
        interf = jnp.einsum("nm,nm,n->m", ap_tx, g_all[u], 1.0 - cell[u])
        sinr = p[u] * own[u] / (interf + env.noise_dn)
        m = jnp.argmax(beta[u] * jnp.log1p(sinr))
        add = p[u] * jnp.outer(cell[u], jax.nn.one_hot(m, env.n_sub))
        return ap_tx + add, m.astype(jnp.int32)

    with jax.named_scope("greedy_rounding"):
        _, subs = jax.lax.scan(step, jnp.zeros((env.n_aps, env.n_sub)),
                               jnp.arange(env.n_users))
    return subs


def assemble_plan(
    env: NetworkEnv, loop: LoopResult, prof: ModelProfile,
    rounding: str = "best", w: EccWeights | None = None,
    backend: str | None = None,
) -> SplitPlan:
    s_star = jnp.argmin(loop.gammas).astype(jnp.int32)
    best = jax.tree.map(lambda x: x[s_star], loop.norms)
    v = to_physical(best, env)
    _, sub_up, viol_up = round_beta(v.beta_up)
    _, sub_dn, viol_dn = round_beta(v.beta_dn)
    if rounding in ("greedy", "best"):
        g_up = greedy_round_up(env, v.beta_up, v.p_up)
        g_dn = greedy_round_dn(env, v.beta_dn, v.p_dn)
        if rounding == "greedy":
            sub_up, sub_dn = g_up, g_dn
        else:
            # best-of: evaluate the discrete utility under both roundings
            # (beyond-paper; the paper's 0.5-rule is kept for Cor.5 metrics).
            assert w is not None

            def disc_util(su, sd):
                vv = GdVars(
                    beta_up=jax.nn.one_hot(su, env.n_sub),
                    beta_dn=jax.nn.one_hot(sd, env.n_sub),
                    p_up=v.p_up, p_dn=v.p_dn, r=v.r,
                )
                return _utility(env, prof, s_star, vv, w, backend=backend)

            u_argmax = disc_util(sub_up, sub_dn)
            u_greedy = disc_util(g_up, g_dn)
            pick = (u_greedy < u_argmax)
            sub_up = jnp.where(pick, g_up, sub_up)
            sub_dn = jnp.where(pick, g_dn, sub_dn)
    return SplitPlan(
        s=s_star,
        sub_up=sub_up,
        sub_dn=sub_dn,
        p_up=v.p_up,
        p_dn=v.p_dn,
        r=v.r,
        utility=loop.gammas[s_star],
        per_layer_utility=loop.gammas,
        iters=loop.iters,
        rounding_violations=viol_up + viol_dn,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "method", "rounding"))
def solve(
    env: NetworkEnv,
    prof: ModelProfile,
    w: EccWeights,
    cfg: GdConfig = GdConfig(),
    method: str = "li_gd",
    rounding: str = "best",
) -> SplitPlan:
    if method not in ("li_gd", "gd"):
        raise KeyError(method)
    loop = gd_loop(env, prof, w, cfg, chain=(method == "li_gd"))
    return assemble_plan(env, loop, prof, rounding=rounding, w=w,
                         backend=cfg.sinr_backend)
