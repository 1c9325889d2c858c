"""PlannerEngine: the unified entry point for single-shot, batched, and
online warm-started ECC planning -- single scenarios, vmapped fleets, and
mesh-sharded fleets.

The engine owns a cache of compiled solver programs keyed on
(entry kind, env shape, GdConfig, method, rounding), so a serving loop that
re-plans every epoch pays tracing/compilation once per network shape. The
entry points share the cache:

  plan(env)             -- one-shot solve (the paper's Table I).
  plan_many(envs)       -- vmapped Monte-Carlo over stacked realizations
                           (one compiled program optimizes all draws). With a
                           mesh attached (mesh=... or engine.shard(mesh)) the
                           fleet dim is split across devices via shard_map.
  replan(prev, env)     -- online Li-GD: every split point warm-starts from
                           the previous epoch's normalized optimum at the
                           same split *and resumes its Adam moments*, so the
                           optimizer continues its trajectory instead of
                           re-biasing from zero. Under time-correlated fading
                           the previous optimum is near-optimal, so this is
                           the paper's warm-start argument (Corollary 4)
                           applied across *time* instead of across split
                           points.
  replan_many(prev, envs) -- the fleet replan: scenarios evolving in
                           parallel, one compiled program; sharded over the
                           mesh when one is attached (the carried PlanState
                           payload is donated to XLA on that path).

All entry points return a PlanState carrying the discrete SplitPlan plus the
solver state needed to warm-start the next epoch: the stacked normalized
optima, the per-split Adam moments and step counts, and the epoch's uplink
gains.

Everything in the replan dispatch path is device-resident: the rho-adaptive
warm gate -- estimate the epoch-to-epoch channel correlation between the
stored and observed gains, and run the exact cold Li-GD chain instead of the
temporal warm starts for any scenario whose estimate drops below
`warm_rho_min` -- is computed *inside* the compiled program
(li_gd.rho_estimate + a traced use_warm select), as is the Adam-moment
decay. replan/replan_many therefore enqueue asynchronously with zero host
syncs; the estimate itself is returned as PlanState.warm_rho. At low
correlation the previous optimum is stale and warm-starting from it costs
iterations instead of saving them. Independently of the gate, each split
point only adopts the temporal start when one utility probe says it beats
the fresh chain carry, so replan is never structurally worse than a cold
sweep.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import channel, li_gd
from repro.core.types import (
    Array,
    EccWeights,
    GdConfig,
    ModelProfile,
    NetworkEnv,
    SplitPlan,
    make_weights,
)
from repro.obs import recorded
from repro.pshard import axis_size, fleet_axis


def _shard_map(fn, *, mesh, in_specs, out_specs):
    """shard_map with varying-axes checking off: the Pallas kernels in the
    solver declare their outputs without a ``vma``, and every output here
    is fully fleet-sharded anyway."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class WarmStateShapeError(ValueError):
    """A warm-start PlanState does not fit the observed network shape
    (user/AP/subchannel count changed, or a fleet state was handed to the
    single-scenario entry point and vice versa); re-plan cold instead."""


class PlanState(NamedTuple):
    """A plan plus the solver state needed to warm-start the next epoch.
    All leaves are device arrays: the state round-trips through
    replan/replan_many without ever being pulled to host."""

    plan: SplitPlan
    norms: dict          # per-split normalized optima, leaves lead with (F+1, ...)
    total_iters: Array   # () total GD iterations spent producing this plan
    moms: tuple | None = None      # per-split Adam moments (m1, m2), leaves (F+1, ...)
    opt_steps: Array | None = None # (F+1,) int32 optimizer steps behind `moms`
    gains: Array | None = None     # g_up of the planned epoch (rho estimation)
    warm_rho: Array | None = None  # () in-jit rho estimate behind the warm gate
                                   # (None when the state came from a cold plan)


def stack_envs(envs: Sequence[NetworkEnv]) -> NetworkEnv:
    """Stack same-shape environments along a leading Monte-Carlo dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *envs)


def member(tree, i: int):
    """Slice fleet member i out of a batched pytree (stacked NetworkEnv or
    batched PlanState). Scalar leaves -- e.g. radio/comp constants that
    Scenario.env_many broadcast or that stayed unbatched -- pass through."""
    return jax.tree.map(lambda x: x[i] if getattr(x, "ndim", 0) > 0 else x,
                        tree)


def _strong_typed(tree):
    """Strip weak types from every leaf. The cold and warm solver programs
    must emit byte-identical PlanState avals: a weak-f32 leaf from the cold
    program would re-trace the warm program once on the first replan (and
    again on the second, when the warm output feeds back)."""
    return jax.tree.map(
        lambda x: jax.lax.convert_element_type(x, x.dtype)
        if getattr(x, "weak_type", False) else x, tree)


def _solve_state(env, prof, w, cfg, method, rounding) -> PlanState:
    loop = li_gd.gd_loop(env, prof, w, cfg, chain=(method == "li_gd"))
    plan = li_gd.assemble_plan(env, loop, prof, rounding=rounding, w=w,
                               backend=cfg.sinr_backend)
    return _strong_typed(
        PlanState(plan=plan, norms=loop.norms, total_iters=loop.total_iters,
                  moms=loop.moms, opt_steps=loop.opt_steps, gains=env.g_up))


def _resolve_state(env, prof, w, warm, warm_mom, warm_steps, prev_gains,
                   cfg, method, rounding, warm_rho_min,
                   warm_moment_decay) -> PlanState:
    """The fully traced replan program: rho gate, moment decay, warm solve,
    and plan assembly all happen on device inside one compiled call."""
    del method  # warm mode supersedes the chain-vs-cold distinction
    rho = li_gd.rho_estimate(prev_gains, env.g_up)
    # warm_rho_min is a trace-time constant per engine; rho is in [0, 1], so
    # warm_rho_min <= 0 means the gate is always open (fallback disabled).
    use_warm = rho >= warm_rho_min
    if warm_moment_decay != 1.0:
        warm_mom = jax.tree.map(lambda x: warm_moment_decay * x, warm_mom)
    loop = li_gd.gd_loop(env, prof, w, cfg, warm=warm, warm_mom=warm_mom,
                         warm_steps=warm_steps, use_warm=use_warm)
    plan = li_gd.assemble_plan(env, loop, prof, rounding=rounding, w=w,
                               backend=cfg.sinr_backend)
    return _strong_typed(
        PlanState(plan=plan, norms=loop.norms, total_iters=loop.total_iters,
                  moms=loop.moms, opt_steps=loop.opt_steps, gains=env.g_up,
                  warm_rho=rho))


class PlannerEngine:
    """Compiled-solver cache + unified planning API for one model profile.

    method: 'li_gd' (paper warm-start chain) or 'gd' (cold-start baseline).
    rounding: 'best' | 'greedy' | 'paper' (see li_gd.assemble_plan).
    mesh: optional jax.sharding.Mesh. When set, plan_many/replan_many run
        via shard_map with the fleet dim split over the mesh's fleet axis
        ('fleet' when present, else the first axis); the fleet size must be
        divisible by that axis. The carried warm-start payload is donated to
        XLA on the sharded replan path (the engine returns the next epoch's
        state, so the previous one is dead weight). engine.shard(mesh) is
        the fluent variant: a sharded twin of an existing engine.
    warm_rho_min: replan's rho-adaptive gate -- a scenario whose estimated
        epoch-to-epoch correlation falls below this threshold has its
        temporal warm starts disabled (the compiled warm program then runs
        the exact cold Li-GD chain), because a stale optimum is a worse
        start than no prior at all. The estimate and the gate are traced
        into the compiled program (no host sync); 0.0 disables the fallback.
    sinr_backend: SINR path traced into every compiled solver program
        ('einsum' | 'pallas' | 'pallas_interpret'; None keeps cfg's value).
        The Pallas pairwise kernel is differentiable (custom_vjp with a
        transposed-streaming backward kernel), so 'pallas' makes the GD hot
        loop itself stream-tiled -- end-to-end, including the vmapped and
        mesh-sharded fleet paths. The choice is folded into GdConfig and
        therefore into the compiled-program cache key: already-compiled
        programs keep the backend they were traced with, and an engine with
        a different backend mints new cache entries instead of mutating
        live ones (channel.set_sinr_backend's global never reaches engine
        programs).
    warm_moment_decay: factor applied to the carried Adam moments on resume
        (inside the compiled program). The sweet spot is a *softened*
        restart: carrying the moments verbatim steers the new epoch with a
        stale direction and over-remembered scale (slightly worse optima),
        while zeroing them re-biases Adam from t=0 and its sign-like opening
        steps walk away from the near-optimal start (many extra iterations).
        Decaying both moments -- with the step count carried so bias
        correction does not re-amplify them -- keeps per-coordinate scale
        memory but lets fresh gradients dominate within a few steps.
        1.0 resumes verbatim, 0.0 zeroes.
    """

    def __init__(
        self,
        prof: ModelProfile,
        weights: EccWeights | None = None,
        cfg: GdConfig = GdConfig(),
        method: str = "li_gd",
        rounding: str = "best",
        warm_rho_min: float = 0.5,
        warm_moment_decay: float = 0.1,
        mesh: Mesh | None = None,
        sinr_backend: str | None = None,
    ):
        if method not in ("li_gd", "gd"):
            raise KeyError(method)
        if sinr_backend is not None:
            cfg = dataclasses.replace(cfg, sinr_backend=sinr_backend)
        # Validate the *effective* backend, whichever route supplied it
        # (the kwarg or GdConfig(sinr_backend=...)), so a bad value fails
        # here instead of deep inside the first plan() trace.
        if cfg.sinr_backend not in channel.SINR_BACKENDS:
            raise ValueError(
                f"sinr_backend must be one of {channel.SINR_BACKENDS}, "
                f"got {cfg.sinr_backend!r}")
        if not 0.0 <= warm_rho_min <= 1.0:
            raise ValueError(f"warm_rho_min must be in [0, 1], got {warm_rho_min}")
        if not 0.0 <= warm_moment_decay <= 1.0:
            raise ValueError(
                f"warm_moment_decay must be in [0, 1], got {warm_moment_decay}")
        if mesh is not None and not mesh.axis_names:
            raise ValueError("mesh must have at least one axis")
        self._prof = prof
        self._weights = weights
        if mesh is None:
            self._prof_rep = self._weights_rep = None
        else:
            # Pre-place replicated copies of the engine constants over the
            # mesh once, so steady-state *sharded* dispatch needs no implicit
            # transfers (fleet-batched inputs are the caller's:
            # pshard.shard_fleet). The originals stay unplaced: the
            # single-scenario plan/replan programs are not mesh programs and
            # would reject mixed device commitments.
            rep = NamedSharding(mesh, P())
            self._prof_rep = jax.device_put(prof, rep)
            self._weights_rep = (None if weights is None
                                 else jax.device_put(weights, rep))
        self.cfg = cfg
        self.method = method
        self.rounding = rounding
        self.warm_rho_min = warm_rho_min
        self.warm_moment_decay = warm_moment_decay
        self._mesh = mesh
        self._cache: dict[tuple, object] = {}

    @property
    def mesh(self) -> Mesh | None:
        """Read-only: the replicated constants and the compiled fleet
        programs are lowered per mesh, so swap meshes via shard(), not by
        assigning the attribute."""
        return self._mesh

    @property
    def prof(self) -> ModelProfile:
        """Read-only: mesh engines hold a replicated copy baked at
        construction; build a new engine for a different profile."""
        return self._prof

    @property
    def weights(self) -> EccWeights | None:
        """Read-only: mesh engines hold a replicated copy baked at
        construction; pass per-call weights or build a new engine."""
        return self._weights

    @property
    def sinr_backend(self) -> str:
        """The SINR backend traced into this engine's compiled programs
        (folded into cfg, hence into every cache key)."""
        return self.cfg.sinr_backend

    def _prof_arg(self, prof: ModelProfile | None,
                  sharded: bool = False) -> ModelProfile:
        """The profile operand for one dispatch. ``prof`` overrides the
        static profile with a *measured* one (repro.online telemetry): it is
        validated against the static profile's layer structure, dtypes and
        name here -- host metadata only, so a mismatch raises a clear
        ProfileShapeError instead of recompiling (or failing inside) the
        jitted solver. A compatible override hits the same compiled program:
        the profile is an operand, never a trace constant."""
        if prof is None:
            return (self._prof_rep if sharded else self._prof)
        self._prof.validate_like(prof)
        if sharded:
            # Replicate the override explicitly, as _w does for weights:
            # sharded dispatch must not pay an implicit per-call reshard.
            return jax.device_put(prof, NamedSharding(self.mesh, P()))
        return prof

    def shard(self, mesh: Mesh | None) -> "PlannerEngine":
        """A twin of this engine whose fleet entry points run shard_map over
        `mesh` (None returns a plain vmapped twin). The compiled-program
        cache is not shared: sharded programs are lowered per mesh."""
        return PlannerEngine(
            self.prof, weights=self.weights, cfg=self.cfg, method=self.method,
            rounding=self.rounding, warm_rho_min=self.warm_rho_min,
            warm_moment_decay=self.warm_moment_decay, mesh=mesh,
        )

    # -- compiled-program cache ------------------------------------------
    def _env_shape(self, env: NetworkEnv) -> tuple:
        return tuple(env.g_up.shape)

    def _fleet_axis_size(self) -> int:
        return axis_size(self.mesh, fleet_axis(self.mesh))

    def _check_fleet_divisible(self, b: int):
        nd = self._fleet_axis_size()
        if b % nd != 0:
            raise ValueError(
                f"fleet size {b} is not divisible by the mesh fleet axis "
                f"'{fleet_axis(self.mesh)}' ({nd} devices); pad the fleet or "
                "use a divisor-sized mesh (repro.pshard.fleet_mesh(n))")

    def _compiled(self, kind: str, env: NetworkEnv):
        # warm_rho_min / warm_moment_decay are trace-time constants of the
        # compiled replan programs, so they belong in the key: retuning them
        # on a live engine must recompile, not silently keep the old gate.
        key = (kind, self._env_shape(env), self.cfg, self.method, self.rounding,
               self.warm_rho_min, self.warm_moment_decay)
        fn = self._cache.get(key)
        if fn is None:
            solve = functools.partial(_solve_state, cfg=self.cfg,
                                      method=self.method, rounding=self.rounding)
            resolve = functools.partial(
                _resolve_state, cfg=self.cfg, method=self.method,
                rounding=self.rounding, warm_rho_min=self.warm_rho_min,
                warm_moment_decay=self.warm_moment_decay)
            if kind == "plan":
                fn = jax.jit(recorded(solve, kind))
            elif kind == "plan_many":
                fn = jax.jit(recorded(
                    jax.vmap(solve, in_axes=(0, None, None)), kind))
            elif kind == "replan":
                fn = jax.jit(recorded(resolve, kind))
            elif kind == "replan_many":
                fn = jax.jit(recorded(
                    jax.vmap(resolve, in_axes=(0, None, None, 0, 0, 0, 0)),
                    kind))
            elif kind == "plan_many_sharded":
                ax = fleet_axis(self.mesh)
                fn = jax.jit(recorded(_shard_map(
                    jax.vmap(solve, in_axes=(0, None, None)), mesh=self.mesh,
                    in_specs=(P(ax), P(), P()), out_specs=P(ax)), kind))
            elif kind == "replan_many_sharded":
                ax = fleet_axis(self.mesh)
                # The carried payload (norms, moms, steps) is donated: the
                # caller threads the *returned* PlanState to the next epoch,
                # so XLA may reuse the previous epoch's buffers in place.
                fn = jax.jit(
                    recorded(_shard_map(
                        jax.vmap(resolve, in_axes=(0, None, None, 0, 0, 0, 0)),
                        mesh=self.mesh,
                        in_specs=(P(ax), P(), P(), P(ax), P(ax), P(ax), P(ax)),
                        out_specs=P(ax)), kind),
                    donate_argnums=(3, 4, 5))
            else:
                raise KeyError(kind)
            self._cache[key] = fn
        return fn

    def cache_size(self) -> int:
        return len(self._cache)

    def cache_keys(self) -> list[tuple]:
        """The compiled-program cache keys, for cache-discipline audits:
        (kind, env shape, GdConfig, method, rounding, warm_rho_min,
        warm_moment_decay). Read-only snapshot."""
        return list(self._cache)

    # -- program introspection (repro.analysis hooks) --------------------
    def program(self, kind: str, env: NetworkEnv):
        """The jitted program this engine dispatches for (kind, env) --
        built and cached on first access exactly as the entry points do.
        Pair with program_args() to trace it (jax.make_jaxpr / eval_shape)
        without executing: the repro.analysis auditor's entry point."""
        return self._compiled(kind, env)

    def program_args(self, kind: str, env: NetworkEnv,
                     prev: PlanState | None = None,
                     weights: EccWeights | None = None,
                     prof: ModelProfile | None = None) -> tuple:
        """The positional argument tuple program(kind, env) is called with.

        ``env`` is a single environment for plan/replan and a stacked fleet
        for the *_many kinds; replan kinds need ``prev`` (a PlanState of
        arrays, or of ShapeDtypeStructs from jax.eval_shape for trace-only
        audits -- the warm payload assembly is pure metadata in that case).
        ``prof`` substitutes a measured profile, exactly as the entry points
        do (validated, same compiled program)."""
        many = "many" in kind
        nu = env.g_up.shape[1] if many else env.n_users
        w = self._w(env, weights, n_users=nu)
        prof = self._prof_arg(prof)
        if kind.startswith("plan"):
            return (env, prof, w)
        if prev is None:
            raise ValueError(
                f"program_args({kind!r}) needs prev= (a PlanState or its "
                "jax.eval_shape avals) to assemble the warm payload")
        norms, moms, steps, prev_gains = self._warm_args(prev, env.g_up)
        return (env, prof, w, norms, moms, steps, prev_gains)

    def _w(self, env: NetworkEnv, weights, n_users: int | None = None,
           sharded: bool = False) -> EccWeights:
        if weights is None:
            if self.weights is not None:
                return self._weights_rep if sharded else self.weights
            weights = make_weights(env.n_users if n_users is None else n_users)
        if sharded:
            # Caller-supplied (or freshly derived) weights: replicate them
            # over the mesh explicitly, or every sharded dispatch pays an
            # implicit reshard (and trips jax.transfer_guard('disallow')).
            return jax.device_put(weights, NamedSharding(self.mesh, P()))
        return weights

    # -- warm-state shape validation (host metadata only, no device sync) --
    @staticmethod
    def _warm_dims(prev: PlanState) -> tuple[int | None, tuple[int, int]]:
        """(fleet size | None, (U, M)) read off a PlanState's norms. Leaves
        are (F+1, U, M) for a single scenario and (B, F+1, U, M) for a
        fleet; the trailing two dims are the network shape in both cases."""
        beta = prev.norms["beta_up"]
        nd = getattr(beta, "ndim", 0)
        if nd == 3:
            return None, tuple(beta.shape[-2:])
        if nd == 4:
            return int(beta.shape[0]), tuple(beta.shape[-2:])
        raise WarmStateShapeError(
            f"warm-start norms have rank-{nd} leaves {tuple(beta.shape)}; "
            "expected (F+1, U, M) for a single scenario or (B, F+1, U, M) "
            "for a fleet")

    # -- entry points ----------------------------------------------------
    def plan(self, env: NetworkEnv, weights: EccWeights | None = None,
             prof: ModelProfile | None = None) -> PlanState:
        """One-shot solve of a static environment. ``prof`` substitutes a
        measured profile (repro.online) for this dispatch: validated against
        the static one, then passed as an operand to the *same* compiled
        program -- closed-loop feedback never recompiles."""
        return self._compiled("plan", env)(
            env, self._prof_arg(prof), self._w(env, weights))

    def plan_many(
        self,
        envs: NetworkEnv | Sequence[NetworkEnv],
        weights: EccWeights | None = None,
        prof: ModelProfile | None = None,
    ) -> PlanState:
        """Batched Monte-Carlo solve: `envs` is either a list of same-shape
        environments or a NetworkEnv whose array leaves carry a leading
        batch dim. Returns a PlanState with the same leading dim. With a
        mesh attached, the batch is split over the fleet axis (shard_map);
        otherwise it is vmapped on one device."""
        if not isinstance(envs, NetworkEnv):
            envs = list(envs)
            if not envs:
                raise ValueError("plan_many needs at least one environment")
            envs = stack_envs(envs)
        if getattr(envs.g_up, "ndim", 0) != 4:
            raise ValueError(
                f"plan_many expects stacked envs with g_up (B, U, N, M); got "
                f"{tuple(envs.g_up.shape)} -- use plan() for a single "
                "scenario")
        if self.mesh is not None:
            self._check_fleet_divisible(envs.g_up.shape[0])
            w = self._w(envs, weights, n_users=envs.g_up.shape[1], sharded=True)
            return self._compiled("plan_many_sharded", envs)(
                envs, self._prof_arg(prof, sharded=True), w)
        w = self._w(envs, weights, n_users=envs.g_up.shape[1])
        return self._compiled("plan_many", envs)(envs, self._prof_arg(prof), w)

    # -- warm-start payload assembly (pure device ops, dispatches async) --
    def _warm_args(self, prev: PlanState, gains: Array):
        """(norms, moms, steps, prev_gains) handed to the compiled replan.
        Everything stays on device: missing moments/steps are zero-filled
        with device ops, and a missing gains record falls back to the new
        epoch's gains (rho estimate 1 -> gate open), matching the legacy
        'no history, trust the warm start' behavior."""
        norms, moms, steps = prev.norms, prev.moms, prev.opt_steps
        if moms is None:
            moms = (jax.tree.map(jnp.zeros_like, norms),
                    jax.tree.map(jnp.zeros_like, norms))
        if steps is None:
            steps = jnp.zeros(norms["beta_up"].shape[:-2], jnp.int32)
        prev_gains = gains if prev.gains is None else prev.gains
        return norms, moms, steps, prev_gains

    def replan(
        self,
        prev: PlanState | None,
        env: NetworkEnv,
        weights: EccWeights | None = None,
        prof: ModelProfile | None = None,
    ) -> PlanState:
        """Online re-plan for the next epoch of a time-correlated scenario:
        every split point starts from the better of `prev.norms[s]` (resuming
        its Adam moments/step counts, so early stopping fires as soon as the
        tracked optimum is re-attained) and the fresh Li-GD chain carry.
        Falls back to a cold plan() when there is no previous state. The
        rho-adaptive gate runs inside the compiled program: when the
        estimated epoch-to-epoch correlation is below `warm_rho_min` the
        temporal starts are disabled on device (use_warm=False -> exact cold
        Li-GD chain, same program). The call dispatches asynchronously --
        shape validation below reads array metadata only. ``prof``
        substitutes a measured profile (repro.online feedback) as an operand
        of the same compiled program."""
        if prev is None:
            return self.plan(env, weights, prof=prof)
        fleet, warm_um = self._warm_dims(prev)
        if fleet is not None:
            raise WarmStateShapeError(
                f"fleet-batched PlanState (B={fleet}) passed to replan(); "
                "use replan_many() for fleets, or planning.member(state, i) "
                "to re-plan one member")
        if warm_um != (env.n_users, env.n_sub) or (
                prev.gains is not None
                and tuple(prev.gains.shape) != tuple(env.g_up.shape)):
            raise WarmStateShapeError(
                f"warm-start state is for a (U, M)={warm_um} network but the "
                f"new env has {tuple(env.g_up.shape)}; scenario shapes (users, "
                "APs, subchannels) must stay static across epochs (use plan() "
                "after a shape change)")
        norms, moms, steps, prev_gains = self._warm_args(prev, env.g_up)
        return self._compiled("replan", env)(
            env, self._prof_arg(prof), self._w(env, weights), norms, moms,
            steps, prev_gains
        )

    def replan_many(
        self,
        prev: PlanState | None,
        envs: NetworkEnv | Sequence[NetworkEnv],
        weights: EccWeights | None = None,
        prof: ModelProfile | None = None,
    ) -> PlanState:
        """Fleet replan: scenarios evolving in parallel, all warm-started in
        one compiled program -- vmapped on one device, or shard_map over the
        mesh's fleet axis when one is attached (the carried payload is
        donated on that path; do not reuse `prev` afterwards). `prev` is the
        batched PlanState from the previous epoch's plan_many/replan_many
        (leaves lead with the fleet dim); `envs` is a stacked NetworkEnv or
        a list of same-shape environments. The rho-adaptive gate applies per
        fleet member inside the program: stale members run the exact cold
        Li-GD chain, fresh members resume their Adam trajectory."""
        if not isinstance(envs, NetworkEnv):
            envs = list(envs)
            if not envs:
                raise ValueError("replan_many needs at least one environment")
            envs = stack_envs(envs)
        if getattr(envs.g_up, "ndim", 0) != 4:
            raise WarmStateShapeError(
                f"replan_many expects stacked envs with g_up (B, U, N, M); "
                f"got {tuple(envs.g_up.shape)} -- use replan() for a single "
                "scenario")
        if prev is None:
            return self.plan_many(envs, weights, prof=prof)
        b, u, m = envs.g_up.shape[0], envs.g_up.shape[1], envs.g_up.shape[3]
        fleet, warm_um = self._warm_dims(prev)
        if fleet is None:
            raise WarmStateShapeError(
                f"single-scenario PlanState (norms leaves "
                f"{tuple(prev.norms['beta_up'].shape)}) passed to "
                "replan_many(); fleet states carry a leading fleet dim -- "
                "start from plan_many(), or use replan() for one scenario")
        if (fleet, *warm_um) != (b, u, m) or (
                prev.gains is not None
                and tuple(prev.gains.shape) != tuple(envs.g_up.shape)):
            raise WarmStateShapeError(
                f"warm-start state is for a fleet of {fleet} (U, M)={warm_um} "
                f"networks but the stacked envs have g_up "
                f"{tuple(envs.g_up.shape)}; fleet and scenario shapes must "
                "stay static across epochs (use plan_many() after a shape "
                "change)")
        norms, moms, steps, prev_gains = self._warm_args(prev, envs.g_up)
        if self.mesh is not None:
            self._check_fleet_divisible(b)
            w = self._w(envs, weights, n_users=u, sharded=True)
            return self._compiled("replan_many_sharded", envs)(
                envs, self._prof_arg(prof, sharded=True), w, norms, moms,
                steps, prev_gains
            )
        w = self._w(envs, weights, n_users=u)
        return self._compiled("replan_many", envs)(
            envs, self._prof_arg(prof), w, norms, moms, steps, prev_gains
        )
