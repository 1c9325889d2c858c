"""Serving runtime: prefill / decode steps, and ECC split-serve.

Split-serve is the paper's deployment shape: the model is cut at the
ECC-planned layer s*; layers [0, s) run on the *device* mesh, layers
[s, F) on the *edge* mesh. These are two separately-compiled programs (the
paper's device and edge are distinct systems joined by a NOMA radio link,
not one SPMD partition); the planner prices the activation transfer with
the NOMA rate model and `transfer_seconds` reports the simulated link time.
"""
from __future__ import annotations

import collections
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import Model
from repro.models.layers import COMPUTE_DTYPE, embed_lookup, logits_out
from repro.obs import host_read, recorded, span
from repro.planning import WarmStateShapeError
from repro.runtime import sharding as shlib


def jit_prefill(model: Model, mesh, max_len: int):
    def fn(params, batch):
        return model.prefill(params, batch, max_len)

    specs = model.specs()
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_shard = shlib.tree_shardings(mesh, specs, params_shapes)
    return jax.jit(fn, in_shardings=(p_shard, None)), p_shard


def jit_decode_step(model: Model, mesh, batch: int, max_len: int):
    """Returns (jitted step, params_sharding, cache_sharding)."""
    specs = model.specs()
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_shard = shlib.tree_shardings(mesh, specs, params_shapes)
    cache_shapes = jax.eval_shape(lambda: model.make_caches(batch, max_len))
    c_shard = shlib.cache_shardings(mesh, cache_shapes, model.cfg)
    tok_shard = NamedSharding(mesh, shlib.batch_spec(mesh, (batch, 1)))

    step = jax.jit(
        model.decode_step,
        in_shardings=(p_shard, c_shard, tok_shard),
        out_shardings=(None, c_shard),
        donate_argnums=(1,),
    )
    return step, p_shard, c_shard


# --------------------------------------------------------------------------
# ECC split-serve
# --------------------------------------------------------------------------
class SplitPrograms(NamedTuple):
    device_fn: object     # (tokens, frontend=None) -> activation (B, S, D);
                          # bound to the device-side stage params
    edge_fn: object       # (activation, frontend=None) -> logits; bound to
                          # the edge-side stage params + final norm/unembed
    split_layer: int
    act_bytes_per_token: int


def _split_params(model: Model, params, s: int):
    """Split stacked stage params at global block index s."""
    a_stages, b_stages = [], []
    seen = 0
    for spec, p_st in zip(model.stages, params["stages"]):
        if seen + spec.n_layers <= s:
            a_stages.append((spec, p_st))
        elif seen >= s:
            b_stages.append((spec, p_st))
        else:
            cut = s - seen
            take = lambda t, sl: jax.tree.map(lambda x: x[sl], t)
            import dataclasses as dc
            a_stages.append((dc.replace(spec, n_layers=cut),
                             take(p_st, slice(0, cut))))
            b_stages.append((dc.replace(spec, n_layers=spec.n_layers - cut),
                             take(p_st, slice(cut, None))))
        seen += spec.n_layers
    return a_stages, b_stages


def make_split_serve(model: Model, params, s: int):
    """Build device/edge programs for split point s (decoder-only archs).

    The weights are operands of the jitted programs, bound here, never
    constants closed over: baked in, a full-width model's weights land in
    every executable (one per sequence length) and in the host memory of
    every compile."""
    cfg = model.cfg
    a_stages, b_stages = _split_params(model, params, s)
    a_specs = [spec for spec, _ in a_stages]
    b_specs = [spec for spec, _ in b_stages]

    def aux(x, frontend):
        b, sl = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(sl, dtype=jnp.int32)[None], (b, sl))
        return {"pos": pos,
                "frontend": None if frontend is None else frontend.astype(COMPUTE_DTYPE),
                "moe_impl": model.moe_impl, "moe_capacity": model.moe_capacity}

    def device_fn(p, tokens, frontend=None):
        embed, stage_params = p
        x, a = embed_lookup(embed, tokens), aux(tokens, frontend)
        for spec, p_st in zip(a_specs, stage_params):
            x, _, _ = model._run_stage(spec, p_st, x, a, None)
        return x.astype(COMPUTE_DTYPE)

    def edge_fn(p, x, frontend=None):
        head, stage_params = p
        a = aux(x, frontend)
        for spec, p_st in zip(b_specs, stage_params):
            x, _, _ = model._run_stage(spec, p_st, x, a, None)
        x = model._final_norm(head, x)
        return logits_out(x, head["unembed"], cfg.vocab_size)

    head = {k: v for k, v in params.items() if k not in ("stages", "embed")}
    act_bytes = cfg.d_model * 2  # bf16 residual stream per token
    return SplitPrograms(
        device_fn=functools.partial(
            jax.jit(device_fn),
            (params["embed"], [p_st for _, p_st in a_stages])),
        edge_fn=functools.partial(
            jax.jit(edge_fn), (head, [p_st for _, p_st in b_stages])),
        split_layer=s, act_bytes_per_token=act_bytes)


def transfer_seconds(n_tokens: int, d_model: int, rate_bps: float) -> float:
    """Simulated NOMA uplink time for the split activation."""
    bits = n_tokens * d_model * 16
    return bits / max(rate_bps, 1e-9)


def planned_transfer_seconds(env, prof, plan):
    """Per-user split-upload seconds under the *discrete* plan: the NOMA
    uplink rate each user actually gets on its assigned subchannel at its
    planned power, pricing prof.w[s] bits. This is the planner-side twin of
    `transfer_seconds` (which prices a raw token count at a given rate): for
    an LM profile built at batch=1, w[s] = seq * d_model * ACT_BITS, so the
    two agree exactly on the same rate. The online telemetry uses this as
    the modeled upload time an observation is compared against."""
    from repro.core import channel  # deferred: runtime must stay importable
                                    # without the solver stack in the loop
    beta_up = jax.nn.one_hot(plan.sub_up, env.n_sub, dtype=env.g_up.dtype)
    r_up = jnp.sum(channel.uplink_rates(env, beta_up, plan.p_up), axis=-1)
    bits = prof.w[plan.s]
    return bits / jnp.maximum(r_up, 1e-9)


def jit_masked_decode_step(model: Model, mesh, batch: int, max_len: int):
    """Slot-masked decode step for continuous batching: like
    jit_decode_step, but takes an `active` (B,) bool mask; inactive slots'
    caches (including pos) are frozen so a slot can idle between requests
    and be overwritten at its next admission. Returns (jitted step,
    params_sharding, cache_sharding); step(params, caches, token, active)
    -> (logits, new_caches)."""
    from repro.online.batcher import slot_where  # deferred: avoid cycle
                                                 # (online.loop imports serve)
    specs = model.specs()
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_shard = shlib.tree_shardings(mesh, specs, params_shapes)
    cache_shapes = jax.eval_shape(lambda: model.make_caches(batch, max_len))
    c_shard = shlib.cache_shardings(mesh, cache_shapes, model.cfg)
    tok_shard = NamedSharding(mesh, shlib.batch_spec(mesh, (batch, 1)))

    def masked_step(params, caches, token, active):
        token = jnp.where(active[:, None], token, 0)
        logits, new_caches = model.decode_step(params, caches, token)
        return logits, slot_where(active, new_caches, caches)

    step = jax.jit(
        masked_step,
        in_shardings=(p_shard, c_shard, tok_shard, None),
        out_shardings=(None, c_shard),
        donate_argnums=(1,),
    )
    return step, p_shard, c_shard


# --------------------------------------------------------------------------
# online split-serve: re-plan as the scenario evolves
# --------------------------------------------------------------------------
class OnlineSplitServer:
    """Couples a PlannerEngine to split-serve across a time-evolving scenario.

    Every `replan_every` epochs the engine warm-start re-plans against the
    newly observed NetworkEnv; `recuts` counts the replans that moved the
    planned split layer. A served model takes the split as an operand of
    its edge program (repro.online.edge), so a moved split builds nothing.

    The epoch loop is device-resident: the engine's replan dispatches
    asynchronously (rho gate and warm payload are traced into the compiled
    program), GD-iteration accounting accumulates in a device scalar (read
    it lazily via the `total_iters` property), and the only host sync per
    replan is fetching the planned split layer s* (with the plan's health,
    below). That read runs under the span ``sync.plan_word`` and the engine
    call under ``dispatch.replan``; ``host_reads`` counts the server's
    reads by name.

    The PlanState threaded across epochs carries the full warm-start payload
    (normalized optima, Adam moments + step counts, and the epoch's gains for
    the engine's rho-adaptive gate). A network shape change (user count /
    subchannel count) invalidates that state: observe() catches the engine's
    shape-change ValueError, resets the warm state, and re-plans cold --
    `cold_resets` counts these events.

    With ``guard_plans=True`` (the default) the same one-scalar sync also
    traps *non-finite or infeasible* plans: the in-jit health check
    (faults.guards.plan_word) packs the plan's health bits above s* in the
    synced word, a bad plan is rejected and the last good PlanState held
    (`bad_plans` counts these, next to `cold_resets`), and the degradation
    ladder -- not the batcher -- decides what serves next. A NaN measured
    profile otherwise flows straight through replan into a served plan:
    utility goes NaN while the power vector can stay finite, so the guard
    checks the whole plan, not just the powers.
    """

    def __init__(self, engine, replan_every: int = 1,
                 guard_plans: bool = True):
        if replan_every < 1:
            raise ValueError(f"replan_every must be >= 1, got {replan_every}")
        self.engine = engine
        self.replan_every = replan_every
        self.guard_plans = bool(guard_plans)
        self.state = None               # planning.PlanState of the last re-plan
        self.split_layer: int | None = None
        self.epoch = 0
        self.recuts = 0
        self.cold_resets = 0
        self.replans = 0                # scheduled + forced engine dispatches
        self.forced_replans = 0         # QoS-triggered (force=True) subset
        self.bad_plans = 0              # guarded replans rejected (held last good)
        self.last_plan_ok: bool | None = None   # outcome of the last dispatch
        self.last_replanned = False     # did the last observe() dispatch?
        self._iters_acc = jnp.zeros((), jnp.int32)  # device-side accumulator
        self._plan_word_fn = None       # jitted guard, built on first use
        self.host_reads = collections.Counter()   # repro.obs.host_read

    @property
    def total_iters(self) -> int:
        """Total GD iterations across all re-plans. Reading it syncs the
        device accumulator; the serving loop itself never does."""
        return int(host_read(self._iters_acc, "iters", self.host_reads))

    def metrics(self) -> dict:
        """Counters of the server's control-plane activity: epochs seen,
        replans dispatched (and how many were QoS-forced off-schedule),
        replans that moved the split, cold resets after network shape
        changes, and total GD iterations (this read syncs the device
        accumulator)."""
        return {
            "epoch": self.epoch,
            "replans": self.replans,
            "forced_replans": self.forced_replans,
            "recuts": self.recuts,
            "cold_resets": self.cold_resets,
            "bad_plans": self.bad_plans,
            "split_layer": self.split_layer,
            "total_iters": self.total_iters,
        }

    def export_host(self) -> dict:
        """The server's host-side control-plane state as JSON scalars, for
        the serving snapshot (repro.state). The device-resident pieces
        (PlanState and the GD-iteration accumulator) travel in the
        snapshot's device tree, not here."""
        return {
            "epoch": self.epoch,
            "recuts": self.recuts,
            "cold_resets": self.cold_resets,
            "replans": self.replans,
            "forced_replans": self.forced_replans,
            "bad_plans": self.bad_plans,
            "split_layer": self.split_layer,
            "last_plan_ok": self.last_plan_ok,
            "last_replanned": self.last_replanned,
        }

    def import_host(self, state: dict, iters_acc) -> None:
        """Inverse of export_host. ``iters_acc`` is the restored device
        scalar."""
        self.epoch = int(state["epoch"])
        self.recuts = int(state["recuts"])
        self.cold_resets = int(state["cold_resets"])
        self.replans = int(state["replans"])
        self.forced_replans = int(state["forced_replans"])
        self.bad_plans = int(state["bad_plans"])
        sl = state["split_layer"]
        self.split_layer = None if sl is None else int(sl)
        ok = state["last_plan_ok"]
        self.last_plan_ok = None if ok is None else bool(ok)
        self.last_replanned = bool(state["last_replanned"])
        self._iters_acc = iters_acc

    def reset_warm(self) -> None:
        """Drop the warm-start payload: the next replan goes cold. The
        degradation ladder calls this before a degraded-stage retry --
        after a run of rejected plans the carried moments/optima are
        themselves suspect."""
        self.state = None

    def _sync_plan(self, env, plan) -> tuple[int, int]:
        """The one host sync per replan: (health, s). Guarded servers pack
        both into a single scalar in-jit (faults.guards.plan_word); the
        guard program is jitted once per server (env consts are closures,
        the plan is an operand -- no cache growth across epochs)."""
        if not self.guard_plans:
            return 0, int(host_read(plan.s, "plan_word", self.host_reads))
        if self._plan_word_fn is None:
            from repro.faults import guards
            self._plan_word_fn = jax.jit(recorded(functools.partial(
                guards.plan_word, n_sub=env.n_sub,
                p_up_max=env.radio.p_up_max_w, p_dn_max=env.radio.p_dn_max_w,
                r_max=env.comp.r_max), "plan_guard"))
        from repro.faults.guards import split_plan_word
        word = host_read(self._plan_word_fn(plan), "plan_word",
                         self.host_reads)
        return split_plan_word(int(word))

    def observe(self, env, prof=None, force: bool = False,
                hold: bool = False) -> None:
        """Advance one epoch: re-plan on schedule (or immediately when
        ``force`` is set -- the QoS monitor's trigger path), counting a
        moved s* as a re-cut. ``prof`` substitutes a measured profile (repro.online
        telemetry) as an operand of the engine's already-compiled programs;
        None plans against the engine's static profile. ``hold`` skips the
        replan outright (the ladder's backoff posture) while still
        advancing the epoch clock."""
        self.last_replanned = False
        if not hold and (force or self.epoch % self.replan_every == 0):
            prev_state = self.state
            try:
                with span("dispatch.replan"):
                    new_state = self.engine.replan(self.state, env, prof=prof)
            except WarmStateShapeError:
                # Shape change: the warm-start state no longer fits this
                # network. Reset it and fall back to a cold plan. (Other
                # ValueErrors propagate -- swallowing them would silently
                # disable warm starts forever.)
                prev_state = self.state = None
                self.cold_resets += 1
                with span("dispatch.replan"):
                    new_state = self.engine.plan(env, prof=prof)
            self.replans += 1
            self.last_replanned = True
            self.forced_replans += int(
                force and self.epoch % self.replan_every != 0)
            self._iters_acc = self._iters_acc + new_state.total_iters
            health, s = self._sync_plan(env, new_state.plan)
            if health:
                # Rung 1 of the ladder: never serve a corrupt plan. Keep
                # the last good state (warm payload included) and let the
                # ladder decide the follow-up posture.
                self.bad_plans += 1
                self.last_plan_ok = False
                self.state = prev_state
            else:
                self.last_plan_ok = True
                self.state = new_state
                if s != self.split_layer:
                    self.split_layer = s
                    self.recuts += 1
        self.epoch += 1
