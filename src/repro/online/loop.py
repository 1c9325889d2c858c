"""The closed loop: streams -> batcher -> telemetry -> QoS -> planner.

One epoch of online serving is ONE compiled program (`kind "online_epoch"`
in planning.compile_log, XLA module ``jit_epoch``) plus one host decision
point:

  device (compiled, state donated in place):
    1. scenario.step/env      -- mobility + fading advance, env materializes
    2. streams.stream_step    -- per-user Poisson arrivals for the epoch
    3. service model          -- per-user end-to-end seconds under the
                                 *current* plan and the measured edge
                                 congestion (occupancy + backlog inflate the
                                 suffix compute), plus the per-layer
                                 Observation the telemetry folds in
    4. batcher enqueue/admit/tick -- continuous batching; completions out
    5. qos_update             -- percentiles, miss EMAs, trigger bool
    6. telemetry_update       -- measured profile EMA

  device, with a served model attached (``model=``): the edge program
  (repro.online.edge, XLA module ``jit_edge``) right after the epoch
  program, with no host read between them -- it prefills and decodes the
  admitted requests through slot caches, at the plan's split s* (an
  operand), and ends the requests whose output is complete. A request's
  edge service is then what the model did (the epochs its slot held it);
  the device and radio parts stay the delay model's. With a model, the
  epoch program completes what the edge ended before it admits, and
  prices no edge compute.

  host (per epoch):
    - read the QoS trigger (one scalar sync, the loop's decision point)
    - OnlineSplitServer.observe(env, prof=measured, force=trigger): replan
      on schedule or on trigger; its one sync is the plan word (s* and the
      plan's health)

Every host read goes through repro.obs.host_read: it runs under the span
``sync.<name>`` (per epoch ``sync.trigger``, ``sync.plan_word``,
``sync.health``, ``sync.record``, ``sync.history``; at the end
``sync.iters``, ``sync.metrics``) and is counted per name in
``host_reads``, which metrics() reports. The epoch program's call runs
under the span ``dispatch.epoch``, the edge program's under
``dispatch.edge``, the server's engine call under ``dispatch.replan``.

Because the plan enters the epoch program as a SplitPlan operand and the
measured profile enters the planner as a ModelProfile operand (same avals
every epoch -- planning._strong_typed + ModelProfile.like), a steady-state
episode compiles each program exactly once and moves no arrays to host
beyond the two decision scalars. Both properties are machine-checked:
planning.compile_log in tests, repro.analysis.online_audit in CI.

Chaos hardening (PR 9) rides the same discipline: fault injection
(repro.faults.injectors) is traced into the epoch program with the rates
as f32-scalar operands and the persistent outage masks as one more donated
state pytree; in-jit guards (repro.faults.guards) pack every health check
into ONE extra int32 synced per epoch; and the host-side degradation
ladder (repro.faults.degrade) turns that word into reject-and-hold /
quarantine / baseline-fallback / backed-off-cold-replan decisions. A loop
constructed without ``degrade=`` is byte-for-byte the PR 8 behavior.

The service model is where the closed loop earns its keep: the edge's
effective speed degrades with load (`1 + load_gain * (occupancy + backlog)
/ capacity`), which the *static* profile cannot see. The telemetry
attributes the inflated suffix times back into effective FLOPs, the
measured profile makes the planner price edge compute honestly, and s*
rises (keep more layers on device) exactly when the edge saturates --
the requests/sec-vs-concurrency benchmark (benchmarks/online_serve.py)
demonstrates the divergence from the static-profile plan.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import channel
from repro.core.types import Array, ModelProfile, SplitPlan, lam, make_weights
from repro.faults import degrade as degradelib
from repro.faults import guards, injectors
from repro.faults.degrade import DegradeLadder, EpochWatchdog, LadderConfig
from repro.faults.injectors import FaultConfig, FaultState
from repro.obs import host_read, recorded, span
from repro.runtime.serve import OnlineSplitServer
from repro.online import batcher as batcherlib
from repro.online.batcher import BatchState, ContinuousBatcher
from repro.online.edge import EdgeConfig, EdgeServer
from repro.online.qos import QosConfig, QosMonitor, QosReport, QosState, qos_update
from repro.online.streams import RequestStream, StreamConfig, StreamState, stream_step
from repro.online.telemetry import (
    Observation,
    Telemetry,
    TelemetryState,
    telemetry_update,
)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Edge service knobs. ``edge_capacity`` is the continuous batch size B;
    ``queue_depth`` the admission ring; ``load_gain`` how hard contention
    degrades the edge (effective suffix cost scales by ``1 + load_gain *
    (occupancy + backlog) / capacity`` -- 0 makes the edge ideal and the
    closed loop converges to the static plan); ``replan_every`` the
    scheduled replan cadence in epochs; ``max_work_epochs`` caps one
    request's slot occupancy."""

    edge_capacity: int = 8
    queue_depth: int = 32
    load_gain: float = 0.0
    replan_every: int = 10
    telemetry_decay: float = 0.9
    max_work_epochs: int = 1000


class EpochOut(NamedTuple):
    """Device-resident per-epoch outputs handed back to the host loop."""

    env: object          # NetworkEnv of the new epoch (the replan operand;
                         # fault-masked gains when injection is active)
    report: QosReport
    counts: Array        # (U,) arrivals this epoch
    completed: Array     # () int32 completions this epoch
    occupancy: Array     # () int32 active slots after the tick
    backlog: Array       # () int32 queued requests after the tick
    congestion: Array    # () f32 edge slowdown factor used this epoch
    health: Array        # () int32 packed health word (faults.guards)
    faulted: Array       # () int32 users in deep fade this epoch


class OnlineLoop:
    """Closed-loop serving over one time-evolving scenario.

    feedback=True plans against the telemetry's measured profile;
    feedback=False is the open-loop control (static profile), same epochs,
    same traffic -- the benchmark's comparison arm.

    ``model`` (an ArchConfig) attaches a served model at the edge, with
    ``params`` its weights or a function drawing them from a key of the
    episode at reset (None: online.edge.init_weights) and
    ``served`` its edge configuration (online.edge.EdgeConfig)."""

    def __init__(self, scenario, engine, stream_cfg: StreamConfig,
                 service_cfg: ServiceConfig = ServiceConfig(),
                 qos_cfg: QosConfig | None = None,
                 model=None, params=None, feedback: bool = True,
                 faults: FaultConfig | None = None,
                 degrade: LadderConfig | None = None,
                 served: EdgeConfig | None = None):
        u = scenario.cfg.n_users
        if model is not None and degrade is not None:
            raise ValueError("a served model runs without the degradation "
                             "ladder")
        self.edge = (None if model is None else EdgeServer(
            model, served or EdgeConfig(), stream_cfg.epoch_dt_s, params))
        self.scenario = scenario
        self.engine = engine
        self.stream_cfg = stream_cfg
        self.service_cfg = service_cfg
        self.feedback = bool(feedback)
        # Fault injection (zero-rate config is an exact identity) and the
        # degradation ladder. ``degrade`` hardens the loop: plan guarding
        # at the server, telemetry quarantine, admission shedding, QoS
        # non-finite guarding, baseline fallback, epoch watchdog. A loop
        # without it behaves exactly as PR 8 shipped -- the chaos
        # benchmark's no-ladder arm.
        self.fault_cfg = faults or FaultConfig()
        self._rates = self.fault_cfg.rates()
        self.ladder = DegradeLadder(degrade) if degrade is not None else None
        self._hardened = degrade is not None
        ladder_cfg = degrade if degrade is not None else LadderConfig()
        self._kappa_max = float(ladder_cfg.kappa_max)
        self._shed_factor = (float(ladder_cfg.shed_service_factor)
                             if self._hardened else 0.0)
        self._watchdog = (EpochWatchdog(ladder_cfg.watchdog_timeout_s)
                          if self._hardened
                          and ladder_cfg.watchdog_timeout_s > 0 else None)
        self.qos_cfg = qos_cfg or QosConfig(
            deadline_s=stream_cfg.deadline_s,
            guard_nonfinite=self._hardened)
        self.stream = RequestStream(stream_cfg, u)
        self.batcher = ContinuousBatcher(
            service_cfg.edge_capacity, service_cfg.queue_depth,
            stream_cfg.max_per_user_epoch)
        self.qos = QosMonitor(self.qos_cfg, u)
        self.telemetry = Telemetry(engine.prof, scenario.cfg.comp,
                                   service_cfg.telemetry_decay)
        self.server = OnlineSplitServer(engine,
                                        replan_every=service_cfg.replan_every,
                                        guard_plans=self._hardened)
        # host reads by name (repro.obs.host_read), shared with the server
        self.host_reads = self.server.host_reads
        # episode state (device pytrees), populated by reset()
        self._sc = self._st = self._bt = self._qs = self._tel = None
        self._fs: FaultState | None = None
        self._edge = None                    # online.edge.EdgeState
        self._plan: SplitPlan | None = None
        self._key: jax.Array | None = None
        self._fb_jit = None                  # jitted fallback plan builder
        self._plan_template = None           # engine plan avals (eval_shape)
        # durable serving (repro.state): host epoch clock, the attached
        # flight recorder, and cached engine PlanState avals by treedef kind
        self.host_epoch = 0
        self._recorder = None
        self._state_avals: dict[str, object] = {}

    # -- the compiled epoch program ---------------------------------------
    def _service_and_observation(self, env, plan: SplitPlan,
                                 congestion: Array):
        """Per-user modeled service seconds + the telemetry Observation,
        both priced at the *discrete* plan (one-hot subchannels, planned
        powers/compute units) with the measured congestion inflating the
        edge suffix. The static profile is the simulator's ground truth.
        With a served model the edge's part is the model's own, so the
        service seconds leave it out."""
        prof, comp = self.engine.prof, self.scenario.cfg.comp
        s = plan.s
        pre = prof.prefix_flops()[s]
        suf = prof.suffix_flops()[s]
        beta_up = jax.nn.one_hot(plan.sub_up, env.n_sub, dtype=env.g_up.dtype)
        beta_dn = jax.nn.one_hot(plan.sub_dn, env.n_sub, dtype=env.g_up.dtype)
        r_up = jnp.maximum(
            jnp.sum(channel.uplink_rates(env, beta_up, plan.p_up), -1), 1e-9)
        r_dn = jnp.maximum(
            jnp.sum(channel.downlink_rates(env, beta_dn, plan.p_dn), -1), 1e-9)
        speed_edge = lam(plan.r, comp) * comp.c_min_edge
        t_dev = pre / comp.c_device
        t_up = prof.w[s] / r_up
        t_edge = (suf * congestion / speed_edge if self.edge is None
                  else 0.0)
        t_dn = prof.m_down[s] / r_dn
        service = t_dev + t_up + t_edge + t_dn                     # (U,)

        f = prof.n_layers
        r_mean = jnp.mean(plan.r)
        on_device = jnp.arange(f) < s
        t_layer = jnp.where(
            on_device, prof.fl / comp.c_device,
            prof.fl * congestion / (lam(r_mean, comp) * comp.c_min_edge))
        rate_mean = jnp.mean(r_up)
        obs = Observation(t_layer=t_layer,
                          t_up=prof.w[s] / rate_mean,
                          rate_up=rate_mean,
                          rate_dn=jnp.mean(r_dn),
                          r_units=r_mean)
        return service, obs

    @functools.cached_property
    def _epoch(self):
        scen, svc = self.scenario, self.service_cfg
        stream_cfg, qos_cfg = self.stream_cfg, self.qos_cfg
        comp_consts = scen.cfg.comp
        dt = stream_cfg.epoch_dt_s
        cap = float(svc.edge_capacity)
        n_users = scen.cfg.n_users
        hardened = self._hardened
        kappa_max = self._kappa_max
        shed_thr = self._shed_factor * stream_cfg.deadline_s
        served = self.edge is not None

        def epoch(base_key, plan: SplitPlan, rates: injectors.FaultRates,
                  sc, st: StreamState, bt: BatchState, qs: QosState,
                  tel: TelemetryState, fs: FaultState):
            k_ep = jax.random.fold_in(base_key, st.epoch)
            k_sc = jax.random.fold_in(k_ep, 1)
            k_fault = jax.random.fold_in(k_ep, 2)
            sc = scen.step(k_sc, sc)
            env = scen.env(sc)
            # Faults realize before anything observes the epoch: the masked
            # gains ARE this epoch's channel, for service and replans alike.
            fs, draw = injectors.fault_step(rates, k_fault, fs)
            env = injectors.apply_env_faults(env, draw, rates)
            st, counts = stream_step(stream_cfg, n_users, base_key, st)
            # Congestion from the load the edge is already carrying when
            # this epoch's work lands.
            load = (batcherlib.occupancy(bt) + batcherlib.backlog(bt)
                    ).astype(jnp.float32)
            congestion = 1.0 + svc.load_gain * load / cap
            service, obs = self._service_and_observation(env, plan,
                                                         congestion)
            service = injectors.spike_service(service, draw)
            obs = injectors.corrupt_observation(obs, draw, rates)
            work = jnp.clip(jnp.ceil(service / dt).astype(jnp.int32), 1,
                            svc.max_work_epochs)
            now = st.epoch.astype(jnp.float32) * dt
            if served:
                # The edge program ended these requests (work 0) last
                # epoch; their slots free before this epoch's admissions,
                # which the edge will serve until it ends them in turn.
                bt, comps = batcherlib.tick(bt, advance=False)
                work = jnp.ones_like(work)
            if hardened and shed_thr > 0:
                # Admission shedding: a user whose modeled service blows
                # past the deadline by the shed factor (deep fade, AP
                # blackout) would jam a batch slot for max_work_epochs --
                # drop its arrivals (and queued heads, in admit) instead of
                # starving the healthy users behind it.
                doomed = (service > shed_thr) | ~jnp.isfinite(service)
                shed_n = jnp.sum(jnp.where(doomed, counts, 0)
                                 ).astype(jnp.int32)
                bt = batcherlib.enqueue(bt, jnp.where(doomed, 0, counts),
                                        now, stream_cfg.max_per_user_epoch)
                bt = bt._replace(shed=bt.shed + shed_n)
                bt = batcherlib.admit(bt, now, service, work, shed=doomed)
            else:
                bt = batcherlib.enqueue(bt, counts, now,
                                        stream_cfg.max_per_user_epoch)
                bt = batcherlib.admit(bt, now, service, work)
            if not served:
                bt, comps = batcherlib.tick(bt)
            qs, report = qos_update(qos_cfg, qs, comps)
            tel_new = telemetry_update(comp_consts, svc.telemetry_decay,
                                       self.engine.prof.fl, tel, plan.s, obs)
            obs_word = guards.observation_health(obs)
            if hardened:
                # Rung 2, in-jit half: a corrupt observation never enters
                # the EMA -- the telemetry state holds, the host-side
                # quarantine decides when to trust the profile again.
                tel = guards.tree_select(obs_word == 0, tel_new, tel)
            else:
                tel = tel_new
            health = guards.pack_health(
                obs_word, guards.service_health(service),
                guards.telemetry_health(tel, kappa_max))
            out = EpochOut(env=env, report=report, counts=counts,
                           completed=jnp.sum(comps.valid).astype(jnp.int32),
                           occupancy=batcherlib.occupancy(bt),
                           backlog=batcherlib.backlog(bt),
                           congestion=congestion,
                           health=health,
                           faulted=jnp.sum(draw.link_down
                                           ).astype(jnp.int32))
            return sc, st, bt, qs, tel, fs, out

        # recorded: each trace of the epoch program logs "online_epoch" to
        # planning.compile_log sinks -- the steady-state compile-once
        # property is asserted against this, exactly like the engine kinds
        # -- and it lowers as module jit_epoch.
        # The fault rates (arg 2) are NOT donated: the same operand tuple
        # re-enters every epoch (and swapping it is how the benchmark
        # sweeps outage rates without retracing).
        return jax.jit(recorded(epoch, "online_epoch", name="epoch"),
                       donate_argnums=(3, 4, 5, 6, 7, 8))

    # -- episode driving ---------------------------------------------------
    def set_fault_rates(self, cfg: FaultConfig) -> None:
        """Swap the fault mix mid-episode. The rates are operands of the
        compiled epoch program (same avals for every config), so this never
        retraces -- the chaos benchmark's outage-rate sweep is this call.
        With a flight recorder attached, the swap is journaled (it is host
        input the deterministic replay cannot re-derive)."""
        self.fault_cfg = cfg
        self._rates = cfg.rates()
        if self._recorder is not None:
            self._recorder.record_rates(self.host_epoch,
                                        dataclasses.asdict(cfg))

    def attach_recorder(self, recorder) -> None:
        """Attach a repro.state.FlightRecorder: every epoch's host trace
        (the packed plan/health word, the QoS trigger, the ladder stage)
        and every fault-rate swap are journaled for deterministic replay.
        Recording syncs s* per epoch (one extra scalar beyond the loop's
        decision reads); pass None to detach."""
        self._recorder = recorder

    def _fallback(self, env) -> SplitPlan:
        """The ladder's rung-3 plan, cast to engine-plan avals (so serving
        it never retraces the epoch program) by a jitted program that is
        warmed at reset -- a mid-episode escalation traces nothing."""
        if self._fb_jit is None:
            w = (self.engine.weights if self.engine.weights is not None
                 else make_weights(self.scenario.cfg.n_users))
            mode = self.ladder.cfg.fallback
            template = self._plan_template
            prof = self.engine.prof

            def fb(env):
                return degradelib.fallback_plan(env, prof, w,
                                                template=template, mode=mode)

            self._fb_jit = jax.jit(recorded(fb, "fallback_plan"))
        return self._fb_jit(env)

    def reset(self, key: jax.Array) -> None:
        """Initialize scenario/stream/batch/QoS/telemetry/fault state and
        take the initial (cold) plan. The telemetry starts at the static
        profile, so feedback and static arms are identical until load
        appears. Hardened loops also warm the fallback-plan program here,
        so a mid-episode ladder escalation traces nothing."""
        k_sc, k_st, self._key = jax.random.split(key, 3)
        self.host_epoch = 0
        self._state_avals.clear()
        self._sc = self.scenario.init(k_sc)
        self._st = self.stream.init(k_st)
        self._bt = self.batcher.init()
        self._qs = self.qos.init()
        self._tel = self.telemetry.init()
        self._fs = injectors.init_fault_state(self.scenario.cfg.n_users,
                                              self.scenario.cfg.n_aps)
        if self.edge is not None:
            self._edge = self.edge.init(jax.random.fold_in(key, 7))
        env0 = self.scenario.env(self._sc)
        if self._hardened:
            # Engine-plan avals without executing the solver: the fallback
            # template (and the epoch program's stability across the
            # planner -> fallback -> planner switches) comes from
            # eval_shape of the cold-plan program.
            plan_fn = self.engine.program("plan", env0)
            shapes = jax.eval_shape(
                plan_fn, *self.engine.program_args("plan", env0))
            self._state_avals["cold"] = shapes
            self._plan_template = shapes.plan
        self.server.observe(env0)          # epoch 0 is always scheduled
        if self.ladder is not None:
            self.ladder.post_replan(self.server.last_plan_ok,
                                    self.server.last_replanned)
        if self.server.state is not None:
            self._plan = self.server.state.plan
            if self._hardened:
                jax.block_until_ready(self._fallback(env0).utility)  # warm
        else:
            # The very first plan was rejected by the guard: serve the
            # baseline fallback until the ladder recovers a real plan.
            self._plan = self._fallback(env0)
        if self.feedback:
            self.measured_profile()        # warm the profile rebuild

    @property
    def episode_key(self) -> jax.Array:
        """The episode's base key (set by reset)."""
        return self._key

    @property
    def edge_state(self):
        """The served model's slot state (online.edge.EdgeState), or None."""
        return self._edge

    def measured_profile(self) -> ModelProfile:
        """The telemetry's current measured profile (a planner operand)."""
        return self.telemetry.profile(self._tel)

    def epoch_args(self) -> tuple:
        """The epoch program's current operand tuple (post-reset), for
        trace-only audits (analysis.fault_audit)."""
        return (self._key, self._plan, self._rates, self._sc, self._st,
                self._bt, self._qs, self._tel, self._fs)

    # -- durable serving (repro.state hooks) -------------------------------
    def _plan_state_avals(self, kind: str):
        """Engine PlanState avals by treedef kind: "cold"/"none" states come
        from the plan program (warm_rho is None there), "warm" from replan.
        jax.eval_shape only -- no solver executes. Cached per episode."""
        want = "cold" if kind == "none" else kind
        if want not in self._state_avals:
            env0 = self.scenario.env(self._sc)
            if "cold" not in self._state_avals:
                self._state_avals["cold"] = jax.eval_shape(
                    self.engine.program("plan", env0),
                    *self.engine.program_args("plan", env0))
            if want == "warm":
                self._state_avals["warm"] = jax.eval_shape(
                    self.engine.program("replan", env0),
                    *self.engine.program_args(
                        "replan", env0, prev=self._state_avals["cold"]))
        return self._state_avals[want]

    def serving_state(self) -> tuple[dict, dict]:
        """The loop's complete episode state as ``(device_tree, host)``.

        ``device_tree`` holds every device-resident pytree the epoch program
        and the planner thread through epochs (PRNG base key, served plan,
        fault rates, scenario/stream/batch/QoS/telemetry/fault state, the
        server's PlanState + GD-iteration accumulator). ``host`` holds the
        JSON-scalar control-plane state (epoch clock, server counters,
        ladder state machine). Restoring both via load_serving_state makes
        the next epoch bit-identical to the uninterrupted run: all per-epoch
        randomness is fold_in(base_key, epoch), and every host decision is a
        deterministic function of the restored counters.

        A rejected-first-plan server (state None) snapshots a zero-filled
        cold-shaped PlanState with ``plan_state_kind == "none"`` so the
        device treedef stays constant across snapshot kinds."""
        if self._st is None:
            raise RuntimeError("serving_state() before reset()")
        if self.edge is not None:
            raise NotImplementedError("a served model's slot caches are not "
                                      "part of the serving state")
        if self.server.state is not None:
            ps = self.server.state
            kind = "warm" if ps.warm_rho is not None else "cold"
        else:
            kind = "none"
            ps = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                              self._plan_state_avals("none"))
        device = {
            "key": self._key, "plan": self._plan, "rates": self._rates,
            "sc": self._sc, "st": self._st, "bt": self._bt, "qs": self._qs,
            "tel": self._tel, "fs": self._fs,
            "server_state": ps, "iters_acc": self.server._iters_acc,
        }
        host = {
            "host_epoch": self.host_epoch,
            "plan_state_kind": kind,
            "server": self.server.export_host(),
            "ladder": (self.ladder.export_state()
                       if self.ladder is not None else None),
        }
        return device, host

    def state_template(self, kind: str):
        """Avals (ShapeDtypeStructs) of serving_state()'s device tree for a
        snapshot whose PlanState treedef kind was ``kind`` -- the
        restore-side validation target. Built from the live episode state
        plus eval_shape of the engine programs, so any stored leaf that
        fails to match these avals is exactly a leaf that would have
        retraced the (already compiled) epoch or planner programs."""
        device, _ = self.serving_state()
        device["server_state"] = self._plan_state_avals(kind)
        return jax.tree.map(
            lambda x: (x if isinstance(x, jax.ShapeDtypeStruct)
                       else jax.ShapeDtypeStruct(jnp.shape(x),
                                                 jnp.result_type(x))),
            device)

    def load_serving_state(self, device: dict, host: dict) -> None:
        """Overwrite the episode with a restored serving_state(). The loop
        must be reset() first (the compiled programs, templates and warmed
        fallback come from reset; the snapshot supplies only state)."""
        if self._st is None:
            raise RuntimeError("load_serving_state() before reset()")
        self._key = device["key"]
        self._plan = device["plan"]
        self._rates = device["rates"]
        self._sc = device["sc"]
        self._st = device["st"]
        self._bt = device["bt"]
        self._qs = device["qs"]
        self._tel = device["tel"]
        self._fs = device["fs"]
        self.host_epoch = int(host["host_epoch"])
        self.server.import_host(host["server"], device["iters_acc"])
        self.server.state = (None if host["plan_state_kind"] == "none"
                             else device["server_state"])
        if self.ladder is not None and host["ladder"] is not None:
            self.ladder.import_state(host["ladder"])

    def config_fingerprint(self) -> str:
        """Hash of everything that shapes the compiled programs and the host
        policy. A snapshot taken under one configuration must not restore
        into a loop built under another (the restored leaves would hit
        different programs); fault *rates* are excluded -- they are operands
        and travel inside the snapshot."""
        parts = repr((self.scenario.cfg, self.stream_cfg, self.service_cfg,
                      self.qos_cfg, self.engine.cfg, self.engine.method,
                      self.engine.rounding, self.engine.warm_rho_min,
                      self.engine.warm_moment_decay,
                      self.ladder.cfg if self.ladder is not None else None,
                      self.feedback))
        return hashlib.sha256(parts.encode()).hexdigest()[:16]

    def _step_epoch_inner(self) -> tuple[EpochOut, bool]:
        with span("dispatch.epoch"):
            (self._sc, self._st, self._bt, self._qs, self._tel, self._fs,
             out) = self._epoch(self._key, self._plan, self._rates, self._sc,
                                self._st, self._bt, self._qs, self._tel,
                                self._fs)
        if self.edge is not None:
            with span("dispatch.edge"):
                self._edge, self._bt, health = self.edge.step(
                    self._edge, self._bt, self._plan.s, self._key,
                    out.health)
            out = out._replace(health=health)
        # the per-epoch decision sync
        trigger = bool(host_read(out.report.trigger, "trigger",
                                 self.host_reads))
        if self.ladder is None:
            prof = self.measured_profile() if self.feedback else None
            self.server.observe(out.env, prof=prof, force=trigger)
            self._plan = self.server.state.plan
            return out, trigger
        # Hardened path: one extra scalar (the packed health word) feeds
        # the ladder; the ladder shapes the replan and the served plan.
        dec = self.ladder.pre_replan(
            int(host_read(out.health, "health", self.host_reads)))
        if dec.force_cold:
            self.server.reset_warm()
        prof = (self.measured_profile()
                if self.feedback and dec.use_measured else None)
        self.server.observe(out.env, prof=prof,
                            force=trigger or dec.force, hold=dec.hold)
        self.ladder.post_replan(self.server.last_plan_ok,
                                self.server.last_replanned)
        if self.server.state is None or self.ladder.serve_fallback:
            self._plan = self._fallback(out.env)
        else:
            self._plan = self.server.state.plan
        return out, trigger

    def step_epoch(self) -> tuple[EpochOut, bool]:
        """One closed-loop epoch. Returns the device-resident EpochOut and
        whether a QoS trigger forced an off-schedule replan (the host-side
        decision read). Hardened loops run under the epoch watchdog: an
        overrun keeps its result (state stays consistent) but escalates
        the ladder. Advances the host epoch clock and, with a flight
        recorder attached, journals the epoch's host trace."""
        if self._watchdog is None:
            out, trigger = self._step_epoch_inner()
        else:
            (out, trigger), fired = self._watchdog.guard(
                self._step_epoch_inner)
            if fired and self.ladder is not None:
                self.ladder.on_timeout()
        self.host_epoch += 1
        if self._recorder is not None:
            s, health = host_read((self._plan.s, out.health), "record",
                                  self.host_reads)
            self._recorder.record_epoch(
                self.host_epoch, s=int(s), health=int(health),
                trigger=bool(trigger),
                stage=self.ladder.stage if self.ladder is not None
                else "normal")
        return out, trigger

    def run(self, key: jax.Array, n_epochs: int,
            record: bool = False) -> dict:
        """Drive a fresh episode for ``n_epochs``. With record=True, per-
        epoch scalars are pulled to host for analysis (benchmark mode; the
        steady-state no-transfer property is audited with record=False).
        Returns summary metrics (and, when recording, the trajectory)."""
        self.reset(key)
        hist = self.history_init()
        for _ in range(n_epochs):
            out, trigger = self.step_epoch()
            if record:
                self.record_history(hist, out, trigger)
        m = self.metrics()
        if record:
            m["history"] = hist
        return m

    def history_init(self) -> dict[str, list]:
        """An empty per-epoch trajectory dict (run()'s record=True columns).
        The crash supervisor shares these helpers so a recovered episode's
        history is column-compatible with an uninterrupted run's."""
        return {k: [] for k in
                ("s", "p50", "p95", "miss_rate", "occupancy", "backlog",
                 "completed", "congestion", "trigger", "health", "faulted",
                 "plan_finite", "stage")}

    def record_history(self, hist: dict[str, list], out: EpochOut,
                       trigger: bool) -> None:
        """Append one epoch's host-visible scalars to ``hist`` (one host
        read)."""
        rep = out.report
        v = host_read({
            "s": self._plan.s, "p50": rep.p50, "p95": rep.p95,
            "miss_rate": rep.miss_rate, "occupancy": out.occupancy,
            "backlog": out.backlog, "completed": out.completed,
            "congestion": out.congestion, "health": out.health,
            "faulted": out.faulted,
            # Was the plan on the air this epoch finite? The chaos
            # benchmark's "no NaN plans served" gate reads this.
            "plan_finite": jnp.isfinite(self._plan.utility),
        }, "history", self.host_reads)
        for k in ("s", "occupancy", "backlog", "completed", "health",
                  "faulted"):
            hist[k].append(int(v[k]))
        for k in ("p50", "p95", "miss_rate", "congestion"):
            hist[k].append(float(v[k]))
        hist["plan_finite"].append(bool(v["plan_finite"]))
        hist["trigger"].append(bool(trigger))
        hist["stage"].append(self.ladder.stage if self.ladder
                             else "normal")

    def _edge_counters(self) -> dict:
        """The served model's episode counters (none without a model)."""
        if self.edge is None:
            return {}
        c = self._edge.counters
        return {"edge_prefill_tokens": c.prefill_tokens,
                "edge_prefill_chunks": c.prefill_chunks,
                "edge_decode_tokens": c.decode_tokens,
                "edge_decode_steps": c.decode_steps,
                "edge_requests_done": c.requests_done,
                "moe_local_assignments": c.moe_local,
                "attn_query_keys": c.attn_pairs,
                "attn_keys_read": c.attn_keys}

    def metrics(self) -> dict:
        """End-of-episode summary, with ``host_reads``: the loop's and the
        server's host reads so far, by name. Syncs the episode counters
        once."""
        m = dict(self.server.metrics())
        counters = host_read({
            "offered": self._st.offered, "completed": self._bt.completed,
            "dropped": self._bt.dropped, "shed": self._bt.shed,
            "served": self._qs.served, "deadline_missed": self._qs.missed,
            "goodput": self._qs.good, "qos_triggers": self._qs.triggers,
            "epochs": self._st.epoch,
            **self._edge_counters(),
        }, "metrics", self.host_reads)
        m.update({k: int(v) for k, v in counters.items()})
        m["duration_s"] = m["epochs"] * self.stream_cfg.epoch_dt_s
        m["host_reads"] = dict(self.host_reads)
        dur = max(m["duration_s"], 1e-9)
        m["requests_per_s"] = m["completed"] / dur
        m["offered_per_s"] = m["offered"] / dur
        m["goodput_per_s"] = m["goodput"] / dur
        if self.ladder is not None:
            m.update(self.ladder.metrics())
        return m
