"""Closed-loop feedback audits: measured-profile replans and the online
serving loop's steady state.

Three probes, mirroring engine_audit's layering:

* audit_online_replan -- trace-only. The engine's replan program called
  with a *measured* profile operand (ModelProfile.like of the static one)
  must satisfy the base rules (no host transfers inside the jaxpr, stable
  signature), and its output avals must be byte-identical whether the next
  dispatch uses the measured or the static profile: the profile is an
  operand, never part of the signature.

* online_feedback_probe -- executing. plan -> replan(static) ->
  replan(measured) -> replan(measured') must compile exactly one plan and
  one replan program with zero cache growth across the profile swaps, and
  the steady-state feedback path -- telemetry update, measured-profile
  rebuild, replan dispatch -- must move nothing to host under
  jax.transfer_guard('disallow').

* online_loop_probe -- executing. A small OnlineLoop (scenario + streams +
  batching + QoS + telemetry + scheduled replans) warmed up and then run
  for several epochs under planning.compile_log() must trace nothing: the
  whole closed loop is one reused epoch program plus reused planner
  programs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.audit import audit
from repro.analysis.report import AuditReport, Finding, merge_reports
from repro.analysis.rules import StableSignature, base_rules
from repro.core.types import GdConfig, NetworkEnv
from repro.obs import compile_log
from repro.planning.engine import PlannerEngine


def _measured_like(engine: PlannerEngine, scale: float):
    """A synthetic measured profile: same structure, perturbed tables."""
    p = engine.prof
    return p.like(p.fl * scale, p.w * scale, p.m_down)


def audit_online_replan(engine: PlannerEngine, env: NetworkEnv,
                        label: str = "online") -> AuditReport:
    """Trace-only audit of the measured-profile replan path."""
    measured = _measured_like(engine, 1.5)
    rules = base_rules()
    plan_fn = engine.program("plan", env)
    cold = jax.eval_shape(plan_fn,
                          *engine.program_args("plan", env, prof=measured))
    replan_fn = engine.program("replan", env)
    args = engine.program_args("replan", env, prev=cold, prof=measured)
    rep = audit(replan_fn, *args, rules=rules,
                label=f"{label}:replan_measured")
    # Swapping back to the static profile must leave the signature alone:
    # measured feedback is an operand substitution, not a new program.
    warm_measured = jax.eval_shape(replan_fn, *args)
    warm_static = jax.eval_shape(
        replan_fn, *engine.program_args("replan", env, prev=warm_measured))
    rep.findings.extend(StableSignature.compare(
        f"{label}:replan_measured", warm_measured, warm_static))
    return rep


def online_feedback_probe(engine: PlannerEngine, env: NetworkEnv,
                          label: str = "online") -> AuditReport:
    """Execute the measured-profile feedback chain and check the dynamic
    invariants: one plan + one replan compile across static and measured
    dispatches, zero compiled-program cache growth from profile swaps, and
    a steady-state telemetry-update -> profile -> replan chain that moves
    no host data under jax.transfer_guard('disallow'). Probe a FRESH
    engine constructed with explicit weights."""
    from repro.online.telemetry import Observation, Telemetry

    report = AuditReport(programs=[f"{label}:feedback"],
                         rules=["stable_signature", "no_host_transfer",
                                "cache_key_discipline"])
    with compile_log() as log:
        state = engine.plan(env)
        state = engine.replan(state, env)            # static profile
        cache_n = engine.cache_size()
        for scale in (2.0, 3.0):
            state = engine.replan(state, env,
                                  prof=_measured_like(engine, scale))
    jax.block_until_ready(state.plan.utility)
    if log != ["plan", "replan"]:
        report.findings.append(Finding(
            rule="stable_signature", program=f"{label}:feedback",
            message=(
                f"static->measured->measured replan chain traced {log}, "
                "expected ['plan', 'replan']: a measured profile must hit "
                "the already-compiled replan program as a plain operand"),
            detail={"compile_log": list(log)}))
    if engine.cache_size() != cache_n:
        report.findings.append(Finding(
            rule="cache_key_discipline", program=f"{label}:feedback",
            message=(
                f"profile swaps grew the compiled-program cache from "
                f"{cache_n} to {engine.cache_size()} entries; the profile "
                "must not be part of the cache key"),
            detail={"before": cache_n, "after": engine.cache_size()}))

    # Steady-state feedback under the transfer guard. The telemetry update
    # and profile rebuild are warmed first (compilation may stage host
    # constants); the guarded region is the per-epoch feedback path.
    tel = Telemetry(engine.prof, env.comp, decay=0.5)
    ts = tel.init()
    f = engine.prof.n_layers
    obs = Observation(
        t_layer=jnp.full((f,), 1e-4, jnp.float32),
        t_up=jnp.float32(1e-3), rate_up=jnp.float32(1e6),
        rate_dn=jnp.float32(1e6), r_units=jnp.float32(2.0))
    s_dev = jnp.int32(max(f // 2, 1))
    ts = tel.update(ts, s_dev, obs)                  # warm the update
    state = engine.replan(state, env, prof=tel.profile(ts))
    env_dev = jax.device_put(env)
    try:
        with jax.transfer_guard("disallow"):
            ts = tel.update(ts, s_dev, obs)
            state = engine.replan(state, env_dev, prof=tel.profile(ts))
        jax.block_until_ready(state.plan.utility)
    except Exception as e:  # noqa: BLE001 -- the guard raises RuntimeError
        report.findings.append(Finding(
            rule="no_host_transfer", program=f"{label}:feedback",
            message=(
                "steady-state profile feedback (telemetry update -> "
                "measured profile -> replan) transferred data to/from host "
                f"under jax.transfer_guard('disallow'): {e}"),
            detail={"error": str(e)}))
    return report


def online_loop_probe(label: str = "online") -> AuditReport:
    """Run a small closed loop end to end: after warmup, further epochs of
    scenario + streams + batching + QoS + telemetry + scheduled replans
    must trace nothing (the epoch program logs as kind 'online_epoch')."""
    from repro.core import profiles
    from repro.online import OnlineLoop, ServiceConfig, StreamConfig
    from repro.scenarios import Scenario, ScenarioConfig

    report = AuditReport(programs=[f"{label}:loop"],
                         rules=["stable_signature"])
    eng = PlannerEngine(profiles.nin(),
                        cfg=GdConfig(step_size=3e-2, max_iters=30,
                                     optimizer="adam"))
    scen = Scenario(ScenarioConfig(n_users=6, n_aps=2, n_sub=3,
                                   fading_rho=0.95))
    loop = OnlineLoop(
        scen, eng,
        StreamConfig(arrival_rate_hz=20.0, epoch_dt_s=0.02),
        ServiceConfig(edge_capacity=4, queue_depth=8, load_gain=4.0,
                      replan_every=3))
    loop.reset(jax.random.PRNGKey(0))
    for _ in range(8):                               # warmup traces
        loop.step_epoch()
    with compile_log() as log:
        for _ in range(6):
            loop.step_epoch()
    if log:
        report.findings.append(Finding(
            rule="stable_signature", program=f"{label}:loop",
            message=(
                f"steady-state online loop traced {log}; expected no "
                "compiles: the epoch program (kind 'online_epoch') and the "
                "planner programs must be reused every epoch"),
            detail={"compile_log": list(log)}))
    return report


def audit_online(engine: PlannerEngine, env: NetworkEnv,
                 label: str = "online", runtime: bool = True) -> AuditReport:
    """The full closed-loop audit: trace-only measured-replan rules, plus
    (unless runtime=False) the executing feedback and loop probes."""
    reports = [audit_online_replan(engine, env, label=label)]
    if runtime:
        reports.append(online_feedback_probe(engine, env, label=label))
        reports.append(online_loop_probe(label=label))
    return merge_reports(reports)
