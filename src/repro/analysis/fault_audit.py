"""Chaos-hardening audits: fault injection must cost zero recompiles and
the guards zero host traffic.

Three probes, mirroring online_audit's layering:

* guard_trace_audit -- trace-only. The hardened epoch program (faults
  injected, guards packed, quarantine gate traced in) and the standalone
  plan-word guard must satisfy NoHostTransfer: every check stays on
  device; the host learns about faults only through the packed health
  word it was going to sync anyway.

* chaos_loop_probe -- executing. A hardened OnlineLoop under an ACTIVE
  fault mix (deep fades, AP blackouts, telemetry corruption, service
  spikes) warmed up and then run under planning.compile_log() must trace
  nothing -- the epoch program compiles exactly once even while the
  ladder escalates, quarantines, and recovers. Swapping the fault mix
  mid-episode (set_fault_rates) must also trace nothing and grow no
  engine cache entries: fault rates are operands, never cache keys.

* plans stay finite -- the same probe asserts the served plan's utility
  is finite after the chaotic episode: the guard chain's end-to-end
  contract (no NaN plan is ever on the air).
"""
from __future__ import annotations

import jax

from repro.analysis.audit import audit
from repro.analysis.report import AuditReport, Finding, merge_reports
from repro.analysis.rules import NoHostTransfer
from repro.core.types import GdConfig

# The chaos mix the probes run under: every injector class active, at the
# acceptance criterion's 20% link-outage operating point.
CHAOS = dict(link_outage_rate=0.2, fade_depth=1e-6, ap_outage_rate=0.05,
             telemetry_drop_rate=0.1, telemetry_spike_rate=0.05,
             service_spike_rate=0.02)


def _small_loop(faults, degrade):
    from repro.core import profiles
    from repro.online import OnlineLoop, ServiceConfig, StreamConfig
    from repro.planning import PlannerEngine
    from repro.scenarios import Scenario, ScenarioConfig

    eng = PlannerEngine(profiles.nin(),
                        cfg=GdConfig(step_size=3e-2, max_iters=30,
                                     optimizer="adam"))
    scen = Scenario(ScenarioConfig(n_users=6, n_aps=2, n_sub=3,
                                   fading_rho=0.95))
    return OnlineLoop(
        scen, eng,
        StreamConfig(arrival_rate_hz=20.0, epoch_dt_s=0.02, deadline_s=0.2),
        ServiceConfig(edge_capacity=4, queue_depth=8, load_gain=4.0,
                      replan_every=3, max_work_epochs=200),
        faults=faults, degrade=degrade)


def guard_trace_audit(label: str = "faults") -> AuditReport:
    """Trace-only: the hardened epoch program and the plan-word guard move
    nothing to host inside their jaxprs."""
    import functools

    from repro.faults import FaultConfig, LadderConfig, guards

    loop = _small_loop(FaultConfig(**CHAOS), LadderConfig())
    loop.reset(jax.random.PRNGKey(0))
    rep = audit(loop._epoch, *loop.epoch_args(), rules=[NoHostTransfer()],
                label=f"{label}:epoch_injected")
    env = loop.scenario.env(loop._sc)
    word_fn = functools.partial(
        guards.plan_word, n_sub=env.n_sub, p_up_max=env.radio.p_up_max_w,
        p_dn_max=env.radio.p_dn_max_w, r_max=env.comp.r_max)
    rep2 = audit(word_fn, loop._plan, rules=[NoHostTransfer()],
                 label=f"{label}:plan_word")
    return merge_reports([rep, rep2])


def chaos_loop_probe(label: str = "faults") -> AuditReport:
    """Executing: under active injection the steady-state hardened loop
    traces nothing, a fault-mix swap mints no cache keys, and the served
    plan ends the episode finite."""
    from repro.faults import FaultConfig, LadderConfig
    from repro.obs import compile_log

    report = AuditReport(programs=[f"{label}:chaos_loop"],
                         rules=["stable_signature", "cache_key_discipline"])
    loop = _small_loop(FaultConfig(**CHAOS),
                       LadderConfig(quarantine_epochs=10, baseline_after=2))
    loop.reset(jax.random.PRNGKey(0))
    for _ in range(12):                              # warmup traces
        loop.step_epoch()
    cache_n = loop.engine.cache_size()
    with compile_log() as log:
        for _ in range(8):
            loop.step_epoch()
        # The operand-swap discipline, fault edition: a new mix re-enters
        # the same compiled epoch program.
        loop.set_fault_rates(FaultConfig(link_outage_rate=0.5,
                                         fade_depth=1e-6,
                                         telemetry_drop_rate=0.3))
        for _ in range(8):
            loop.step_epoch()
    if log:
        report.findings.append(Finding(
            rule="stable_signature", program=f"{label}:chaos_loop",
            message=(
                f"steady-state hardened loop under active fault injection "
                f"traced {log}; expected no compiles: fault draws, guards, "
                "quarantine gating and the rate swap must all reuse the "
                "one epoch program"),
            detail={"compile_log": list(log)}))
    if loop.engine.cache_size() != cache_n:
        report.findings.append(Finding(
            rule="cache_key_discipline", program=f"{label}:chaos_loop",
            message=(
                f"fault injection grew the engine's compiled-program cache "
                f"from {cache_n} to {loop.engine.cache_size()} entries; "
                "fault operands must not be cache keys"),
            detail={"before": cache_n, "after": loop.engine.cache_size()}))
    if not bool(jax.numpy.isfinite(loop._plan.utility)):
        report.findings.append(Finding(
            rule="stable_signature", program=f"{label}:chaos_loop",
            message=("the served plan ended a chaotic episode non-finite; "
                     "the guard chain let a corrupt plan on the air"),
            detail={"utility": float(loop._plan.utility)}))
    return report


def audit_faults(label: str = "faults",
                 runtime: bool = True) -> AuditReport:
    """The full chaos audit: trace-only guard rules, plus (unless
    runtime=False) the executing chaos-loop probe."""
    reports = [guard_trace_audit(label=label)]
    if runtime:
        reports.append(chaos_loop_probe(label=label))
    return merge_reports(reports)
