"""Engine-level audits: PlannerEngine programs and cache discipline.

Two layers:

* audit_engine -- trace-only. Pulls the engine's compiled plan/replan/
  replan_many programs via engine.program()/program_args() (jax.make_jaxpr,
  nothing executes) and runs the rule catalog over each, plus the
  cold->warm->warm signature chain via jax.eval_shape: replan fed its own
  output must trace to byte-identical avals, or every epoch recompiles
  (the PR 3 weak-type bug, now machine-checked).

* CacheKeyDiscipline / runtime_probe -- probe a LIVE engine. The former
  perturbs the engine (same shape, new kind, new shape, gate retune, cfg
  change) and asserts the compiled-program cache grows exactly when it
  should; the latter executes the replan path on a small env under
  planning.compile_log() and jax.transfer_guard to prove the exact compile
  count and zero-host-transfer dispatch dynamically.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.analysis.audit import audit
from repro.analysis.report import AuditReport, Finding, merge_reports
from repro.analysis.rules import (
    Rule,
    StableSignature,
    base_rules,
    kernel_rules,
)
from repro.core.types import NetworkEnv
from repro.kernels.noma_rates import dense_tile_count
from repro.obs import compile_log
from repro.planning.engine import PlannerEngine, stack_envs


def engine_rules(engine: PlannerEngine, env: NetworkEnv) -> list[Rule]:
    """The catalog subset an engine program must satisfy. Memory-model rules
    apply only to Pallas-backed programs: the einsum reference legitimately
    materializes the pairwise tensor (that is what it is for). The engine
    traces the dense tile schedule today (layout=None -- see the ROADMAP
    engine-threading item, whose acceptance gate is this expectation moving
    to CellLayout.n_tiles)."""
    u = int(env.g_up.shape[-3])
    rules = base_rules()
    if engine.sinr_backend != "einsum":
        rules += kernel_rules(u, expected_tiles=dense_tile_count(u, u))
    return rules


def audit_engine(
    engine: PlannerEngine,
    env: NetworkEnv,
    fleet: int = 2,
    label: str | None = None,
    rules: list[Rule] | None = None,
) -> AuditReport:
    """Audit the engine's plan, replan and replan_many programs for ``env``
    (trace-only; cheap even for paper-scale interpret-mode programs)."""
    label = label or engine.sinr_backend
    rules = engine_rules(engine, env) if rules is None else rules
    reports = []

    plan_fn = engine.program("plan", env)
    plan_args = engine.program_args("plan", env)
    reports.append(audit(plan_fn, *plan_args, rules=rules,
                         label=f"{label}:plan"))

    # replan, traced at the avals a cold plan would hand it
    cold = jax.eval_shape(plan_fn, *plan_args)
    replan_fn = engine.program("replan", env)
    replan_args = engine.program_args("replan", env, prev=cold)
    rep = audit(replan_fn, *replan_args, rules=rules,
                label=f"{label}:replan")
    # the signature chain: replan fed its own output must agree with itself
    warm = jax.eval_shape(replan_fn, *replan_args)
    warm2 = jax.eval_shape(
        replan_fn, *engine.program_args("replan", env, prev=warm))
    rep.findings.extend(
        StableSignature.compare(f"{label}:replan", warm, warm2))
    reports.append(rep)

    # the fleet path: vmapped pallas_calls prepend the batch dim to the
    # grid; the rules read the trailing dims, so the same set applies
    envs = stack_envs([env] * fleet)
    many_fn = engine.program("replan_many", envs)
    cold_many = jax.eval_shape(engine.program("plan_many", envs),
                               *engine.program_args("plan_many", envs))
    many_args = engine.program_args("replan_many", envs, prev=cold_many)
    reports.append(audit(many_fn, *many_args, rules=rules,
                         label=f"{label}:replan_many"))
    return merge_reports(reports)


class CacheKeyDiscipline:
    """Probes a live engine with config perturbations and asserts the
    compiled-program cache grows exactly when it should: reuse on identical
    dispatch, a new entry per kind / env shape / gate retune / cfg change.
    Trace-only (engine.program builds cache entries without executing).

    Probe a FRESH engine: pre-existing cache entries shift the expected
    counts. The engine's warm_rho_min and cfg are restored on exit."""

    name = "cache_key_discipline"

    def probe(self, engine: PlannerEngine, env: NetworkEnv,
              env_other_shape: NetworkEnv | None = None,
              label: str = "engine") -> AuditReport:
        report = AuditReport(programs=[f"{label}:cache"], rules=[self.name])

        def expect(step: str, want: int):
            got = engine.cache_size()
            if got != want:
                report.findings.append(Finding(
                    rule=self.name, program=f"{label}:cache",
                    message=(
                        f"after {step} the compiled-program cache holds "
                        f"{got} entries, expected {want}; the cache key "
                        "(kind, env shape, cfg, method, rounding, "
                        "warm_rho_min, warm_moment_decay) is not minting "
                        "entries exactly when dispatch semantics change"),
                    detail={"step": step, "got": got, "want": want}))

        base = engine.cache_size()
        engine.program("plan", env)
        expect("first plan program", base + 1)
        engine.program("plan", env)
        expect("repeat plan program (must reuse)", base + 1)
        engine.program("replan", env)
        expect("new kind (replan)", base + 2)
        if env_other_shape is not None:
            engine.program("plan", env_other_shape)
            expect("new env shape", base + 3)
            base += 1
        old_gate = engine.warm_rho_min
        old_cfg = engine.cfg
        try:
            engine.warm_rho_min = 0.25 if old_gate != 0.25 else 0.75
            engine.program("replan", env)
            expect("warm_rho_min retune (must recompile)", base + 3)
            engine.cfg = dataclasses.replace(
                old_cfg, max_iters=old_cfg.max_iters + 1)
            engine.program("plan", env)
            expect("cfg change (must recompile)", base + 4)
        finally:
            engine.warm_rho_min = old_gate
            engine.cfg = old_cfg
        return report


def runtime_probe(engine: PlannerEngine, env: NetworkEnv,
                  env_second: NetworkEnv | None = None,
                  label: str = "engine") -> AuditReport:
    """Execute the plan->replan->replan chain on a (small) env and check the
    dynamic invariants a trace can't: the chain compiles exactly one plan
    and one replan program -- a second env of the same shape, and the warm
    state fed back, reuse them -- and steady-state replan dispatch moves no
    host data (jax.transfer_guard). Probe a FRESH engine constructed with
    explicit weights (deriving weights per call allocates on host and would
    trip the guard by design)."""
    report = AuditReport(programs=[f"{label}:runtime"],
                         rules=["stable_signature", "no_host_transfer"])
    with compile_log() as log:
        state = engine.plan(env)
        state = engine.replan(state, env)
        state = engine.replan(state, env)
        if env_second is not None:
            s2 = engine.plan(env_second)
            s2 = engine.replan(s2, env_second)
            jax.block_until_ready(s2.plan.utility)
    jax.block_until_ready(state.plan.utility)
    if log != ["plan", "replan"]:
        report.findings.append(Finding(
            rule="stable_signature", program=f"{label}:runtime",
            message=(
                f"cold->warm->warm{'->second-env' if env_second is not None else ''} "
                f"chain traced {log}, expected ['plan', 'replan']: the warm "
                "output's avals differ from the cold ones (weak types?) or "
                "the cache key churns -- every epoch would recompile"),
            detail={"compile_log": list(log)}))
    # make_env leaves the radio/comp constants as python floats; a device-
    # resident pipeline (Scenario.env_many is jitted) has them on device
    # already, so place them once before the guarded dispatch.
    env_dev = jax.device_put(env)
    try:
        with jax.transfer_guard("disallow"):
            state = engine.replan(state, env_dev)
        jax.block_until_ready(state.plan.utility)
    except Exception as e:  # noqa: BLE001 -- the guard raises RuntimeError
        report.findings.append(Finding(
            rule="no_host_transfer", program=f"{label}:runtime",
            message=(
                "steady-state replan dispatch transferred data to/from host "
                f"under jax.transfer_guard('disallow'): {e}; keep the gate, "
                "moment decay and warm payload on device"),
            detail={"error": str(e)}))
    return report
