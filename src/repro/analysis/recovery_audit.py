"""Durable-serving audits: crash recovery must be bit-exact, retrace-free,
and deterministically replayable.

Two executing probes over a chaos-hardened OnlineLoop (same small
configuration as fault_audit), machine-checking the ISSUE-10 acceptance
criteria:

* resume_probe -- run T epochs uninterrupted (arm A) and T epochs with a
  mid-episode crash + snapshot restore (arm B, driven by CrashSupervisor
  over a SnapshotStore). The two final serving states must agree
  leaf-for-leaf (device tree: plans, warm Adam payload, QoS rings,
  telemetry EMA, fault Markov state, PRNG key) and counter-for-counter
  (host: server + degradation-ladder state machines). Arm B's flight
  recorder is then replayed from the journal alone: the served
  (s*, health) trajectory must reproduce with no divergence.

* retrace_probe -- snapshot a warmed loop, restore it into a *fresh*
  process stand-in (new loop + engine from the same factory), warm the
  fresh programs, then run steady-state epochs (including a snapshot
  export) under planning.compile_log: nothing may trace, and the fresh
  engine's compiled-program cache must be no larger than the
  uninterrupted loop's -- restored leaves hit the exact avals the live
  programs were compiled for (StableSignature, restore edition).
"""
from __future__ import annotations

import json
import os
import tempfile

import jax
import numpy as np

from repro.analysis.report import AuditReport, Finding, merge_reports
from repro.core.types import GdConfig

# Active but moderate chaos: the ladder gets exercised across the crash
# while most epochs still serve planner output.
CHAOS = dict(link_outage_rate=0.1, fade_depth=1e-6, ap_outage_rate=0.02,
             telemetry_drop_rate=0.05, service_spike_rate=0.02)

T_EPOCHS = 18
CADENCE = 6
CRASH_AT = 14          # between cadences: restore rewinds to epoch 12


def _factory():
    from repro.core import profiles
    from repro.faults import FaultConfig, LadderConfig
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
        faults=FaultConfig(**CHAOS),
        degrade=LadderConfig(quarantine_epochs=10, baseline_after=2))


def _diff_leaves(tree_a, tree_b) -> list[str]:
    """Key-paths of leaves that differ in value, dtype, or shape."""
    flat_a = jax.tree_util.tree_flatten_with_path(tree_a)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(tree_b)[0]
    bad = []
    for (path, a), (_, b) in zip(flat_a, flat_b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype != b.dtype or a.shape != b.shape or not np.array_equal(
                a, b, equal_nan=True):
            bad.append(jax.tree_util.keystr(path))
    return bad


def resume_probe(label: str = "recovery") -> AuditReport:
    """Crash + restore vs uninterrupted: final state equal leaf-for-leaf;
    journal replay reproduces the served trajectory exactly."""
    from repro.state import (
        FlightRecorder,
        SimulatedCrash,
        SnapshotConfig,
        SnapshotStore,
        read_journal,
        replay,
    )
    from repro.state.supervisor import CrashSupervisor

    report = AuditReport(
        programs=[f"{label}:resume", f"{label}:replay"],
        rules=["bit_exact_resume", "replay_divergence"])
    key = jax.random.PRNGKey(0)
    with tempfile.TemporaryDirectory() as td:
        sup_a = CrashSupervisor(_factory)
        sup_a.run(key, T_EPOCHS)
        dev_a, host_a = sup_a.loop.serving_state()

        rec = FlightRecorder(os.path.join(td, "flight.jsonl"))
        store = SnapshotStore(
            os.path.join(td, "snaps"),
            SnapshotConfig(every=CADENCE, keep_n=2, asynchronous=False))
        armed = [True]

        def chaos(next_epoch: int) -> None:
            if next_epoch == CRASH_AT and armed[0]:
                armed[0] = False
                raise SimulatedCrash("injected mid-episode kill")

        sup_b = CrashSupervisor(_factory, store=store, recorder=rec)
        sup_b.run(key, T_EPOCHS, seed=0, chaos=chaos)
        dev_b, host_b = sup_b.loop.serving_state()
        rec.close()

        if not sup_b.restored_from or sup_b.restored_from[0] <= 0:
            report.findings.append(Finding(
                rule="bit_exact_resume", program=f"{label}:resume",
                message=("the crash arm never restored from a snapshot "
                         "(cold start instead) -- the probe is vacuous"),
                detail={"restored_from": sup_b.restored_from,
                        "cold_restarts": sup_b.cold_restarts}))
        bad = _diff_leaves(dev_a, dev_b)
        if bad:
            report.findings.append(Finding(
                rule="bit_exact_resume", program=f"{label}:resume",
                message=(f"{len(bad)} device leaves differ between the "
                         f"uninterrupted run and the crashed-and-restored "
                         f"run after {T_EPOCHS} epochs: {bad[:6]}"),
                detail={"leaves": bad}))
        if json.dumps(host_a, sort_keys=True) != json.dumps(
                host_b, sort_keys=True):
            report.findings.append(Finding(
                rule="bit_exact_resume", program=f"{label}:resume",
                message=("host control-plane state (server/ladder counters) "
                         "differs across the restore"),
                detail={"uninterrupted": host_a, "restored": host_b}))

        records, clean = read_journal(os.path.join(td, "flight.jsonl"))
        if not clean or not records:
            report.findings.append(Finding(
                rule="replay_divergence", program=f"{label}:replay",
                message="flight journal unreadable or empty",
                detail={"records": len(records), "clean": clean}))
        else:
            res = replay(records, _factory)
            if res["divergence"] is not None:
                report.findings.append(Finding(
                    rule="replay_divergence", program=f"{label}:replay",
                    message=(
                        "journal replay diverged from the recorded served "
                        f"trajectory at epoch {res['divergence']['t']}"),
                    detail=res["divergence"]))
    return report


def retrace_probe(label: str = "recovery") -> AuditReport:
    """Restore into a fresh loop must mint zero steady-state compiles and
    no extra engine cache entries beyond the uninterrupted run's."""
    from repro.obs import compile_log
    from repro.state import load_snapshot, save_snapshot

    report = AuditReport(
        programs=[f"{label}:retrace"],
        rules=["stable_signature", "cache_key_discipline"])
    key = jax.random.PRNGKey(0)
    with tempfile.TemporaryDirectory() as td:
        loop = _factory()
        loop.reset(key)
        for _ in range(2 * CADENCE):
            loop.step_epoch()
        save_snapshot(td, loop)
        cache_ref = loop.engine.cache_size()

        fresh = _factory()                 # new engine: a process restart
        fresh.reset(key)
        load_snapshot(td, fresh, 2 * CADENCE)
        for _ in range(2 * fresh.service_cfg.replan_every):  # warm programs
            fresh.step_epoch()
        with compile_log() as log:
            for _ in range(CADENCE):
                fresh.step_epoch()
            fresh.serving_state()          # the snapshot export path too
        if log:
            report.findings.append(Finding(
                rule="stable_signature", program=f"{label}:retrace",
                message=(
                    f"steady state after a snapshot restore traced {log}; "
                    "restored leaves must have the live programs' exact "
                    "avals so resume mints zero compiles"),
                detail={"compile_log": list(log)}))
        if fresh.engine.cache_size() > cache_ref:
            report.findings.append(Finding(
                rule="cache_key_discipline", program=f"{label}:retrace",
                message=(
                    f"restore grew the engine cache to "
                    f"{fresh.engine.cache_size()} entries vs {cache_ref} "
                    "uninterrupted; restored state must not mint new "
                    "compiled programs"),
                detail={"restored": fresh.engine.cache_size(),
                        "uninterrupted": cache_ref}))
    return report


def audit_recovery(label: str = "recovery") -> AuditReport:
    """The full durable-serving audit (both probes execute the loop)."""
    return merge_reports([resume_probe(label=label),
                          retrace_probe(label=label)])
