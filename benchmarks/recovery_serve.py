"""Recovery benchmark: goodput and recovery cost under crashes.

Each arm drives the SAME chaos-hardened serving loop (same traffic, same
faults, same seed) through repro.state.CrashSupervisor with crashes
injected at fixed epochs:

  durable        -- SnapshotStore on a fixed cadence: a crash resumes
                    bit-exactly from the newest snapshot, re-executing at
                    most ``cadence`` epochs
  no_checkpoint  -- store=None: every crash is the PR-9 ladder cold start
                    from epoch 0, re-executing the whole prefix

Because resume is bit-exact, both arms end an episode with identical
*simulated* metrics -- what crashes cost is re-executed work and wall
clock. The headline rows are therefore goodput per WALL second (finite
in-deadline completions divided by elapsed time including recovery) and
``recovery_epochs`` (epochs re-executed after crashes). A third pair of
crash-free arms measures the snapshot tax: wall-time overhead % of
cutting snapshots on cadence vs running bare.

  PYTHONPATH=src python -m benchmarks.recovery_serve            # full
  PYTHONPATH=src python -m benchmarks.recovery_serve --quick    # CI smoke
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax

from benchmarks.paper_common import emit
from repro.core import profiles
from repro.core.types import GdConfig
from repro.online import (
    FaultConfig,
    LadderConfig,
    OnlineLoop,
    ServiceConfig,
    StreamConfig,
)
from repro.planning import PlannerEngine
from repro.scenarios import Scenario, ScenarioConfig
from repro.state import SimulatedCrash, SnapshotConfig, SnapshotStore
from repro.state.supervisor import CrashSupervisor

CFG = GdConfig(step_size=3e-2, eps=1e-4, max_iters=60, optimizer="adam")
STREAM = StreamConfig(arrival_rate_hz=30.0, epoch_dt_s=0.02, deadline_s=0.2)
SERVICE = ServiceConfig(edge_capacity=4, queue_depth=32, load_gain=4.0,
                        replan_every=5, max_work_epochs=200)
LADDER = LadderConfig(quarantine_epochs=15, baseline_after=2)
FAULTS = FaultConfig(link_outage_rate=0.1, fade_depth=1e-6,
                     ap_outage_rate=0.02, telemetry_drop_rate=0.05,
                     service_spike_rate=0.02)
SEED = 7


def _factory() -> OnlineLoop:
    eng = PlannerEngine(profiles.nin(), cfg=CFG)
    scen = Scenario(ScenarioConfig(n_users=6, n_aps=2, n_sub=3,
                                   fading_rho=0.95))
    return OnlineLoop(scen, eng, STREAM, SERVICE, faults=FAULTS,
                      degrade=LADDER)


def _episode(n_epochs: int, crashes: tuple[int, ...], cadence: int,
             checkpointed: bool, tmpdir: str) -> dict:
    store = None
    if checkpointed:
        store = SnapshotStore(
            os.path.join(tmpdir, f"snaps_{len(crashes)}"),
            SnapshotConfig(every=cadence, keep_n=3, asynchronous=True))
    pending = set(crashes)

    def chaos(next_epoch: int) -> None:
        if next_epoch in pending:
            pending.discard(next_epoch)
            raise SimulatedCrash(f"injected kill before epoch {next_epoch}")

    sup = CrashSupervisor(_factory, store=store,
                          max_restarts=len(crashes) + 2)
    t0 = time.perf_counter()
    m = sup.run(jax.random.PRNGKey(SEED), n_epochs, record=True,
                chaos=chaos if crashes else None)
    m["wall_s"] = time.perf_counter() - t0
    if store is not None:
        store.wait()
    return m


def run(quick: bool = False) -> None:
    n_epochs = 40 if quick else 120
    cadence = 8 if quick else 10
    crashes = (25,) if quick else (50, 95)

    rows = []
    results: dict[str, dict] = {}
    with tempfile.TemporaryDirectory() as td:
        for arm, checkpointed in (("durable", True), ("no_checkpoint", False)):
            m = _episode(n_epochs, crashes, cadence, checkpointed, td)
            results[arm] = m
            wall = max(m["wall_s"], 1e-9)
            rows.append((
                f"{arm}:goodput_per_wall_s", m["goodput"] / wall,
                "finite in-deadline completions per wall-clock second, "
                "crash recovery included (at smoke scale restart "
                "recompilation dominates the wall; recovery_epochs is the "
                "scale-free recovery cost)"))
            rows.append((
                f"{arm}:recovery_epochs", m["supervisor_recovery_epochs"],
                "epochs re-executed after crashes (durable: bounded by the "
                "snapshot cadence; no-checkpoint: the whole prefix)"))

        # Snapshot tax: crash-free wall time, snapshotting vs bare.
        base = _episode(n_epochs, (), cadence, checkpointed=False, tmpdir=td)
        snap = _episode(n_epochs, (), cadence, checkpointed=True, tmpdir=td)
        overhead = 100.0 * (snap["wall_s"] - base["wall_s"]) \
            / max(base["wall_s"], 1e-9)
        rows.append((
            "snapshot_overhead_pct", overhead,
            f"wall-time cost of async snapshots every {cadence} epochs, "
            "zero crashes"))

    dur, noc = results["durable"], results["no_checkpoint"]
    saved = (noc["supervisor_recovery_epochs"]
             - dur["supervisor_recovery_epochs"])
    rows.append((
        "recovery_epochs_saved", saved,
        "re-executed epochs avoided by durable snapshots across the crash "
        "schedule"))

    emit("recovery_serve", rows)

    # Sanity gates: recovery must actually recover (all crashes survived,
    # full epoch count served, every served plan finite), and snapshots
    # must beat cold restarts on re-executed work.
    for arm, m in results.items():
        assert m["restarts"] == len(crashes), (arm, m["restarts"])
        assert m["epochs"] == n_epochs, (arm, m["epochs"])
        assert all(m["history"]["plan_finite"]), (arm, "non-finite plan")
    assert dur["supervisor_recovery_epochs"] \
        < noc["supervisor_recovery_epochs"], (dur, noc)
    # Bit-exact resume means both arms end with identical simulated
    # metrics -- crashes cost wall clock, never correctness.
    assert dur["goodput"] == noc["goodput"], (dur["goodput"], noc["goodput"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="one crash, fewer epochs (CI smoke)")
    args = ap.parse_args()
    print("name,label,value,derived")
    run(quick=args.quick)


if __name__ == "__main__":
    main()
