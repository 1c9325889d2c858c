"""Chaos serving benchmark: goodput and availability under fault injection.

Each (link-outage rate, fault mix) point runs the SAME traffic and the
SAME time-evolving scenario through three arms of repro.online.OnlineLoop:

  closed    -- measured-profile feedback AND the degradation ladder
               (plan guards, telemetry quarantine, admission shedding,
               baseline fallback): the hardened loop this PR ships
  static    -- ladder on, feedback off: how much of the resilience is the
               ladder alone, without measured-profile replans
  no_ladder -- feedback on, ladder off: PR 8's loop under the same faults

The fault mixes compose the injector catalog (repro.faults.injectors):
deep fades riding a Gilbert-Elliott link process, whole-cell AP
blackouts, telemetry dropout/corruption, and service-time spikes. The
headline metric is goodput/sec -- finite, in-deadline completions -- not
raw completions: a NaN service time "completes" in one epoch, so the
unguarded arm's completion counter is inflated by requests that never
really ran.

  PYTHONPATH=src python -m benchmarks.chaos_serve            # full sweep
  PYTHONPATH=src python -m benchmarks.chaos_serve --quick    # CI smoke
"""
from __future__ import annotations

import argparse

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

CFG = GdConfig(step_size=3e-2, eps=1e-4, max_iters=60, optimizer="adam")
STREAM = StreamConfig(arrival_rate_hz=30.0, epoch_dt_s=0.02, deadline_s=0.2)
SERVICE = ServiceConfig(edge_capacity=4, queue_depth=32, load_gain=4.0,
                        replan_every=5, max_work_epochs=200)
LADDER = LadderConfig(quarantine_epochs=15, baseline_after=2)

# The acceptance operating point: 20% of epochs in link outage.
GATE_OUTAGE = 0.2


def _mix(name: str, outage: float) -> FaultConfig:
    """Fault mixes over the injector catalog; ``outage`` scales the
    Gilbert-Elliott link process (fades mix) and rides along in full."""
    if name == "fades":
        return FaultConfig(link_outage_rate=outage, fade_depth=1e-6,
                           ap_outage_rate=0.05)
    if name == "telemetry":
        return FaultConfig(telemetry_drop_rate=0.1,
                           telemetry_spike_rate=0.05,
                           service_spike_rate=0.02)
    if name == "full":
        return FaultConfig(link_outage_rate=outage, fade_depth=1e-6,
                           ap_outage_rate=0.05, telemetry_drop_rate=0.1,
                           telemetry_spike_rate=0.05, service_spike_rate=0.02)
    raise ValueError(name)


ARMS = {
    "closed": dict(feedback=True, degrade=LADDER),
    "static": dict(feedback=False, degrade=LADDER),
    "no_ladder": dict(feedback=True, degrade=None),
}


def _episode(arm: str, faults: FaultConfig, n_epochs: int,
             seed: int) -> dict:
    eng = PlannerEngine(profiles.nin(), cfg=CFG)
    scen = Scenario(ScenarioConfig(n_users=6, n_aps=2, n_sub=3,
                                   fading_rho=0.95))
    loop = OnlineLoop(scen, eng, STREAM, SERVICE, faults=faults, **ARMS[arm])
    return loop.run(jax.random.PRNGKey(seed), n_epochs, record=True)


def run(quick: bool = False) -> None:
    outages = (GATE_OUTAGE,) if quick else (0.0, 0.1, GATE_OUTAGE)
    mixes = ("full",) if quick else ("fades", "telemetry", "full")
    n_epochs = 40 if quick else 120

    rows = []
    per_point: dict[tuple, dict] = {}
    for outage in outages:
        for mix in mixes:
            # The outage axis only moves the link process; sweeping it
            # under the telemetry-only mix would rerun identical episodes.
            if mix == "telemetry" and outage != outages[-1]:
                continue
            faults = _mix(mix, outage)
            for arm in ARMS:
                m = _episode(arm, faults, n_epochs, seed=7)
                per_point[(outage, mix, arm)] = m
                h = m["history"]
                availability = (sum(h["plan_finite"])
                                / max(len(h["plan_finite"]), 1))
                rows.append((
                    f"out{outage:g}:{mix}:{arm}:goodput_per_s",
                    m["goodput_per_s"],
                    "finite in-deadline completions/sec under fault "
                    "injection (raw completions inflate on NaN service)",
                ))

    # The claim the artifact exists to record: at the 20%-outage operating
    # point the ladder keeps goodput up and every served plan finite while
    # the unguarded loop collapses.
    gate_mix = mixes[-1]                     # "full" in both modes
    for outage in outages:
        cl = per_point[(outage, gate_mix, "closed")]
        nl = per_point[(outage, gate_mix, "no_ladder")]
        ratio = (cl["goodput_per_s"] / nl["goodput_per_s"]
                 if nl["goodput_per_s"] > 0 else float("inf"))
        rows.append((
            f"out{outage:g}:{gate_mix}:ladder_over_no_ladder", ratio,
            "goodput/sec ratio, hardened over unguarded; no-ladder served "
            f"non-finite plans: {not all(nl['history']['plan_finite'])}",
        ))

    emit("chaos_serve", rows)

    # Sanity gates (fail loudly rather than record a dead chaos loop):
    # the hardened arm must never put a non-finite plan on the air, and at
    # the 20%-outage full mix its goodput must be >= 2x the unguarded arm.
    for (outage, mix, arm), m in per_point.items():
        if arm != "no_ladder":
            assert all(m["history"]["plan_finite"]), \
                (outage, mix, arm, "non-finite plan served")
    cl = per_point[(GATE_OUTAGE, gate_mix, "closed")]
    nl = per_point[(GATE_OUTAGE, gate_mix, "no_ladder")]
    assert cl["goodput_per_s"] >= 2.0 * nl["goodput_per_s"], \
        (cl["goodput_per_s"], nl["goodput_per_s"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="gate operating point only, fewer epochs (CI smoke)")
    args = ap.parse_args()
    print("name,label,value,derived")
    run(quick=args.quick)


if __name__ == "__main__":
    main()
