#!/usr/bin/env python3
"""Chip smoke test: the system's main path on a TPU, at deployment scale.

    python chip_smoke.py             # one chip: planner, online loop, split serve
    python chip_smoke.py --chips 4   # four chips: sharded fleet planning only

Phases, in order, on one chip:

  planner  PlannerEngine(vgg16, sinr_backend="pallas"): a cold plan and a
           warm replan of the paper's Sec. VI cell (U=1250 users, N=16 APs,
           M=250 subchannels), checked against the einsum engine (float32
           contractions) on the same env: equal split layer s*, utility
           within 1e-4 relative, every split's utility within 1e-4
           relative; and the relaxed utility Gamma_s of one transmitting
           split and its gradient, kernels vs that reference, at the cold
           start and at the warm replan's optimum.
  online   OnlineLoop over a slow-fading Scenario of the same cell with the
           same engine, 4 epochs (replan every epoch): the health word is 0
           in every epoch and nothing compiles after epoch 1.
  serve    repro.launch.serve for qwen1.5-0.5b at full width (4 requests x
           64 tokens, 4 new tokens); edge_fn(device_fn(tokens)) logits match
           Model.forward on the same params to bf16 tolerance, at the
           planned split and at the middle layer (activations cross there).

With --chips 4 only the fleet phase runs: a 4-scenario fleet at the same
scale planned cold, then warm, through PlannerEngine(mesh=fleet_mesh())
-> replan_many over shard_fleet, and compared member by member with the
same fleet replan_many'd on one device (s*, utility and every split's
utility per member), with Gamma_s and its gradient at each epoch's
optimum through shard_map over the fleet mesh vs one device; it prints
which chip holds each member's plan.

Each phase prints one line with its wall time, compile time (trace, lower
and compile, summed from jax.monitoring), persistent-cache hits and the
device's peak bytes in use. The script exits non-zero if JAX finds no TPU
(before any phase) or if any phase fails; on success the last line of
stdout is {"ok": true, "device": {"platform", "kind", "count"}}. Weights
and channels are random, made from --seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The paper's Sec. VI cell.
N_USERS, N_APS, N_SUB = 1250, 16, 250
# Li-GD iterations per split point: bounds the solve (25 vgg16 split points
# of projected Adam; a pallas gradient step on the dense tile list takes
# ~0.2 s on v5e) so the whole script stays within minutes; the check is
# plan agreement with the einsum reference at the same budget, not
# convergence.
GD_ITERS = 2
BACKEND = "pallas"
ONLINE_EPOCHS = 4
FLEET = 4
# The fleet phase's one-device reference runs all FLEET members on one chip;
# a smaller budget keeps that four-chip phase short.
FLEET_GD_ITERS = 1
SLOW_FADING_RHO = 0.99
SERVE_ARGS = ["--arch", "qwen1.5-0.5b", "--requests", "4", "--seq", "64",
              "--new-tokens", "4"]
UTILITY_RTOL = 1e-4
GRAD_RTOL = 1e-3          # relative to the largest |gradient| entry
# The einsum reference runs its contractions in float32: at TPU's default
# matmul precision they round their inputs to bf16.
REF_PRECISION = "highest"
# A split point that transmits (s < F), for the direct utility check.
GAMMA_SPLIT = 5
LOGIT_TOL = 2e-2          # bf16: relative to the largest |logit|

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Meter:
    """Compile seconds and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event in _COMPILE_EVENTS:
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _rel(got, want) -> float:
    """Largest |got - want| over the largest |want| (one scalar for a
    whole array, so near-zero entries do not blow it up)."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _compare_plans(tag: str, got, want) -> bool:
    """PlanStates agree: s* equal, the utility and every split's utility
    within UTILITY_RTOL. The per-split optima and the rounded subchannels
    are printed, not limited: where the utility is flat in beta (at s = F
    nothing transmits) they are not unique, and float32 rounding that
    differs between two programs moves them freely."""
    import jax
    import numpy as np
    diff = np.max(np.stack([
        np.max(np.abs(np.asarray(a) - np.asarray(b)).reshape(a.shape[0], -1),
               axis=1)
        for a, b in zip(jax.tree.leaves(got.norms), jax.tree.leaves(want.norms))
    ]), axis=0)                                   # (F+1,) per split
    got, want = got.plan, want.plan
    s_got, s_want = int(got.s), int(want.s)
    rel = _rel(got.utility, want.utility)
    rel_split = max(_rel(a, b) for a, b in zip(
        np.asarray(got.per_layer_utility), np.asarray(want.per_layer_utility)))
    diff_up = float(np.mean(np.asarray(got.sub_up) != np.asarray(want.sub_up)))
    diff_dn = float(np.mean(np.asarray(got.sub_dn) != np.asarray(want.sub_dn)))
    ok = (s_got == s_want and rel <= UTILITY_RTOL and rel_split <= UTILITY_RTOL
          and bool(np.all(np.isfinite(np.asarray(got.per_layer_utility)))))
    print(f"  {tag}: s*={s_got} (ref {s_want}) utility={float(got.utility)!r} "
          f"(ref {float(want.utility)!r}) rel={rel:.3e} "
          f"per_split_utility_rel={rel_split:.3e} | optima max_abs_diff "
          f"{float(diff.max()):.3e} at split {int(diff.argmax())}, "
          f"transmitting splits {float(diff[:-1].max()):.3e}; "
          f"sub_up_differs={diff_up:.4f} sub_dn_differs={diff_dn:.4f} "
          f"{'ok' if ok else 'MISMATCH'}", flush=True)
    return ok


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _gamma_vg(w, backend: str):
    """(env, normalized point) -> (Gamma_s, its gradient) for the
    transmitting split GAMMA_SPLIT: the relaxed utility (eq. 22) reaches
    the SINR path directly, which a plan's utility does not when s* keeps
    every layer on the device."""
    import jax
    import jax.numpy as jnp
    from repro.core import li_gd, profiles
    from repro.core.utility import utility

    prof = profiles.vgg16()

    def gamma(env, norm):
        return utility(env, prof, jnp.int32(GAMMA_SPLIT),
                       li_gd.to_physical(norm, env), w, backend=backend)
    return jax.value_and_grad(gamma, argnums=1)


def _compare_gamma(tag: str, got, want) -> bool:
    """Gamma_s within UTILITY_RTOL and its gradient within GRAD_RTOL."""
    import jax
    import numpy as np
    rel = _rel(got[0], want[0])
    grad = max(_rel(a, b) for a, b in zip(jax.tree.leaves(got[1]),
                                          jax.tree.leaves(want[1])))
    ok = rel <= UTILITY_RTOL and grad <= GRAD_RTOL and all(
        bool(np.all(np.isfinite(np.asarray(x)))) for x in jax.tree.leaves(got))
    print(f"  {tag}: gamma[s={GAMMA_SPLIT}]={float(got[0])!r} "
          f"(ref {float(want[0])!r}) rel={rel:.3e} grad_rel={grad:.3e} "
          f"{'ok' if ok else 'MISMATCH'}", flush=True)
    return ok


def phase_planner(ctx: dict) -> bool:
    import jax
    from repro.core import GdConfig, li_gd, make_env, make_weights, profiles
    from repro.planning import PlannerEngine

    env = make_env(jax.random.PRNGKey(ctx["seed"]), N_USERS, N_APS, N_SUB)
    w = make_weights(N_USERS)
    cfg = GdConfig(max_iters=GD_ITERS, optimizer="adam")
    pal = PlannerEngine(profiles.vgg16(), weights=w, cfg=cfg,
                        sinr_backend=BACKEND)
    ein = PlannerEngine(profiles.vgg16(), weights=w, cfg=cfg)
    ctx["engine"] = pal
    runs = {}
    for name, eng, prec in ((BACKEND, pal, None),
                            ("einsum", ein, REF_PRECISION)):
        with jax.default_matmul_precision(prec):
            cold, t_cold = _timed(lambda: eng.plan(env))
            warm, t_warm = _timed(lambda: eng.replan(cold, env))
        print(f"  {name}: plan {t_cold:.3f}s (incl. compile) replan "
              f"{t_warm:.3f}s (incl. compile) gd_iters cold="
              f"{int(cold.total_iters)} warm={int(warm.total_iters)}",
              flush=True)
        runs[name] = (cold, warm)
    ok = _compare_plans("cold", runs[BACKEND][0], runs["einsum"][0])
    ok &= _compare_plans("warm", runs[BACKEND][1], runs["einsum"][1])
    # Gamma_s at the cold start and at the kernels' warm optimum.
    kern = jax.jit(_gamma_vg(w, BACKEND))
    ref = jax.jit(_gamma_vg(w, "einsum"))
    points = (("cold start", li_gd.cold_init(env)),
              ("warm optimum", jax.tree.map(lambda x: x[GAMMA_SPLIT],
                                            runs[BACKEND][1].norms)))
    for tag, point in points:
        got = kern(env, point)
        with jax.default_matmul_precision(REF_PRECISION):
            want = ref(env, point)
        ok &= _compare_gamma(f"{tag} {BACKEND} vs einsum[{REF_PRECISION}]",
                             got, want)
    return ok


def phase_online(ctx: dict) -> bool:
    import jax
    from repro.online import OnlineLoop, ServiceConfig, StreamConfig
    from repro.planning import compile_log
    from repro.scenarios import Scenario, ScenarioConfig

    scfg = ScenarioConfig(name="paper_slow_fading", n_users=N_USERS,
                          n_aps=N_APS, n_sub=N_SUB,
                          fading_rho=SLOW_FADING_RHO, speed_mps=0.0)
    loop = OnlineLoop(Scenario(scfg), ctx["engine"], StreamConfig(),
                      ServiceConfig(replan_every=1))
    healths, compiles, times = [], [], []
    with compile_log() as log:
        loop.reset(jax.random.PRNGKey(ctx["seed"] + 1))
        for _ in range(ONLINE_EPOCHS):
            t0 = time.perf_counter()
            out, _ = loop.step_epoch()
            jax.block_until_ready(loop.server.state.plan.utility)
            times.append(time.perf_counter() - t0)
            healths.append(int(out.health))
            compiles.append(list(log))
    late = compiles[-1][len(compiles[0]):]
    print(f"  epochs={ONLINE_EPOCHS} epoch_s={[round(t, 4) for t in times]} "
          f"health={healths} compiled_by_epoch1={compiles[0]} "
          f"compiled_after_epoch1={late} replans={loop.server.replans}",
          flush=True)
    return all(h == 0 for h in healths) and not late


def phase_serve(ctx: dict) -> bool:
    import jax
    import numpy as np
    from repro.launch import serve
    from repro.runtime.serve import make_split_serve

    res = serve.main(SERVE_ARGS)
    model, params, tokens = res["model"], res["params"], res["tokens"]
    ref = jax.jit(lambda p, t: model.forward(p, t)[0])(params, tokens)
    vocab = model.cfg.vocab_size
    want = np.asarray(ref[..., :vocab], np.float32)
    scale = float(np.max(np.abs(want)))
    # The planned split (from launch.serve), then the middle layer, where
    # the device half runs stages and the activations cross to the edge.
    mid = model.cfg.n_layers // 2
    progs = make_split_serve(model, params, mid)
    ok = True
    for s, logits in ((int(res["plan"].s), res["logits"]),
                      (mid, progs.edge_fn(progs.device_fn(tokens)))):
        got = np.asarray(logits[..., :vocab], np.float32)
        err = float(np.max(np.abs(got - want)))
        top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        split_ok = (got.shape == (*tokens.shape, vocab)
                    and bool(np.all(np.isfinite(got)))
                    and err <= LOGIT_TOL * scale)
        print(f"  split s={s}/{model.cfg.n_layers} d_model={model.cfg.d_model} "
              f"logits {got.shape} max|diff|={err:.4e} "
              f"(limit {LOGIT_TOL * scale:.4e}) top1_agree={top1:.4f} "
              f"{'ok' if split_ok else 'MISMATCH'}", flush=True)
        ok &= split_ok
    print(f"  generated={np.asarray(res['generated']).shape}", flush=True)
    return ok


def phase_fleet(ctx: dict) -> bool:
    import jax
    from repro.core import GdConfig, make_weights, profiles
    from repro.planning import (PlannerEngine, fleet_mesh, member,
                                shard_fleet)
    from repro.pshard import fleet_axis
    from repro.scenarios import Scenario, ScenarioConfig
    from jax.sharding import PartitionSpec as P

    mesh = fleet_mesh(FLEET)
    sc = Scenario(ScenarioConfig(name="paper_fleet", n_users=N_USERS,
                                 n_aps=N_APS, n_sub=N_SUB,
                                 fading_rho=SLOW_FADING_RHO, speed_mps=0.0))
    k0, k1 = jax.random.split(jax.random.PRNGKey(ctx["seed"] + 2))
    states = sc.init_many(jax.random.split(k0, FLEET))
    epochs = [sc.env_many(states),
              sc.env_many(sc.step_many(jax.random.split(k1, FLEET), states))]
    w = make_weights(N_USERS)
    cfg = GdConfig(max_iters=FLEET_GD_ITERS, optimizer="adam")
    kw = dict(weights=w, cfg=cfg, sinr_backend=BACKEND)
    sharded = PlannerEngine(profiles.vgg16(), mesh=mesh, **kw)
    single = PlannerEngine(profiles.vgg16(), **kw)
    vg = jax.vmap(_gamma_vg(w, BACKEND))
    ax = P(fleet_axis(mesh))
    gamma_sharded = jax.jit(jax.shard_map(vg, mesh=mesh, in_specs=(ax, ax),
                                          out_specs=ax, check_vma=False))
    gamma_single = jax.jit(vg)
    device0 = jax.devices()[0]

    ok, sh, one = True, None, None
    for epoch, envs in enumerate(epochs):   # cold plan_many, then warm
        envs_sh = shard_fleet(envs, mesh)
        sh, t_sh = _timed(lambda: sharded.replan_many(sh, envs_sh))
        one, t_one = _timed(lambda: single.replan_many(one, envs))
        owner = {s.index[0].start: s.device.id
                 for s in sh.plan.utility.addressable_shards}
        print(f"  epoch {epoch}: replan_many sharded {t_sh:.3f}s, one device "
              f"{t_one:.3f}s (incl. compile); sharded plan.utility "
              f"{sh.plan.utility.sharding}, member->device {owner}; "
              f"one-device plan.utility {one.plan.utility.sharding}",
              flush=True)
        ok &= sorted(owner.values()) == sorted(d.id for d in mesh.devices)
        # Gamma_s at the sharded plan's optimum, the same point both ways.
        # Read before the next replan_many donates the sharded payload.
        point = jax.tree.map(lambda x: x[:, GAMMA_SPLIT], sh.norms)
        g_sh = gamma_sharded(envs_sh, point)
        g_one = gamma_single(envs, jax.device_put(point, device0))
        for i in range(FLEET):
            ok &= _compare_plans(f"epoch {epoch} member {i}", member(sh, i),
                                 member(one, i))
            ok &= _compare_gamma(f"epoch {epoch} member {i} sharded vs one "
                                 "device", member(g_sh, i), member(g_one, i))
    return ok


def run(phases, ctx: dict) -> bool:
    """Run each phase, print its line, and return whether all passed."""
    meter = Meter()
    all_ok = True
    for name, fn in phases:
        c0, h0, t0 = meter.compile_s, meter.cache_hits, time.perf_counter()
        try:
            ok = bool(fn(ctx))
        except Exception:
            traceback.print_exc()
            ok = False
        wall = time.perf_counter() - t0
        print(f"phase={name} ok={ok} wall_s={wall:.3f} "
              f"compile_s={meter.compile_s - c0:.3f} "
              f"cache_hits={meter.cache_hits - h0} "
              f"peak_bytes_in_use={_peak_bytes()}", flush=True)
        all_ok &= ok
    return all_ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.chips == 4:
        phases = [("fleet", phase_fleet)]
    else:
        phases = [("planner", phase_planner), ("online", phase_online),
                  ("serve", phase_serve)]
    if not run(phases, {"seed": args.seed}):
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {"platform": d[0].platform,
                                             "kind": d[0].device_kind,
                                             "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
