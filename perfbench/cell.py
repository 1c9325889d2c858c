"""One run of one cell: build the closed loop from the cell's files, set it
up, measure a window of epochs, read the metrics, and check the window's
last replan against the plain reference.

Set-up is everything from process start to the first timed epoch: JAX
start-up, compilation or loading from the persistent cache, the
configuration's objects, the episode reset with its cold plan, and
``replan_every`` warm-up epochs, so that the warm-up holds exactly one
scheduled replan and every program the window runs has run once.

The window runs whole epochs (``OnlineLoop.step_epoch``, no recording) and
ends at the first epoch boundary after ``seconds``. An epoch ends when its
health word and the served plan are ready on the device; both are only
waited for, and the health words are read after the window.

What a configuration brings. Its file's ``"reference"`` key names a module
(a path relative to the checkout, ``spec.config_module``). That module
gives:

``profile(model)``            required: the reference's (fl (F,), w (F+1,),
                              m_down (F+1,)) from the configuration's
                              ``model`` section. It imports nothing of
                              ``repro``. The planner's own profile is the
                              ``repro.core.profiles`` function that
                              ``model.profile`` names, called with the
                              keyword arguments ``model.profile_args``
                              (none where the key is absent).
``build_loop(cfg, traffic, engine, build)``
                              optional, the one seam for a served model:
                              returns the ``OnlineLoop`` to run. ``build``
                              is :func:`build`, whose keyword arguments
                              reach ``OnlineLoop`` (``model=``,
                              ``params=``), so the hook builds the served
                              model from its configuration's ``served``
                              section and hands it over. Without the hook
                              the loop is ``build(cfg, traffic, engine)``.
``served_checks(cfg, loop)``  optional: called once the window has closed
                              and the peak memory has been read, before
                              the loop is freed; returns {name: {"value",
                              "limit"}}, merged into the run's checks, so
                              each decides ``correct`` and is printed
                              beside its limit. A name the planner's checks
                              already use stays the planner's, and a hook
                              that raises fails the run.
``tiny(cfg)``                 optional: the configuration cut for the CPU
                              rehearsal (``rehearse.tiny``), after its
                              deployment has been cut.

A metric reader reads :class:`RunData`: besides the host clock it holds
the program's own episode counters over the window (``counters``) and,
in a traced run, each device operation's name stack (``scopes``).
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
import traceback
from typing import NamedTuple

import numpy as np

from perfbench import check as checklib
from perfbench import spans
from perfbench import spec as speclib
from perfbench import trace as tracelib
from perfbench import work

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Meter:
    """Compile seconds, compile events and persistent-cache hits, from
    jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event in _COMPILE_EVENTS:
                self.compile_s += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


class Recorder:
    """Stands in for the planner engine inside the server: forwards every
    call and keeps device references to the operands and the result of the
    last replan. It reads nothing back."""

    def __init__(self, engine):
        self._engine = engine
        self.last = None

    def replan(self, prev, env, weights=None, prof=None):
        import jax
        with jax.profiler.TraceAnnotation("perfbench.replan"):
            out = self._engine.replan(prev, env, weights, prof=prof)
        self.last = (prev, env, out)
        return out

    def __getattr__(self, name):
        return getattr(self._engine, name)


class RunData(NamedTuple):
    """What a metric reader reads."""
    cell: speclib.Cell
    setup_s: float
    window_s: float              # host clock, first epoch start to last end
    epoch_s: list[float]         # host clock, per epoch
    epochs: int
    replans: int                 # engine replans dispatched in the window
    gd_iters: int                # GD iterations of those replans (counter)
    counters: dict[str, int]     # the loop's episode counters over the window
    sizes: work.Sizes
    peak: work.Peak | None
    trace: tracelib.Trace | None
    scopes: dict[str, str] | None  # traced: op (HLO text) -> its name stack


class Result(NamedTuple):
    correct: bool
    attempted: int
    failed: int
    metrics: dict                # name -> {"value", "unit"}
    device: dict
    breakdown: dict | None
    checks: dict                 # name -> {"value", "limit"}
    notes: list[str]             # lines for standard error


def key_from_seed(seed: int):
    """A PRNG key that tells apart every seed up to 2**64."""
    import jax
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def build(cfg: dict, traffic: dict, engine=None, **loop_kwargs):
    """The closed loop of one configuration under one traffic mix: the
    engine, scenario, request stream and service exactly as the files
    state them. ``engine`` reuses an engine built here before, with its
    compiled programs; ``loop_kwargs`` go to ``OnlineLoop``."""
    from repro.core import GdConfig, make_weights, profiles
    from repro.core.types import ComputeConstants, RadioConstants
    from repro.online import OnlineLoop, ServiceConfig, StreamConfig
    from repro.planning import PlannerEngine
    from repro.scenarios import Scenario, ScenarioConfig

    dep, planner, model = cfg["deployment"], cfg["planner"], cfg["model"]
    radio = RadioConstants(**cfg["radio"])
    comp = ComputeConstants(**cfg["compute"])
    if engine is None:
        engine = PlannerEngine(
            getattr(profiles, model["profile"])(
                **model.get("profile_args", {})),
            weights=make_weights(dep["n_users"], cfg["model"]["w_T"]),
            cfg=GdConfig(**planner["gd"]), method=planner["method"],
            rounding=planner["rounding"],
            warm_rho_min=planner["warm_rho_min"],
            warm_moment_decay=planner["warm_moment_decay"],
            sinr_backend=planner["sinr_backend"])
    scenario = Scenario(ScenarioConfig(
        name=traffic["name"], n_users=dep["n_users"], n_aps=dep["n_aps"],
        n_sub=dep["n_sub"], radio=radio, comp=comp, **traffic["scenario"]))
    return OnlineLoop(scenario, engine, StreamConfig(**traffic["stream"]),
                      ServiceConfig(**traffic["service"]),
                      feedback=traffic["feedback"], **loop_kwargs)


def make_loop(module, cfg: dict, traffic: dict, engine=None):
    """The loop a run drives: the configuration module's ``build_loop``
    hook where it has one, else :func:`build`."""
    hook = getattr(module, "build_loop", None)
    if hook is None:
        return build(cfg, traffic, engine)
    return hook(cfg, traffic, engine, build)


def counters(loop) -> dict[str, int]:
    """The integer entries of ``OnlineLoop.metrics()``: the episode
    counters (replans, GD iterations, requests offered, served, ...). The
    read syncs the device."""
    return {k: v for k, v in loop.metrics().items()
            if isinstance(v, int) and not isinstance(v, bool)}


def _served_checks(module, cfg: dict, loop) -> dict[str, dict]:
    """The configuration module's ``served_checks``; a hook that raises
    fails the run through the check ``served_checks_raised``."""
    hook = getattr(module, "served_checks", None)
    if hook is None:
        return {}
    try:
        return dict(hook(cfg, loop))
    except Exception:                   # the hook's fault fails the run
        traceback.print_exc()
        return {"served_checks_raised": {"value": 1, "limit": 0}}


def _replan_record(recorder: Recorder) -> tuple[dict, dict, dict]:
    """The last replan's operands and result as host arrays: the network
    (g_up, g_dn, ap), the previous state (norms, m1, m2, steps, gains) and
    the result (s, gammas, norms, m1, sub_up, sub_dn)."""
    import jax
    prev, env, out = recorder.last
    if prev is None:
        raise RuntimeError("the window's last planner call was a cold plan")
    tree = jax.device_get({
        "env": {"g_up": env.g_up, "g_dn": env.g_dn, "ap": env.ap},
        "prev": {"norms": prev.norms, "m1": prev.moms[0], "m2": prev.moms[1],
                 "steps": prev.opt_steps, "gains": prev.gains},
        "out": {"s": out.plan.s, "gammas": out.plan.per_layer_utility,
                "norms": out.norms,
                "m1": out.moms[0], "sub_up": out.plan.sub_up,
                "sub_dn": out.plan.sub_dn},
    })
    tree = jax.tree.map(np.asarray, tree)
    return tree["env"], tree["prev"], tree["out"]


def run(cell: speclib.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        control: bool = False, engine=None) -> Result:
    """Run ``cell`` once. ``t_start`` is the process's start on the host
    clock (set-up is measured from it). Without ``require_tpu`` the run
    goes on whatever JAX finds (the CPU rehearsal and the tests); its
    numbers are then not device numbers and must not be reported as such.
    ``control`` adds the bfloat16 control's readings to the checks;
    ``engine`` reuses a planner engine of an earlier run of the cell."""
    import jax
    from repro.planning import compile_log

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise SystemExit(f"the cell needs {cell.chips} chips, JAX sees "
                         f"{len(devices)}")
    cfg, traffic = cell.config, cell.traffic
    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul_precision"])
    meter = Meter()
    notes: list[str] = []

    # -- set-up --------------------------------------------------------------
    loop = make_loop(cell.module, cfg, traffic, engine)
    recorder = Recorder(loop.server.engine)
    loop.server.engine = recorder
    loop.reset(key_from_seed(seed))
    for _ in range(traffic["service"]["replan_every"]):
        out, _ = loop.step_epoch()
        jax.block_until_ready((out.health, loop.server.state.plan.utility))
    counts0, reads0 = counters(loop), dict(loop.host_reads)
    compile_s, cache_hits = meter.compile_s, meter.cache_hits
    # Set-up's garbage (traced programs, lowered modules) is collected here,
    # so that no full collection of it stalls an epoch of the window.
    gc.collect()
    gc0 = [g["collections"] for g in gc.get_stats()]

    # -- the window ----------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    healths, epoch_s, raised = [], [], 0
    compiles0 = meter.compiles
    with compile_log() as traced:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracelib.WINDOW):
            while True:
                ts = time.perf_counter()
                try:
                    with jax.profiler.TraceAnnotation(tracelib.EPOCH):
                        out, _ = loop.step_epoch()
                        jax.block_until_ready(
                            (out.health, loop.server.state.plan.utility))
                except Exception:           # the epoch failed: count it, stop
                    traceback.print_exc()
                    raised += 1
                    break
                te = time.perf_counter()
                epoch_s.append(te - ts)
                healths.append(out.health)
                if te - t0 >= seconds:
                    break
        t1 = time.perf_counter()
        if trace_dir:
            jax.profiler.stop_trace()
    t_stop = time.perf_counter()
    compiled = meter.compiles - compiles0
    gcs = [g["collections"] - n for g, n in zip(gc.get_stats(), gc0)]

    # -- after the window: counters, health, memory ---------------------------
    setup_s = t0 - t_start
    reads1 = dict(loop.host_reads)
    counts = {k: v - counts0.get(k, 0) for k, v in counters(loop).items()}
    counts.update({f"host_reads.{k}": v - reads0.get(k, 0)
                   for k, v in reads1.items()})
    gd_iters, replans = counts["total_iters"], counts["replans"]
    bad = sum(int(h) != 0 for h in jax.device_get(healths))
    stats = devices[0].memory_stats() or {}
    served = _served_checks(cell.module, cfg, loop)
    env, prev, out_h = _replan_record(recorder)
    notes.append(
        f"setup: compile_s={compile_s!r} cache_hits={cache_hits} "
        f"setup_s={setup_s!r}")
    notes.append(
        f"window: epochs={len(epoch_s)} replans={replans} "
        f"forced={counts['forced_replans']} "
        f"gd_iters={gd_iters} unhealthy={bad} raised={raised} "
        f"compile_events={compiled} traced_programs={list(traced)} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"plan_s={int(out_h['s'])} gc_collections={gcs}")
    if epoch_s:
        ms = sorted(1e3 * t for t in epoch_s)
        at = sorted(range(len(epoch_s)), key=epoch_s.__getitem__)[:-6:-1]
        notes.append(
            f"epoch_wall_ms: min={ms[0]!r} median={float(np.median(ms))!r} "
            f"max={ms[-1]!r} largest={ms[:-6:-1]!r} largest_at={at}")
    del loop, recorder, out, healths
    gc.collect()

    dep = cfg["deployment"]
    z = work.sizes(dep["n_users"], dep["n_aps"], dep["n_sub"], env["ap"])
    tr = scopes = None
    if trace_dir:
        t_read = time.perf_counter()
        try:
            path = tracelib.find_xplane(trace_dir)
            tr, scopes = tracelib.load(path), spans.op_scopes(path)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        notes.append(f"trace: stop_s={t_stop - t1!r} "
                     f"read_s={time.perf_counter() - t_read!r} "
                     f"device_ops={sum(ln.start.size for ln in tr.ops)}")
        gaps = tracelib.epoch_largest_gaps(tr, tracelib.EPOCH)
        if gaps.size:
            # One gap far above the median epoch's is a stall of the
            # profiler, which idle_share then counts as idle time.
            notes.append(f"trace: largest_idle_gap_s={float(gaps.max())!r} "
                         f"median_epoch_largest_gap_s="
                         f"{float(np.median(gaps))!r} epochs={gaps.size}")
    pk = (work.peak(devices[0].device_kind)
          if devices[0].platform == "tpu" else None)
    data = RunData(cell=cell, setup_s=setup_s, window_s=t1 - t0,
                   epoch_s=epoch_s, epochs=len(epoch_s), replans=replans,
                   gd_iters=gd_iters, counters=counts, sizes=z, peak=pk,
                   trace=tr, scopes=scopes)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = speclib.reader(cell.root, m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    breakdown = None
    if tr is not None:
        device["busy_s"] = tracelib.busy_s(tr)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tracelib.top_ops(tr),
                     "idle_gaps": tracelib.idle_gaps(tr)}

    # -- correctness -----------------------------------------------------------
    checks = checklib.check(cfg, cell.module, env, prev, out_h,
                            control=control)
    checks["unhealthy_epochs"] = {"value": bad + raised, "limit": 0}
    checks["window_compiles"] = {"value": compiled + len(traced), "limit": 0}
    checks.update({k: v for k, v in served.items() if k not in checks})
    return Result(correct=checklib.passed(checks), attempted=len(epoch_s) + raised,
                  failed=bad + raised, metrics=metrics, device=device,
                  breakdown=breakdown, checks=checks, notes=notes)


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all values."""
    ranked = sorted(values)
    return ranked[max(math.ceil(q / 100.0 * len(ranked)) - 1, 0)]


def print_checks(result: Result, file=sys.stderr) -> None:
    for name, c in result.checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=file, flush=True)
