"""The program's own marks: each jitted program lowers under its kind's
name, host reads are counted by name, and spans and reads are plain values
when no profiler session is active."""
import collections

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import GdConfig, make_weights, profiles
from repro.faults import LadderConfig
from repro.online import OnlineLoop, ServiceConfig, StreamConfig
from repro.planning import PlannerEngine, compile_log, stack_envs
from repro.pshard import fleet_mesh
from repro.scenarios import Scenario, ScenarioConfig

CFG = GdConfig(step_size=3e-2, eps=1e-4, max_iters=4, optimizer="adam")
SCEN = ScenarioConfig(n_users=6, n_aps=2, n_sub=3, fading_rho=0.95)
STREAM = StreamConfig(arrival_rate_hz=30.0, epoch_dt_s=0.02, deadline_s=0.2)
SERVICE = ServiceConfig(edge_capacity=4, queue_depth=16, replan_every=3)


def _module(lowered) -> str:
    return lowered.as_text().split("\n", 1)[0].split()[1]


def _engine(mesh=None):
    return PlannerEngine(profiles.nin(), weights=make_weights(SCEN.n_users),
                         cfg=CFG, mesh=mesh)


@pytest.fixture(scope="module")
def env():
    sc = Scenario(SCEN)
    return sc.env(sc.init(jax.random.PRNGKey(0)))


@pytest.mark.parametrize("kind", ["plan", "replan", "plan_many",
                                  "replan_many", "plan_many_sharded",
                                  "replan_many_sharded"])
def test_engine_programs_lower_named_by_kind(env, kind):
    eng = _engine(fleet_mesh() if kind.endswith("_sharded") else None)
    e = stack_envs([env]) if "many" in kind else env
    prev = None
    if kind.startswith("replan"):
        cold = kind.replace("replan", "plan")
        prev = jax.eval_shape(eng.program(cold, e),
                              *eng.program_args(cold, e))
    args = eng.program_args(kind, e, prev=prev)
    with compile_log() as log:
        lowered = eng.program(kind, e).lower(*args)
    assert _module(lowered) == f"@jit_{kind}"
    assert log == [kind]                      # the compile-log kind is kept


def _loop(hardened: bool) -> OnlineLoop:
    return OnlineLoop(Scenario(SCEN), _engine(), STREAM, SERVICE,
                      degrade=LadderConfig() if hardened else None)


def test_loop_programs_keep_their_names():
    """The epoch program stays module jit_epoch (its compile-log kind is
    online_epoch); the ladder's fallback and the server's guard take their
    kinds' names."""
    loop = _loop(hardened=True)
    loop.reset(jax.random.PRNGKey(0))
    with compile_log() as log:
        epoch = loop._epoch.lower(*loop.epoch_args())
    assert _module(epoch) == "@jit_epoch" and log == ["online_epoch"]
    env = loop.scenario.env(loop._sc)
    assert _module(loop._fb_jit.lower(env)) == "@jit_fallback_plan"
    guard = loop.server._plan_word_fn
    assert _module(guard.lower(loop.server.state.plan)) == "@jit_plan_guard"


@pytest.mark.parametrize("hardened", [False, True])
def test_host_reads_are_counted_by_name(hardened):
    """One trigger read per epoch and one plan-word read per replan (the
    cold plan in reset included); the hardened loop adds one health read
    per epoch. metrics() reports the counts, its own read included."""
    loop = _loop(hardened)
    loop.reset(jax.random.PRNGKey(1))
    for _ in range(7):
        loop.step_epoch()
    want = {"trigger": 7, "plan_word": loop.server.replans}
    if hardened:
        want["health"] = 7
    assert dict(loop.host_reads) == want
    assert loop.server.replans >= 3          # reset, epochs 3 and 6
    m = loop.metrics()
    want.update(iters=1, metrics=1)
    assert m["host_reads"] == want


def test_recorded_history_is_one_read_per_epoch():
    loop = _loop(hardened=False)
    m = loop.run(jax.random.PRNGKey(2), 4, record=True)
    assert m["host_reads"]["history"] == 4
    assert len(m["history"]["s"]) == 4
    assert all(isinstance(v, int) for v in m["history"]["occupancy"])
    assert all(isinstance(v, float) for v in m["history"]["p95"])


def test_host_read_returns_host_values_and_counts():
    counts = collections.Counter()
    got = obs.host_read({"a": jnp.int32(3), "b": jnp.ones(2)}, "x", counts)
    assert int(got["a"]) == 3 and got["b"].tolist() == [1.0, 1.0]
    assert counts == {"x": 1}
    with obs.span("dispatch.test"):          # no profiler session: a no-op
        obs.host_read(jnp.float32(1.0), "x", counts)
    assert counts == {"x": 2}


def test_recorded_names_and_logs():
    prog = obs.recorded(lambda x: x + 1, "demo")
    assert prog.__name__ == "demo"
    assert obs.recorded(prog, "kind", name="other").__name__ == "other"
    with compile_log() as log:
        lowered = jax.jit(prog).lower(jnp.float32(0))
    assert log == ["demo"] and _module(lowered) == "@jit_demo"
