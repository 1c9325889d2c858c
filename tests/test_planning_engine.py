"""PlannerEngine: unified single-shot / batched / online warm-start planning,
plus the simplex-projection edge cases the solver relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    GdConfig,
    make_env,
    make_weights,
    profiles,
    project_simplex_floor,
    solve,
)
from repro.planning import (
    PlannerEngine,
    PlanState,
    compile_log,
    member,
    stack_envs,
)
from repro.scenarios import Scenario, ScenarioConfig


ADAM_CFG = GdConfig(step_size=1e-2, eps=1e-4, max_iters=400, optimizer="adam")


@pytest.fixture(scope="module")
def engine(weights, gd_cfg):
    return PlannerEngine(profiles.nin(), weights=weights, cfg=gd_cfg)


# -- simplex projection edge cases (floors) --------------------------------
def test_simplex_floor_row_below_floor():
    """A row entirely below the floor must be lifted onto the floored simplex."""
    floor = 0.05
    y = jnp.full((3, 4), -2.0)
    x = project_simplex_floor(y, floor)
    np.testing.assert_allclose(np.sum(np.asarray(x), -1), 1.0, atol=1e-6)
    assert bool(jnp.all(x >= floor - 1e-6))
    # symmetric input -> uniform output
    np.testing.assert_allclose(np.asarray(x), 0.25, atol=1e-6)


def test_simplex_floor_tight_budget():
    """m * floor ~ 1: almost no slack, projection must pin every entry at
    (approximately) the floor without going negative or overshooting."""
    m, floor = 4, 0.2499
    y = jax.random.normal(jax.random.PRNGKey(0), (5, m)) * 10.0
    x = project_simplex_floor(y, floor)
    np.testing.assert_allclose(np.sum(np.asarray(x), -1), 1.0, atol=1e-5)
    assert bool(jnp.all(x >= floor - 1e-6))
    assert bool(jnp.all(x <= floor + (1.0 - m * floor) + 1e-5))


def test_simplex_floor_exact_budget():
    """m * floor == 1 exactly: the floored simplex is the single point
    x = floor * ones."""
    m = 5
    floor = 1.0 / m
    y = jax.random.normal(jax.random.PRNGKey(1), (3, m)) * 3.0
    x = project_simplex_floor(y, floor)
    np.testing.assert_allclose(np.asarray(x), floor, atol=1e-6)


def test_simplex_floor_infeasible_budget():
    """m * floor > 1 (Corollary 1's feasibility violated): the effective
    floor is clamped to 1/m, so the output stays on the simplex instead of
    silently summing to the negative residual budget."""
    m = 4
    for floor in (0.3, 1.0, 7.5):
        y = jax.random.normal(jax.random.PRNGKey(2), (6, m)) * 5.0
        x = project_simplex_floor(y, floor)
        np.testing.assert_allclose(np.sum(np.asarray(x), -1), 1.0, atol=1e-5)
        assert bool(jnp.all(x >= 0.0))
        np.testing.assert_allclose(np.asarray(x), 1.0 / m, atol=1e-5)


# -- engine entry points ---------------------------------------------------
def test_engine_plan_matches_solve(small_env, weights, gd_cfg, engine):
    state = engine.plan(small_env)
    ref = solve(small_env, profiles.nin(), weights, gd_cfg)
    assert isinstance(state, PlanState)
    assert int(state.plan.s) == int(ref.s)
    assert float(state.plan.utility) == pytest.approx(float(ref.utility), abs=1e-6)
    # norms carry one optimum per split point for the next epoch's warm start
    assert state.norms["beta_up"].shape[0] == profiles.nin().n_layers + 1


def test_engine_plan_many_matches_sequential(weights, gd_cfg, engine):
    envs = [make_env(jax.random.PRNGKey(s), 8, 2, 4) for s in (0, 1, 2)]
    batched = engine.plan_many(envs)
    assert batched.plan.s.shape == (3,)
    for i, env in enumerate(envs):
        single = solve(env, profiles.nin(), weights, gd_cfg)
        assert int(batched.plan.s[i]) == int(single.s)
        assert float(batched.plan.utility[i]) == pytest.approx(
            float(single.utility), abs=1e-4)


def test_engine_plan_many_accepts_stacked(weights, gd_cfg, engine):
    envs = stack_envs([make_env(jax.random.PRNGKey(s), 8, 2, 4) for s in (3, 4)])
    out = engine.plan_many(envs)
    assert out.plan.s.shape == (2,)


def test_engine_cache_reuse(gd_cfg):
    eng = PlannerEngine(profiles.nin(), cfg=gd_cfg)  # weights derived per env
    e1 = make_env(jax.random.PRNGKey(0), 8, 2, 4)
    e2 = make_env(jax.random.PRNGKey(1), 8, 2, 4)
    eng.plan(e1)
    eng.plan(e2)
    assert eng.cache_size() == 1          # same shape -> one compiled program
    eng.plan(make_env(jax.random.PRNGKey(2), 6, 2, 3))
    assert eng.cache_size() == 2          # new shape -> new program


def test_engine_pallas_backend_matches_einsum_plan(small_env, weights):
    """Acceptance: the kernel backend's plan(env) returns the same
    split/allocation as the einsum engine on a small env (the kernels run
    in the Pallas interpreter on CPU)."""
    cfg = GdConfig(max_iters=40, optimizer="adam")
    e_ein = PlannerEngine(profiles.nin(), weights=weights, cfg=cfg)
    e_pal = PlannerEngine(profiles.nin(), weights=weights, cfg=cfg,
                          sinr_backend="pallas_interpret")
    assert (e_ein.sinr_backend == "einsum"
            and e_pal.sinr_backend == "pallas_interpret")
    s1 = e_ein.plan(small_env)
    s2 = e_pal.plan(small_env)
    assert int(s1.plan.s) == int(s2.plan.s)
    np.testing.assert_array_equal(np.asarray(s1.plan.sub_up),
                                  np.asarray(s2.plan.sub_up))
    np.testing.assert_array_equal(np.asarray(s1.plan.sub_dn),
                                  np.asarray(s2.plan.sub_dn))
    for a, b in ((s1.plan.p_up, s2.plan.p_up), (s1.plan.p_dn, s2.plan.p_dn),
                 (s1.plan.r, s2.plan.r)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-3,
                                   atol=1e-4)
    np.testing.assert_allclose(float(s2.plan.utility), float(s1.plan.utility),
                               rtol=1e-4)


def test_engine_pallas_backend_fleet_paths(weights):
    """The custom_vjp'd pallas_call must stay batchable: plan_many and
    replan_many (the vmapped fleet paths) with the kernel backend agree
    with the einsum fleet programs per member."""
    cfg = GdConfig(max_iters=25, optimizer="adam")
    e_pal = PlannerEngine(profiles.nin(), weights=weights, cfg=cfg,
                          sinr_backend="pallas_interpret")
    e_ein = PlannerEngine(profiles.nin(), weights=weights, cfg=cfg)
    envs = stack_envs([make_env(jax.random.PRNGKey(s), 8, 2, 4)
                       for s in (0, 1)])
    sp = e_pal.plan_many(envs)
    se = e_ein.plan_many(envs)
    np.testing.assert_array_equal(np.asarray(sp.plan.s), np.asarray(se.plan.s))
    np.testing.assert_allclose(np.asarray(sp.plan.utility),
                               np.asarray(se.plan.utility), rtol=1e-4)
    envs2 = stack_envs([make_env(jax.random.PRNGKey(s), 8, 2, 4)
                        for s in (2, 3)])
    rp = e_pal.replan_many(sp, envs2)
    re = e_ein.replan_many(se, envs2)
    np.testing.assert_array_equal(np.asarray(rp.plan.s), np.asarray(re.plan.s))
    np.testing.assert_allclose(np.asarray(rp.plan.utility),
                               np.asarray(re.plan.utility), rtol=1e-4)


def test_engine_backend_cache_keys(small_env, weights):
    """Compiled programs keep the backend they were traced with: flipping
    the channel-module global must neither retrace nor change a cached
    engine program's results, while a differing engine backend mints a new
    cache key instead of mutating the live one."""
    import dataclasses

    from repro.core import channel

    cfg = GdConfig(max_iters=25, optimizer="adam")
    eng = PlannerEngine(profiles.nin(), weights=weights, cfg=cfg)
    ref = eng.plan(small_env)
    assert eng.cache_size() == 1
    prev = channel.set_sinr_backend("pallas_interpret")
    try:
        again = eng.plan(small_env)
    finally:
        channel.set_sinr_backend(prev)
    assert eng.cache_size() == 1          # global switch: no new program
    np.testing.assert_allclose(float(again.plan.utility),
                               float(ref.plan.utility))
    # a different engine backend is a different cache key (cfg is in the key)
    eng.cfg = dataclasses.replace(cfg, sinr_backend="pallas_interpret")
    pal = eng.plan(small_env)
    assert eng.cache_size() == 2
    np.testing.assert_allclose(float(pal.plan.utility),
                               float(ref.plan.utility), rtol=1e-4)
    with pytest.raises(ValueError, match="sinr_backend"):
        PlannerEngine(profiles.nin(), cfg=cfg, sinr_backend="cuda")


def test_replan_identical_env_warm_equivalence(small_env):
    """Warm-start replan on an unchanged env must not need more iterations
    than the fresh plan, and must land on an optimum at least as good."""
    w = make_weights(small_env.n_users)
    eng = PlannerEngine(profiles.nin(), weights=w, cfg=ADAM_CFG)
    fresh = eng.plan(small_env)
    warm = eng.replan(fresh, small_env)
    assert int(warm.total_iters) <= int(fresh.total_iters)
    assert float(warm.plan.utility) <= float(fresh.plan.utility) + 1e-4
    assert int(warm.plan.s) == int(fresh.plan.s)


def test_replan_none_falls_back_to_plan(small_env, weights, gd_cfg, engine):
    state = engine.replan(None, small_env)
    ref = engine.plan(small_env)
    assert int(state.plan.s) == int(ref.plan.s)
    assert float(state.plan.utility) == pytest.approx(float(ref.plan.utility),
                                                      abs=1e-6)


@pytest.mark.slow
def test_online_episode_warm_beats_cold():
    """Acceptance: across a >= 10-epoch correlated-fading episode, online
    warm-start re-planning spends strictly fewer total GD iterations than
    cold re-planning, without giving up solution quality. (slow: 12-epoch
    episode solved twice.)"""
    scfg = ScenarioConfig(n_users=8, n_aps=2, n_sub=4, fading_rho=0.995,
                          speed_mps=0.0, arrival_rate_hz=0.0)
    w = make_weights(scfg.n_users)
    prof = profiles.nin()
    warm_eng = PlannerEngine(prof, weights=w, cfg=ADAM_CFG)
    cold_eng = PlannerEngine(prof, weights=w, cfg=ADAM_CFG)
    sc = Scenario(scfg)
    state = None
    cold_total = warm_total = 0
    cold_util = warm_util = 0.0
    for t, env in enumerate(sc.episode(jax.random.PRNGKey(0), 12)):
        cold = cold_eng.plan(env)
        state = warm_eng.replan(state, env)
        if t >= 1:  # epoch 0 is cold for both
            cold_total += int(cold.total_iters)
            warm_total += int(state.total_iters)
            cold_util += float(cold.plan.utility)
            warm_util += float(state.plan.utility)
    assert warm_total < cold_total
    assert warm_util <= cold_util * 1.05


@pytest.mark.slow
def test_replan_warm_vs_cold_regression_rho095():
    """Regression for the PR 1 warm-start defect: at rho = 0.95 (below the
    old ~0.99 break-even) warm replan must still spend no more GD iterations
    than cold re-planning, at equal-or-better utility. (slow: 6-epoch episode
    solved twice; the benchmark --quick smoke covers the same property.)"""
    scfg = ScenarioConfig(n_users=8, n_aps=2, n_sub=4, fading_rho=0.95,
                          speed_mps=0.0, arrival_rate_hz=0.0)
    w = make_weights(scfg.n_users)
    prof = profiles.nin()
    warm_eng = PlannerEngine(prof, weights=w, cfg=ADAM_CFG)
    cold_eng = PlannerEngine(prof, weights=w, cfg=ADAM_CFG)
    sc = Scenario(scfg)
    state = None
    cold_total = warm_total = 0
    cold_util = warm_util = 0.0
    for t, env in enumerate(sc.episode(jax.random.PRNGKey(1), 6)):
        cold = cold_eng.plan(env)
        state = warm_eng.replan(state, env)
        if t >= 1:  # epoch 0 is cold for both
            cold_total += int(cold.total_iters)
            warm_total += int(state.total_iters)
            cold_util += float(cold.plan.utility)
            warm_util += float(state.plan.utility)
    assert warm_total <= cold_total, (warm_total, cold_total)
    assert warm_util <= cold_util + 1e-3, (warm_util, cold_util)
    # and the warm engine must actually have used its temporal state
    assert warm_total < cold_total


@pytest.mark.slow
def test_replan_many_matches_sequential():
    """Batched warm-start replan over a stacked fleet == per-scenario
    sequential replan, epoch by epoch (same s*, utility, and iteration
    counts). (slow: compiles both the fleet and per-member programs.)"""
    scfg = ScenarioConfig(n_users=8, n_aps=2, n_sub=4, fading_rho=0.97,
                          speed_mps=0.0, arrival_rate_hz=0.0)
    fleet = 8
    w = make_weights(scfg.n_users)
    prof = profiles.nin()
    fleet_eng = PlannerEngine(prof, weights=w, cfg=ADAM_CFG)
    seq_eng = PlannerEngine(prof, weights=w, cfg=ADAM_CFG)
    sc = Scenario(scfg)
    states = sc.init_many(jax.random.split(jax.random.PRNGKey(4), fleet))
    batched, seq = None, [None] * fleet
    for t in range(3):
        envs = sc.env_many(states)
        batched = fleet_eng.replan_many(batched, envs)
        assert batched.plan.s.shape == (fleet,)
        for i in range(fleet):
            seq[i] = seq_eng.replan(seq[i], member(envs, i))
            assert int(batched.plan.s[i]) == int(seq[i].plan.s), (t, i)
            assert int(batched.total_iters[i]) == int(seq[i].total_iters), (t, i)
            assert float(batched.plan.utility[i]) == pytest.approx(
                float(seq[i].plan.utility), abs=1e-4), (t, i)
        states = sc.step_many(jax.random.split(jax.random.PRNGKey(100 + t),
                                               fleet), states)


def test_replan_many_none_and_shape_checks():
    prof = profiles.nin()
    eng = PlannerEngine(prof, cfg=ADAM_CFG)
    envs = stack_envs([make_env(jax.random.PRNGKey(s), 8, 2, 4) for s in range(2)])
    state = eng.replan_many(None, envs)          # falls back to plan_many
    assert state.plan.s.shape == (2,)
    bad = stack_envs([make_env(jax.random.PRNGKey(9), 6, 2, 4) for _ in range(2)])
    with pytest.raises(ValueError):
        eng.replan_many(state, bad)
    with pytest.raises(ValueError):
        eng.replan_many(state, [])


def test_shape_guard_batched_vs_single_states(small_env):
    """The guards must read the network shape off the right trailing dims
    for both state layouts: a fleet state handed to replan() (and a single
    state handed to replan_many()) is told exactly what to use instead --
    not given a garbled (U, M) mismatch from misread leading dims."""
    from repro.planning import WarmStateShapeError

    eng = PlannerEngine(profiles.nin(), cfg=ADAM_CFG)
    envs = stack_envs([make_env(jax.random.PRNGKey(s), 8, 2, 4) for s in range(2)])
    fleet_state = eng.plan_many(envs)
    single_state = eng.plan(small_env)
    with pytest.raises(WarmStateShapeError, match="replan_many"):
        eng.replan(fleet_state, small_env)
    with pytest.raises(WarmStateShapeError, match="plan_many|replan\\(\\)"):
        eng.replan_many(single_state, envs)
    # fleet size mismatch: 2-member state vs 3-member envs
    envs3 = stack_envs([make_env(jax.random.PRNGKey(s), 8, 2, 4)
                        for s in (5, 6, 7)])
    with pytest.raises(WarmStateShapeError, match="fleet of 2"):
        eng.replan_many(fleet_state, envs3)
    # single-scenario (U, M) mismatch keeps its message
    with pytest.raises(WarmStateShapeError, match="users"):
        eng.replan(single_state, make_env(jax.random.PRNGKey(3), 6, 2, 4))
    # an unbatched env is told to use replan()/plan(), not misread
    with pytest.raises(WarmStateShapeError, match="use replan\\(\\)"):
        eng.replan_many(fleet_state, small_env)
    with pytest.raises(ValueError, match="use plan\\(\\)"):
        eng.plan_many(small_env)


@pytest.fixture(scope="module")
def adam_engine(weights):
    return PlannerEngine(profiles.nin(), weights=weights, cfg=ADAM_CFG)


def test_replan_exposes_in_jit_rho_estimate(small_env, adam_engine):
    """PlanState.warm_rho is the gate's traced correlation estimate: None
    from a cold plan, ~1 when the env repeats, and low for a fresh draw."""
    eng = adam_engine
    fresh = eng.plan(small_env)
    assert fresh.warm_rho is None
    warm = eng.replan(fresh, small_env)
    assert float(warm.warm_rho) == pytest.approx(1.0, abs=1e-5)
    other = eng.replan(fresh, make_env(jax.random.PRNGKey(11), 8, 2, 4))
    assert 0.0 <= float(other.warm_rho) < 1.0


def test_replan_dispatch_no_host_transfer(small_env, adam_engine):
    """Acceptance: replan and replan_many enqueue with zero host-side numpy
    -- the rho gate, moment decay, and warm payload are all device ops, so
    dispatch survives jax.transfer_guard('disallow') once compiled."""
    eng = adam_engine
    # make_env leaves the radio/comp constants as python floats; a device-
    # resident pipeline (Scenario.env_many is jitted) has them on device
    # already, so place them once before the guarded dispatch.
    env2 = jax.device_put(make_env(jax.random.PRNGKey(21), 8, 2, 4))
    state = eng.replan(eng.plan(small_env), jax.device_put(small_env))
    envs = stack_envs([small_env, env2])
    fleet = eng.replan_many(eng.plan_many(envs), envs)
    jax.block_until_ready((state, fleet))
    with jax.transfer_guard("disallow"):
        state2 = eng.replan(state, env2)
        fleet2 = eng.replan_many(fleet, envs)
    jax.block_until_ready((state2, fleet2))
    assert float(state2.warm_rho) >= 0.0
    assert fleet2.warm_rho.shape == (2,)


def test_replan_rho_threshold_one_equals_cold(small_env):
    """warm_rho_min=1.0: the correlation estimate is (almost surely) below
    threshold, so replan runs the exact cold Li-GD chain -- same split, same
    utility, same iteration count as a fresh plan()."""
    w = make_weights(small_env.n_users)
    eng = PlannerEngine(profiles.nin(), weights=w, cfg=ADAM_CFG,
                        warm_rho_min=1.0)
    first = eng.plan(small_env)
    env2 = make_env(jax.random.PRNGKey(42), 8, 2, 4)  # uncorrelated draw
    warm = eng.replan(first, env2)
    ref = eng.plan(env2)
    assert int(warm.total_iters) == int(ref.total_iters)
    assert int(warm.plan.s) == int(ref.plan.s)
    assert float(warm.plan.utility) == pytest.approx(float(ref.plan.utility),
                                                     abs=1e-6)


def test_gate_retune_recompiles(small_env):
    """warm_rho_min is a trace-time constant of the compiled replan program,
    so retuning it on a live engine must compile a fresh program (cache key)
    and actually change the gate -- not silently keep the old threshold."""
    w = make_weights(small_env.n_users)
    eng = PlannerEngine(profiles.nin(), weights=w, cfg=ADAM_CFG,
                        warm_rho_min=0.0)
    first = eng.plan(small_env)
    env2 = make_env(jax.random.PRNGKey(42), 8, 2, 4)  # uncorrelated draw
    eng.replan(first, env2)                           # gate open at 0.0
    n = eng.cache_size()
    eng.warm_rho_min = 1.0
    gated = eng.replan(first, env2)
    assert eng.cache_size() == n + 1
    # threshold 1.0 now gates the stale start off: exact cold Li-GD chain
    ref = eng.plan(env2)
    assert int(gated.total_iters) == int(ref.total_iters)
    assert float(gated.plan.utility) == pytest.approx(
        float(ref.plan.utility), abs=1e-6)


def test_engine_rejects_unknown_method():
    with pytest.raises(KeyError):
        PlannerEngine(profiles.nin(), method="newton")
    with pytest.raises(ValueError):
        PlannerEngine(profiles.nin(), warm_rho_min=1.5)
    with pytest.raises(ValueError):
        PlannerEngine(profiles.nin(), warm_moment_decay=-0.1)


# -- online serving hook ---------------------------------------------------
def test_online_split_server_replan_schedule(small_env):
    from repro.runtime.serve import OnlineSplitServer

    w = make_weights(small_env.n_users)
    eng = PlannerEngine(profiles.nin(), weights=w, cfg=ADAM_CFG)
    srv = OnlineSplitServer(eng, replan_every=2)
    scfg = ScenarioConfig(n_users=8, n_aps=2, n_sub=4, fading_rho=0.99,
                          speed_mps=0.0, arrival_rate_hz=0.0)
    sc = Scenario(scfg)
    for env in sc.episode(jax.random.PRNGKey(1), 5):
        srv.observe(env)
    assert srv.epoch == 5
    # replans at epochs 0, 2, 4; the first one must have re-cut
    assert srv.state is not None
    assert 1 <= srv.recuts <= 3
    assert srv.split_layer == int(srv.state.plan.s)
    assert srv.total_iters > 0
    with pytest.raises(ValueError):
        OnlineSplitServer(eng, replan_every=0)


def test_online_split_server_shape_change_resets_cold(small_env):
    """A network shape change mid-serve (user churn beyond slot replacement)
    must not raise: observe() resets the warm state and re-plans cold, as the
    engine docstring promises."""
    from repro.runtime.serve import OnlineSplitServer

    eng = PlannerEngine(profiles.nin(), cfg=ADAM_CFG)
    srv = OnlineSplitServer(eng, replan_every=1)
    srv.observe(small_env)                                  # (8, 2, 4)
    assert srv.cold_resets == 0
    grown = make_env(jax.random.PRNGKey(5), 10, 2, 4)       # U changed
    srv.observe(grown)                                      # must not raise
    assert srv.cold_resets == 1
    assert srv.state is not None
    assert srv.state.norms["beta_up"].shape[1:] == (10, 4)
    srv.observe(make_env(jax.random.PRNGKey(6), 10, 2, 4))  # warm again
    assert srv.cold_resets == 1
    assert srv.epoch == 3
    # the metrics() view agrees with the attribute counters and carries the
    # control-plane totals the online loop reports
    m = srv.metrics()
    assert m["cold_resets"] == 1 and m["epoch"] == 3
    assert m["replans"] == 3 and m["forced_replans"] == 0
    assert m["split_layer"] == int(srv.state.plan.s)
    assert m["total_iters"] == srv.total_iters > 0


def test_online_split_server_forced_and_measured_replans(small_env):
    """QoS-forced replans run off-schedule and are counted separately; a
    measured profile (ModelProfile.like) reuses the compiled replan program;
    an incompatible profile raises ProfileShapeError before dispatch."""
    import dataclasses

    from repro.core.types import ProfileShapeError
    from repro.runtime.serve import OnlineSplitServer

    prof = profiles.nin()
    eng = PlannerEngine(prof, cfg=ADAM_CFG)
    srv = OnlineSplitServer(eng, replan_every=4)
    srv.observe(small_env)                        # epoch 0: scheduled
    srv.observe(small_env)                        # epoch 1: no replan
    assert srv.metrics()["replans"] == 1
    srv.observe(small_env, force=True)            # epoch 2: forced (traces)
    measured = prof.like(prof.fl * 2.0, prof.w, prof.m_down)
    with compile_log() as log:
        srv.observe(small_env, prof=measured, force=True)  # epoch 3: forced
    assert log == []                              # same compiled program
    m = srv.metrics()
    assert m["replans"] == 3 and m["forced_replans"] == 2
    bad = dataclasses.replace(prof, fl=prof.fl[:-1])
    with pytest.raises(ProfileShapeError):
        srv.observe(small_env, prof=bad, force=True)
