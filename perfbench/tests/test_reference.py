"""The plain reference agrees with the system's einsum path at a small size
on the CPU: rates, utility, one replan's split solves and the rounding."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference as ref
from perfbench.check import best_rounding

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "sec6-vgg16-einsum.json"


@pytest.fixture(scope="module")
def setting():
    from repro.core import make_env, profiles
    from repro.core.types import ComputeConstants, RadioConstants
    cfg = json.load(open(CONFIG))
    env = make_env(jax.random.PRNGKey(3), 24, 3, 8,
                   radio=RadioConstants(**cfg["radio"]),
                   comp=ComputeConstants(**cfg["compute"]))
    net = ref.Net(env.g_up, env.g_dn, env.ap)
    return cfg, env, net, ref.consts(cfg), profiles.vgg16()


def _point(u, m, key):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(key), 3)
    share = lambda k: jax.nn.softmax(jax.random.normal(k, (u, m)), -1)
    return {"beta_up": share(k1), "beta_dn": share(k2),
            "p_up": jax.random.uniform(k3, (u,)), "p_dn": jnp.full((u,), 0.3),
            "r": jnp.full((u,), 0.7)}


def test_profile_is_the_systems_vgg16(setting):
    cfg, *_, prof = setting
    for a, b in zip(ref.profile(cfg["model"]), (prof.fl, prof.w, prof.m_down)):
        np.testing.assert_array_equal(a, np.asarray(b))


NIN = {"input_hwc": [32, 32, 3], "input_bits": 8, "act_bits": 16,
       "result_bits": 320, "layers": [
           ["conv", 192, 5, 1], ["conv", 160, 1, 1], ["conv+pool", 96, 1, 1, 2],
           ["conv", 192, 5, 1], ["conv", 192, 1, 1], ["conv+pool", 192, 1, 1, 2],
           ["conv", 192, 3, 1], ["conv", 192, 1, 1], ["conv", 10, 1, 1]]}
YOLOV2 = {"input_hwc": [64, 64, 3], "input_bits": 8, "act_bits": 16,
          "result_bits": 2 * 2 * 125 * 16, "layers": [
              ["conv+pool", 32, 3, 1, 2], ["conv+pool", 64, 3, 1, 2],
              ["conv", 128, 3, 1], ["conv", 64, 1, 1], ["conv+pool", 128, 3, 1, 2],
              ["conv", 256, 3, 1], ["conv", 128, 1, 1], ["conv+pool", 256, 3, 1, 2],
              ["conv", 512, 3, 1], ["conv", 256, 1, 1], ["conv", 512, 3, 1],
              ["conv", 256, 1, 1], ["conv+pool", 512, 3, 1, 2],
              ["conv", 1024, 3, 1], ["conv", 512, 1, 1], ["conv", 1024, 3, 1],
              ["conv", 125, 1, 1]]}


@pytest.mark.parametrize("name, model", [("nin", NIN), ("yolov2", YOLOV2)])
def test_other_chains_from_a_model_section(name, model):
    """A NiN or YOLOv2 configuration needs only its model section."""
    from repro.core import profiles
    prof = getattr(profiles, name)()
    for a, b in zip(ref.profile(model), (prof.fl, prof.w, prof.m_down)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_rates_and_utility_match(setting):
    from repro.core import channel, li_gd, make_weights
    from repro.core.utility import utility
    cfg, env, net, c, prof = setting
    norm = _point(24, 8, 0)
    v = li_gd.to_physical(norm, env)
    want = channel.user_rates(env, v.beta_up, v.beta_dn, v.p_up, v.p_dn,
                              backend="einsum")
    got = ref.rates(net, c, v.beta_up, v.beta_dn, v.p_up, v.p_dn)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6)
    p = ref.profile(cfg["model"])
    w = make_weights(24, cfg["model"]["w_T"])
    for s in (0, 5, 24):
        want = utility(env, prof, jnp.int32(s), v, w, backend="einsum")
        got = ref.gamma(net, p, c, s, ref.physical(norm, c))
        np.testing.assert_allclose(got, want, rtol=2e-6)


def test_replan_and_rounding_match(setting):
    from repro.core import GdConfig, make_weights
    from repro.planning import PlannerEngine
    cfg, env, net, c, prof = setting
    gd = cfg["planner"]["gd"]
    eng = PlannerEngine(prof, weights=make_weights(24, 0.5), cfg=GdConfig(**gd))
    prev = eng.plan(env)
    env2 = env.__class__(g_up=env.g_up * 1.01, g_dn=env.g_dn, ap=env.ap,
                         radio=env.radio, comp=env.comp)
    out = eng.replan(prev, env2)
    net2 = ref.Net(env2.g_up, env2.g_dn, env2.ap)
    solved = ref.replan_by_split(
        net2, ref.profile(cfg["model"]), c, gd, 0.5, 0.1,
        {"norms": prev.norms, "m1": prev.moms[0], "m2": prev.moms[1],
         "steps": prev.opt_steps, "gains": prev.gains}, out.norms)
    np.testing.assert_allclose(solved.gamma, out.plan.per_layer_utility,
                               rtol=1e-5)
    for k in ("beta_up", "p_up", "r"):
        np.testing.assert_allclose(solved.m1[k][:-1], out.moms[0][k][:-1],
                                   rtol=1e-3, atol=1e-6)
    s = int(out.plan.s)
    r = ref.rounding_of(net2, ref.profile(cfg["model"]), c, s,
                        jax.tree.map(lambda x: x[s], out.norms))
    pick = best_rounding(r)
    np.testing.assert_array_equal(r[pick][0], out.plan.sub_up)
    np.testing.assert_array_equal(r[pick][1], out.plan.sub_dn)
