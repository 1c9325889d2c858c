"""Whole runs of the harness on the CPU at 24 users, skipping its look for
a chip: a cell added by data files alone, a model configuration added by
files alone, the bfloat16 control, and the timed path broken underneath in
each way this planner cell can break."""
import dataclasses
import json
import shutil
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cell as celllib
from perfbench import check as checklib
from perfbench import reference, spec
from perfbench.rehearse import tiny

ROOT = Path(__file__).resolve().parents[1]
TINY = "vgg16-einsum.replan10"


def _cell(name=TINY):
    return tiny(spec.cell(name))


def _run(c, control=False, seconds=0.2, trace=False):
    return celllib.run(c, seed=2**31 + 5, seconds=seconds, trace=trace,
                       t_start=time.perf_counter(), require_tpu=False,
                       control=control)


def _failed(checks, control=False):
    return [k for k, v in checks.items()
            if k.startswith("control.") == control and v["value"] > v["limit"]]


def test_a_cell_added_by_files_alone(tmp_path):
    shutil.copytree(ROOT, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.BENCHMARK, tmp_path / "BENCHMARK.json")
    root = tmp_path / "perfbench"
    traffic = json.load(open(root / "traffic" / "replan10.json"))
    traffic.update(name="throwaway")
    traffic["service"]["replan_every"] = 3
    traffic["scenario"]["fading_rho"] = 0.9
    json.dump(traffic, open(root / "traffic" / "throwaway.json", "w"))
    (root / "metrics" / "throwaway_epochs.py").write_text(
        "def read(run):\n    return float(run.epochs)\n")
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["workloads"].append({"name": "vgg16-einsum.throwaway",
                               "config": "sec6-vgg16-einsum",
                               "traffic": "throwaway", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "throwaway_epochs", "unit": "epochs",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["vgg16-einsum.throwaway"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    c = tiny(spec.cell("vgg16-einsum.throwaway",
                       benchmark=tmp_path / "BENCHMARK.json", root=root))
    assert c.traffic["service"]["replan_every"] == 3
    r = _run(c)
    assert r.correct, r.checks
    assert r.metrics["throwaway_epochs"]["value"] == r.attempted > 0
    assert {"epoch_ms", "setup_s"} <= set(r.metrics)


def test_program_passes_and_the_bf16_control_fails():
    r = _run(_cell(), control=True)
    assert r.correct, r.checks
    assert not _failed(r.checks)
    assert _failed(r.checks, control=True), r.checks


def _stale_step(monkeypatch):
    """A Li-GD step that returns its state unchanged."""
    from repro.core import li_gd
    orig = li_gd.gd_solve

    def gd_solve(env, prof, s, w, init_norm, cfg, *a, **k):
        return orig(env, prof, s, w, init_norm,
                    dataclasses.replace(cfg, max_iters=0), *a, **k)
    monkeypatch.setattr(li_gd, "gd_solve", gd_solve)


def _half_batch(monkeypatch):
    """Gamma_s over half of the users, the mean taken over the rest."""
    from repro.core import li_gd
    from repro.core.utility import per_user_utility

    def utility(env, prof, s, v, w, backend=None, layout=None):
        per = per_user_utility(env, prof, s, v, w, backend=backend,
                               layout=layout)
        half = per.shape[0] // 2
        return 2.0 * jnp.sum(per[:half])
    monkeypatch.setattr(li_gd, "_utility", utility)


def _altered_answer(monkeypatch):
    """The plan's uplink subchannels altered where they are produced."""
    from repro.core import li_gd
    orig = li_gd.assemble_plan

    def assemble_plan(env, loop, prof, **k):
        plan = orig(env, loop, prof, **k)
        return dataclasses.replace(plan, sub_up=(plan.sub_up + 1) % env.n_sub)
    monkeypatch.setattr(li_gd, "assemble_plan", assemble_plan)


# The exchange between chips cannot be left out: these cells run on one.
@pytest.mark.parametrize("fault", [_stale_step, _half_batch, _altered_answer])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = _run(_cell())
    assert not r.correct
    assert _failed(r.checks), r.checks


def test_a_window_that_compiles_is_not_correct(monkeypatch):
    """Every epoch traces and compiles a new program: the window's compile
    events make the run fail, whatever the plan."""
    import jax

    from repro.online import loop as looplib
    orig = looplib.OnlineLoop.step_epoch

    def step_epoch(self):
        jax.block_until_ready(jax.jit(lambda x: x + 1.0)(jnp.float32(0.0)))
        return orig(self)
    monkeypatch.setattr(looplib.OnlineLoop, "step_epoch", step_epoch)
    r = _run(_cell())
    assert not r.correct
    assert r.checks["window_compiles"]["value"] > 0
    assert _failed(r.checks) == ["window_compiles"]


# -- a model configuration brought by files alone ------------------------------
# A decoder LM whose split the planner prices. Its configuration file states
# the model twice: as the keyword arguments of the program's profile
# function (``profile_args``) and as the layer chain its own reference
# module reads, with a layer kind, "block", that perfbench/reference.py
# does not know.
LM_ARCH = {"name": "lm-test", "family": "dense", "n_layers": 8,
           "d_model": 256, "n_heads": 4, "n_kv_heads": 2, "d_ff": 512,
           "vocab_size": 1024}
LM_MODEL = {
    "profile": "lm_blocks",
    "profile_args": {"arch": LM_ARCH, "seq": 128, "batch": 1},
    "w_T": 0.5, "seq": 128, "batch": 1, "vocab_size": 1024,
    "token_bits": 32, "act_bits": 16,
    "layers": [["block", 256, 4, 2, 512]] * 8,
}

# The configuration's module: the model's reference (it imports nothing of
# the program), the loop hook, the served checks and the rehearsal's cut.
# ALTER, CORE and RAISE break it for the failure cases.
LM_MODULE = '''
import numpy as np

ALTER, CORE, RAISE = {alter}, {core}, {raise_}


def profile(model):
    seq, batch = model["seq"], model["batch"]
    fl = []
    for kind, d, heads, kv_heads, d_ff in model["layers"]:
        if kind != "block":
            raise ValueError(kind)
        kv = kv_heads * (d // heads)
        proj = 2.0 * seq * d * (2 * d + 2 * kv)      # q, k, v, o
        core = 4.0 * seq * seq * d * CORE            # scores and AV
        mlp = 6.0 * seq * d * d_ff                   # SwiGLU
        fl.append((proj + core + mlp) * batch)
    f = len(fl)
    w = np.full(f + 1, float(batch * seq * model["layers"][0][1]
                             * model["act_bits"]))
    w[0], w[f] = batch * seq * model["token_bits"], 0.0
    m_down = np.full(f + 1, float(batch * model["vocab_size"]
                                  * model["act_bits"]))
    m_down[f] = 0.0
    return (np.asarray(fl, np.float32), w.astype(np.float32),
            m_down.astype(np.float32))


def build_loop(cfg, traffic, engine, build):
    loop = build(cfg, traffic, engine)
    loop.built_by = cfg["name"]
    return loop


def served_checks(cfg, loop):
    import jax
    if RAISE:
        raise RuntimeError("the served model's reference failed")
    s = int(jax.device_get(loop.server.state.plan.s))
    return {{"served_split": {{"value": abs(loop.server.split_layer - s)
                                        + ALTER, "limit": 0}},
            "built_by_hook": {{"value": int(getattr(loop, "built_by", None)
                                            != cfg["name"]), "limit": 0}}}}


def tiny(cfg):
    model = cfg["model"]
    model["layers"] = model["layers"][:4]
    model["profile_args"]["arch"]["n_layers"] = 4
    return cfg
'''

COUNTER_READER = ("def read(run):\n"
                  "    n = run.counters.get('completed', 0)\n"
                  "    return n / run.epochs if n and run.epochs else None\n")
SCOPE_READER = ("from perfbench import spans\n\n\n"
                "def read(run):\n"
                "    if not run.scopes:\n"
                "        return None\n"
                "    return 1e3 * spans.scope_seconds(run.trace, run.scopes,\n"
                "                                     'gd_iter')\n")
RECORDED = ROOT / "tests" / "data" / "tpu_loop_einsum.xplane.pb"


def lm_blocks(arch, seq, batch):
    """The program's profile of a decoder LM from JSON arguments: stands in
    for the per-layer-kind LM profile the program does not have yet."""
    from repro.configs import ArchConfig
    from repro.core import profiles
    return profiles.from_arch_config(ArchConfig(**arch), seq, batch)


def _lm_cell(tmp_path, monkeypatch, alter=0, core=1.0, raise_=False):
    """The cell ``lm-test.replan1`` in a copy of the benchmark to which only
    files and entries were added."""
    from repro.core import profiles
    monkeypatch.setattr(profiles, "lm_blocks", lm_blocks, raising=False)
    shutil.copytree(ROOT, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    root = tmp_path / "perfbench"
    cfg = json.load(open(root / "configs" / "sec6-vgg16-einsum.json"))
    cfg.update(name="lm-test", model=json.loads(json.dumps(LM_MODEL)),
               reference="perfbench/models/lm_test.py")
    json.dump(cfg, open(root / "configs" / "lm-test.json", "w"))
    (root / "models").mkdir()
    (root / "models" / "lm_test.py").write_text(
        LM_MODULE.format(alter=alter, core=core, raise_=raise_))
    (root / "metrics" / "lm_completed_per_epoch.py").write_text(
        COUNTER_READER)
    (root / "metrics" / "lm_gd_scope_ms.py").write_text(SCOPE_READER)
    bench = json.load(open(spec.BENCHMARK))
    bench["configs"].append({"name": "lm-test", "source": "test",
                             "file": "perfbench/configs/lm-test.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "lm-test.replan1", "config": "lm-test",
                               "traffic": "replan1", "chips": 1,
                               "why": "test"})
    for name in ("lm_completed_per_epoch", "lm_gd_scope_ms"):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "program_counter", "layer": "test", "moves": "epoch_ms",
            "workloads": ["lm-test.replan1"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    return tiny(spec.cell("lm-test.replan1",
                          benchmark=tmp_path / "BENCHMARK.json", root=root))


def test_the_lm_layer_kind_is_unknown_to_the_planner_reference():
    with pytest.raises(ValueError, match="block"):
        reference.profile({"input_hwc": [1, 1, 1], "input_bits": 8,
                           "act_bits": 16, "result_bits": 8,
                           "layers": LM_MODEL["layers"]})


def test_a_model_configuration_added_by_files_alone(tmp_path, monkeypatch):
    """Its reference module prices the split, its hook builds the loop, its
    served checks join the run's, its cut reaches the rehearsal, and
    readers of a new counter and of a new scope find what they read. The
    run's trace is replaced by one recorded on the chip, since a CPU trace
    holds no device name stacks."""
    from perfbench import spans
    from perfbench import trace as tracelib
    recorded = tracelib.load(str(RECORDED)), spans.op_scopes(str(RECORDED))
    c = _lm_cell(tmp_path, monkeypatch)
    assert len(c.config["model"]["layers"]) == 4
    monkeypatch.setattr(tracelib, "load", lambda _: recorded[0])
    monkeypatch.setattr(spans, "op_scopes", lambda _: recorded[1])
    r = _run(c, trace=True)
    assert r.correct, r.checks
    assert r.checks["served_split"] == {"value": 0, "limit": 0}
    assert r.checks["built_by_hook"] == {"value": 0, "limit": 0}
    assert r.metrics["lm_completed_per_epoch"]["value"] > 0
    assert r.metrics["lm_gd_scope_ms"]["value"] > 0


@pytest.mark.parametrize("fault, failing", [
    (dict(alter=1), ["served_split"]),
    (dict(core=0.0), ["split_utility"]),
    (dict(raise_=True), ["served_checks_raised"]),
], ids=["hook-number-altered", "reference-profile-disagrees", "hook-raises"])
def test_a_model_configuration_turns_incorrect(tmp_path, monkeypatch, fault,
                                               failing):
    """The hook's number altered, a reference profile that leaves out the
    attention scores, or a hook that raises: each fails the run."""
    r = _run(_lm_cell(tmp_path, monkeypatch, **fault))
    assert not r.correct
    assert set(failing) <= set(_failed(r.checks)), r.checks


@pytest.mark.parametrize("name", ["vgg16-einsum.replan10",
                                  "vgg16-pallas.replan1"])
def test_vgg16_checks_through_the_configured_module_are_bit_equal(
        monkeypatch, name):
    """The checks of a rehearsal run, computed through the module the
    configuration names, equal those of a direct call into
    perfbench/reference.py, bit for bit."""
    c = _cell(name)
    assert c.config["reference"] == "perfbench/reference.py"
    assert c.module is not reference
    seen = []
    orig = checklib.check

    def check(cfg, model, *args, **kw):
        seen.append((cfg, args))
        return orig(cfg, model, *args, **kw)
    monkeypatch.setattr(checklib, "check", check)
    r = _run(c)
    assert r.correct, r.checks
    cfg, args = seen[0]
    direct = orig(cfg, reference, *args)
    via = {k: v for k, v in r.checks.items() if k in direct}
    assert via.keys() == direct.keys()
    for k, v in direct.items():
        assert np.float64(via[k]["value"]).tobytes() == \
            np.float64(v["value"]).tobytes(), k
