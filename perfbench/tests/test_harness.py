"""Whole runs of the harness on the CPU at 24 users, skipping its look for
a chip: a cell added by data files alone, the bfloat16 control, and the
timed path broken underneath in each way this planner cell can break."""
import dataclasses
import json
import shutil
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from perfbench import cell as celllib
from perfbench import spec
from perfbench.rehearse import tiny

ROOT = Path(__file__).resolve().parents[1]
TINY = "vgg16-einsum.replan10"


def _cell(name=TINY):
    return tiny(spec.cell(name))


def _run(c, control=False, seconds=0.2):
    return celllib.run(c, seed=2**31 + 5, seconds=seconds, trace=False,
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
