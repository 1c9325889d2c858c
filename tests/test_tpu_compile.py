"""Ahead-of-time compiles for a TPU v5e chip that is described, not attached.

The Pallas interpreter accepts kernel bodies the TPU compiler (Mosaic)
refuses -- 3-D broadcasts, integer indexing of refs, illegal block shapes,
too much SMEM or VMEM -- so the interpret-mode parity tests cannot show the
kernels run on the chip. These tests compile the NOMA pairwise kernels,
forward and backward, at the paper's scale (U = 1250, M = 250) for a small
and a massive AP count, plus one pallas-backend Li-GD step, and check the
kernel made it into the program (``tpu_custom_call``). A small replan
program shows what its named scopes leave in the compiled program.

The topology is described inside a module fixture (never at import time or
in ``parametrize``): only one process may load the TPU library, and under
pytest-xdist only the worker given this file may try. The persistent
compilation cache is off around these compiles, since an entry written
without a chip cannot be read back.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import GdConfig, li_gd, make_env, make_weights, profiles
from repro.kernels import ops
from repro.kernels.noma_rates import AUTOTUNE_BLOCKS

U, M = 1250, 250


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shape-only stand-ins for tree's array leaves, placed on the chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _env_shapes(sharding, n_aps: int):
    env = jax.eval_shape(
        lambda k: make_env(k, U, n_aps, M), jax.random.PRNGKey(0))
    return _on(sharding, env)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


_PAIRWISE = {"up": ops.noma_pairwise_up, "dn": ops.noma_pairwise_dn}


def _pairwise_grad(pairwise, **blocks):
    def loss(env, tx):
        intra, inter = pairwise(env, tx, **blocks)
        return jnp.sum(intra) + jnp.sum(inter)
    return jax.grad(loss, argnums=1)


@pytest.mark.parametrize("n_aps", [16, 4096])
@pytest.mark.parametrize("link", ["up", "dn"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_noma_pairwise_compiles_for_v5e(one_chip, n_aps, link, direction):
    """Forward (intra, inter) and jax.grad through the custom_vjp at
    DEFAULT_BLOCKS: the cell-intra, per-AP and contract kernels all lower
    through Mosaic, with the dense tile list in SMEM."""
    pairwise = _PAIRWISE[link]
    fn = pairwise if direction == "fwd" else _pairwise_grad(pairwise)
    env = _env_shapes(one_chip, n_aps)
    tx = jax.ShapeDtypeStruct((U, M), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(fn, env, tx)


@pytest.mark.parametrize("blocks", AUTOTUNE_BLOCKS,
                         ids=lambda b: "x".join(map(str, b)))
def test_autotune_candidates_compile_for_v5e(one_chip, blocks):
    """Every (BU, BV, BM, BN) the autotuner may pick compiles, both links,
    forward and backward (one program)."""
    bu, bv, bm, bn = blocks
    kw = dict(block_u=bu, block_v=bv, block_m=bm, block_n=bn)
    grads = [_pairwise_grad(p, **kw) for p in _PAIRWISE.values()]
    env = _env_shapes(one_chip, 16)
    tx = jax.ShapeDtypeStruct((U, M), jnp.float32, sharding=one_chip)
    text = _compile_text(lambda e, t: [g(e, t) for g in grads], env, tx)
    assert text.count("tpu_custom_call") >= 2


def test_gd_solve_step_compiles_for_v5e(one_chip):
    """One jitted pallas-backend Li-GD gradient step (max_iters=1) at the
    paper's Sec. VI cell: the kernels inside the solver's while_loop."""
    cfg = GdConfig(max_iters=1, optimizer="adam", sinr_backend="pallas")
    prof = profiles.vgg16()

    def step(env, w):
        res = li_gd.gd_solve(env, prof, jnp.int32(5), w, li_gd.cold_init(env),
                             cfg)
        return res.gamma

    env = _env_shapes(one_chip, 16)
    w = _on(one_chip, jax.eval_shape(lambda: make_weights(U)))
    assert "tpu_custom_call" in _compile_text(step, env, w)


@pytest.fixture(scope="module")
def replan_text(one_chip):
    """The optimized v5e HLO of the planner's replan program on the Pallas
    path, at a small cell (U=24, N=3, M=8)."""
    from repro.planning import PlannerEngine

    u, n, m = 24, 3, 8
    env = jax.eval_shape(lambda k: make_env(k, u, n, m),
                         jax.random.PRNGKey(0))
    eng = PlannerEngine(profiles.vgg16(), weights=make_weights(u),
                        cfg=GdConfig(max_iters=2, optimizer="adam"),
                        sinr_backend="pallas")
    cold = jax.eval_shape(eng.program("plan", env),
                          *eng.program_args("plan", env))
    args = _on(one_chip, eng.program_args("replan", env, prev=cold))
    lowered = eng.program("replan", env).lower(*args)
    assert lowered.as_text().startswith("module @jit_replan")
    return lowered.compile().as_text()


@pytest.mark.parametrize("scope", [
    "noma_intra_up_fwd", "noma_intra_dn_fwd", "noma_per_ap_up_fwd",
    "noma_contract_dn_fwd", "noma_intra_up_bwd", "noma_intra_dn_bwd",
    "noma_contract_up_bwd", "noma_per_ap_dn_bwd"])
def test_kernel_calls_are_named_by_their_scope(replan_text, scope):
    """The TPU compiler names each NOMA kernel's custom call after its
    innermost named scope (kernel, link, pass), so the device trace's op
    events tell the calls apart."""
    calls = re.findall(r"^\s*(?:ROOT )?%(\S+) = [^\n]*custom-call\(",
                       replan_text, re.M)
    assert any(scope in c for c in calls)


@pytest.mark.parametrize("scope", ["gd_iter", "warm_gate", "greedy_rounding"])
def test_solver_phases_reach_the_op_metadata(replan_text, scope):
    """Fusions and loops keep their generic names; the solver's phase
    scopes reach every op's name stack (op_name), which the trace carries
    as each op's tf_op."""
    assert re.search(rf'op_name="jit\(replan\)/[^"]*\b{scope}/',
                     replan_text)
