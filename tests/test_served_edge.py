"""The served model's edge program (repro.online.edge) against the plain
float32 reference of perfbench/trinity_mini.py, at a small size on
the CPU: chunked prefill then decode through the slot caches, across the
sliding window, at every kind of split; the expert share; the split as an
operand; the planner profile; and the loop with and without a model."""
import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import cell as celllib  # noqa: E402
from perfbench import reference, spec  # noqa: E402
from perfbench import trinity_mini as tm  # noqa: E402
from perfbench.rehearse import tiny  # noqa: E402
from repro import configs  # noqa: E402
from repro.configs import ArchConfig  # noqa: E402
from repro.core import profiles  # noqa: E402
from repro.core.types import GdConfig  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models.layers import init_params  # noqa: E402
from repro.online import OnlineLoop, ServiceConfig, StreamConfig  # noqa: E402
from repro.online import batcher, edge  # noqa: E402
from repro.planning import PlannerEngine, compile_log  # noqa: E402
from repro.scenarios import Scenario, ScenarioConfig  # noqa: E402

CELL = "trinity-mini.chat"
# Trinity-Mini's block at a small width: two leading dense layers, then
# experts, sliding window 16 with full attention every 4th layer.
ARCH = {"name": "trinity-mini", "family": "moe", "n_layers": 8,
        "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 128, "vocab_size": 512, "rope_theta": 10000.0,
        "norm_eps": 1e-5, "n_experts": 16, "top_k": 4, "moe_d_ff": 32,
        "n_shared_experts": 1, "first_dense_layers": 2, "window": 16,
        "global_attn_every": 4, "score_func": "sigmoid", "route_scale": 2.826}
HELD = (0, 4)
# One 40-token prompt (past the window) in chunks of 8, 12 output tokens,
# 3 decode steps an epoch.
ECFG = edge.EdgeConfig(slots=2, max_context=64, chunk=8, prefill_budget=16,
                       decode_steps=3, prompt=(40, 0.0, 40, 40),
                       output=(12, 0.0, 12, 12))


# The reference module's view of this model: its architecture and share.
MODEL = {"model": {"profile_args": {"arch": ARCH}},
         "served": {"experts_held": HELD}}


@pytest.fixture(scope="module")
def served():
    """The edge serving the reference module's weights."""
    cfg = ArchConfig(**ARCH, experts_held=HELD)
    server = edge.EdgeServer(cfg, ECFG, dt=0.1,
                             params=tm.weights(MODEL, jax.random.PRNGKey(3)))
    server.init(jax.random.PRNGKey(4))
    return server


def _one_request(server, s_of_epoch, epochs=6):
    """Serve one request in slot 0; its decode steps' positions and what
    the program kept at each (logits, each MoE layer's input, experts and
    output), and the slot's tokens."""
    st = edge.init_state(server.cfg, ECFG)
    bt = batcher.init_state(ECFG.slots, 4)
    bt = bt._replace(active=bt.active.at[0].set(True),
                     work=bt.work.at[0].set(1))
    key = jax.random.PRNGKey(9)
    for e in range(epochs):
        st, bt, health = server.step(st, bt, jnp.int32(s_of_epoch(e)), key,
                                     jnp.int32(0))
        assert int(health) == 0
    n = int(st.n_out[0]) - 1
    kept = np.flatnonzero(np.asarray(st.kept_rid[0]) == int(st.rid[0]))
    order = kept[np.argsort(np.asarray(st.kept_pos[0])[kept])]
    steps = {"at": int(st.prompt[0]) + np.arange(n),
             "kept_at": np.asarray(st.kept_pos[0])[order],
             "logits": np.asarray(st.kept_logits[0], np.float64)[order],
             "moe_in": np.asarray(st.kept_moe_in[0])[order],
             "experts": np.asarray(st.kept_experts[0])[order],
             "moe_out": np.asarray(st.kept_moe_out[0], np.float64)[order]}
    return steps, np.asarray(st.tokens[0]), st, bt


@pytest.mark.parametrize("split", [0, 4, 8], ids=["s0", "mid", "F"])
def test_chunked_prefill_and_decode_match_the_reference(served, split):
    """Prefill in chunks, then decode through the slot caches, past the
    16-position window: every decode step's logits agree with the
    reference's full forward over the same tokens, and each MoE layer's
    experts and output with the reference's layer on the program's own
    input, whatever the split."""
    steps, tokens, st, bt = _one_request(served, lambda e: split)
    at = steps["at"]
    assert len(at) == 11                    # 12 outputs: 1 from prefill
    assert at[0] == 40 and at[-1] == 50     # across the window boundary
    assert np.array_equal(steps["kept_at"], at)   # 4 epochs of 3 steps kept
    gaps = tm._gaps([steps], [_reference(steps, tokens)])
    assert gaps["served_logits"] < 0.03, gaps
    assert gaps["served_experts"] == 0.0, gaps
    assert gaps["served_moe"] < 0.01, gaps
    # the request ended: the batcher completes it next epoch
    assert not bool(st.busy[0]) and int(bt.work[0]) == 0
    assert int(st.counters.requests_done) == 1
    assert int(st.counters.prefill_tokens) == 40
    assert int(st.counters.decode_tokens) == 11


def _reference(steps, tokens, store=None):
    """The reference's logits, experts and MoE outputs at the kept steps."""
    params = tm.weights(MODEL, jax.random.PRNGKey(3))
    at = steps["kept_at"]
    logits, _ = tm.forward(params, ARCH, HELD, tokens[:at.max() + 1], at,
                           store)
    moe_out, experts = tm.moe_layers(params, ARCH, HELD, steps["moe_in"],
                                     steps["experts"], store)
    return {"logits": logits, "experts": experts, "moe_out": moe_out}


@pytest.mark.parametrize("fault", ["previous_token", "local_top_k", "float8"])
def test_the_served_checks_read_a_planted_fault(served, fault):
    """Each served check fails on the fault it guards against, against the
    configuration's limits: decode steps that consumed the previous token
    (logits), a router that picks among the held experts only (experts),
    and expert weights and caches stored in float8_e4m3fn, the control
    (the MoE layers' output)."""
    steps, tokens, _, _ = _one_request(served, lambda e: 0)
    want = _reference(steps, tokens)
    got, name = dict(steps), {"previous_token": "served_logits",
                              "local_top_k": "served_experts",
                              "float8": "served_moe"}[fault]
    if fault == "previous_token":
        got["logits"] = _reference(steps, np.roll(tokens, 1))["logits"]
    elif fault == "local_top_k":
        params = tm.weights(MODEL, jax.random.PRNGKey(3))
        h = steps["moe_in"].astype(np.float32)            # (n, n_moe, d)
        scores = np.stack([h[:, i] @ np.asarray(p["moe"]["router"], np.float32)
                           for i, p in enumerate(
                               q for q in params["layers"] if "moe" in q)], 1)
        got["experts"] = HELD[0] + np.argsort(
            -scores[..., HELD[0]:HELD[1]], -1)[..., :ARCH["top_k"]]
    else:
        got = _reference(steps, tokens, jnp.float8_e4m3fn)
    limits = spec.cell(CELL).config["limits"]
    assert tm._gaps([got], [want])[name] > limits[name]
    assert tm._gaps([steps], [want])[name] <= limits[name]


def test_the_split_is_an_operand(served):
    """A split that moves every epoch compiles nothing and changes no
    token."""
    _one_request(served, lambda e: 0, epochs=1)          # compiled here
    with compile_log() as log:
        moved, tok_moved, _, _ = _one_request(served, lambda e: (0, 8, 3)[e % 3])
    assert log == []
    still, tok_still, _, _ = _one_request(served, lambda e: 0)
    assert np.array_equal(tok_moved, tok_still)


def test_the_expert_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of a 16-expert layer: their partial outputs, with
    the shared expert counted once, add up to the uncut reference layer."""
    cfg = dataclasses.replace(configs.get("trinity-mini").reduced(),
                              n_experts=16, top_k=4)
    p = init_params(moe.moe_defs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    with pytest.raises(ValueError, match="impl='dense'"):
        moe.moe_apply(p, jnp.zeros((1, 2, cfg.d_model), jnp.bfloat16),
                      dataclasses.replace(cfg, experts_held=(0, 2)))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.d_model)
                          ).astype(jnp.bfloat16)
    total = 0.0
    for i in range(8):
        held = (2 * i, 2 * i + 2)
        part = dict(p, **{k: p[k][held[0]:held[1]] for k in ("w1", "w2", "w3")})
        y, _ = moe.moe_apply(part, h, dataclasses.replace(
            cfg, experts_held=held), impl="dense")
        total = total + np.asarray(y[0], np.float64)
    shared = np.asarray(moe.shared_expert(p, h[0]), np.float64)
    arch = tm._Arch(top_k=cfg.top_k, route_scale=cfg.route_scale)
    with jax.default_matmul_precision("highest"):
        whole, _ = tm._moe(p, np.asarray(h[0], np.float32), arch, (0, 16),
                           None)
    want = np.asarray(whole, np.float64)
    got = total - 7 * shared
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 0.02


def test_the_profiles_agree_and_vgg16_stays():
    """The program's per-layer-kind profile equals the configuration
    module's numpy profile; VGG16's equals the planner reference's."""
    model = spec.cell(CELL).config["model"]
    prog = profiles.lm(**model["profile_args"])
    for got, want in zip((prog.fl, prog.w, prog.m_down), tm.profile(model)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    vgg = spec.cell("vgg16-einsum.replan10").config["model"]
    for got, want in zip((profiles.vgg16().fl, profiles.vgg16().w,
                          profiles.vgg16().m_down), reference.profile(vgg)):
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    # the window/full pattern and the leading dense layers are priced apart
    fl = np.asarray(prog.fl)
    assert fl[0] == fl[1] < fl[2] < fl[3] and fl[3] == fl[7]


def test_the_configuration_is_the_registered_model_cut():
    """The configuration's architecture is the registered Trinity-Mini cut
    to 8 layers and a vocabulary slice, and states the catalog's widths."""
    cfg = spec.cell(CELL).config
    arch = ArchConfig(**cfg["model"]["profile_args"]["arch"])
    assert arch == dataclasses.replace(configs.get("trinity-mini"),
                                       n_layers=8, vocab_size=25024)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["num_dense_layers"], cfg["route_scale"]) == (
        arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim,
        arch.d_ff, arch.moe_d_ff, arch.top_k, arch.window,
        arch.first_dense_layers, arch.route_scale)
    assert cfg["num_hidden_layers"] == arch.n_layers
    assert cfg["vocab_size"] == arch.vocab_size
    assert cfg["layer_types"] == [
        "full_attention" if arch.layer_window(i) == 0 else "sliding_attention"
        for i in range(arch.n_layers)]
    first, stop = cfg["served"]["experts_held"]
    assert (stop - first, cfg["published"]["num_experts"]) == (
        cfg["num_experts"], arch.n_experts)


@pytest.mark.parametrize("served_model", [False, True],
                         ids=["no-model", "model"])
def test_a_loop_builds_the_edge_program_only_with_a_model(served_model):
    """Without a model the loop builds and dispatches no edge program;
    with one, each epoch runs it after the epoch program."""
    cfg = ArchConfig(**ARCH, experts_held=HELD) if served_model else None
    eng = PlannerEngine(profiles.nin(), cfg=GdConfig(max_iters=2,
                                                     optimizer="adam"))
    loop = OnlineLoop(Scenario(ScenarioConfig(n_users=6, n_aps=2, n_sub=3)),
                      eng, StreamConfig(arrival_rate_hz=2.0),
                      ServiceConfig(edge_capacity=2, replan_every=4),
                      model=cfg, served=ECFG if served_model else None)
    with compile_log() as log:
        loop.reset(jax.random.PRNGKey(0))
        for _ in range(3):
            loop.step_epoch()
    assert ("edge" in log) == served_model
    assert (loop.edge is None) != served_model
    m = loop.metrics()
    assert ("edge_decode_steps" in m) == served_model
    assert m["host_reads"]["trigger"] == 3


def test_the_cell_rehearses_correct():
    """The CPU rehearsal of the cell: correct, the served checks within
    their limits, and the float8 control failing one of them."""
    c = tiny(spec.cell(CELL))
    r = celllib.run(c, seed=2**31 + 11, seconds=0.3, trace=False,
                    t_start=time.perf_counter(), require_tpu=False)
    assert r.correct, r.checks
    assert r.checks["served_slots_missing"]["value"] == 0
    assert r.checks["window_compiles"]["value"] == 0
    assert r.checks["served_logits"]["value"] < \
        r.checks["control.served_logits"]["value"]
    assert r.checks["control.served_moe"]["value"] > \
        r.checks["served_moe"]["limit"]
