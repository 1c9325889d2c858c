"""Trinity-Mini (arcee-ai, ``model_type`` afmoe) served at the edge of the
Sec. VI cell: the plain float32 reference of the served layers, the
reference's planner profile, the configuration's hooks (``perfbench/cell.py``)
and the operations and bytes its per-layer metrics are measured against.

The reference imports nothing of ``repro``. It restates, in plain
``jax.numpy`` at float32 and matmul precision "highest", with no cache, no
kernel and no batching, the block the configuration states (its
``model.profile_args.arch``): pre-norm RMSNorm (weight stored as an offset
from 1), grouped-query attention with RoPE on every layer, a sliding window
on every layer but each ``global_attn_every``-th, which attends to all
positions; SwiGLU FFNs on the first ``first_dense_layers`` layers; on the
others a router of ``n_experts`` outputs whose float32 sigmoid scores pick
the top ``top_k``, gated by those scores normalised to sum 1 times
``route_scale``, over the experts this chip holds (``served.experts_held``;
a token routed elsewhere adds nothing here), plus the shared expert.

The weights are this module's: :func:`weights` draws them with plain
``jax.random`` from the key the loop gives its edge at reset
(``loop.edge.key``), at the configuration's widths, each entry N(0, 0.02^2)
in bfloat16, in the layout the program takes. The loop is handed that
function, and the checks draw the same arrays again, widened to float32:
nothing the program made enters the reference.

The served checks replay up to 8 slots, chosen by the seed, that decoded in
the window's last epoch, at the decode steps the program kept (its last
epochs'):

``served_logits``   the logits, together, against the reference's full
                    forward over the same tokens (teacher-forced), as one
                    relative L2 gap;
``served_experts``  the share of (step, MoE layer) top-k expert sets that
                    differ from the reference router's on the layer's own
                    normalised input, as the program kept it;
``served_moe``      each MoE layer's expert output (held and shared
                    experts), together, against the reference's expert
                    layer on that same input and those same experts, as
                    one relative L2 gap: the bfloat16 compute of one layer,
                    with no drift from the layers before it.

The control is the reference with its keys, values and expert weights (held
and shared) stored in float8_e4m3fn: it must fail one of those limits.
"""
from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512             # query and token rows per block of the reference
MAX_SLOTS = 8           # slots replayed by the served checks
NEG = -1e30
SCALE = 0.02            # standard deviation of every weight


# -- the planner profile ------------------------------------------------------
def _windows(a: dict) -> list[int]:
    every = a.get("global_attn_every", 0)
    return [0 if every and (i + 1) % every == 0 else a.get("window", 0)
            for i in range(a["n_layers"])]


def profile(model: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(fl (F,), w (F+1,), m_down (F+1,)) float32 for one request of
    ``seq`` prompt and ``decode`` output tokens, per layer by its kind:
    q/k/v/o projections of every token; 4 FLOPs per query-key pair and head
    dimension, the prefill's ``seq`` queries over min(seq, window) keys and
    decode step t over min(seq + t + 1, window); a SwiGLU of width
    ``d_ff`` on the leading dense layers, else the router and the top_k +
    shared active experts of width ``moe_d_ff``. w: the prompt's ids at
    s = 0, every position's ``act_bits``-wide activation between layers,
    nothing at F; m_down: the output ids, nothing at F. FLOPs are in units
    of ``flop_unit``, the compute constants' unit."""
    args = model["profile_args"]
    a, seq, dec = args["arch"], args["seq"], args["decode"]
    d, h, kv = a["d_model"], a["n_heads"], a["n_kv_heads"]
    hd = a.get("head_dim") or d // h
    tokens = seq + dec
    fl = []
    for i, win in enumerate(_windows(a)):
        ctx = [seq + t + 1 for t in range(dec)]
        pairs = seq * (min(seq, win) if win else seq) + sum(
            min(c, win) if win else c for c in ctx)
        flops = 2.0 * tokens * d * (2 * h + 2 * kv) * hd + 4.0 * pairs * h * hd
        if i >= a.get("first_dense_layers", 0):
            active = a["top_k"] + a.get("n_shared_experts", 0)
            flops += tokens * (6.0 * d * a["moe_d_ff"] * active
                               + 2.0 * d * a["n_experts"])
        else:
            flops += tokens * 6.0 * d * a["d_ff"]
        fl.append(flops / args.get("flop_unit", 1.0))
    f = len(fl)
    w = np.full(f + 1, float(tokens * d * args["act_bits"]))
    w[0], w[f] = float(seq * args["token_bits"]), 0.0
    m_down = np.full(f + 1, float(dec * args["token_bits"]))
    m_down[f] = 0.0
    return (np.asarray(fl, np.float32), w.astype(np.float32),
            m_down.astype(np.float32))


# -- the reference forward --------------------------------------------------
def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def _rope(x, pos, theta):
    """x (T, heads, hd) at positions pos (T,)."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                   * (jnp.log(theta) / half))
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _store(x, store):
    return x if store is None else x.astype(store).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("a", "store"))
def _qkv(p, x, pos, a, store):
    hd = a["head_dim"]
    h = _rms(x, p["ln1_w"], a["norm_eps"])
    q = (h @ _f32(p["attn"]["wq"])).reshape(-1, a["n_heads"], hd)
    k = (h @ _f32(p["attn"]["wk"])).reshape(-1, a["n_kv_heads"], hd)
    v = (h @ _f32(p["attn"]["wv"])).reshape(-1, a["n_kv_heads"], hd)
    k = _rope(k, pos, a["rope_theta"])
    return (_rope(q, pos, a["rope_theta"]), _store(k, store),
            _store(v, store))


@functools.partial(jax.jit, static_argnames=("a", "window"))
def _attend(p, x, q, k, v, q0, a, window):
    """Rows q0.. of the residual stream after attention: their queries over
    every key (causal, within ``window``)."""
    n, kv, hd = q.shape[0], a["n_kv_heads"], a["head_dim"]
    g = a["n_heads"] // kv
    qp = q0 + jnp.arange(n)
    kp = jnp.arange(k.shape[0])
    ok = kp[None, :] <= qp[:, None]
    if window:
        ok &= qp[:, None] - kp[None, :] < window
    qg = q.reshape(n, kv, g, hd) * hd ** -0.5
    s = jnp.einsum("qkgd,ckd->kgqc", qg, k)
    s = jnp.where(ok[None, None], s, NEG)
    o = jnp.einsum("kgqc,ckd->qkgd", jax.nn.softmax(s, -1), v)
    return x + o.reshape(n, -1) @ _f32(p["attn"]["wo"])


def _moe(m, h, a, held, store, idx=None):
    """The expert layer on normalised rows ``h``: the held experts' part
    and the shared expert, and the rows' top-k experts (``idx`` where
    given, else the router's)."""
    silu = jax.nn.silu
    scores = jax.nn.sigmoid(h @ _f32(m["router"]))
    if idx is None:
        idx = jax.lax.top_k(scores, a["top_k"])[1]
    top = jnp.take_along_axis(scores, idx, -1)
    gates = top / jnp.sum(top, -1, keepdims=True) * a["route_scale"]
    first, stop = held
    comb = jnp.sum(jax.nn.one_hot(idx - first, stop - first) * gates[..., None],
                   axis=1)                                   # (n, held)
    w = {k: _store(_f32(m[k]), store) for k in m if k != "router"}
    hid = silu(jnp.einsum("nd,edf->enf", h, w["w1"])) \
        * jnp.einsum("nd,edf->enf", h, w["w3"])
    y = jnp.einsum("end,ne->nd", jnp.einsum("enf,efd->end", hid, w["w2"]),
                   comb)
    if "sw1" in w:
        y = y + (silu(h @ w["sw1"]) * (h @ w["sw3"])) @ w["sw2"]
    return y, idx


@functools.partial(jax.jit, static_argnames=("a", "held", "store"))
def _moe_layers(ms, h, idx, a, held, store):
    """Each MoE layer on its kept input: rows ``h`` (n, n_moe, d) and the
    program's experts ``idx`` (n, n_moe, k); returns the layers' outputs
    (n, n_moe, d) and the router's own top-k (n, n_moe, k)."""
    outs = [_moe(m, _f32(h[:, i]), a, held, store, idx[:, i])[0]
            for i, m in enumerate(ms)]
    tops = [jax.lax.top_k(jax.nn.sigmoid(_f32(h[:, i]) @ _f32(m["router"])),
                          a["top_k"])[1] for i, m in enumerate(ms)]
    return jnp.stack(outs, 1), jnp.stack(tops, 1)


@functools.partial(jax.jit, static_argnames=("a", "held", "store"))
def _ffn(p, x, a, held, store):
    """Rows after the FFN, and their top-k experts (-1 on a dense layer)."""
    h = _rms(x, p["ln2_w"], a["norm_eps"])
    if "moe" in p:
        y, idx = _moe(p["moe"], h, a, held, store)
        return x + y, idx
    m = p["mlp"]
    silu = jax.nn.silu
    y = (silu(h @ _f32(m["w1"])) * (h @ _f32(m["w3"]))) @ _f32(m["w2"])
    return x + y, jnp.full((x.shape[0], a["top_k"]), -1, jnp.int32)


@functools.partial(jax.jit, static_argnames=("a",))
def _logits(params, x, a):
    x = _rms(x, params["final_norm_w"], a["norm_eps"])
    return x @ _f32(params["unembed"][:a["vocab_size"]]).T


def _arch_of(arch: dict) -> "_Arch":
    return _Arch(arch, head_dim=arch.get("head_dim") or
                 arch["d_model"] // arch["n_heads"],
                 rope_theta=float(arch.get("rope_theta", 1e4)),
                 norm_eps=float(arch.get("norm_eps", 1e-5)))


def _hashable(a: dict) -> tuple:
    return tuple(sorted(a.items()))


class _Arch(dict):
    __hash__ = lambda self: hash(_hashable(self))  # noqa: E731


def forward(params: dict, arch: dict, held: tuple, tokens, at,
            store=None):
    """The reference over ``tokens`` (T,): the logits (n, V) at positions
    ``at`` (n,) and every MoE layer's top-k experts there (n, n_moe, k).
    ``store`` (a dtype) rounds the keys, values and expert weights to it:
    the control. Rows are computed in blocks of :data:`BLOCK`, every key of
    a layer before its queries."""
    a = _arch_of(arch)
    held = tuple(int(x) for x in held)
    tokens = np.asarray(tokens)
    t = tokens.shape[0]
    t_pad = -(-t // BLOCK) * BLOCK
    rows = [slice(i, i + BLOCK) for i in range(0, t_pad, BLOCK)]
    experts = []
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(np.pad(tokens, (0, t_pad - t)))
        x = _f32(params["embed"])[ids]
        pos = jnp.arange(t_pad)
        for p, win in zip(params["layers"], _windows(arch)):
            qkv = [_qkv(p, x[r], pos[r], a, store) for r in rows]
            k = jnp.concatenate([b[1] for b in qkv])
            v = jnp.concatenate([b[2] for b in qkv])
            x = jnp.concatenate([
                _attend(p, x[r], b[0], k, v, r.start, a, win)
                for r, b in zip(rows, qkv)])
            out = [_ffn(p, x[r], a, held, store) for r in rows]
            x = jnp.concatenate([o[0] for o in out])
            if "moe" in p:
                experts.append(jnp.concatenate([o[1] for o in out])[at])
        logits = _logits(params, x[jnp.asarray(at)], a)
    ex = (jnp.stack(experts, 1) if experts
          else jnp.zeros((len(at), 0, arch["top_k"]), jnp.int32))
    return np.asarray(logits, np.float64), np.asarray(ex)


def moe_layers(params: dict, arch: dict, held: tuple, h, idx, store=None):
    """The reference's MoE layers on inputs the program kept: ``h``
    (n, n_moe, d) and its experts ``idx`` (n, n_moe, k). Returns each
    layer's output (n, n_moe, d) at those experts, with gates from the
    reference's own scores, and the reference router's top-k there."""
    ms = tuple(p["moe"] for p in params["layers"] if "moe" in p)
    with jax.default_matmul_precision("highest"):
        y, top = _moe_layers(ms, jnp.asarray(h), jnp.asarray(idx),
                             _arch_of(arch), tuple(int(x) for x in held),
                             store)
    return np.asarray(y, np.float64), np.asarray(top)


# -- the weights ----------------------------------------------------------------
def _shapes(a: dict, held) -> dict:
    """The served model's arrays, in the program's layout: embedding and
    head (V, d), the final norm, and per layer its two norms, attention,
    and a SwiGLU FFN (leading dense layers) or the router over every
    expert, the held experts and the shared expert. Norm weights are
    offsets from 1."""
    d = a["d_model"]
    hd = a.get("head_dim") or d // a["n_heads"]
    q, kv = a["n_heads"] * hd, a["n_kv_heads"] * hd
    layers = []
    for i in range(a["n_layers"]):
        p = {"ln1_w": (d,), "ln2_w": (d,),
             "attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                      "wo": (q, d)}}
        if i < a.get("first_dense_layers", 0):
            f = a["d_ff"]
            p["mlp"] = {"w1": (d, f), "w3": (d, f), "w2": (f, d)}
        else:
            e, f = held[1] - held[0], a["moe_d_ff"]
            p["moe"] = {"router": (d, a["n_experts"]), "w1": (e, d, f),
                        "w3": (e, d, f), "w2": (e, f, d)}
            fs = f * a.get("n_shared_experts", 0)
            if fs:
                p["moe"].update(sw1=(d, fs), sw3=(d, fs), sw2=(fs, d))
        layers.append(p)
    v = a["vocab_size"]
    return {"embed": (v, d), "unembed": (v, d), "final_norm_w": (d,),
            "layers": layers}


@functools.partial(jax.jit, static_argnums=0)
def _normal(shapes: tuple, key) -> list:
    keys = jax.random.split(key, len(shapes))
    return [(SCALE * jax.random.normal(k, s, jnp.float32)).astype(jnp.bfloat16)
            for k, s in zip(keys, shapes)]


def weights(cfg: dict, key) -> dict:
    """The served model's weights drawn from ``key``: every array of
    :func:`_shapes` N(0, SCALE^2), in bfloat16."""
    shapes = _shapes(cfg["model"]["profile_args"]["arch"],
                     cfg["served"]["experts_held"])
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.unflatten(tree, _normal(tuple(leaves), key))


# -- the configuration's hooks ------------------------------------------------
def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = (_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def _arch(cfg: dict):
    from repro.configs import ArchConfig
    return ArchConfig(**cfg["model"]["profile_args"]["arch"],
                      experts_held=tuple(cfg["served"]["experts_held"]))


def build_loop(cfg, traffic, engine, build):
    """The Sec. VI loop with the model served at its edge: the architecture
    from ``model.profile_args.arch`` holding ``served.experts_held``, the
    edge from the traffic's ``served`` section, QoS thresholds at the
    stream's deadline (a request takes seconds, not the planner cells'
    0.5 s), and :func:`weights` to draw the weights at each reset.
    ``served.traffic`` (the rehearsal's cut) overrides the traffic."""
    from repro.online.edge import EdgeConfig
    from repro.online.qos import QosConfig
    traffic = _merge(traffic, cfg["served"].get("traffic", {}))
    ecfg = EdgeConfig(**traffic["served"],
                      act_bits=cfg["model"]["profile_args"]["act_bits"])
    if traffic["service"]["edge_capacity"] != ecfg.slots:
        raise ValueError("the batcher's slots are the served model's slots")
    d = traffic["stream"]["deadline_s"]
    return build(cfg, traffic, engine, model=_arch(cfg), served=ecfg,
                 params=functools.partial(weights, cfg),
                 qos_cfg=QosConfig(deadline_s=d, p95_max_s=d, p50_max_s=d))


def _rel_l2(got, want) -> float:
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _replayed(loop) -> list[dict]:
    """The slots the checks replay: up to MAX_SLOTS of those that decoded
    in the window's last epoch, chosen by the episode key, each with its
    tokens and, for its kept decode steps (the last epochs'), the position
    each consumed and what the program kept there: the logits and each MoE
    layer's input, experts and output."""
    edge = loop.edge_state
    st = jax.device_get(edge._replace(k=(), v=()))
    steps = loop.edge.ecfg.decode_steps
    rid, kept_rid = np.asarray(st.rid), np.asarray(st.kept_rid)
    last = [(int(st.counters.decode_steps) - 1 - i) % kept_rid.shape[1]
            for i in range(steps)]
    mine = (kept_rid == rid[:, None]) & (rid[:, None] >= 0)
    slots = np.flatnonzero(mine[:, last].any(1))
    seed = np.asarray(jax.random.key_data(loop.episode_key)).astype(np.uint64)
    rng = np.random.default_rng(seed.tolist())
    pick = np.sort(rng.permutation(slots)[:MAX_SLOTS])
    out = []
    for b in pick:
        kept = np.flatnonzero(mine[b])
        at = np.asarray(st.kept_pos[b])[kept]
        out.append({"tokens": np.asarray(st.tokens[b])[:at.max() + 1],
                    "at": at,
                    "logits": np.asarray(st.kept_logits[b], np.float64)[kept],
                    "moe_in": np.asarray(st.kept_moe_in[b])[kept],
                    "experts": np.asarray(st.kept_experts[b])[kept],
                    "moe_out": np.asarray(st.kept_moe_out[b], np.float64)[
                        kept]})
    return out


def _gaps(got: list[dict], want: list[dict]) -> dict[str, float]:
    """``served_logits``, ``served_experts`` and ``served_moe`` (module
    docstring) of ``got`` against ``want``, lists of {"logits", "experts",
    "moe_out"} per slot."""
    cat = lambda rs, k: np.concatenate([r[k] for r in rs])  # noqa: E731
    a, b = (np.sort(cat(rs, "experts"), -1) for rs in (got, want))
    differ = np.any(a != b, -1)
    return {"served_logits": _rel_l2(cat(got, "logits"), cat(want, "logits")),
            "served_experts": float(differ.mean()) if differ.size else 0.0,
            "served_moe": _rel_l2(cat(got, "moe_out"), cat(want, "moe_out"))}


def served_checks(cfg: dict, loop) -> dict:
    """The served model against the reference (module docstring), each
    beside its limit in ``cfg["limits"]``, and the float8 control's
    readings beside the same limits. ``served_slots_missing`` fails a run
    in whose last epoch no slot decoded: nothing could be compared."""
    replay = _replayed(loop)
    checks = {"served_slots_missing": {"value": int(not replay),
                                       "limit": 0}}
    if not replay:
        return checks
    arch = cfg["model"]["profile_args"]["arch"]
    held = cfg["served"]["experts_held"]
    params = weights(cfg, loop.edge.key)

    def reference(store=None):
        out = []
        for r in replay:
            logits, _ = forward(params, arch, held, r["tokens"], r["at"],
                                store)
            moe_out, experts = moe_layers(params, arch, held, r["moe_in"],
                                          r["experts"], store)
            out.append({"logits": logits, "experts": experts,
                        "moe_out": moe_out})
        return out
    ref = reference()
    got, ctl = _gaps(replay, ref), _gaps(reference(jnp.float8_e4m3fn), ref)
    limits = cfg["limits"]
    for name, value in got.items():
        checks[name] = {"value": value, "limit": limits[name]}
        checks[f"control.{name}"] = {"value": ctl[name],
                                     "limit": limits[name]}
    return checks


def tiny(cfg: dict) -> dict:
    """The CPU rehearsal's cut: widths, experts and vocabulary shrunk, the
    edge to 4 slots of 96 positions with a 16-position window, and requests
    frequent enough to fill them from 24 users."""
    cfg["model"]["profile_args"]["arch"].update(
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=512, n_experts=16, top_k=4, moe_d_ff=32, window=16)
    cfg["served"]["experts_held"] = [0, 4]
    cfg["served"]["traffic"] = {
        "stream": {"arrival_rate_hz": 1.0},
        "service": {"edge_capacity": 4, "queue_depth": 16},
        "served": {"slots": 4, "max_context": 96, "chunk": 8,
                   "prefill_budget": 16, "decode_steps": 2,
                   "prompt": [24, 0.5, 10, 64], "output": [8, 0.5, 3, 16]}}
    return cfg


# -- operations and bytes of the per-layer metrics ----------------------------
def _dims(cfg: dict) -> dict:
    a = cfg["model"]["profile_args"]["arch"]
    first, stop = cfg["served"]["experts_held"]
    return dict(a, hd=a.get("head_dim") or a["d_model"] // a["n_heads"],
                held=stop - first,
                n_moe=a["n_layers"] - a.get("first_dense_layers", 0))


def expert_work(cfg: dict, counters: dict) -> tuple[float, float]:
    """(operations, bytes) the expert layers' results need over a window:
    every call (a prefill chunk or a decode step) reads the held and the
    shared experts' weights (bfloat16) of every MoE layer once; each
    (token, held expert) pair and each real token's shared expert costs
    3 matmuls of d x moe_d_ff."""
    z = _dims(cfg)
    per_expert = 3 * z["d_model"] * z["moe_d_ff"]
    shared = z.get("n_shared_experts", 0)
    calls = counters["edge_prefill_chunks"] + counters["edge_decode_steps"]
    tokens = counters["edge_prefill_tokens"] + counters["edge_decode_tokens"]
    flops = 2.0 * per_expert * (counters["moe_local_assignments"]
                                + shared * tokens * z["n_moe"])
    nbytes = 2.0 * per_expert * (z["held"] + shared) * z["n_moe"] * calls
    return flops, nbytes


def attention_work(cfg: dict, counters: dict) -> tuple[float, float]:
    """(operations, bytes) the attention cores' results need over a window:
    scores and AV, 4 FLOPs per query-key pair and head dimension of every
    head; each cached key and value read once per call (bfloat16)."""
    z = _dims(cfg)
    flops = 4.0 * counters["attn_query_keys"] * z["n_heads"] * z["hd"]
    nbytes = 2.0 * 2 * counters["attn_keys_read"] * z["n_kv_heads"] * z["hd"]
    return flops, nbytes
