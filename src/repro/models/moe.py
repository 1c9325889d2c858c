"""Mixture-of-Experts layer: top-k routing with shared experts.

Two implementations, selectable via `impl`:

  * "sorted" (default): sort-based token dispatch -- token slots are sorted
    by expert id, scattered into a capacity-bounded (E, C, D) buffer, run
    through batched expert matmuls, and combined by scatter-add. Only real
    FLOPs are the expert matmuls (gathers/scatters are data movement), so
    HLO FLOPs track active-expert MODEL_FLOPS.
  * "dense": every expert runs on every token, combined with routing probs.
    Trivially shardable and numerically identical, but E/k x the FLOPs --
    kept as the oracle for tests and as a fallback.

Router z-loss and load-balance aux loss are returned for the train step.

Scores are a softmax over the experts or, for sigmoid routers (afmoe), a
float32 sigmoid of each expert's logit; either way the gates are the top-k
scores normalised to sum 1, times ``cfg.route_scale``. An expert-parallel
layer holds the experts ``cfg.held`` = (first, stop) only: its router keeps
all ``n_experts`` outputs and its top-k, and it computes the part of the
result that its own experts give (the dense path over the held experts,
which the sorted path refuses; a token routed elsewhere adds nothing here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import COMPUTE_DTYPE, Array, ParamDef, einsum_f32


def moe_defs(cfg) -> dict:
    d, f = cfg.d_model, cfg.moe_d_ff
    first, stop = cfg.held
    e = stop - first
    defs = {
        "router": ParamDef((d, cfg.n_experts), ("embed", "experts_row")),
        "w1": ParamDef((e, d, f), ("experts", "embed", "mlp")),
        "w3": ParamDef((e, d, f), ("experts", "embed", "mlp")),
        "w2": ParamDef((e, f, d), ("experts", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        defs["sw1"] = ParamDef((d, fs), ("embed", "mlp"))
        defs["sw3"] = ParamDef((d, fs), ("embed", "mlp"))
        defs["sw2"] = ParamDef((fs, d), ("mlp", "embed"))
    return defs


def route(p: dict, xt: Array, cfg) -> tuple[Array, Array, Array, Array]:
    """Returns (gates (N,k) f32, idx (N,k), scores (N,E) f32, logits)."""
    if cfg.score_func == "sigmoid":
        logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.sigmoid(logits)
    else:
        logits = (xt @ p["router"].astype(COMPUTE_DTYPE)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates * cfg.route_scale, idx, probs, logits


def _router(p: dict, xt: Array, cfg) -> tuple[Array, Array, Array]:
    """Returns (gates (N,k), idx (N,k), aux_loss ())."""
    gates, idx, probs, logits = route(p, xt, cfg)
    # Switch-style load-balance loss + router z-loss
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0
    )
    lb = e * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates, idx, lb + 1e-3 * z


def _experts_sorted(p: dict, xt: Array, gates: Array, idx: Array, cfg,
                    capacity_factor: float = 1.25) -> Array:
    n, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    nk = n * k
    cap = int((nk / e) * capacity_factor + 0.5)
    cap = max(8, ((cap + 7) // 8) * 8)

    flat_e = idx.reshape(nk)                        # expert of each slot
    order = jnp.argsort(flat_e)                     # stable sort by expert
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank = jnp.arange(nk) - starts[sorted_e]        # position within expert
    keep = rank < cap
    dest = jnp.where(keep, sorted_e * cap + rank, e * cap)  # OOB -> dropped
    tok = order // k                                # source token per slot

    buf = jnp.zeros((e * cap, d), COMPUTE_DTYPE)
    buf = buf.at[dest].set(xt[tok], mode="drop")
    h = buf.reshape(e, cap, d)
    dt = COMPUTE_DTYPE
    hidden = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["w1"].astype(dt)))
    hidden = hidden * jnp.einsum("ecd,edf->ecf", h, p["w3"].astype(dt))
    out = jnp.einsum("ecf,efd->ecd", hidden, p["w2"].astype(dt))
    out_flat = out.reshape(e * cap, d)

    gate_slot = gates.reshape(nk)[order].astype(dt)  # aligned with sorted slots
    contrib = out_flat[jnp.where(keep, dest, 0)] * jnp.where(keep, gate_slot, 0.0)[:, None]
    y = jnp.zeros((n, d), dt).at[tok].add(contrib, mode="drop")
    return y


def _experts_dense(p: dict, xt: Array, gates: Array, idx: Array, cfg) -> Array:
    """Every held expert on every token, combined with the gates of the
    tokens routed to it: each expert's hidden units are scaled by the
    token's gate, and one contraction over the experts and their hidden
    units sums in float32, which the result keeps."""
    first, stop = cfg.held
    dt = COMPUTE_DTYPE
    # combine weights (N, E_held): sum of gate over the slots routed to e
    comb = jnp.sum(jax.nn.one_hot(idx - first, stop - first,
                                  dtype=jnp.float32) * gates[..., None], axis=1)
    hidden = jax.nn.silu(jnp.einsum("nd,edf->enf", xt, p["w1"].astype(dt)))
    hidden = hidden * jnp.einsum("nd,edf->enf", xt, p["w3"].astype(dt))
    hidden = (hidden * comb.T[:, :, None]).astype(dt)
    return einsum_f32("enf,efd->nd", hidden, p["w2"].astype(dt))


def shared_expert(p: dict, xt: Array) -> Array:
    dt = COMPUTE_DTYPE
    h = jax.nn.silu(xt @ p["sw1"].astype(dt)) * (xt @ p["sw3"].astype(dt))
    return h @ p["sw2"].astype(dt)


def moe_apply(p: dict, x: Array, cfg, impl: str = "sorted",
              capacity_factor: float = 1.25) -> tuple[Array, Array]:
    """x: (B, S, D). Returns (y, aux_loss)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    if impl == "sorted" and cfg.held != (0, cfg.n_experts):
        raise ValueError("the sorted dispatch computes every expert; a layer "
                         "holding a share of them takes impl='dense'")
    gates, idx, aux = _router(p, xt, cfg)
    if impl == "sorted":
        y = _experts_sorted(p, xt, gates, idx, cfg, capacity_factor)
    else:
        y = _experts_dense(p, xt, gates, idx, cfg)
    if cfg.n_shared_experts:
        y = y + shared_expert(p, xt)
    return y.reshape(b, s, d).astype(x.dtype), aux
