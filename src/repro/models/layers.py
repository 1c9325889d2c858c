"""Model primitives: norms, RoPE, MLPs, embeddings, parameter descriptors.

Parameters are plain dict pytrees. Every parameter is described by a
ParamDef(shape, axes) where `axes` are *logical* axis names resolved to mesh
axes by repro.runtime.sharding. Initializers are deterministic per-path.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Array = jax.Array
COMPUTE_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple
    axes: tuple          # logical axis names, len == len(shape)
    init: str = "normal" # normal | zeros | ones
    scale: float = 0.02


def init_params(defs: dict, key: jax.Array, n_stack: int = 0) -> dict:
    """Initialize a (possibly nested) dict of ParamDefs. If n_stack > 0 a
    leading 'layers' dimension of that size is added to every leaf."""
    flat, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    keys = jax.random.split(key, len(flat))
    out = []
    for kd, d in zip(keys, flat):
        shape = (n_stack, *d.shape) if n_stack else d.shape
        if d.init == "zeros":
            arr = jnp.zeros(shape, jnp.float32)
        elif d.init == "ones":
            arr = jnp.ones(shape, jnp.float32)
        else:
            arr = d.scale * jax.random.normal(kd, shape, jnp.float32)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def param_specs(defs: dict, stacked: bool = False) -> dict:
    flat, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    specs = [("layers", *d.axes) if stacked else d.axes for d in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
def rms_norm(x: Array, w: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x: Array, w: Array, b: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd); positions: (..., S) int32. On-the-fly frequencies
    (no precomputed table: at 500k context a table would cost ~0.5 GB)."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    half = hd // 2
    freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def einsum_f32(spec: str, a: Array, b: Array) -> Array:
    """``jnp.einsum`` of compute-type operands, summed in float32."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def mlp_apply(p: dict, x: Array, act: str) -> Array:
    """SwiGLU (w1/w3/w2) or GELU (w1/w2) MLP."""
    dt = COMPUTE_DTYPE
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w1"].astype(dt)) * (x @ p["w3"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["w1"].astype(dt))
    return h @ p["w2"].astype(dt)


def mlp_defs(d_model: int, d_ff: int, act: str) -> dict:
    defs = {
        "w1": ParamDef((d_model, d_ff), ("embed", "mlp")),
        "w2": ParamDef((d_ff, d_model), ("mlp", "embed")),
    }
    if act == "swiglu":
        defs["w3"] = ParamDef((d_model, d_ff), ("embed", "mlp"))
    return defs


def pad_vocab(v: int, multiple: int = 256) -> int:
    return ((v + multiple - 1) // multiple) * multiple


def embed_lookup(table: Array, tokens: Array) -> Array:
    return jnp.take(table, tokens, axis=0).astype(COMPUTE_DTYPE)


def logits_out(x: Array, table: Array, vocab: int) -> Array:
    """Project to (padded) vocab; mask the padding rows to -inf."""
    logits = (x @ table.astype(COMPUTE_DTYPE).T).astype(jnp.float32)
    vp = table.shape[0]
    if vp != vocab:
        mask = jnp.arange(vp) < vocab
        logits = jnp.where(mask, logits, -1e30)
    return logits
