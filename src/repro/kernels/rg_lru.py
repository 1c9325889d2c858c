"""Pallas TPU kernel for the RG-LRU linear recurrence (RecurrentGemma).

    h_t = exp(log_a_t) * h_{t-1} + b_t        (per channel)

TPU adaptation: the (B, S, W) problem is tiled as (batch block, width block)
parallel x (sequence block) sequential grid. The hidden state h (BB, BW)
lives in fp32 VMEM scratch and is carried across sequence blocks; inside a
block a fori_loop steps through time on VPU lanes. Width blocks of 128 match
the lane count; the sequential dependence is over S only, so all (B, W)
tiles advance in parallel -- this is the structure a GPU implementation
would express with one CUDA block per (batch, channel-tile), adapted to the
TPU's sequential grid + VMEM carry idiom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(log_a_ref, b_ref, h0_ref, out_ref, h_ref, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    def step(i, h):
        h = jnp.exp(log_a_ref[:, i, :]) * h + b_ref[:, i, :]
        out_ref[:, i, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])


def rg_lru_kernel(
    log_a: jax.Array,   # (B, S, W) fp32
    b: jax.Array,       # (B, S, W) fp32
    h0: jax.Array | None = None,   # (B, W)
    block_b: int = 8,
    block_s: int = 256,
    block_w: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bsz, s, w = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, w), jnp.float32)
    bb, bs, bw = min(block_b, bsz), min(block_s, s), min(block_w, w)
    grid = (pl.cdiv(bsz, bb), pl.cdiv(w, bw), pl.cdiv(s, bs))

    kernel = functools.partial(_kernel, block_s=bs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((bb, bs, bw), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((bb, bw), lambda bi, wi, si: (bi, wi)),
        ],
        out_specs=pl.BlockSpec((bb, bs, bw), lambda bi, wi, si: (bi, si, wi)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b, h0)
