"""Pallas TPU flash attention (causal / local-window / bidirectional, GQA).

TPU adaptation (see DESIGN.md Sec. 4): the kernel tiles Q into VMEM blocks of
(block_q, head_dim) and iterates KV blocks as the innermost ("arbitrary")
grid dimension, carrying the online-softmax state (m, l, acc) in fp32 VMEM
scratch across KV steps -- the classic FlashAttention-2 schedule mapped onto
the TPU's sequential grid. Matmul tiles are (block_q x hd) @ (hd x block_k),
MXU-aligned for hd in {64, 128, 256} and blocks that are multiples of 128.

Grid: (batch * kv_heads * group, n_q_blocks, n_kv_blocks).
K/V are laid out (B * KV, S, hd); the index map divides the leading grid
coordinate by `group` so G query heads share one KV head without
materializing repeated KV (GQA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: int, block_q: int, block_k: int,
            sm_scale: float, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (block_q, hd)
    k = k_ref[0]                       # (block_k, hd)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale                        # (block_q, block_k)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,            # (BH_q, Sq, hd)  where BH_q = B * KV * G
    k: jax.Array,            # (BH_kv, Sk, hd) where BH_kv = B * KV
    v: jax.Array,
    group: int,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    kv_len: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, hd = q.shape
    sk = k.shape[1]
    kv_len = sk if kv_len is None else kv_len
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    sm_scale = hd ** -0.5

    kernel = functools.partial(
        _kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, sm_scale=sm_scale, kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki, g=group: (b // g, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki, g=group: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, hd), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
