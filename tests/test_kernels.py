"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import channel, make_env
from repro.kernels import ops, ref


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,h,kv,hd,causal,window",
    [
        (1, 32, 32, 4, 4, 32, True, 0),
        (2, 64, 64, 4, 2, 32, True, 0),
        (2, 48, 48, 8, 1, 64, True, 0),       # MQA, non-multiple seq (pads)
        (1, 64, 64, 4, 2, 32, False, 0),      # bidirectional
        (1, 64, 64, 4, 4, 32, True, 16),      # local window
        (1, 8, 64, 4, 2, 32, True, 0),        # short q vs long kv (decode-ish)
    ],
)
def test_flash_attention_sweep(dtype, b, sq, sk, h, kv, hd, causal, window):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, sq, h, hd)).astype(dtype)
    k = jax.random.normal(keys[1], (b, sk, kv, hd)).astype(dtype)
    v = jax.random.normal(keys[2], (b, sk, kv, hd)).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=16, block_k=16, interpret=True)
    g = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    r = ref.flash_attention_ref(qf, kf, vf, group=g, causal=causal,
                                window=window)
    r = r.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(r, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "b,s,w,bs,bw,with_h0",
    [
        (1, 32, 64, 8, 32, False),
        (2, 40, 96, 16, 32, True),     # non-multiple seq (pads)
        (3, 128, 128, 64, 128, True),
        (2, 16, 200, 16, 128, False),  # width pads
    ],
)
def test_rg_lru_sweep(b, s, w, bs, bw, with_h0):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    log_a = -jnp.abs(jax.random.normal(keys[0], (b, s, w)))
    bb = jax.random.normal(keys[1], (b, s, w))
    h0 = jax.random.normal(keys[2], (b, w)) if with_h0 else None
    out = ops.rg_lru(log_a, bb, h0, interpret=True, block_s=bs, block_w=bw)
    r = ref.rg_lru_ref(log_a, bb, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize(
    "u,n,m,bu,bm",
    [
        (6, 2, 4, 4, 4),
        (10, 3, 6, 4, 8),    # non-divisible users + subchannels
        (16, 4, 8, 8, 8),
        (9, 2, 12, 8, 8),    # non-divisible M too (12 % 8 != 0)
    ],
)
def test_noma_rates_sweep(u, n, m, bu, bm):
    env = make_env(jax.random.PRNGKey(2), n_users=u, n_aps=n, n_sub=m)
    key = jax.random.PRNGKey(3)
    beta = jax.random.dirichlet(key, jnp.ones(m), (u,))
    p = jax.random.uniform(jax.random.PRNGKey(4), (u,), minval=0.01, maxval=0.3)
    out = ops.noma_uplink_rates(env, beta, p, interpret=True,
                                block_u=bu, block_v=bu, block_m=bm)
    r = channel.uplink_rates(env, beta, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), rtol=2e-5,
                               atol=1e-3)


@pytest.mark.parametrize("bu,bv", [(8, 16), (16, 8)])
def test_noma_rates_mismatched_blocks(bu, bv):
    """Receiver (U) and interferer (V) tiles are padded independently: with
    U=20, block_u=8 pads the receiver axis to 24, which a block_v=16 grid
    cannot tile -- the regression this guards produced NaN/garbage whenever
    block_v != block_u."""
    u, n, m = 20, 3, 6
    env = make_env(jax.random.PRNGKey(7), n_users=u, n_aps=n, n_sub=m)
    beta = jax.random.dirichlet(jax.random.PRNGKey(8), jnp.ones(m), (u,))
    p = jax.random.uniform(jax.random.PRNGKey(9), (u,), minval=0.01, maxval=0.3)
    out = ops.noma_uplink_rates(env, beta, p, interpret=True,
                                block_u=bu, block_v=bv, block_m=8)
    r = channel.uplink_rates(env, beta, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), rtol=2e-5,
                               atol=1e-3)


@pytest.mark.parametrize("bu,bv", [(8, 16), (16, 8)])
def test_noma_pairwise_dn_mismatched_blocks(bu, bv):
    """Downlink decomposition under block_u != block_v matches the einsum
    reference end-to-end (SINR level)."""
    u, n, m = 20, 3, 6
    env = make_env(jax.random.PRNGKey(10), n_users=u, n_aps=n, n_sub=m)
    beta = jax.random.dirichlet(jax.random.PRNGKey(11), jnp.ones(m), (u,))
    p = jax.random.uniform(jax.random.PRNGKey(12), (u,), minval=0.1, maxval=10.0)
    ref_sinr = channel.downlink_sinr(env, beta, p, backend="einsum")
    intra, inter = ops.noma_pairwise_dn(env, beta * p[:, None], interpret=True,
                                        block_u=bu, block_v=bv, block_m=8)
    own = env.own_gain_dn()
    ker_sinr = p[:, None] * own / (intra * own + inter + env.noise_dn)
    np.testing.assert_allclose(np.asarray(ker_sinr), np.asarray(ref_sinr),
                               rtol=1e-5, atol=1e-5 * float(np.max(ref_sinr)))


def test_noma_pairwise_oracle_matches_channel_decomposition(small_env):
    """The kernel's (intra, inter) decomposition reproduces uplink_sinr."""
    env = small_env
    u, m = env.n_users, env.n_sub
    beta = jnp.ones((u, m)) / m
    p = jnp.full((u,), 0.2)
    own = env.own_gain_up().astype(jnp.float32)
    tx = beta * p[:, None]
    g_vu = env.g_up[:, env.ap, :].astype(jnp.float32)
    same = env.same_cell()
    intra, inter = ref.noma_pairwise_ref(own, own, tx * own, tx, g_vu, same,
                                         descending=True)
    sinr = p[:, None] * own / (intra + inter + env.noise_up)
    np.testing.assert_allclose(
        np.asarray(sinr), np.asarray(channel.uplink_sinr(env, beta, p)),
        rtol=1e-4,
    )


def _gather_free_case(u, n, m, seed=0):
    env = make_env(jax.random.PRNGKey(seed), n_users=u, n_aps=n, n_sub=m)
    beta = jax.random.dirichlet(jax.random.PRNGKey(seed + 1), jnp.ones(m), (u,))
    p = jax.random.uniform(jax.random.PRNGKey(seed + 2), (u,),
                           minval=0.01, maxval=0.3)
    tx = (beta * p[:, None]).astype(jnp.float32)
    own_up = env.own_gain_up().astype(jnp.float32)
    own_dn = env.own_gain_dn().astype(jnp.float32)
    return env, tx, own_up, own_dn


def _run_pairwise(env, own, w_intra, tx, uplink, descending, with_layout,
                  block_u, block_v, block_m, block_n):
    """noma_pairwise_kernel in the caller's user order: directly on the
    unsorted operands, or through a CellLayout (AP-sorted operands and the
    block-diagonal tile list, outputs mapped back by the inverse
    permutation)."""
    from repro.kernels import build_cell_layout
    from repro.kernels.noma_rates import noma_pairwise_kernel

    if not with_layout:
        return noma_pairwise_kernel(
            own, own, w_intra, tx, (env.g_up if uplink else env.g_dn).astype(
                jnp.float32), env.ap, env.ap, descending=descending,
            uplink=uplink, block_u=block_u, block_v=block_v, block_m=block_m,
            block_n=block_n, interpret=True)
    layout = build_cell_layout(env, block_u=block_u, block_v=block_v)
    senv = layout.env
    g_raw = (senv.g_up if uplink else senv.g_dn).astype(jnp.float32)
    perm = lambda x: jnp.take(x, layout.perm, axis=0)
    own_s = perm(own)
    out = noma_pairwise_kernel(
        own_s, own_s, perm(w_intra), perm(tx), g_raw, senv.ap, senv.ap,
        descending=descending, uplink=uplink, block_u=layout.block_u,
        block_v=layout.block_v, block_m=block_m, block_n=block_n,
        tiles=(layout.tile_u, layout.tile_v), interpret=True)
    return tuple(jnp.take(o, layout.inv, axis=0) for o in out)


@pytest.mark.parametrize("u,n,m,bu,bv,bm,bn", [
    (10, 3, 6, 4, 8, 8, 2),    # non-divisible U/V/M, mismatched block_u/block_v
    (20, 3, 6, 16, 8, 8, 8),   # block_n > n_aps (clamped in-kernel)
    (13, 5, 7, 8, 4, 128, 4),  # non-divisible N too (5 % 4 != 0)
    (12, 13, 6, 8, 8, 8, 8),   # non-divisible N at block 8 (13 % 8 != 0)
])
@pytest.mark.parametrize("uplink", [True, False])
@pytest.mark.parametrize("descending", [True, False])
@pytest.mark.parametrize("with_layout", [False, True])
def test_noma_gather_free_parity(u, n, m, bu, bv, bm, bn, uplink, descending,
                                 with_layout):
    """The gather-free cell-block kernels (raw gains + int32 AP ids in, AP
    selection and same_cell derived in-kernel, N-tiled accumulators) match
    BOTH oracles at 1e-5: the old gathered-kernel reference (explicit
    g_vu = g[*, ap, *] + same mask -- the math the pre-gather kernel
    computed) and the gather-free reference, for both links, both SIC
    orders, and both schedules (the dense tile grid and a CellLayout's
    block-diagonal list) -- including N not divisible by block_n, where
    boundary N blocks are iota-masked."""
    env, tx, own_up, own_dn = _gather_free_case(u, n, m, seed=u + n)
    own = own_up if uplink else own_dn
    g_raw = (env.g_up if uplink else env.g_dn).astype(jnp.float32)
    w_intra = tx * own if uplink else tx

    ki, kx = _run_pairwise(env, own, w_intra, tx, uplink, descending,
                           with_layout, bu, bv, bm, bn)
    gi, gx = ref.noma_pairwise_gather_free_ref(own, own, w_intra, tx, g_raw,
                                               env.ap, descending=descending,
                                               uplink=uplink)
    g_vu = (env.g_up[:, env.ap, :] if uplink
            else env.g_dn[env.ap, :, :]).astype(jnp.float32)
    oi, ox = ref.noma_pairwise_ref(own, own, w_intra, tx, g_vu,
                                   env.same_cell(), descending=descending)
    for got, want in ((ki, gi), (kx, gx), (ki, oi), (kx, ox)):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-5 * max(np.abs(want).max(), 1e-30))


@pytest.mark.parametrize("uplink", [True, False])
@pytest.mark.parametrize("with_layout", [False, True])
def test_noma_gather_free_single_cell_inter_is_exactly_zero(uplink,
                                                            with_layout):
    """N=1: every user shares the one AP, so the inter-cell term must be
    EXACTLY zero (the in-kernel other-cell mask is identically false),
    not merely small -- with and without a CellLayout."""
    env, tx, own_up, own_dn = _gather_free_case(9, 1, 12, seed=3)
    own = own_up if uplink else own_dn
    w_intra = tx * own if uplink else tx
    _, inter = _run_pairwise(env, own, w_intra, tx, uplink, uplink,
                             with_layout, 8, 8, 8, 8)
    np.testing.assert_array_equal(np.asarray(inter), 0.0)


_SMALL_BLOCKS = dict(block_u=8, block_v=8, block_m=128, block_n=8)


def _rel_gap(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) / max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("u,n,m", [
    (140, 5, 260),   # U and M past one default block, multiples of neither
    (20, 3, 6),      # every default block larger than its extent
])
@pytest.mark.parametrize("link", ["up", "dn"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_default_blocks_match_small_block_schedule(u, n, m, link, direction):
    """DEFAULT_BLOCKS against the (8, 8, 128, 8) schedule, forward (intra,
    inter) and the VJP of each through the custom_vjp. The intra kernel
    sums over the streamed users in order whatever the blocks, so its
    terms are equal bit for bit. The inter terms may differ in the last
    bit: the backward gain kernels sum each block of APs or users as a
    tree, and XLA's CPU backend fuses the gain kernels' multiply-adds by
    block shape, so their gap is bounded at 1e-6 of the largest term."""
    env, tx, _, _ = _gather_free_case(u, n, m, seed=u)
    pairwise = ops.noma_pairwise_up if link == "up" else ops.noma_pairwise_dn

    def run(blocks):
        f = functools.partial(pairwise, env, interpret=True, **blocks)
        if direction == "fwd":
            return f(tx)
        out, vjp = jax.vjp(f, tx)
        ct = jax.random.uniform(jax.random.PRNGKey(u + 1), out[0].shape)
        zeros = jnp.zeros_like(ct)
        return vjp((ct, zeros))[0], vjp((zeros, ct))[0]

    (intra, inter), (intra_s, inter_s) = run({}), run(_SMALL_BLOCKS)
    np.testing.assert_array_equal(np.asarray(intra), np.asarray(intra_s))
    assert _rel_gap(inter, inter_s) <= 1e-6


def test_autotune_candidates_fit_vmem_ceiling():
    """Every (BU, BV, BM, BN) configuration the kernel_bench autotuner is
    allowed to pick stays under the 16 MB VMEM ceiling -- for both
    directions and both links, and INDEPENDENT of the total AP count: the
    budget at n_aps=4096 must equal the budget at n_aps=16 (the N-tiled
    accumulators are (BN, BM) blocks, so n_aps only clamps BN)."""
    from repro.kernels.noma_rates import (AUTOTUNE_BLOCKS,
                                          VMEM_CEILING_BYTES,
                                          vmem_block_bytes)

    for bu, bv, bm, bn in AUTOTUNE_BLOCKS:
        budgets = {}
        for n_aps in (16, 1024, 4096):
            for direction in ("fwd", "bwd"):
                for uplink in (True, False):
                    b = vmem_block_bytes(bu, bv, bm, bn, n_aps=n_aps,
                                         direction=direction, uplink=uplink)
                    assert b < VMEM_CEILING_BYTES, (
                        (bu, bv, bm, bn), n_aps, direction, uplink, b)
                    budgets.setdefault((direction, uplink), set()).add(b)
        for key, vals in budgets.items():
            assert len(vals) == 1, ((bu, bv, bm, bn), key, vals)
