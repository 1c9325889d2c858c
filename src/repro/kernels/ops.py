"""jit'd public wrappers around the Pallas kernels.

Each wrapper handles padding to block multiples, GQA reshapes, and exposes
`interpret=` so the CPU container can execute the kernel bodies for
validation (the compiled Mosaic path needs real TPU hardware).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cells import CellLayout
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.noma_rates import (
    DEFAULT_BLOCKS,
    noma_pairwise_bwd_kernel,
    noma_pairwise_kernel,
)
from repro.kernels.rg_lru import rg_lru_kernel
from repro.core.types import LOG2, NetworkEnv


def _pad_to(x, mult, axis):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,   # (B, Sq, H, hd)
    k: jax.Array,   # (B, Sk, KV, hd)
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, hd)
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, sk))
    qp = _pad_to(qf, bq, 1)
    kp = _pad_to(kf, bk, 1)
    vp = _pad_to(vf, bk, 1)
    out = flash_attention_kernel(
        qp, kp, vp, group=g, causal=causal, window=window,
        block_q=bq, block_k=bk, kv_len=sk, interpret=interpret,
    )[:, :sq]
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


def _layout_blocks(layout, env, block_u, block_v):
    """Resolve the intra block sizes. The tile lists are block-granular and
    tied to one env, so when a CellLayout is supplied ITS blocks are
    authoritative (they were fixed at build_cell_layout time) and override
    the arguments -- channel-layer callers thread layout= without having to
    re-thread matching block sizes. A layout built for a different user
    count is a silent-wrong-answer bug and is refused."""
    if layout is None:
        return block_u, block_v
    if layout.n_users != env.n_users:
        raise ValueError(
            f"CellLayout built for U={layout.n_users}, env has "
            f"U={env.n_users}; rebuild with build_cell_layout(env, ...).")
    return layout.block_u, layout.block_v


def _noma_pairwise(own, w_intra, w_power, g_raw, ap, uplink, descending,
                   interpret, block_u, block_v, block_m, block_n, tiles):
    """Run the cell-block forward kernel on the UNPADDED operands.

    The kernel masks boundary blocks in-kernel (clamped cdiv grid), so no
    _pad_to copies -- and no pad ops in the jaxpr -- on any operand; the
    receiver (U) and interferer (V) axes still tile independently
    (block_u vs block_v), and the AP axis tiles in block_n. tiles is the
    layout's block-diagonal intra list (dense grid when None)."""
    return noma_pairwise_kernel(
        own, own, w_intra, w_power, g_raw, ap, ap,
        descending=descending, uplink=uplink,
        block_u=block_u, block_v=block_v, block_m=block_m, block_n=block_n,
        tiles=tiles, interpret=interpret,
    )


def _noma_pairwise_bwd(own, g_raw, ap, d_intra, d_inter, uplink, descending,
                       interpret, block_u, block_v, block_m, block_n, tiles):
    """Backward twin of _noma_pairwise: the transposed-streaming kernels on
    the same unpadded raw-gain operands; returns (V, M) weight cotangents.
    tiles is the layout's BACKWARD list (the same tile set reordered for the
    swapped receiver/streamed roles); boundary blocks are masked in-kernel
    (the cotangents arrive unpadded, so garbage OOB lanes must not
    contribute)."""
    d_wi, d_wp = noma_pairwise_bwd_kernel(
        own, own, g_raw, ap, ap,
        d_intra.astype(jnp.float32), d_inter.astype(jnp.float32),
        descending=descending, uplink=uplink,
        block_u=block_u, block_v=block_v, block_m=block_m, block_n=block_n,
        tiles=tiles, interpret=interpret,
    )
    return d_wi, d_wp


def _zeros_cot(tree):
    """Zero cotangents matching a primal pytree: float leaves get dense
    zeros (weak types preserved via zeros_like), integer leaves get the
    float0 arrays custom_vjp requires for non-differentiable dtypes."""
    def z(x):
        if jnp.issubdtype(jax.typeof(x).dtype, jnp.inexact):
            return jnp.zeros_like(x)
        return np.zeros(jnp.shape(x), jax.dtypes.float0)
    return jax.tree.map(z, tree)


def _used_env(env: NetworkEnv, layout: CellLayout | None) -> NetworkEnv:
    """The environment the kernels actually consume: the layout's AP-sorted
    copy when a CellLayout is supplied, the caller's env otherwise. The big
    gain permutation was paid eagerly at build_cell_layout time -- nothing
    here gathers a 3D tensor inside the traced step."""
    return env if layout is None else layout.env


def _up_inputs(env: NetworkEnv):
    """The uplink kernel inputs derived from the (used) environment, all
    constants of the GD path: own-AP gains, the RAW (V, N, M) uplink gains
    -- no g_up[:, ap, :] gather, the AP selection is an in-kernel id
    compare -- and the raw int32 ap ids."""
    own = env.own_gain_up().astype(jnp.float32)
    g_raw = env.g_up.astype(jnp.float32)
    return own, g_raw, env.ap


def _dn_inputs(env: NetworkEnv):
    """Downlink analogue: the RAW (N, U, M) downlink gains consumed
    receiver-major (no g_dn[ap, :, :] gather, no transpose copy)."""
    own = env.own_gain_dn().astype(jnp.float32)
    g_raw = env.g_dn.astype(jnp.float32)
    return own, g_raw, env.ap


def _sort_in(x, layout):
    """(U, M) decision variables into the layout's sorted user order -- the
    only per-call cost of the cell-block schedule (a 2D row take)."""
    return x if layout is None else jnp.take(x, layout.perm, axis=0)


def _sort_out(x, layout):
    """Kernel outputs back to the caller's original user order."""
    return x if layout is None else jnp.take(x, layout.inv, axis=0)


def _fwd_tiles(layout):
    return None if layout is None else (layout.tile_u, layout.tile_v)


def _bwd_tiles(layout):
    return None if layout is None else (layout.bwd_tile_v, layout.bwd_tile_u)


_PAIR_NONDIFF = (3, 4, 5, 6, 7)   # interpret + block sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=_PAIR_NONDIFF)
def _pairwise_up(env, tx, layout, interpret, block_u, block_v, block_m,
                 block_n):
    return _pairwise_up_fwd(env, tx, layout, interpret, block_u, block_v,
                            block_m, block_n)[0]


def _pairwise_up_fwd(env, tx, layout, interpret, block_u, block_v, block_m,
                     block_n):
    own, g_raw, ap = _up_inputs(_used_env(env, layout))
    tx = _sort_in(tx.astype(jnp.float32), layout)
    out = _noma_pairwise(own, tx * own, tx, g_raw, ap, True, True,
                         interpret, block_u, block_v, block_m, block_n,
                         _fwd_tiles(layout))
    # Residuals are exactly the kernel inputs -- no pairwise intermediates
    # are saved (own/g_raw/ap re-derive from env or layout.env, so the
    # residual adds only the O(U*M) own gains); the backward kernels
    # re-stream the same raw blocks through the same tile lists.
    return tuple(_sort_out(o, layout) for o in out), (env, layout, own)


def _pairwise_up_bwd(interpret, block_u, block_v, block_m, block_n, res, ct):
    env, layout, own = res
    _, g_raw, ap = _up_inputs(_used_env(env, layout))
    d_i, d_x = (_sort_in(c, layout) for c in ct)
    d_wi, d_wp = _noma_pairwise_bwd(own, g_raw, ap, d_i, d_x, True, True,
                                    interpret, block_u, block_v, block_m,
                                    block_n, _bwd_tiles(layout))
    # Forward fed the kernel w_intra = tx * own and w_power = tx; chain back
    # to the one differentiable input. env and layout carry only GD-path
    # constants (zero cotangents, float0 for the int permutations/tiles).
    d_tx = _sort_out(d_wi * own + d_wp, layout)
    return _zeros_cot(env), d_tx, _zeros_cot(layout)


_pairwise_up.defvjp(_pairwise_up_fwd, _pairwise_up_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=_PAIR_NONDIFF)
def _pairwise_dn(env, tx, layout, interpret, block_u, block_v, block_m,
                 block_n):
    return _pairwise_dn_fwd(env, tx, layout, interpret, block_u, block_v,
                            block_m, block_n)[0]


def _pairwise_dn_fwd(env, tx, layout, interpret, block_u, block_v, block_m,
                     block_n):
    own, g_raw, ap = _dn_inputs(_used_env(env, layout))
    tx = _sort_in(tx.astype(jnp.float32), layout)
    out = _noma_pairwise(own, tx, tx, g_raw, ap, False, False,
                         interpret, block_u, block_v, block_m, block_n,
                         _fwd_tiles(layout))
    return tuple(_sort_out(o, layout) for o in out), (env, layout, own)


def _pairwise_dn_bwd(interpret, block_u, block_v, block_m, block_n, res, ct):
    env, layout, own = res
    _, g_raw, ap = _dn_inputs(_used_env(env, layout))
    d_i, d_x = (_sort_in(c, layout) for c in ct)
    d_wi, d_wp = _noma_pairwise_bwd(own, g_raw, ap, d_i, d_x, False, False,
                                    interpret, block_u, block_v, block_m,
                                    block_n, _bwd_tiles(layout))
    # Downlink feeds tx into both weight slots (the receiver-side own-gain
    # factor of eq. 8 is applied by the caller, outside the kernel).
    return _zeros_cot(env), _sort_out(d_wi + d_wp, layout), _zeros_cot(layout)


_pairwise_dn.defvjp(_pairwise_dn_fwd, _pairwise_dn_bwd)


def noma_pairwise_up(
    env: NetworkEnv,
    tx: jax.Array,        # (U, M) beta_up * p_up
    interpret: bool = False,
    block_u: int = DEFAULT_BLOCKS[0],
    block_v: int = DEFAULT_BLOCKS[1],
    block_m: int = DEFAULT_BLOCKS[2],
    block_n: int = DEFAULT_BLOCKS[3],
    layout: CellLayout | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Uplink (intra, inter) interference terms of eq. (5) via the Pallas
    kernels: the exact denominators consumed by channel.uplink_sinr.

    Differentiable in tx (jax.custom_vjp): the backward pass re-streams the
    same cell-block kernels in noma_rates.py, so the GD gradient path never
    materializes (U, V, M) in either direction. With a CellLayout
    (kernels/cells.py, built once per env) the intra grid covers only the
    same-cell block-diagonal tiles -- sum-of-cell-sizes^2 work, not U^2 --
    and tx/outputs cross the sort as cheap (U, M) row takes; results are
    returned in the caller's original user order either way.

    Deliberately NOT jitted: the hot callers (channel.uplink_sinr inside
    gd_solve / the engine's compiled programs) are already inside jit, and
    a nested jit only adds a closed-call trace layer. Direct eager callers
    should use noma_pairwise_up_jit."""
    block_u, block_v = _layout_blocks(layout, env, block_u, block_v)
    return _pairwise_up(env, tx, layout, interpret, block_u, block_v,
                        block_m, block_n)


def noma_pairwise_dn(
    env: NetworkEnv,
    tx: jax.Array,        # (U, M) beta_dn * p_dn
    interpret: bool = False,
    block_u: int = DEFAULT_BLOCKS[0],
    block_v: int = DEFAULT_BLOCKS[1],
    block_m: int = DEFAULT_BLOCKS[2],
    block_n: int = DEFAULT_BLOCKS[3],
    layout: CellLayout | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Downlink (intra, inter) terms of eq. (8). The returned intra term is
    sum_v stronger*same * tx[v]; the caller multiplies by own-gain (the
    receiver-side factor in eq. 8), matching channel.downlink_sinr.
    Differentiable in tx via the same custom_vjp discipline as the uplink,
    with the same CellLayout contract. Unjitted for in-jit composition; see
    noma_pairwise_up."""
    block_u, block_v = _layout_blocks(layout, env, block_u, block_v)
    return _pairwise_dn(env, tx, layout, interpret, block_u, block_v,
                        block_m, block_n)


def noma_uplink_rates(
    env: NetworkEnv,
    beta_up: jax.Array,   # (U, M)
    p_up: jax.Array,      # (U,)
    interpret: bool = False,
    block_u: int = DEFAULT_BLOCKS[0],
    block_v: int = DEFAULT_BLOCKS[1],
    block_m: int = DEFAULT_BLOCKS[2],
    block_n: int = DEFAULT_BLOCKS[3],
    layout: CellLayout | None = None,
) -> jax.Array:
    """Kernel-backed replacement for repro.core.channel.uplink_rates.

    Like channel.uplink_sinr's pallas branch, the channel gains are
    detached so the env gradient is coherently zero (the kernel's
    custom_vjp already returns zero env cotangents). Unjitted for in-jit
    composition; direct eager callers use noma_uplink_rates_jit."""
    own = jax.lax.stop_gradient(env.own_gain_up()).astype(jnp.float32)
    tx = beta_up * p_up[:, None]
    intra, inter = noma_pairwise_up(env, tx, interpret=interpret,
                                    block_u=block_u, block_v=block_v,
                                    block_m=block_m, block_n=block_n,
                                    layout=layout)
    sinr = p_up[:, None] * own / (intra + inter + env.noise_up)
    bw = env.radio.bandwidth_up_hz / env.n_sub
    return beta_up * bw * jnp.log1p(sinr) / LOG2


def noma_downlink_rates(
    env: NetworkEnv,
    beta_dn: jax.Array,   # (U, M)
    p_dn: jax.Array,      # (U,)
    interpret: bool = False,
    block_u: int = DEFAULT_BLOCKS[0],
    block_v: int = DEFAULT_BLOCKS[1],
    block_m: int = DEFAULT_BLOCKS[2],
    block_n: int = DEFAULT_BLOCKS[3],
    layout: CellLayout | None = None,
) -> jax.Array:
    """Kernel-backed replacement for repro.core.channel.downlink_rates:
    assembles eq. (8)'s SINR from the pairwise terms (the intra term carries
    the receiver-side own-gain factor) and applies eq. (9). Channel gains
    are detached, as in noma_uplink_rates. Unjitted for in-jit composition;
    direct eager callers use noma_downlink_rates_jit."""
    own = jax.lax.stop_gradient(env.own_gain_dn()).astype(jnp.float32)
    tx = beta_dn * p_dn[:, None]
    intra, inter = noma_pairwise_dn(env, tx, interpret=interpret,
                                    block_u=block_u, block_v=block_v,
                                    block_m=block_m, block_n=block_n,
                                    layout=layout)
    sinr = p_dn[:, None] * own / (intra * own + inter + env.noise_dn)
    bw = env.radio.bandwidth_dn_hz / env.n_sub
    return beta_dn * bw * jnp.log1p(sinr) / LOG2


# Jitted entry points for direct (eager) callers -- benchmarks, notebooks,
# launch scripts. The unjitted functions above remain the composable core:
# re-entering jit from an already-jitted gd_solve/engine program was pure
# trace overhead. layout stays an operand (its tile lists are array leaves;
# the tile COUNT is pytree metadata, so a different cell population
# recompiles -- by design, the grid size is the point).
_NOMA_STATIC = ("interpret", "block_u", "block_v", "block_m", "block_n")
noma_pairwise_up_jit = functools.partial(jax.jit, static_argnames=_NOMA_STATIC)(
    noma_pairwise_up)
noma_pairwise_dn_jit = functools.partial(jax.jit, static_argnames=_NOMA_STATIC)(
    noma_pairwise_dn)
noma_uplink_rates_jit = functools.partial(jax.jit, static_argnames=_NOMA_STATIC)(
    noma_uplink_rates)
noma_downlink_rates_jit = functools.partial(jax.jit, static_argnames=_NOMA_STATIC)(
    noma_downlink_rates)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b", "block_s", "block_w"))
def rg_lru(
    log_a: jax.Array,   # (B, S, W)
    b: jax.Array,
    h0: jax.Array | None = None,
    interpret: bool = False,
    block_b: int = 8,
    block_s: int = 256,
    block_w: int = 128,
) -> jax.Array:
    bsz, s, w = log_a.shape
    bb = min(block_b, bsz)
    bs = min(block_s, s)
    bw = min(block_w, w)
    la = _pad_to(_pad_to(_pad_to(log_a, bb, 0), bs, 1), bw, 2)
    bp = _pad_to(_pad_to(_pad_to(b, bb, 0), bs, 1), bw, 2)
    h0p = None
    if h0 is not None:
        h0p = _pad_to(_pad_to(h0, bb, 0), bw, 1)
    out = rg_lru_kernel(la, bp, h0p, block_b=bb, block_s=bs, block_w=bw,
                        interpret=interpret)
    return out[:bsz, :s, :w]
