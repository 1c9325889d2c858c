"""NOMA channel model: environment sampling, SINR and achievable rates.

Implements paper eqs. (5)-(10):
  * uplink SIC at the AP: stronger users decoded first, so user i is interfered
    by same-cell users on the same subchannel with *weaker* own-cell gain,
    plus all other-cell users transmitting on that subchannel (inter-cell),
    plus noise.
  * downlink SIC at the user: weaker users decode first; user i is interfered
    by same-cell users with *stronger* gain, plus other APs' transmissions on
    the subchannel.

The relaxed subchannel variable beta[u, m] in [0, 1] (rows sum to 1) scales both
the interference a user causes and the bandwidth share it gets, matching the
paper's relaxation (Corollary 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import (
    LOG2,
    Array,
    ComputeConstants,
    NetworkEnv,
    RadioConstants,
)

# SINR backend: 'einsum' is the XLA reference; 'pallas' routes the pairwise
# interference reductions through the cell-block kernels in
# repro.kernels.noma_rates (custom_vjp: forward AND backward stream blocked
# tiles, so the GD gradient path runs tiled at paper scale) compiled for the
# TPU, with no fallback; 'pallas_interpret' runs the same kernels in the
# Pallas interpreter (CPU tests). The kernels are GATHER-FREE: they consume
# the raw (U, N, M) channel state plus
# the int32 AP ids -- no g[:, ap, :] materialization, no same_cell mask
# input, no padded operand copies -- and their VMEM budget is O(BN),
# independent of the AP count. Passing a precomputed CellLayout
# (repro.kernels.cells.build_cell_layout, once per env) additionally
# restricts the intra/SIC grid to same-cell block-diagonal tiles:
# sum-of-cell-sizes^2 pairwise work instead of U^2, forward and backward.
# Both backends produce identical gradients to 1e-5
# (tests/test_grad_kernels.py, tests/test_cell_layout.py).
SINR_BACKENDS = ("einsum", "pallas", "pallas_interpret")
_SINR_BACKEND = "einsum"


def set_sinr_backend(backend: str) -> str:
    """Select the default SINR backend; returns the previous one.

    The global is resolved at *trace* time: programs already jitted keep the
    backend they were traced with (no retrace on switch). Inside long-lived
    jitted code, pass backend= explicitly instead of relying on the global."""
    global _SINR_BACKEND
    if backend not in SINR_BACKENDS:
        raise ValueError(f"backend must be one of {SINR_BACKENDS}, got {backend!r}")
    prev, _SINR_BACKEND = _SINR_BACKEND, backend
    return prev


def _pallas_interpret(backend: str) -> bool:
    return backend == "pallas_interpret"


def make_env(
    key: jax.Array,
    n_users: int,
    n_aps: int,
    n_sub: int,
    radio: RadioConstants = RadioConstants(),
    comp: ComputeConstants = ComputeConstants(),
) -> NetworkEnv:
    """Sample user/AP positions and i.i.d. Rayleigh fading per subchannel."""
    k_ap, k_user, k_up, k_dn = jax.random.split(key, 4)
    side = radio.cell_radius_m * max(1.0, n_aps**0.5)
    ap_pos = jax.random.uniform(k_ap, (n_aps, 2), minval=0.0, maxval=side)
    user_pos = jax.random.uniform(k_user, (n_users, 2), minval=0.0, maxval=side)
    d = jnp.linalg.norm(user_pos[:, None, :] - ap_pos[None, :, :], axis=-1)
    d = jnp.maximum(d, 1.0)
    path = d ** (-radio.path_loss_exp)  # (U, N)
    # Rayleigh fading: |h|^2 ~ Exp(1), i.i.d. per (user, AP, subchannel).
    fad_up = jax.random.exponential(k_up, (n_users, n_aps, n_sub))
    fad_dn = jax.random.exponential(k_dn, (n_users, n_aps, n_sub))
    g_up = path[:, :, None] * fad_up
    g_dn = jnp.swapaxes(path[:, :, None] * fad_dn, 0, 1)  # (N, U, M)
    # Nearest-AP policy == maximum average channel gain (paper [48]).
    ap = jnp.argmax(path, axis=1).astype(jnp.int32)
    return NetworkEnv(g_up=g_up, g_dn=g_dn, ap=ap, radio=radio, comp=comp)


def _cell_onehot(env: NetworkEnv) -> Array:
    """(U, N) one-hot of the serving AP."""
    return jax.nn.one_hot(env.ap, env.n_aps, dtype=env.g_up.dtype)


def uplink_sinr(env: NetworkEnv, beta_up: Array, p_up: Array,
                backend: str | None = None, layout=None) -> Array:
    """Paper eq. (5). Returns SINR (U, M). layout: optional CellLayout
    (kernels backend only) restricting the SIC grid to same-cell tiles."""
    backend = _SINR_BACKEND if backend is None else backend
    if backend not in SINR_BACKENDS:
        raise ValueError(f"backend must be one of {SINR_BACKENDS}, got {backend!r}")
    own = env.own_gain_up()                      # (U, M) gain to own AP
    tx = beta_up * p_up[:, None]                  # (U, M) effective tx power
    if backend != "einsum":
        from repro.kernels import ops
        # The kernel's custom_vjp treats the channel gains as constants
        # (zero env cotangents); detach the outside-kernel own-gain uses too
        # so the pallas env-gradient is coherently zero rather than a silent
        # mixture. Differentiating w.r.t. gains requires backend="einsum".
        own = jax.lax.stop_gradient(own)
        intra, inter = ops.noma_pairwise_up(env, tx, layout=layout,
                                            interpret=_pallas_interpret(backend))
    else:
        cell = _cell_onehot(env)                  # (U, N)
        # Inter-cell interference received at AP n from users NOT in cell n,
        # computed directly with an off-cell mask (no subtraction: fp32-safe).
        inter_at = jnp.einsum("vn,vm,vnm->nm", 1.0 - cell, tx, env.g_up)  # (N, M)
        inter = jnp.einsum("un,nm->um", cell, inter_at)
        same = env.same_cell().astype(own.dtype)  # (U, U)
        # Intra-cell: same-cell users with weaker own-gain (decoded after me).
        weaker = (own[None, :, :] < own[:, None, :]).astype(own.dtype)  # (U, V, M)
        intra = jnp.einsum("uvm,vm->um", weaker * same[:, :, None], tx * own)
    sig = p_up[:, None] * own
    return sig / (intra + inter + env.noise_up)


def uplink_rates(env: NetworkEnv, beta_up: Array, p_up: Array,
                 backend: str | None = None, layout=None) -> Array:
    """Paper eq. (6): per-(user, subchannel) rate in bit/s; sum over m gives
    the user's total rate under the relaxation."""
    sinr = uplink_sinr(env, beta_up, p_up, backend=backend, layout=layout)
    bw = env.radio.bandwidth_up_hz / env.n_sub
    return beta_up * bw * jnp.log1p(sinr) / LOG2


def downlink_sinr(env: NetworkEnv, beta_dn: Array, p_dn: Array,
                  backend: str | None = None, layout=None) -> Array:
    """Paper eq. (8). Returns SINR (U, M). layout as in uplink_sinr."""
    backend = _SINR_BACKEND if backend is None else backend
    if backend not in SINR_BACKENDS:
        raise ValueError(f"backend must be one of {SINR_BACKENDS}, got {backend!r}")
    own = env.own_gain_dn()                       # (U, M) gain my AP -> me
    tx = beta_dn * p_dn[:, None]                  # (U, M) power my AP spends on me
    if backend != "einsum":
        from repro.kernels import ops
        # See uplink_sinr: gains are constants under the kernel backend.
        own = jax.lax.stop_gradient(own)
        intra, inter = ops.noma_pairwise_dn(env, tx, layout=layout,
                                            interpret=_pallas_interpret(backend))
        intra = intra * own
    else:
        cell = _cell_onehot(env)                  # (U, N)
        # Total tx power of AP n on subchannel m: (N, M)
        ap_tx = jnp.einsum("un,um->nm", cell, tx)
        # Interference from *other* APs received at me, masked directly
        # (no subtraction: fp32-safe): sum_{l != ap(u)} ap_tx[l,m] * g_dn[l,u,m]
        g_all = jnp.swapaxes(env.g_dn, 0, 1)      # (U, N, M)
        inter = jnp.einsum("nm,unm,un->um", ap_tx, g_all, 1.0 - cell)
        # Intra-cell: same-cell users with *stronger* downlink gain (decoded after me)
        same = env.same_cell().astype(own.dtype)
        stronger = (own[None, :, :] > own[:, None, :]).astype(own.dtype)
        intra = jnp.einsum("uvm,vm->um", stronger * same[:, :, None], tx) * own
    sig = p_dn[:, None] * own
    return sig / (intra + inter + env.noise_dn)


def downlink_rates(env: NetworkEnv, beta_dn: Array, p_dn: Array,
                   backend: str | None = None, layout=None) -> Array:
    """Paper eq. (9)."""
    sinr = downlink_sinr(env, beta_dn, p_dn, backend=backend, layout=layout)
    bw = env.radio.bandwidth_dn_hz / env.n_sub
    return beta_dn * bw * jnp.log1p(sinr) / LOG2


def user_rates(
    env: NetworkEnv, beta_up: Array, beta_dn: Array, p_up: Array, p_dn: Array,
    backend: str | None = None, layout=None,
) -> tuple[Array, Array]:
    """Total uplink/downlink rate per user (bit/s), floored for stability.

    Differentiable in (beta, p) under every backend: the Pallas path
    carries a custom_vjp whose backward kernels re-stream interferer blocks
    (see kernels/noma_rates.py), so the GD gradient path (utility ->
    user_rates) may run tiled at paper scale. Gradients w.r.t. the channel
    gains exist only under "einsum" -- the kernel backend stop_gradients
    the env (coherently zero, never a partial mixture). None resolves the
    module default at trace time; the solver passes GdConfig.sinr_backend
    explicitly. layout: optional precomputed CellLayout for the kernel
    backends (same-cell block-diagonal SIC grid), ignored under einsum."""
    r_up = jnp.sum(uplink_rates(env, beta_up, p_up, backend=backend,
                                layout=layout), axis=-1)
    r_dn = jnp.sum(downlink_rates(env, beta_dn, p_dn, backend=backend,
                                  layout=layout), axis=-1)
    return jnp.maximum(r_up, 1e-9), jnp.maximum(r_dn, 1e-9)


def oma_rates(env: NetworkEnv, p_up: Array, p_dn: Array) -> tuple[Array, Array]:
    """OMA baseline: each user gets a dedicated share of its best subchannel,
    TDMA-style equal split within the cell; no intra-cell interference, but
    also no frequency reuse gain (spectrum divided among same-cell users)."""
    own_up = env.own_gain_up()
    own_dn = env.own_gain_dn()
    # Users per cell -> each gets 1/|U_n| of the band.
    counts = jnp.sum(env.same_cell(), axis=1).astype(own_up.dtype)
    bw_up = env.radio.bandwidth_up_hz / counts
    bw_dn = env.radio.bandwidth_dn_hz / counts
    g_up = jnp.max(own_up, axis=1)
    g_dn = jnp.max(own_dn, axis=1)
    snr_up = p_up * g_up / (env.noise_up * env.n_sub)   # full-band noise share
    snr_dn = p_dn * g_dn / (env.noise_dn * env.n_sub)
    r_up = bw_up * jnp.log1p(snr_up) / LOG2
    r_dn = bw_dn * jnp.log1p(snr_dn) / LOG2
    return jnp.maximum(r_up, 1e-9), jnp.maximum(r_dn, 1e-9)
