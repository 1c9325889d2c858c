"""SINR backend switch: the Pallas pairwise-kernel path (run here in the
Pallas interpreter) must reproduce the einsum reference (acceptance: within
1e-5) for both link directions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import channel, make_env


def _vars(key, u, m):
    ku, kp, kq = jax.random.split(key, 3)
    beta = jax.random.dirichlet(ku, jnp.ones(m), (u,))
    p_up = jax.random.uniform(kp, (u,), minval=1e-3, maxval=0.3)
    p_dn = jax.random.uniform(kq, (u,), minval=0.1, maxval=10.0)
    return beta, p_up, p_dn


@pytest.mark.parametrize("u,n,m", [(8, 2, 4), (10, 3, 6), (16, 4, 8)])
def test_pallas_backend_matches_einsum(u, n, m):
    env = make_env(jax.random.PRNGKey(u), n_users=u, n_aps=n, n_sub=m)
    beta, p_up, p_dn = _vars(jax.random.PRNGKey(1), u, m)

    for fn, p in ((channel.uplink_sinr, p_up), (channel.downlink_sinr, p_dn)):
        ref = np.asarray(fn(env, beta, p, backend="einsum"))
        ker = np.asarray(fn(env, beta, p, backend="pallas_interpret"))
        np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-5 * ref.max())


def test_pallas_backend_rates_match(small_env):
    env = small_env
    beta, p_up, p_dn = _vars(jax.random.PRNGKey(2), env.n_users, env.n_sub)
    r_ref = np.asarray(channel.uplink_rates(env, beta, p_up, backend="einsum"))
    r_ker = np.asarray(channel.uplink_rates(env, beta, p_up, backend="pallas_interpret"))
    np.testing.assert_allclose(r_ker, r_ref, rtol=1e-5, atol=1e-5 * r_ref.max())
    d_ref = np.asarray(channel.downlink_rates(env, beta, p_dn, backend="einsum"))
    d_ker = np.asarray(channel.downlink_rates(env, beta, p_dn, backend="pallas_interpret"))
    np.testing.assert_allclose(d_ker, d_ref, rtol=1e-5, atol=1e-5 * d_ref.max())


def test_set_sinr_backend_switch(small_env):
    beta, p_up, _ = _vars(jax.random.PRNGKey(3), small_env.n_users,
                          small_env.n_sub)
    ref = np.asarray(channel.uplink_sinr(small_env, beta, p_up))
    prev = channel.set_sinr_backend("pallas_interpret")
    try:
        assert prev == "einsum"
        out = np.asarray(channel.uplink_sinr(small_env, beta, p_up))
    finally:
        channel.set_sinr_backend(prev)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5 * ref.max())
    with pytest.raises(ValueError):
        channel.set_sinr_backend("cuda")
