"""Baselines + profiles sanity (paper Sec. VI comparisons)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, make_weights, planner, profiles, solve


def test_profile_counts():
    assert profiles.nin().n_layers == 9
    assert profiles.yolov2().n_layers == 17
    assert profiles.vgg16().n_layers == 24


def test_profile_invariants():
    for name, fn in profiles.PAPER_MODELS.items():
        p = fn()
        pre, suf = p.prefix_flops(), p.suffix_flops()
        np.testing.assert_allclose(
            np.asarray(pre + suf), float(jnp.sum(p.fl)), rtol=1e-6
        )
        assert float(p.w[-1]) == 0.0        # split at F: no upload
        assert float(p.m_down[-1]) == 0.0   # split at F: no download
        assert float(p.w[0]) > 0.0          # raw input has a size
        assert bool(jnp.all(p.fl >= 0))


def test_device_only_ignores_radio(small_env):
    p = profiles.nin()
    o = baselines.device_only(small_env, p)
    total = float(jnp.sum(p.fl))
    np.testing.assert_allclose(
        np.asarray(o.T), total / small_env.comp.c_device, rtol=1e-6
    )


def test_neurosurgeon_beats_endpoints_on_latency(small_env):
    """argmin over splits can't be worse than s=0 or s=F under its own model."""
    p = profiles.vgg16()
    o = baselines.neurosurgeon(small_env, p)
    dev = baselines.device_only(small_env, p)
    assert bool(jnp.all(o.T <= dev.T + 1e-9))


def test_dnn_surgery_no_faster_than_neurosurgeon(small_env):
    """Shared edge resources can only slow DNN-Surgery down."""
    p = profiles.vgg16()
    a = baselines.neurosurgeon(small_env, p)
    b = baselines.dnn_surgery(small_env, p)
    assert float(jnp.mean(b.T)) >= float(jnp.mean(a.T)) - 1e-9


def test_ecc_oma_feasible(small_env, weights, gd_cfg):
    o = baselines.ecc_oma(small_env, profiles.nin(), weights, gd_cfg)
    assert bool(jnp.all(jnp.isfinite(o.T))) and bool(jnp.all(o.T > 0))
    assert bool(jnp.all(jnp.isfinite(o.E))) and bool(jnp.all(o.E > 0))


def test_compare_all_keys(small_env, weights, gd_cfg):
    res = planner.compare_all(small_env, profiles.nin(), weights, gd_cfg)
    assert set(res) == {
        "ecc_noma", "ecc_oma", "device_only", "edge_only",
        "neurosurgeon", "dnn_surgery",
    }
    for name, o in res.items():
        assert bool(jnp.all(jnp.isfinite(o.T))), name
        assert bool(jnp.all(jnp.isfinite(o.E))), name


def test_lm_profile_extraction():
    from repro.configs import ArchConfig
    cfg = ArchConfig(name="toy", family="dense", n_layers=4, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=1000)
    p = profiles.from_arch_config(cfg, seq=128)
    assert p.n_layers == 4
    assert float(p.w[1]) == 128 * 64 * 16  # bf16 residual stream
    assert float(p.w[-1]) == 0.0
