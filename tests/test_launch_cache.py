"""Where the persistent compilation cache goes: JAX_COMPILATION_CACHE_DIR
when it is set (and nothing set in code), else a fixed directory in the
checkout, so a second run of the same program finds the first's entries."""
import jax

from repro.launch.cache import CHECKOUT, enable_compile_cache


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert path == str(CHECKOUT / ".jax_cache")
        assert (CHECKOUT / "pyproject.toml").is_file()
        assert enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
