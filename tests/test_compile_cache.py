"""The launchers' persistent compilation cache location."""
import jax

from repro.launch import compile_cache as CC


def test_cache_honours_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.cache_dir() == str(tmp_path)
    assert CC.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing else is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_one_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = CC.enable_compile_cache()
        assert first == CC.enable_compile_cache() == CC.cache_dir()
        assert jax.config.jax_compilation_cache_dir == first
        assert first == str(CC.CHECKOUT_CACHE)
        assert CC.CHECKOUT_CACHE.parent.joinpath("src", "repro").is_dir()
        assert CC.CHECKOUT_CACHE.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
