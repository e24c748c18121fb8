"""The entry points' persistent compilation cache: where it goes."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of turning a cache on in
    the test process."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


@pytest.mark.quick
def test_env_dir_is_left_to_jax(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert config_updates == []


def test_default_dir_is_fixed_at_checkout_root(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()  # same every run
    root = compile_cache.CHECKOUT_ROOT
    assert first == str(root / ".jax_cache")
    assert (root / "chip_smoke.py").is_file()
    assert config_updates == [("jax_compilation_cache_dir", first)] * 2
