"""Where the entry points put JAX's persistent compilation cache.  The
config update is recorded, never applied: tests run with the cache off."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def updates(monkeypatch):
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_variable_set_is_used_and_nothing_else_is_set(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert compile_cache.enable_compile_cache() == "/somewhere/cache"
    assert updates == []


def test_variable_unset_uses_the_fixed_checkout_path(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    root = compile_cache.CHECKOUT
    assert (root / "chip_smoke.py").exists() and (root / "src").is_dir()
    assert got == str(root / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", got)]
    # the same path on every call: never a temp name, pid or time
    assert compile_cache.enable_compile_cache() == got
