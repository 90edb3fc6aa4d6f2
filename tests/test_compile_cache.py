"""``repro/compile_cache.py``: where the persistent compilation cache goes."""
import os

from repro import compile_cache


def test_env_dir_is_left_to_jax(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert calls == []


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", got)]
    assert compile_cache.enable() == got      # the same path every run
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
