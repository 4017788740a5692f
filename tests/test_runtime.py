"""What the program derives from the platform (``repro.runtime``): kernel
interpret mode, the default paged backend, the compile-cache location."""
import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.kernels.paged_attention import paged_attention


def test_interpret_mode_follows_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert runtime.interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        runtime.interpret_mode()


def test_kernel_refuses_other_platforms(monkeypatch):
    """A kernel traced where it would neither compile nor be validated
    raises instead of silently running the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    jax.clear_caches()
    try:
        q = jnp.zeros((1, 2, 128), jnp.float32)
        pool = jnp.zeros((3, 8, 1, 128), jnp.float32)
        with pytest.raises(RuntimeError, match="'gpu'"):
            paged_attention(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                            jnp.ones((1,), jnp.int32))
    finally:
        jax.clear_caches()


def test_paged_backend_defaults_to_platform(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.paged_backend() == "pallas"
    assert runtime.paged_backend("jnp") == "jnp"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert runtime.paged_backend() == "jnp"
    assert runtime.paged_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="paged_backend"):
        runtime.paged_backend("cuda")


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(
        monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert runtime.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = runtime.enable_compile_cache()
        assert path == str(runtime.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        # at the root of the checkout, beside src/
        assert (runtime.CACHE_DIR.parent / "src" / "repro" /
                "runtime.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
