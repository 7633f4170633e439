"""Fixtures of the chip benchmark's CPU tests."""
import pytest


@pytest.fixture
def cache_restored():
    """Let a test run the harness, which turns on the persistent
    compilation cache, and put JAX's cache settings back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_max_size")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
