"""Shared fixtures.

``subprocess_env`` gives every subprocess integration test (dryrun
lower+compile, multidevice selftest, hlo analysis, train/serve drivers)
ONE session-scoped JAX persistent-compilation-cache directory, stable
across pytest sessions: ``JAX_COMPILATION_CACHE_DIR`` when it is set,
else ``.pytest_cache/jax_persistent_cache``.  The first full-tier run
pays the XLA compiles, later runs load the compiled artifacts from
disk, keeping the slow tier fast.
"""
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def compiled_artifact_cache() -> str:
    """Session-scoped (and session-surviving) compiled-artifact cache
    directory shared by all subprocess tests."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _ROOT, ".pytest_cache", "jax_persistent_cache")
    os.makedirs(cache, exist_ok=True)
    return cache


@pytest.fixture(scope="session", autouse=True)
def _inprocess_compiled_artifact_cache(compiled_artifact_cache):
    """Point the in-process JAX at the same persistent cache, so the
    compile-heavy in-process tests (arch smoke forward/train steps, the
    jitted cost-model evaluators) also skip recompiles on warm runs."""
    import jax
    jax.config.update("jax_compilation_cache_dir", compiled_artifact_cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield


@pytest.fixture(scope="session")
def subprocess_env(compiled_artifact_cache):
    """Factory for the environment of a JAX subprocess: repo PYTHONPATH,
    no inherited XLA_FLAGS, and the shared persistent compilation cache
    (caching even fast compiles, so the many small programs of the
    drivers all hit it)."""
    def make(extra=None):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        env.pop("XLA_FLAGS", None)
        env["JAX_COMPILATION_CACHE_DIR"] = compiled_artifact_cache
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
        if extra:
            env.update(extra)
        return env
    return make
