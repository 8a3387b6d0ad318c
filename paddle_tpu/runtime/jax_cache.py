"""Where JAX's persistent compilation cache lives for this repo's
compiling entry points (chip_smoke.py, bench.py, bench_configs.py,
scripts/bench_*.py).

The directory is part of every cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the caller set it (JAX reads that
variable itself — nothing else is configured here), else the fixed
``<repo>/.jax_cache`` inside the checkout. The test suite stays off the
persistent cache (tests/conftest.py says why).
"""
from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def configure() -> str:
    """Point JAX's persistent compilation cache at the directory described
    above and cache every program, however quick its compile. Touches only
    ``jax.config`` — no backend is initialised. Returns the directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
