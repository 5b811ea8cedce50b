"""Process-level JAX setup shared by the entry points (``serve_secure``,
``benchmarks.run``, ``chip_smoke.py``).

* :func:`enable_compile_cache` — JAX's persistent compilation cache.  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
  set here.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed
  path, so a second run of the same program finds what the first compiled.
* :func:`device_info` — the device a run executes on, as JAX reports it,
  for printing beside every result.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; call before the first
    compile.  Returns the directory in use."""
    import jax
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend's devices."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
