"""The persistent XLA compilation cache of every entry point
(``chip_smoke.py``, ``benchmarks/simperf.py``, ``benchmarks/run.py``)."""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[1] / "artifacts" / "xla_cache"


def enable_persistent_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
    already taken it as its own setting and nothing else is set; otherwise
    the cache is the fixed ``artifacts/xla_cache/`` of this checkout.
    Compile time is set-up time: callers report it, they never turn the
    cache off to measure it."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # Something may have compiled before this call (module-level jnp
    # constants in repro.core.simlock): re-point the live cache.
    from jax.experimental.compilation_cache import compilation_cache as cc
    cc.reset_cache()
    return str(CACHE_DIR)
