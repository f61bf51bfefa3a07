"""Where JAX's persistent compilation cache lives for this checkout.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself, so no directory is set in code), otherwise
``<checkout>/artifacts/xla_cache``.
"""
from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "artifacts", "xla_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compile persists (the time and size thresholds are zeroed): the
    serving programs are individually small but numerous, exactly what the
    default one-second threshold would skip."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
