"""Where JAX's persistent compilation cache lives.

One function places it, for every entry point that wants compiled
executables to survive the process (`chip_smoke.py`, `bench.py`, the
fleet/recovery tools, and `DecodeEngine` under ``FLAGS_compile_cache_dir``).
The directory is part of the cache key, so it must never move between
runs: no temp name, pid or time goes into it.
"""
from __future__ import annotations

import os

import jax

# fixed and git-ignored: <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(cache_dir: str | None = None) -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins over everything: JAX reads it
    itself, and nothing here sets another directory.  Otherwise the cache
    goes to ``cache_dir`` (the engine passes ``FLAGS_compile_cache_dir``)
    or, by default, to `DEFAULT_DIR`.  Every executable is kept, however
    small or quick to compile: a cold start is hundreds of small eager-op
    programs besides the few large step programs.  Process-global and
    idempotent."""
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(cache_dir or DEFAULT_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # JAX latches its cache decision at the FIRST compile; anything
        # jitted before this call (model construction, eager dispatch)
        # already concluded "no cache" — reset so the next compile
        # initializes against the directory
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    return path
