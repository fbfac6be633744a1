"""JAX's persistent compilation cache, placed the same way by every
entry point (`python -m repro.sweep`, the benchmark scripts'
`main()`, `chip_smoke.py`).

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and no
other directory is used.  Otherwise the cache lives at the fixed path
`<checkout>/.jax_cache`: the directory is part of the cache key, so a
path that moved between runs would never hit.  Call `enable()` from an
entry point, never at import time.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the persistent cache on for this process; returns its path.

    Every compiled program is cached, however small or quick to build:
    the PDHG kernels are few and shape-bucketed, and a call on a fresh
    machine otherwise recompiles all of them."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
