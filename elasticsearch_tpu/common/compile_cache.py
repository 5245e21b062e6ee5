"""Where JAX's persistent compilation cache lives.

The cache's directory is part of its key, so it must not move between
runs: never a temp name, a pid or a time. The operator places it with
`JAX_COMPILATION_CACHE_DIR` (JAX reads that variable itself, so nothing is
set in code then); otherwise it sits in `.jax_cache/` at the root of the
checkout that holds this package (listed in `.gitignore`).
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns its directory.
    Called once, by `start_node`: the entry points `python -m
    elasticsearch_tpu`, `python3 -m benchmark` and `chip_smoke.py` all
    start the node through it."""
    import jax

    placed = os.environ.get(_ENV)
    if not placed:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    # serving programs are many and small: cache every one of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed or jax.config.jax_compilation_cache_dir
