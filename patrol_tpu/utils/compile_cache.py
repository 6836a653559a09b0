"""JAX's persistent compilation cache, configured in one place.

Every entry point that compiles kernels (``cli.main``, ``chip_smoke.py``,
``bench.py``) calls :func:`enable` before the first compile, so a second
boot of the server finds the first boot's kernels instead of recompiling
every take/merge/commit variant of the warm-up.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at a fixed path
inside the checkout (``.jax_cache/``, gitignored): the path is part of
the cache key, so a directory named after a pid or the time never hits,
and one under ``/tmp`` escapes the checkout.

Import-light: :func:`cache_dir` needs no JAX, so a JAX-free parent (the
chip smoke's driver) can name the directory without touching a backend.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def cache_dir() -> str:
    """The directory the cache uses: the environment's, else the fixed
    in-checkout default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable() -> str:
    """Point JAX's persistent cache at :func:`cache_dir` and cache every
    compile, however short (the warm-up compiles dozens of small
    variants). Touches JAX's config only — never a backend."""
    import jax

    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
