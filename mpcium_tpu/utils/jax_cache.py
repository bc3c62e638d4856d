"""The one place this program points JAX's persistent compile cache.

The cache directory is part of the cache key, so a directory that moves
never hits. The rule, for every entry point (chip_smoke.py, bench.py, the
pre-warmer, the scripts):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; set nothing
  in code, whoever set it owns the placement;
- unset: ``<checkout>/.jax_cache``, fixed (git-ignored), or an
  operator's explicit directory (the daemon's ``warm_cache_dir``).

``tests/conftest.py`` keeps its own directory for the CPU tests.
"""
from __future__ import annotations

import os
import re
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_dir() -> str:
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure(
    explicit_dir: Optional[str] = None, min_compile_s: float = 0.5
) -> Optional[str]:
    """Apply the rule above and return the directory in effect.
    ``min_compile_s`` is the persistence floor (the pre-warmer passes 0
    so every warmed executable lands on disk)."""
    import jax

    # File names in a program's locations, less the checkout's own path: a
    # Pallas kernel travels inside its program as a serialized module WITH
    # its locations, which the cache's key therefore covers (the key drops
    # the debug information of the program around it, not of what a custom
    # call carries), so the same kernel in another checkout never hit
    # (PERF.md, PR 29: ten GG18 programs recompiled in every checkout).
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(_CHECKOUT + os.sep),
    )
    if not os.environ.get(ENV_VAR):
        cache_dir = explicit_dir or default_dir()
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_s
    )
    return jax.config.jax_compilation_cache_dir
