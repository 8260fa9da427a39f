"""Process-level set-up shared by the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.distributed``, ``benchmarks/*``) call
:func:`enable_compile_cache` once before their first compile; importing a
library module never changes JAX's configuration.
"""

from __future__ import annotations

import os

# the checkout: src/repro/runtime.py → parents[2]
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at one fixed place.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache``: the directory is part of what a cached
    program is found by, so it never comes from a temporary name, a pid or
    the time. Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
