"""JAX's persistent compile cache, switched on by the entry points only.

A fused admission step takes about a minute to compile for a TPU, and a
cold ``engine.warmup()`` compiles one per batch width and direction. The
entry points (``chip_smoke.py``, ``bench.py``, ``python -m
sentinel_tpu.cluster``, ``python -m sentinel_tpu.envoy_rls``) call
:func:`enable` before their first compile. Importing ``sentinel_tpu``
never does, and neither do the tests.
"""

from __future__ import annotations

import os
from pathlib import Path

# The checkout (or install prefix) holding the ``sentinel_tpu`` package.
CHECKOUT = Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, and no other
    directory is set. Otherwise the cache lives at ``<checkout>/.jax_cache``,
    a fixed path, so later runs from the same checkout find it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
