"""JAX's persistent compilation cache, placed from outside or in the checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
(git ignores it): one fixed path, so every process of this checkout finds
what an earlier one compiled.  Entry points call :func:`enable_compile_cache`
before their first compile (launch/serve.py, benchmarks/run.py,
chip_smoke.py).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax

    outside = os.environ.get(ENV)
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
