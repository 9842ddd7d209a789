"""JAX's on-disk compilation cache, placed where a run can find it again.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable` once; library code such as ``simulate_fleet`` never does, so
a test run writes nothing into the checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and this sets no other directory. Otherwise the cache goes to
``.jax_cache/`` at the checkout root — a fixed path, because the path is
part of what a later process must match to hit the cache.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
