"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, the train driver and
the benchmarks): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it; otherwise the cache lives at the
fixed path ``.jax_cache/`` in the checkout.  A fixed path matters
because the directory is part of what a later process looks up: a cache
placed by process id, time or temp directory is never found again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
