"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
checkout's root (the repository's test run collects ``tests/`` only)."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_configure(config):
    # the tests compile for the CPU: keep them out of the checkout's
    # compilation cache, which the benchmark's chip runs use
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
