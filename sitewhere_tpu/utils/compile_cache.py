"""Where the persistent XLA compile cache lives.

Every entry point that runs on the chip calls :func:`configure_compile_cache`
before its first compile: ``chip_smoke.py``, ``bench.py``,
``scripts/bench_spmd.py``, ``python -m sitewhere_tpu.loadgen`` and
``run_rank``. It is deliberately not called on ``import sitewhere_tpu`` or
from the test harness.

The cache key includes the directory, so the path is fixed: never built
from a temp name, a pid or the time. ``.gitignore`` lists it.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Return the cache directory in use. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is changed; otherwise the cache
    goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
