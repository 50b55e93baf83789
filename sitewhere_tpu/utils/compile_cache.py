"""Where the persistent XLA compile cache lives.

Every entry point that runs on the chip calls :func:`configure_compile_cache`
before its first compile: ``benchmark/harness.py``, ``chip_smoke.py``,
``python -m sitewhere_tpu.loadgen`` and ``run_rank``. It is deliberately not called on ``import sitewhere_tpu`` or
from the test harness.

The cache key includes the directory, so the path is fixed: never built
from a temp name, a pid or the time. ``.gitignore`` lists it.

The key also covers each op's metadata (its named-scope path and source
location). JAX leaves that out by default, and then a program that
differs from a cached one only in its scopes loads the cached executable,
whose stale op names are what a profile of it shows: the device trace
would put the fused step's time under the stages of another version of
the code.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Return the cache directory in use. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself; otherwise the cache goes to
    ``<repo>/.jax_cache``. Either way op metadata is part of the key."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
