"""Compile cache placement (sitewhere_tpu/utils/compile_cache.py): where
JAX_COMPILATION_CACHE_DIR is set, compiled programs land there and nowhere
else; unset, they land in the one fixed in-checkout path. Each case runs
in a child process so no test worker's cache configuration changes."""

import os
import subprocess
import sys
import uuid

import pytest

from sitewhere_tpu.utils.compile_cache import CACHE_DIR

REPO = CACHE_DIR.parent

_CHILD = """
import jax, jax.numpy as jnp
from sitewhere_tpu.utils.compile_cache import configure_compile_cache
print(configure_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def {name}(x):
    return x * 3 + 1
jax.jit({name})(jnp.arange(7)).block_until_ready()
"""


def _entries(d, name):
    return sorted(p.name for p in d.glob(f"jit_{name}-*")) if d.exists() else []


@pytest.mark.parametrize("from_env", [True, False])
def test_compiled_programs_land_in_one_place(tmp_path, from_env):
    name = f"cache_probe_{uuid.uuid4().hex}"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    want = tmp_path / "cache" if from_env else CACHE_DIR
    try:
        out = subprocess.run([sys.executable, "-c", _CHILD.format(name=name)],
                             env=env, cwd=tmp_path, capture_output=True,
                             text=True, timeout=120, check=True)
        assert out.stdout.strip().splitlines()[-1] == str(want)
        assert _entries(want, name)
        if from_env:
            assert not _entries(CACHE_DIR, name)
    finally:
        for entry in _entries(CACHE_DIR, name):
            (CACHE_DIR / entry).unlink()


_SCOPED = """
import sys
import jax, jax.numpy as jnp
from sitewhere_tpu.utils.compile_cache import configure_compile_cache
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def {name}(x):
    with jax.named_scope(sys.argv[1]):
        return x * 3 + 1
jax.jit({name})(jnp.arange(7)).block_until_ready()
"""


def test_named_scopes_key_the_cache(tmp_path):
    """Two versions of a program that differ only in a named scope do not
    share a cache entry: a profile of the second never shows the first's
    op names."""
    name = f"scope_probe_{uuid.uuid4().hex}"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for scope in ("append", "merge", "append"):
        subprocess.run([sys.executable, "-c", _SCOPED.format(name=name),
                        scope], env=env, cwd=tmp_path, capture_output=True,
                       text=True, timeout=120, check=True)
    assert len(_entries(tmp_path / "cache", name)) == 2
