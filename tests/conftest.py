"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors SURVEY.md §4's test plan: unit kernels vs numpy oracles, single-process
integration with in-memory ingest, and multi-chip sharding validated with
``--xla_force_host_platform_device_count`` CPU emulation (ICI collectives run
without hardware).
"""

import os

# Set before jax initializes a backend; the XLA flag also reaches child
# processes that tests start.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
