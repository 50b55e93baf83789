"""Compiles for a described TPU v5e chip: the main path's programs at the
sizes a deployment runs, with nothing executed.

The only test file that describes a chip. The topology is built inside a
module fixture, never at import time: one process at a time may load the
TPU library, and pytest-xdist workers all import this file. What the
chip's compiler refuses here (a kernel over its VMEM limit, a program
over HBM) costs no chip time. A pass is a compile, never a chip run.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

# One chip's share of BASELINE config 5, as chip_smoke.py phase (a) runs it.
DEPLOYMENT = dict(device_capacity=1 << 20, token_capacity=1 << 21,
                  assignment_capacity=1 << 21, store_capacity=1 << 22,
                  analytics_devices=4096, analytics_window=128)
BATCH = 16384
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _report(name, compiled):
    mem = compiled.memory_analysis()
    print(f"{name}: {mem}")
    return mem


def test_fused_step_compiles_at_deployment_size(one_chip):
    from sitewhere_tpu.core.events import EventBatch
    from sitewhere_tpu.engine import EngineConfig
    from sitewhere_tpu.pipeline import (PipelineConfig, PipelineState,
                                        make_pipeline_step)

    channels = EngineConfig().channels
    state = jax.eval_shape(functools.partial(
        PipelineState.create, channels=channels, **DEPLOYMENT))
    batch = jax.eval_shape(lambda: EventBatch.zeros(BATCH, channels))
    step = make_pipeline_step(PipelineConfig(auto_register=True,
                                             default_device_type=0))
    compiled = step.lower(_placed(state, one_chip),
                          _placed(batch, one_chip)).compile()
    mem = _report("pipeline_step", compiled)
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < need < HBM_BYTES


@pytest.mark.parametrize("channels", [8, 100])
def test_window_features_kernel_compiles(one_chip, channels):
    from sitewhere_tpu.ops.window_features import window_features_pallas

    x = jax.ShapeDtypeStruct((4096, 128, channels), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(window_features_pallas).lower(x).compile()
    _report(f"window_features C={channels}", compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    from sitewhere_tpu.ops.attention import flash_attention_pallas

    x = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(functools.partial(
        flash_attention_pallas, causal=True)).lower(x, x, x).compile()
    _report("flash_attention", compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_store_append_writes_in_place(one_chip):
    """The store append at deployment size (2^22 rows, 16,384 × 4 expanded
    rows) writes its ring windows in place: one whole-column copy of
    ``values`` alone would be 134 MB of temporaries, a version that copied
    every column took 2.2 GB. Copies that reuse one buffer column after
    column stay under that bound, so look for them by shape too."""
    from sitewhere_tpu.core.registry import MAX_ACTIVE_ASSIGNMENTS
    from sitewhere_tpu.core.store import EventStore
    from sitewhere_tpu.core.types import AUX_LANES
    from sitewhere_tpu.engine import EngineConfig
    from sitewhere_tpu.ops.persist import append_events

    channels = EngineConfig().channels
    e = BATCH * MAX_ACTIVE_ASSIGNMENTS
    store = jax.eval_shape(functools.partial(
        EventStore.zeros, DEPLOYMENT["store_capacity"], channels))
    i32 = jax.ShapeDtypeStruct((e,), jnp.int32)
    rows = dict(
        valid=jax.ShapeDtypeStruct((e,), jnp.bool_),
        values=jax.ShapeDtypeStruct((e, channels), jnp.float32),
        vmask=jax.ShapeDtypeStruct((e, channels), jnp.bool_),
        aux=jax.ShapeDtypeStruct((e, AUX_LANES), jnp.int32),
        **{k: i32 for k in ("etype", "device", "assignment", "tenant",
                            "area", "customer", "asset", "ts_ms",
                            "received_ms")})
    compiled = jax.jit(append_events, donate_argnums=0).lower(
        _placed(store, one_chip), **_placed(rows, one_chip)).compile()
    mem = _report("append_events", compiled)
    assert mem.temp_size_in_bytes < 256 * 2**20
    assert mem.alias_size_in_bytes > 0
    rows_s = DEPLOYMENT["store_capacity"]
    column_copies = [
        line for line in compiled.as_text().splitlines()
        if re.search(rf"= \S+\[{rows_s}[,\]].* copy(-start)?\(", line)]
    assert column_copies == []
