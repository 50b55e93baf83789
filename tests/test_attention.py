"""Long-context attention stack: Pallas flash kernel (interpret mode) vs jnp
oracle, ring / Ulysses sequence parallelism on the 8-device CPU mesh, and the
sequence-parallel transformer matching its single-device forward.

SURVEY.md §4 plan (a)+(c): kernel-vs-oracle unit tests plus multi-chip
collectives under --xla_force_host_platform_device_count emulation.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from sitewhere_tpu.ops.attention import flash_attention, mha_reference
from sitewhere_tpu.parallel.ring_attention import (
    ring_attention_sharded,
    ulysses_attention_sharded,
)
from sitewhere_tpu.models.transformer import (
    TransformerConfig,
    forecast_scores,
    forecast_scores_sp,
    init_params,
    loss_fn,
    make_train_step,
)

# Streaming-softmax f32 tolerance: the oracle itself sits ~3e-3 from a
# float64 softmax on N(0,1) inputs, so block-order differences of the same
# magnitude are expected.
TOL = dict(atol=2e-2, rtol=2e-2)


def _qkv(rng, b=2, s=256, h=4, d=32):
    return tuple(
        jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_oracle(rng, causal):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=64,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, **TOL)


def test_flash_attention_lane_padding(rng):
    # D=32 pads to 128 lanes inside the kernel; result must be unchanged.
    q, k, v = _qkv(rng, s=64, h=2, d=32)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(out, mha_reference(q, k, v), **TOL)


def test_flash_attention_odd_block_fallback(rng):
    # S=96 is not divisible by the preferred 512 block; picker must find one.
    q, k, v = _qkv(rng, s=96, h=2, d=64)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(out, mha_reference(q, k, v), **TOL)


@pytest.fixture
def sp_mesh():
    return Mesh(np.array(jax.devices()[:8]), ("sp",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_oracle(rng, sp_mesh, causal):
    q, k, v = _qkv(rng, s=256, h=8, d=32)
    out = ring_attention_sharded(q, k, v, sp_mesh, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, **TOL)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_oracle(rng, sp_mesh, causal):
    q, k, v = _qkv(rng, s=128, h=8, d=32)   # H == mesh size
    out = ulysses_attention_sharded(q, k, v, sp_mesh, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, **TOL)


def _small_cfg():
    return TransformerConfig(sensors=8, d_model=64, heads=4, layers=2,
                             mlp=128, dtype=jnp.float32)


def test_transformer_sp_scores_match_single_device(rng, sp_mesh):
    cfg = _small_cfg()
    params = init_params(jax.random.key(0), cfg)
    x = jnp.asarray(rng.standard_normal((2, 64, cfg.sensors)), jnp.float32)
    ref = forecast_scores(
        params, x, cfg, attention_fn=functools.partial(mha_reference, causal=True)
    )
    sp = forecast_scores_sp(params, x, cfg, sp_mesh)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(ref), atol=5e-3,
                               rtol=5e-3)


def test_transformer_train_step_reduces_loss(rng):
    cfg = _small_cfg()
    params = init_params(jax.random.key(0), cfg)
    # learnable structure: a lagged sine across all channels
    t = np.arange(64)
    x = np.stack([np.sin(0.3 * t + p) for p in np.linspace(0, 1, 8)], axis=-1)
    x = jnp.asarray(np.stack([x, x * 0.5]), jnp.float32)   # [2, 64, 8]
    tx = optax.adam(3e-3)
    step = jax.jit(make_train_step(cfg, tx))
    opt_state = tx.init(params)
    first = float(loss_fn(params, x, cfg))
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, x)
    assert float(loss) < first * 0.5, (first, float(loss))


def test_transformer_sp_grads_finite(rng, sp_mesh):
    """AD flows through the ring (fori_loop + ppermute) — grads are finite
    and match the single-device gradient direction. Depth AND width are
    trimmed purely for gradient-compile time on the virtual CPU mesh
    (~70s at the _small_cfg size): the differentiated ring is identical
    per layer and per head."""
    import dataclasses

    cfg = dataclasses.replace(_small_cfg(), layers=1, d_model=32, heads=2,
                              mlp=64)
    params = init_params(jax.random.key(0), cfg)
    x = jnp.asarray(rng.standard_normal((1, 32, cfg.sensors)), jnp.float32)

    def sp_loss(p):
        return jnp.mean(forecast_scores_sp(p, x, cfg, sp_mesh))

    def ref_loss(p):
        return jnp.mean(forecast_scores(
            p, x, cfg, attention_fn=functools.partial(mha_reference, causal=True)
        ))

    g_sp = jax.grad(sp_loss)(params)
    g_ref = jax.grad(ref_loss)(params)
    flat_sp = jnp.concatenate([jnp.ravel(l) for l in jax.tree_util.tree_leaves(g_sp)])
    flat_ref = jnp.concatenate([jnp.ravel(l) for l in jax.tree_util.tree_leaves(g_ref)])
    assert bool(jnp.all(jnp.isfinite(flat_sp)))
    cos = jnp.vdot(flat_sp, flat_ref) / (
        jnp.linalg.norm(flat_sp) * jnp.linalg.norm(flat_ref) + 1e-12
    )
    assert float(cos) > 0.99, float(cos)
