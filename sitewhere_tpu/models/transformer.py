"""Long-window telemetry transformer — causal forecaster for anomaly scoring
with a first-class sequence-parallel path.

Complements the autoencoder/LSTM scorers (models/anomaly.py, BASELINE.json
config #4) for windows far beyond one chip's comfortable attention range:
the model is written as pure functions over an explicit param pytree so the
SAME forward runs

  * single-chip with the Pallas flash-attention kernel (ops/attention.py), or
  * sequence-parallel under ``shard_map`` with ring attention
    (parallel/ring_attention.py): every non-attention op (embedding, LayerNorm,
    MLP, readout) is per-timestep and therefore acts on the local sequence
    shard unchanged; only attention communicates, via ppermute ring hops over
    ICI. Positions and the forecast shift use ``lax.axis_index`` so the
    sharded forward is numerically the single-device forward.

TPU notes: d_model/mlp multiples of 128 (MXU tiles), bfloat16 matmuls with
float32 LayerNorm/softmax/score accumulation, time loop free (attention is
the only cross-timestep op). The reference has no model zoo at all
(SURVEY.md §2.9 — no tensors anywhere); this family is the TPU build's
native analytics capability.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sitewhere_tpu.ops.attention import flash_attention, mha_reference
from sitewhere_tpu.parallel.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    sensors: int = 100          # input channels C
    d_model: int = 256          # MXU-friendly
    heads: int = 8
    layers: int = 4
    mlp: int = 1024
    dtype: Any = jnp.bfloat16


def _pos_encoding(positions: jax.Array, d_model: int) -> jax.Array:
    """Sinusoidal positions -> [..., d_model] float32. Taking positions as an
    argument (not an arange) lets sequence shards encode their GLOBAL offset."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Explicit param pytree (dict-of-dicts), Xavier-ish init, float32 master
    weights (cast to cfg.dtype inside the forward)."""
    keys = jax.random.split(rng, 2 + cfg.layers)

    def dense(key, fan_in, fan_out):
        scale = (2.0 / (fan_in + fan_out)) ** 0.5
        return {
            "w": jax.random.normal(key, (fan_in, fan_out), jnp.float32) * scale,
            "b": jnp.zeros((fan_out,), jnp.float32),
        }

    d = cfg.d_model
    params = {
        "embed": dense(keys[0], cfg.sensors, d),
        "readout": dense(keys[1], d, cfg.sensors),
        "ln_f": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "blocks": [],
    }
    for i in range(cfg.layers):
        ks = jax.random.split(keys[2 + i], 6)
        params["blocks"].append({
            "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "qkv": dense(ks[0], d, 3 * d),
            "proj": dense(ks[1], d, d),
            "mlp_in": dense(ks[2], d, cfg.mlp),
            "mlp_out": dense(ks[3], cfg.mlp, d),
        })
    return params


def _layer_norm(x: jax.Array, p: dict) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + 1e-6) * p["g"] + p["b"]).astype(x.dtype)


def _dense(x: jax.Array, p: dict, dtype) -> jax.Array:
    return x.astype(dtype) @ p["w"].astype(dtype) + p["b"].astype(dtype)


def forward(
    params: dict,
    x: jax.Array,
    cfg: TransformerConfig,
    *,
    positions: jax.Array | None = None,
    attention_fn=None,
) -> jax.Array:
    """Causal transformer forecast: [B, S, C] -> next-step prediction [B, S, C]
    (prediction at t targets x[t+1]).

    ``positions``: global timestep index per token ([S]); defaults to arange —
    the sequence-parallel wrapper passes shard-offset positions.
    ``attention_fn(q, k, v)``: swap point — flash kernel (default), oracle, or
    ring attention bound to a mesh axis.
    """
    b, s, _ = x.shape
    d, h = cfg.d_model, cfg.heads
    if positions is None:
        positions = jnp.arange(s)
    if attention_fn is None:
        attention_fn = functools.partial(flash_attention, causal=True)

    hh = _dense(x, params["embed"], cfg.dtype)
    hh = hh + _pos_encoding(positions, d)[None].astype(cfg.dtype)
    for blk in params["blocks"]:
        y = _layer_norm(hh, blk["ln1"])
        qkv = _dense(y, blk["qkv"], cfg.dtype).reshape(b, s, 3, h, d // h)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        att = attention_fn(q, k, v).reshape(b, s, d)
        hh = hh + _dense(att, blk["proj"], cfg.dtype)
        y = _layer_norm(hh, blk["ln2"])
        y = jax.nn.gelu(_dense(y, blk["mlp_in"], cfg.dtype))
        hh = hh + _dense(y, blk["mlp_out"], cfg.dtype)
    return _dense(_layer_norm(hh, params["ln_f"]), params["readout"], cfg.dtype)


def forecast_scores(params: dict, x: jax.Array, cfg: TransformerConfig,
                    **kw) -> jax.Array:
    """Per-window anomaly score [B]: mean squared next-step forecast error."""
    preds = forward(params, x, cfg, **kw)
    err = jnp.square(preds[:, :-1].astype(jnp.float32) - x[:, 1:])
    return jnp.mean(err, axis=(1, 2))


def loss_fn(params: dict, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    return jnp.mean(forecast_scores(params, x, cfg))


def make_train_step(cfg: TransformerConfig, tx: optax.GradientTransformation):
    def train_step(params, opt_state, x):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, x, cfg))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# --- sequence-parallel forward/scoring (ring attention over 'sp') -----------

def _sp_forward_local(params, x_local, cfg, axis):
    """Forward on one sequence shard inside shard_map."""
    s_local = x_local.shape[1]
    offset = lax.axis_index(axis) * s_local
    positions = offset + jnp.arange(s_local)
    att = functools.partial(ring_attention, axis_name=axis, causal=True)

    def attention_fn(q, k, v):
        return att(q, k, v)

    return forward(params, x_local, cfg, positions=positions,
                   attention_fn=attention_fn)


def _sp_scores_local(params, x_local, cfg, axis, total_len):
    """Forecast scores on sequence shards: the target for the LAST local
    prediction is the FIRST timestep of the next shard, fetched with a single
    neighbor ppermute (reverse ring hop)."""
    n = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    preds = _sp_forward_local(params, x_local, cfg, axis)     # [B, Sl, C]
    s_local = x_local.shape[1]
    # dest i receives shard (i+1)'s first timestep
    nxt = lax.ppermute(x_local[:, :1], axis,
                       [((j + 1) % n, j) for j in range(n)])   # [B, 1, C]
    targets = jnp.concatenate([x_local[:, 1:], nxt], axis=1)   # [B, Sl, C]
    err = jnp.square(preds.astype(jnp.float32) - targets)      # [B, Sl, C]
    # Drop the final global position (no next-step target exists).
    gpos = idx * s_local + jnp.arange(s_local)
    valid = (gpos < total_len - 1).astype(jnp.float32)[None, :, None]
    local = jnp.sum(err * valid, axis=(1, 2))
    denom = jnp.float32((total_len - 1) * x_local.shape[2])
    return lax.psum(local, axis) / denom                       # [B] replicated


def forecast_scores_sp(
    params: dict,
    x: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    axis: str = "sp",
) -> jax.Array:
    """Sequence-parallel anomaly scoring of [B, S, C] windows with S sharded
    over ``axis``. Numerically equals ``forecast_scores`` on one device."""
    s = x.shape[1]
    fn = jax.shard_map(
        functools.partial(_sp_scores_local, cfg=cfg, axis=axis, total_len=s),
        mesh=mesh,
        in_specs=(P(), P(None, axis, None)),
        out_specs=P(),
    )
    x = jax.device_put(x, NamedSharding(mesh, P(None, axis, None)))
    return fn(params, x)
