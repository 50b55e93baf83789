"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long telemetry windows (SURVEY.md §5.7 — the long-context design axis the
reference lacks) can exceed one chip's HBM/VMEM budget. Two standard
TPU-native decompositions, both pure XLA collectives over the ICI mesh:

  * **Ring attention** (`ring_attention`): shard the sequence axis over mesh
    axis ``sp``. Each device keeps its query shard pinned and streams the
    key/value shards around the ring with ``lax.ppermute`` (neighbor hops —
    exactly the ICI-friendly pattern), folding each arriving block into the
    flash-attention running softmax. Compute and communication overlap: the
    matmul for block t hides the permute for block t+1 (XLA schedules the
    ppermute async). Memory per device: O(S/n) — no full-sequence tensor
    anywhere.

  * **Ulysses all-to-all** (`ulysses_attention`): for moderate sequences with
    enough heads, ``lax.all_to_all`` re-shards [B, S/n, H, D] -> [B, S, H/n, D],
    runs dense local attention per head group, and re-shards back. Two
    all-to-alls total, best when H >= n and S fits per-device after the swap.

Both are written to run INSIDE ``shard_map`` (they take the mesh axis name),
with `*_sharded` wrappers that build the shard_map over a Mesh. Causal
masking uses global positions derived from ``lax.axis_index``, so results are
bit-for-bit the same attention as the single-device oracle.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sitewhere_tpu.ops.attention import mha_reference

_NEG_INF = -1e30


def _block_scores(q, k, q_off, k_off, scale, causal):
    """Scaled (+ causally masked) scores for one ring step.

    q: [B, Sq, H, D], k: [B, Sk, H, D] -> [B, H, Sq, Sk] float32.
    Offsets are the global positions of the first row/col of each shard.
    """
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        row = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((col > row)[None, None], _NEG_INF, s)
    return s


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Ring attention over sequence shards. Call inside shard_map.

    q, k, v: [B, S/n, H, D] local shards (sequence axis sharded over
    ``axis_name``); returns the local [B, S/n, H, D] output shard.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / float(d) ** 0.5
    q_off = idx * sq

    # Initial accumulators are device-varying (they fold in shard-local
    # scores), so mark them varying along the mesh axis for shard_map's
    # manual-axes type system.
    m = lax.pcast(jnp.full((b, h, sq), _NEG_INF, jnp.float32), axis_name,
                  to="varying")
    l = lax.pcast(jnp.zeros((b, h, sq), jnp.float32), axis_name,
                  to="varying")
    acc = lax.pcast(jnp.zeros((b, sq, h, d), jnp.float32), axis_name,
                    to="varying")
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(t, carry):
        k_cur, v_cur, m, l, acc = carry
        # After t forward hops, this device holds the block that originated
        # on device (idx - t) mod n.
        k_off = ((idx - t) % n) * sq
        s = _block_scores(q, k_cur, q_off, k_off, scale, causal)  # [B,H,Sq,Sk]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        acc = acc * jnp.swapaxes(alpha, 1, 2)[..., None] + pv
        # Rotate KV one hop around the ring. The final iteration's hop is
        # unused (one redundant neighbor transfer), the price of a uniform
        # loop body that compiles to a single scan region.
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l, acc

    _, _, m, l, acc = lax.fori_loop(0, n, body, (k, v, m, l, acc))
    l = jnp.swapaxes(l, 1, 2)[..., None]                  # [B, Sq, H, 1]
    safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism. Call inside
    shard_map. Requires H % n == 0.

    [B, S/n, H, D] --a2a--> [B, S, H/n, D] --local attention--> --a2a--> back.
    """
    # split heads across devices, gather the sequence
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = mha_reference(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def _sharded(
    fn: Callable,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str,
    **kw,
) -> jax.Array:
    spec = P(None, axis, None, None)
    mapped = jax.shard_map(
        functools.partial(fn, axis_name=axis, **kw),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    sh = NamedSharding(mesh, spec)
    return mapped(jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh))


def ring_attention_sharded(q, k, v, mesh: Mesh, axis: str = "sp", *,
                           causal: bool = False, sm_scale: float | None = None):
    """Full-array convenience wrapper: shards [B, S, H, D] over ``axis`` and
    runs ring attention. S must divide evenly by the axis size."""
    return _sharded(ring_attention, q, k, v, mesh, axis,
                    causal=causal, sm_scale=sm_scale)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, axis: str = "sp", *,
                              causal: bool = False, sm_scale: float | None = None):
    """Full-array convenience wrapper for Ulysses all-to-all attention."""
    return _sharded(ulysses_attention, q, k, v, mesh, axis,
                    causal=causal, sm_scale=sm_scale)
