"""Blockwise (flash) attention for long telemetry windows — Pallas TPU kernel
plus a jnp oracle.

The reference has no attention anywhere (SURVEY.md §5.7: long-context is a new
TPU-first design axis, not a ported one). This op is the compute core of the
long-window analytics models (models/transformer.py): telemetry windows grow
to tens of thousands of timesteps per device, so attention must be blockwise
(never materialize the [S, S] score matrix in HBM) and, across chips,
sequence-parallel (parallel/ring_attention.py reuses the same streaming-softmax
update this kernel applies per block).

TPU mapping:
  * scores are computed tile-by-tile in VMEM with the MXU doing the
    [block_q, D] @ [D, block_k] and [block_q, block_k] @ [block_k, D]
    matmuls in bfloat16/float32;
  * the softmax runs in streaming form (running row-max m, normalizer l,
    unnormalized accumulator acc) so only O(block_q * D) state lives across
    key blocks — the flash-attention recurrence;
  * grid = (batch*heads, q-blocks, k-blocks) with the k axis innermost and
    sequential ("arbitrary"), accumulating into VMEM scratch.

The jnp reference is the oracle for tests and what runs on the CPU
backend (interpret mode covers the kernel itself in CI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Plain multi-head attention oracle.

    q, k, v: [B, S, H, D] -> [B, S, H, D]. Softmax in float32.
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / float(d) ** 0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = jnp.arange(sq)[:, None]
        col = jnp.arange(sk)[None, :]
        s = jnp.where(col > row, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  sm_scale, causal, block_q, block_k, num_kb):
    """One (bh, qi, ki) grid step: fold key block ki into the running softmax
    state for query block qi. Scratch persists across the sequential k axis."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # Causal: key blocks entirely above the diagonal contribute nothing —
    # skip their matmuls (halves the causal FLOPs).
    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [block_q, D]
        k = k_ref[0].astype(jnp.float32)          # [block_k, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                               # [block_q, block_k]

        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col > row, _NEG_INF, s)

        m_prev = m_sc[:, 0]                        # [block_q]
        l_prev = l_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # [block_q, D]
        acc_sc[:] = acc_sc[:] * alpha[:, None] + pv
        m_sc[:] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new[:, None], l_sc.shape)

    @pl.when(ki == num_kb - 1)
    def _emit():
        # Fully-masked rows (padding) have l == 0; emit 0 for them.
        l = l_sc[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / safe[:, None]).astype(o_ref.dtype)


def _pick_block(s: int, preferred: int) -> int:
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if b <= s and s % b == 0:
            return b
    return s


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k",
                              "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise attention, [B, S, H, D] -> [B, S, H, D].

    The jnp oracle on a CPU backend; the Pallas kernel everywhere else,
    where a compile error raises. ``interpret=True`` runs the kernel in
    interpret mode on any backend (the CPU tests).
    """
    if jax.default_backend() == "cpu" and not interpret:
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    return flash_attention_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """The Pallas kernel behind :func:`flash_attention`. D is padded to a
    lane-friendly multiple of 128 inside the kernel and sliced back."""
    b, s, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / float(d) ** 0.5
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)

    dp = -d % 128
    if dp:
        pad = ((0, 0), (0, 0), (0, 0), (0, dp))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    dd = d + dp

    # [B, S, H, D] -> [B*H, S, D]
    def bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, dd)

    qf, kf, vf = bh(q), bh(k), bh(v)
    num_kb = s // bk
    grid = (b * h, s // bq, num_kb)

    kernel = functools.partial(
        _flash_kernel, sm_scale=scale, causal=causal,
        block_q=bq, block_k=bk, num_kb=num_kb,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dd), lambda bh_, qi, ki: (bh_, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dd), lambda bh_, qi, ki: (bh_, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dd), lambda bh_, qi, ki: (bh_, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, dd), lambda bh_, qi, ki: (bh_, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, dd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_attention",
        interpret=interpret,
    )(qf, kf, vf)

    out = out.reshape(b, h, s, dd)[..., :d]
    return jnp.swapaxes(out, 1, 2)
