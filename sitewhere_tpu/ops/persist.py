"""Batched event persistence into the HBM ring store.

Replaces the reference's per-event time-series writes
(service-event-management/.../kafka/EventPersistenceMapper.java:61-120 →
InfluxDbDeviceEventManagement.java:63-161 point builds) with one compaction
sort and, per arena, a contiguous ring-window write per batch. The sort
groups valid rows by arena in batch order; an arena's rows then land on
consecutive ring rows from its cursor, so each store column is written
through at most two windows of E rows (the head at the cursor and the wrap
at the arena's row 0) with ``dynamic_update_slice``, in place, with no
per-row scatter. Invalid (padding / unexpanded) rows sort to the back and
are never written, so they cost no ring capacity.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from sitewhere_tpu.core.store import EventStore
from sitewhere_tpu.ops.segment import lex_argsort


class PersistResult(NamedTuple):
    store: EventStore
    appended: jax.Array  # int32[] events written this batch


def append_events(
    store: EventStore,
    valid: jax.Array,       # bool[E]
    etype: jax.Array,       # int32[E]
    device: jax.Array,      # int32[E]
    assignment: jax.Array,  # int32[E]
    tenant: jax.Array,      # int32[E]
    area: jax.Array,        # int32[E]
    customer: jax.Array,    # int32[E]
    asset: jax.Array,       # int32[E]
    ts_ms: jax.Array,       # int32[E]
    received_ms: jax.Array, # int32[E]
    values: jax.Array,      # float32[E, C]
    vmask: jax.Array,       # bool[E, C]
    aux: jax.Array,         # int32[E, AUX]
) -> PersistResult:
    """Append up to E events at each arena's ring cursor. Rows route to
    arena ``tenant % A`` (A=1: the single shared ring). E may exceed an
    arena's remaining space; that arena wraps (oldest rows overwritten),
    mirroring retention-policy expiry in the reference's InfluxDB backend
    (INFLUX_RETENTION_POLICY override, InfluxDbDeviceEventManagement.java).
    With multiple arenas this is the hard per-tenant retention guarantee:
    a burst only wraps its own arena."""
    a_n = store.arenas
    acap = store.arena_capacity
    w = valid.shape[0]
    # With w <= acap an arena's rows of one batch fill at most one lap,
    # so the two windows below cover them all and no ring row is written
    # twice in one batch. Sizes are static: reject the rest at trace time.
    if w > acap:
        raise ValueError(
            f"expanded batch ({w} rows) exceeds per-arena event-store "
            f"capacity ({acap}); allocate store_capacity >= "
            "batch_capacity * MAX_ACTIVE_ASSIGNMENTS * arenas"
        )

    # Route each valid row to its tenant's arena and group rows by arena
    # (stable: batch order preserved within an arena). Arena a's rows are
    # then sorted rows off[a] .. off[a]+counts[a]-1, and its k-th goes to
    # ring row a*acap + (cursor[a] + k) % acap.
    arena = jnp.where(valid & (tenant >= 0), tenant % a_n,
                      jnp.where(valid, 0, a_n))   # a_n = padding sentinel
    (s_arena,), perm = lex_argsort([arena])
    counts = jnp.sum(
        (s_arena[:, None] == jnp.arange(a_n)[None, :]).astype(jnp.int32),
        axis=0)
    off = jnp.cumsum(counts) - counts
    batch = dict(etype=etype, device=device, assignment=assignment,
                 tenant=tenant, area=area, customer=customer, asset=asset,
                 ts_ms=ts_ms, received_ms=received_ms, values=values,
                 vmask=vmask, aux=aux)
    # Sorted rows padded by w on both sides, so that a window's batch rows
    # are one dynamic slice whatever the cursor (no gather by position).
    padded = {k: _pad_rows(v[perm], w) for k, v in batch.items()}
    padded["valid"] = jnp.pad(jnp.ones((w,), jnp.bool_), (w, w))
    cols = {}

    # Per arena a head window from the cursor (pulled back to stay in the
    # arena) and a wrap window from the arena's row 0; they overlap where
    # acap < 2w. Each holds, for its ring rows p, sorted row off + k with
    # k = (p - cur) mod acap where k < counts[a] and the old row elsewhere,
    # so the two writes agree where they overlap. A slice start that falls
    # outside the padded rows is clamped; all its rows are then masked.
    row = jnp.arange(w, dtype=jnp.int32)
    windows = []
    for a in range(a_n):
        cur, cnt = store.cursor[a], counts[a]
        for start in (jnp.minimum(cur, acap - w), jnp.int32(0)):
            k = start - cur + row       # rank of ring row start + j, unwrapped
            windows.append((a * acap + start, w + off[a] + start - cur,
                            (k >= 0) & (k < cnt), k + acap < cnt))
    for name, src_rows in padded.items():
        col = getattr(store, name)
        # Read every old window before writing any: a read after a write
        # makes XLA copy the whole column, and so does a read fused into a
        # later window's write, which the barrier rules out.
        old = lax.optimization_barrier(
            [_rows(col, at, w) for at, *_ in windows])
        for (at, src, head, wrapped), prev in zip(windows, old):
            new = _select(head, _rows(src_rows, src, w),
                          _select(wrapped, _rows(src_rows, src + acap, w),
                                  prev))
            col = lax.dynamic_update_slice_in_dim(col, new, at, axis=0)
        cols[name] = col

    new_cursor = store.cursor + counts
    new = EventStore(
        cursor=new_cursor % jnp.int32(acap),
        epoch=store.epoch + new_cursor // jnp.int32(acap),
        **cols,
    )
    return PersistResult(store=new, appended=jnp.sum(counts))


def _pad_rows(x: jax.Array, w: int) -> jax.Array:
    """``x`` with w zero rows before and after, a ``[rows, C]`` array
    pinned rows minor-most, as the TPU lays out the store's 2-D columns.
    Left free, the compiler lays the window selects out channel-minor and
    transposes every window against the store."""
    x = jnp.pad(x, [(w, w)] + [(0, 0)] * (x.ndim - 1))
    if x.ndim == 1:
        return x
    return with_layout_constraint(x, Layout(tuple(range(x.ndim))[::-1]))


def _rows(x: jax.Array, start: jax.Array, w: int) -> jax.Array:
    return lax.dynamic_slice_in_dim(x, start, w, axis=0)


def _select(mask: jax.Array, new: jax.Array, old: jax.Array) -> jax.Array:
    return jnp.where(mask.reshape(mask.shape + (1,) * (new.ndim - 1)),
                     new, old)
