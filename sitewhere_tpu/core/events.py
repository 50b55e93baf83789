"""EventBatch: fixed-width structure-of-arrays device-event records.

The reference moves events between pipeline stages as per-message protobuf
payloads on Kafka topics (GDecodedEventPayload / GPreprocessedEventPayload /
GProcessedEventPayload marshaled by EventModelMarshaler; see
service-event-management/.../processing/OutboundPayloadEnrichmentLogic.java:48-50).
Here a *batch* of decoded events is one pytree of flat arrays so the whole
pipeline stage is a single XLA program over vector lanes — the TPU-native
replacement for the per-message JVM hot loop
(service-inbound-processing/.../kafka/DeviceLookupMapper.java:50-93).

Timestamps are int32 milliseconds relative to a host-held epoch base
(`EpochBase`), keeping all device arithmetic in 32-bit (TPU-friendly, no x64).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.core.types import AUX_LANES, DEFAULT_VALUE_CHANNELS, NULL_ID


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EventBatch:
    """A padded batch of decoded device events (structure-of-arrays).

    Shapes use B = batch capacity, C = value channels. Padding rows have
    ``valid == False`` and id lanes set to NULL_ID.
    """

    valid: jax.Array        # bool[B]    slot holds a real event
    etype: jax.Array        # int32[B]   EventType ordinal
    token_id: jax.Array     # int32[B]   interned device-token id (host interner)
    tenant_id: jax.Array    # int32[B]
    ts_ms: jax.Array        # int32[B]   event time, ms since EpochBase
    received_ms: jax.Array  # int32[B]   receive time, ms since EpochBase
    values: jax.Array       # float32[B, C] payload values (layout per EventType)
    vmask: jax.Array        # bool[B, C] which value channels are populated
    aux: jax.Array          # int32[B, AUX_LANES] interned discriminator ids
    seq: jax.Array          # int32[B]   per-batch sequence for stable ordering

    @property
    def capacity(self) -> int:
        return self.valid.shape[0]

    @property
    def channels(self) -> int:
        return self.values.shape[1]

    def count(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32))

    @staticmethod
    def zeros(capacity: int, channels: int = DEFAULT_VALUE_CHANNELS) -> "EventBatch":
        return EventBatch(
            valid=jnp.zeros((capacity,), jnp.bool_),
            etype=jnp.zeros((capacity,), jnp.int32),
            token_id=jnp.full((capacity,), NULL_ID, jnp.int32),
            tenant_id=jnp.full((capacity,), NULL_ID, jnp.int32),
            ts_ms=jnp.zeros((capacity,), jnp.int32),
            received_ms=jnp.zeros((capacity,), jnp.int32),
            values=jnp.zeros((capacity, channels), jnp.float32),
            vmask=jnp.zeros((capacity, channels), jnp.bool_),
            aux=jnp.full((capacity, AUX_LANES), NULL_ID, jnp.int32),
            seq=jnp.zeros((capacity,), jnp.int32),
        )


def pack_batches(batches: list[EventBatch]) -> np.ndarray:
    """Pack numpy-backed EventBatches into ONE contiguous uint8 array
    [K, row_bytes]: one host->device transfer instead of 10 per-field
    arrays (the per-transfer cost on the chip host is not measured); the
    device side un-packs with free bitcasts (:func:`unpack_batch`)."""
    rows = []
    for b in batches:
        rows.append(np.concatenate([
            np.ascontiguousarray(b.valid).view(np.uint8).ravel(),
            np.ascontiguousarray(b.etype).view(np.uint8).ravel(),
            np.ascontiguousarray(b.token_id).view(np.uint8).ravel(),
            np.ascontiguousarray(b.tenant_id).view(np.uint8).ravel(),
            np.ascontiguousarray(b.ts_ms).view(np.uint8).ravel(),
            np.ascontiguousarray(b.received_ms).view(np.uint8).ravel(),
            np.ascontiguousarray(b.values).view(np.uint8).ravel(),
            np.ascontiguousarray(b.vmask).view(np.uint8).ravel(),
            np.ascontiguousarray(b.aux).view(np.uint8).ravel(),
        ]))
    return np.stack(rows)


def unpack_batch(row, capacity: int, channels: int) -> EventBatch:
    """Inverse of :func:`pack_batches` for one packed row — jnp bitcasts and
    reshapes only (fused away by XLA), run INSIDE the consuming jit."""
    from sitewhere_tpu.core.types import AUX_LANES

    b, c = capacity, channels
    off = 0

    def take(nbytes):
        nonlocal off
        part = jax.lax.dynamic_slice_in_dim(row, off, nbytes)
        off += nbytes
        return part

    def as_i32(part, shape):
        return jax.lax.bitcast_convert_type(
            part.reshape(shape + (4,)), jnp.int32).reshape(shape)

    def as_f32(part, shape):
        return jax.lax.bitcast_convert_type(
            part.reshape(shape + (4,)), jnp.float32).reshape(shape)

    valid = take(b).astype(jnp.bool_)
    etype = as_i32(take(4 * b), (b,))
    token_id = as_i32(take(4 * b), (b,))
    tenant_id = as_i32(take(4 * b), (b,))
    ts_ms = as_i32(take(4 * b), (b,))
    received_ms = as_i32(take(4 * b), (b,))
    values = as_f32(take(4 * b * c), (b, c))
    vmask = take(b * c).reshape(b, c).astype(jnp.bool_)
    aux = as_i32(take(4 * b * AUX_LANES), (b, AUX_LANES))
    return EventBatch(
        valid=valid, etype=etype, token_id=token_id, tenant_id=tenant_id,
        ts_ms=ts_ms, received_ms=received_ms, values=values, vmask=vmask,
        aux=aux, seq=jnp.arange(b, dtype=jnp.int32),
    )


class EpochBase:
    """Host-side epoch base for int32 millisecond timestamps.

    int32 ms wraps at ~24.8 days; the base is refreshed by the ingest host at
    checkpoint boundaries. All device-side comparisons are within one epoch.
    """

    def __init__(self, base_unix_s: float | None = None):
        self.base_unix_s = float(base_unix_s if base_unix_s is not None else time.time())

    def to_ms(self, unix_s: float) -> int:
        return int((unix_s - self.base_unix_s) * 1000.0)

    def now_ms(self) -> int:
        return self.to_ms(time.time())

    def to_unix_s(self, ms: int) -> float:
        return self.base_unix_s + ms / 1000.0


class HostEventBuffer:
    """Host-side staging buffer that accumulates decoded events into numpy
    arrays and emits padded ``EventBatch`` pytrees.

    This is the boundary between the variable-rate protocol edge (ingest
    receivers, reference §2.1) and the fixed-shape XLA pipeline. Batches are
    always emitted at full ``capacity`` with a valid-mask — a fixed shape means
    one compiled program, no recompiles (SURVEY.md §7 "hard parts").
    """

    def __init__(self, capacity: int, channels: int = DEFAULT_VALUE_CHANNELS):
        self.capacity = capacity
        self.channels = channels
        self._n = 0
        self._alloc()

    def _alloc(self) -> None:
        cap, ch = self.capacity, self.channels
        self.etype = np.zeros(cap, np.int32)
        self.token_id = np.full(cap, NULL_ID, np.int32)
        self.tenant_id = np.full(cap, NULL_ID, np.int32)
        self.ts_ms = np.zeros(cap, np.int32)
        self.received_ms = np.zeros(cap, np.int32)
        self.values = np.zeros((cap, ch), np.float32)
        self.vmask = np.zeros((cap, ch), np.bool_)
        self.aux = np.full((cap, AUX_LANES), NULL_ID, np.int32)

    def __len__(self) -> int:
        return self._n

    @property
    def full(self) -> bool:
        return self._n >= self.capacity

    def append(
        self,
        etype: int,
        token_id: int,
        tenant_id: int,
        ts_ms: int,
        received_ms: int,
        values: Any = (),
        aux0: int = NULL_ID,
        aux1: int = NULL_ID,
    ) -> bool:
        """Append one decoded event; returns False when the buffer is full."""
        i = self._n
        if i >= self.capacity:
            return False
        self.etype[i] = etype
        self.token_id[i] = token_id
        self.tenant_id[i] = tenant_id
        self.ts_ms[i] = ts_ms
        self.received_ms[i] = received_ms
        nvals = min(len(values), self.channels)
        if nvals:
            self.values[i, :nvals] = values[:nvals]
            self.vmask[i, :nvals] = True
        self.aux[i, 0] = aux0
        self.aux[i, 1] = aux1
        self._n = i + 1
        return True

    def emit(self) -> EventBatch:
        """Produce an EventBatch from the staged rows and reset the buffer.

        The batch is NUMPY-backed: the jit dispatch transfers all leaves in
        one grouped host->device hop instead of per-field ``jnp.asarray``
        round trips. The buffer re-allocates, so the emitted arrays are
        never aliased by later staging."""
        n = self._n
        valid = np.zeros(self.capacity, np.bool_)
        valid[:n] = True
        batch = EventBatch(
            valid=valid,
            etype=self.etype,
            token_id=self.token_id,
            tenant_id=self.tenant_id,
            ts_ms=self.ts_ms,
            received_ms=self.received_ms,
            values=self.values,
            vmask=self.vmask,
            aux=self.aux,
            seq=np.arange(self.capacity, dtype=np.int32),
        )
        self._n = 0
        self._alloc()
        return batch
