"""Event persistence: HBM-resident ring-buffer time-series store.

The reference persists each event to a pluggable time-series backend —
InfluxDB / Cassandra / Warp10 chosen per tenant
(service-event-management/.../persistence/{influxdb,cassandra,warp10}/,
selected by configuration/providers/TimeSeriesProvider.java) — one network
write per event (EventPersistenceMapper.java:61-120, "hot loop #2").

Here persistence is a batched append into a fixed-capacity HBM ring:
each batch writes a contiguous window of ring rows per arena (two where
it wraps) with dynamic_update_slice, no per-event work (ops/persist.py). The ring carries a
tenant lane (logical multi-tenant isolation, like the per-tenant Influx
databases) and a monotonically increasing 64-bit-equivalent write cursor
(epoch:int32 + offset), so the host can compute durable watermarks for the
replayable ingest log (SURVEY.md §5.5 resume plan). Host-side spill of
overwritten segments to disk (utils/archive.py) plays the role of the
external DB's long-term retention.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from sitewhere_tpu.core.types import AUX_LANES, DEFAULT_VALUE_CHANNELS, NULL_ID


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EventStore:
    """Ring buffer of persisted events. S = capacity (power of two), C = value
    channels, A = tenant arenas.

    With ``arenas == 1`` (default) the whole store is one ring. With
    ``arenas > 1`` the rows partition into A equal sub-rings and every event
    appends into arena ``tenant_id % A`` — hard per-tenant retention
    isolation: one tenant's burst can only evict that arena's rows, never
    another arena's (the per-tenant-HBM-arena answer to the reference's
    engine-per-tenant isolation, InboundProcessingMicroservice.java:84-86).
    ``cursor[a]``/``epoch[a]`` track arena a's write position; row i of
    arena a's logical event k is a*(S/A) + k % (S/A)."""

    cursor: jax.Array       # int32[A] per-arena writes (wraps with epoch)
    epoch: jax.Array        # int32[A] increments on cursor wrap
    etype: jax.Array        # int32[S]
    device: jax.Array       # int32[S]
    assignment: jax.Array   # int32[S]
    tenant: jax.Array       # int32[S]
    area: jax.Array         # int32[S]
    customer: jax.Array     # int32[S]
    asset: jax.Array        # int32[S]
    ts_ms: jax.Array        # int32[S]
    received_ms: jax.Array  # int32[S]
    values: jax.Array       # float32[S, C]
    vmask: jax.Array        # bool[S, C]
    aux: jax.Array          # int32[S, AUX_LANES]
    valid: jax.Array        # bool[S]

    @property
    def capacity(self) -> int:
        return self.etype.shape[0]

    @property
    def arenas(self) -> int:
        return self.cursor.shape[0]

    @property
    def arena_capacity(self) -> int:
        return self.capacity // self.arenas

    @staticmethod
    def zeros(capacity: int, channels: int = DEFAULT_VALUE_CHANNELS,
              arenas: int = 1) -> "EventStore":
        assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
        assert arenas >= 1 and capacity % arenas == 0, \
            "arenas must divide capacity"
        s, c = capacity, channels
        i32 = jnp.int32
        return EventStore(
            cursor=jnp.zeros((arenas,), i32),
            epoch=jnp.zeros((arenas,), i32),
            etype=jnp.zeros((s,), i32),
            device=jnp.full((s,), NULL_ID, i32),
            assignment=jnp.full((s,), NULL_ID, i32),
            tenant=jnp.full((s,), NULL_ID, i32),
            area=jnp.full((s,), NULL_ID, i32),
            customer=jnp.full((s,), NULL_ID, i32),
            asset=jnp.full((s,), NULL_ID, i32),
            ts_ms=jnp.zeros((s,), i32),
            received_ms=jnp.zeros((s,), i32),
            values=jnp.zeros((s, c), jnp.float32),
            vmask=jnp.zeros((s, c), jnp.bool_),
            aux=jnp.full((s, AUX_LANES), NULL_ID, i32),
            valid=jnp.zeros((s,), jnp.bool_),
        )
