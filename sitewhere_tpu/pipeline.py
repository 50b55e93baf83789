"""The fused TPU event pipeline: one jit-compiled step per event batch.

The reference spreads this flow over four microservices connected by Kafka
topics (SURVEY.md §1-L2): event-sources decode -> inbound-processing lookup ->
event-management persistence + outbound fork -> device-state aggregation.
Each stage there is a per-message JVM loop with a blocking RPC or DB write
inside (SURVEY.md §3.2 hot loops 1-3). Here the whole chain is ONE XLA
program over a batch, with all stores HBM-resident and donated between steps:

    lookup (gather)                 ~ DeviceLookupMapper gRPC per message
    auto-register (batched scatter) ~ service-device-registration round trip
    assignment expansion            ~ DeviceAssignmentsLookupMapper flatMap
    ring-store append               ~ InfluxDB/Cassandra per-event writes
    windowed state merge            ~ Kafka Streams 5s window + JPA merge

Outbound consumers (device-state queries, connectors, command delivery) read
the ring store / state store by cursor — the at-least-once consumer-group
analog of the reference's outbound-events topic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from sitewhere_tpu.core.events import EventBatch
from sitewhere_tpu.core.registry import RegistryTables
from sitewhere_tpu.core.state import DeviceStateStore
from sitewhere_tpu.core.store import EventStore
from sitewhere_tpu.core.types import NULL_ID, EventType
from sitewhere_tpu.models.windows import TelemetryWindows, append_measurements
from sitewhere_tpu.ops.lookup import expand_assignments, lookup_devices
from sitewhere_tpu.ops.persist import append_events
from sitewhere_tpu.ops.registration import register_misses
from sitewhere_tpu.ops.rules import RulesState, rules_update
from sitewhere_tpu.ops.segment import compact_valid_front
from sitewhere_tpu.ops.window import merge_batch_state, presence_sweep


# devicewatch program-family names (ISSUE 11) for the compiled steps
# these builders return: every engine wraps each program in a
# utils/devicewatch watch scope under these names — one budgeted
# program per engine per family, so a shape churn (a batch that stopped
# padding, a dtype that drifted) is a LOUD retrace-excess event instead
# of a silent compile storm. Defined here, next to the builders, so the
# engine and the tests can never disagree on the names.
FAMILY_STEP = "ingest.step"
FAMILY_PACKED_SCAN = "ingest.packed_scan"
FAMILY_ARENA_SCAN = "ingest.arena_scan"
FAMILY_SWEEP = "presence.sweep"
FAMILY_RULES_HARVEST = "rules.harvest"

# per-tenant device-side counter grid: tenants bucket by ``id %
# TENANT_COUNTER_BUCKETS`` (static, so the compiled program never
# re-traces as tenants grow; deployments beyond 64 tenants alias buckets
# — exact attribution stays with the readback-based tenant_metrics path)
TENANT_COUNTER_BUCKETS = 64
TENANT_COUNTER_LANES = ("accepted", "dedup_dropped", "geofence_hit",
                        "invalid")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ZoneTable:
    """Device-resident geofence polygons (ops/geofence.pack_zones layout)
    for the in-step geofence-hit counter — the zone monitor's polygons,
    resident in HBM so the already-running program can count containment
    without any extra dispatch."""

    verts: jax.Array    # float32[Z, V, 2] (lat, lon), padded per pack_zones
    valid: jax.Array    # bool[Z]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PipelineMetrics:
    """Device-side counters mirroring the reference's Prometheus metrics
    (e.g. InboundEventSource.java:50-59 decode counters,
    EventPersistenceMapper.java:46-47 processed-event counters)."""

    processed: jax.Array    # int32[] valid events seen
    found: jax.Array        # int32[] events matched to a registered device
    missed: jax.Array       # int32[] unregistered-device events (post-registration)
    registered: jax.Array   # int32[] devices auto-registered
    persisted: jax.Array    # int32[] event rows appended to the store
    reg_overflow: jax.Array # int32[] batches that hit registry capacity
    # packed per-tenant lifecycle grid, accumulated INSIDE the step (no
    # extra dispatch, no readback until a metrics scrape):
    # int32[TENANT_COUNTER_BUCKETS, len(TENANT_COUNTER_LANES)]
    tenant_counters: jax.Array

    @staticmethod
    def zeros() -> "PipelineMetrics":
        # distinct arrays: aliased buffers break donation in jitted steps
        return PipelineMetrics(
            *(jnp.zeros((), jnp.int32) for _ in range(6)),
            tenant_counters=jnp.zeros(
                (TENANT_COUNTER_BUCKETS, len(TENANT_COUNTER_LANES)),
                jnp.int32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PipelineState:
    """All device-resident engine state, donated through every step."""

    registry: RegistryTables
    device_state: DeviceStateStore
    store: EventStore
    next_device: jax.Array      # int32[] device-row allocation counter
    next_assignment: jax.Array  # int32[]
    metrics: PipelineMetrics
    # optional HBM-resident telemetry windows feeding the analytics service
    # (BASELINE.json north star); None disables the update stage.
    windows: TelemetryWindows | None = None
    # optional geofence polygons for the in-step geofence-hit counter
    # (Engine.set_geofence_zones); None keeps the lane at zero.
    zones: ZoneTable | None = None
    # optional streaming-rules CEP tier (ops/rules.py): rule parameter
    # tables + carried accumulators + continuous rollups, evaluated
    # inside this same program at ingest cadence. None (the default)
    # compiles the step without the tier — zero cost when unused.
    # Installed/swapped by Engine.set_rules (rules/manager.py).
    rules: RulesState | None = None

    @staticmethod
    def create(
        device_capacity: int,
        token_capacity: int,
        assignment_capacity: int,
        store_capacity: int,
        channels: int = 8,
        bootstrap: RegistryTables | None = None,
        next_device: int = 0,
        next_assignment: int = 0,
        analytics_devices: int = 0,
        analytics_window: int = 128,
        store_arenas: int = 1,
    ) -> "PipelineState":
        return PipelineState(
            registry=bootstrap
            if bootstrap is not None
            else RegistryTables.zeros(device_capacity, token_capacity, assignment_capacity),
            device_state=DeviceStateStore.zeros(device_capacity, channels),
            store=EventStore.zeros(store_capacity, channels, store_arenas),
            next_device=jnp.asarray(next_device, jnp.int32),
            next_assignment=jnp.asarray(next_assignment, jnp.int32),
            metrics=PipelineMetrics.zeros(),
            windows=(
                TelemetryWindows.zeros(analytics_devices, analytics_window, channels)
                if analytics_devices > 0
                else None
            ),
        )


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Static (compile-time) pipeline configuration — the analog of the
    reference's per-tenant JSON component config (SURVEY.md §5.6)."""

    auto_register: bool = True
    default_device_type: int = 0
    default_area: int = NULL_ID
    default_customer: int = NULL_ID


def _tenant_counter_delta(batch: EventBatch, accepted: jax.Array,
                          invalid: jax.Array,
                          zones: ZoneTable | None) -> jax.Array:
    """[T_BUCKETS, 4] per-tenant lifecycle deltas for this batch, computed
    entirely inside the already-running program:

      accepted       rows matched to a registered device
      dedup_dropped  in-batch alternate-id duplicates (same token + same
                     aux1 correlation id appearing more than once — the
                     AlternateIdDeduplicator's redelivery signature,
                     detected with one stable sort instead of a host
                     LRU). Both staging paths populate aux1: the
                     per-request process() path interns the request's
                     alternate id, and the native batch/arena decoders
                     extract ``alternateId`` into the aux1 lane through
                     the same event-id interner (parity pinned by
                     tests/test_flight.py).
      geofence_hit   location rows inside any configured zone polygon
      invalid        rows still unmatched after auto-registration

    The reduction is a one-hot matmul (MXU-friendly, no scatter), the
    pattern of engine._tenant_event_counts."""
    b = batch.capacity
    aux1 = batch.aux[:, 1]
    has_alt = batch.valid & (aux1 != NULL_ID)
    # rows without an alternate id get unique sentinel keys so they can
    # never pair; two-pass stable argsort = lexsort by (token, aux1)
    alt_key = jnp.where(has_alt, aux1, -2 - jnp.arange(b, dtype=jnp.int32))
    order1 = jnp.argsort(alt_key)
    order = order1[jnp.argsort(batch.token_id[order1])]
    st = batch.token_id[order]
    sa = alt_key[order]
    dup_sorted = jnp.concatenate([
        jnp.zeros((1,), bool), (st[1:] == st[:-1]) & (sa[1:] == sa[:-1])])
    dedup = jnp.zeros(b, bool).at[order].set(dup_sorted) & has_alt

    if zones is not None:
        from sitewhere_tpu.ops.geofence import points_in_zones

        is_loc = (batch.valid & (batch.etype == int(EventType.LOCATION))
                  & batch.vmask[:, 0])
        inz = points_in_zones(batch.values[:, :2], zones.verts, zones.valid)
        geo = is_loc & jnp.any(inz, axis=1)
    else:
        geo = jnp.zeros(b, bool)

    bucket = jnp.where(batch.valid,
                       batch.tenant_id % TENANT_COUNTER_BUCKETS, -1)
    onehot = (bucket[:, None]
              == jnp.arange(TENANT_COUNTER_BUCKETS)[None, :]).astype(
                  jnp.int32)                                      # [B, T]
    lanes = jnp.stack([accepted, dedup, geo, invalid],
                      axis=-1).astype(jnp.int32)                  # [B, 4]
    return jnp.einsum("bt,bc->tc", onehot, lanes)


class StepOutput(NamedTuple):
    """Host-visible per-step results. Token lists are compacted, NULL_ID
    padded."""

    n_found: jax.Array        # int32[]
    n_missed: jax.Array       # int32[]
    n_registered: jax.Array   # int32[]
    n_persisted: jax.Array    # int32[]
    new_tokens: jax.Array     # int32[B] tokens auto-registered this step
    dead_tokens: jax.Array    # int32[B] unregistered tokens (DLQ analog of the
                              #          unregistered-device-events topic)
    store_cursor: jax.Array   # int32[] ring cursor after append
    store_epoch: jax.Array    # int32[]


def pipeline_step(
    state: PipelineState, batch: EventBatch, config: PipelineConfig
) -> tuple[PipelineState, StepOutput]:
    """Process one decoded-event batch end to end (pure function; jit with
    ``donate_argnums=0`` via :func:`make_pipeline_step`)."""
    reg = state.registry
    b = batch.capacity

    # each stage runs under a named scope: the scope lands in every op's
    # metadata, so a device trace can sum time per stage by name, whatever
    # the fusions are numbered (benchmark/metrics/step_append_ms.py)
    # 1. device lookup (inbound-processing analog)
    with jax.named_scope("lookup"):
        res = lookup_devices(reg, batch.token_id, batch.tenant_id,
                             batch.valid)

    # 2. auto-registration of the miss set (device-registration analog)
    with jax.named_scope("register"):
        if config.auto_register:
            regres = register_misses(
                reg,
                state.next_device,
                state.next_assignment,
                batch.token_id,
                batch.tenant_id,
                res.miss,
                jnp.int32(config.default_device_type),
                jnp.int32(config.default_area),
                jnp.int32(config.default_customer),
            )
            reg = regres.registry
            next_device = regres.next_device
            next_assignment = regres.next_assignment
            n_registered = regres.n_registered
            new_tokens = regres.new_tokens
            reg_overflow = regres.overflow.astype(jnp.int32)
            # re-lookup so this batch's events flow through for
            # just-registered devices (the reference re-injects events
            # after registration)
            res = lookup_devices(reg, batch.token_id, batch.tenant_id,
                                 batch.valid)
        else:
            next_device = state.next_device
            next_assignment = state.next_assignment
            n_registered = jnp.zeros((), jnp.int32)
            new_tokens = jnp.full((b,), NULL_ID, jnp.int32)
            reg_overflow = jnp.zeros((), jnp.int32)

        # remaining misses -> dead-letter list (unregistered-device-events
        # analog)
        n_miss, perm = compact_valid_front(res.miss)
        dead_tokens = jnp.where(jnp.arange(b) < n_miss,
                                batch.token_id[perm], NULL_ID)

    # 3. per-assignment expansion (PreprocessedEventMapper flatMap analog)
    with jax.named_scope("expand"):
        exp = expand_assignments(reg, res)

    # 4. persistence append (event-management analog)
    with jax.named_scope("append"):
        src = exp.source_row
        persist = append_events(
            state.store,
            valid=exp.valid,
            etype=batch.etype[src],
            device=exp.device,
            assignment=exp.assignment,
            tenant=batch.tenant_id[src],
            area=exp.area,
            customer=exp.customer,
            asset=exp.asset,
            ts_ms=batch.ts_ms[src],
            received_ms=batch.received_ms[src],
            values=batch.values[src],
            vmask=batch.vmask[src],
            aux=batch.aux[src],
        )

    # 5. telemetry-window update for the analytics service (devices with
    #    dense id < analytics capacity get HBM-resident sliding windows)
    windows = state.windows
    if windows is not None:
        with jax.named_scope("windows"):
            windows = append_measurements(
                windows, res.device, res.found, batch.etype, batch.ts_ms,
                batch.seq, batch.values,
            )

    # 5.5 streaming-rules CEP tier (ops/rules.py): standing rules +
    #     continuous rollups evaluate on the post-lookup view INSIDE this
    #     same program — a rule is a predicate that never leaves the
    #     batch. Fires land in device-resident pending slots harvested at
    #     reporting cadence (Engine.poll_rule_fires); nothing here syncs.
    rules = state.rules
    if rules is not None:
        with jax.named_scope("rules"):
            rules = rules_update(rules, batch, res.device, res.found, reg)

    # 6. windowed device-state merge (device-state analog)
    with jax.named_scope("merge"):
        new_device_state = merge_batch_state(
            state.device_state,
            dev=res.device,
            found=res.found,
            etype=batch.etype,
            ts_ms=batch.ts_ms,
            seq=batch.seq,
            values=batch.values,
            vmask=batch.vmask,
            aux=batch.aux,
        )

    n_found = jnp.sum(res.found.astype(jnp.int32))
    m = state.metrics
    metrics = PipelineMetrics(
        processed=m.processed + batch.count(),
        found=m.found + n_found,
        missed=m.missed + n_miss,
        registered=m.registered + n_registered,
        persisted=m.persisted + persist.appended,
        reg_overflow=m.reg_overflow + reg_overflow,
        tenant_counters=m.tenant_counters + _tenant_counter_delta(
            batch, accepted=res.found, invalid=res.miss,
            zones=state.zones),
    )

    new_state = PipelineState(
        registry=reg,
        device_state=new_device_state,
        store=persist.store,
        next_device=next_device,
        next_assignment=next_assignment,
        metrics=metrics,
        windows=windows,
        zones=state.zones,
        rules=rules,
    )
    out = StepOutput(
        n_found=n_found,
        n_missed=n_miss,
        n_registered=n_registered,
        n_persisted=persist.appended,
        new_tokens=new_tokens,
        dead_tokens=dead_tokens,
        store_cursor=persist.store.cursor,
        store_epoch=persist.store.epoch,
    )
    return new_state, out


@functools.cache
def make_pipeline_step(config: PipelineConfig):
    """Compile the pipeline step with state donation (no HBM copies between
    steps — the state stays resident, the analog of Kafka Streams' local
    state stores without the serialization)."""
    return jax.jit(
        functools.partial(pipeline_step, config=config), donate_argnums=(0,)
    )


@functools.cache
def make_packed_scan_step(config: PipelineConfig, capacity: int,
                          channels: int):
    """Like :func:`make_pipeline_scan_step`, but the K batches arrive as ONE
    contiguous ``uint8[K, row_bytes]`` buffer (core/events.pack_batches) —
    a single host->device transfer per chunk instead of 10 per batch
    (per-transfer overhead on the chip host is not measured). Unpacking
    is bitcast/reshape only, fused into the step."""
    from sitewhere_tpu.core.events import unpack_batch

    def multi(state: PipelineState, packed):
        def body(st, row):
            return pipeline_step(st, unpack_batch(row, capacity, channels),
                                 config)

        return jax.lax.scan(body, state, packed)

    # donate ONLY the state: the packed wire buffer has no same-shaped
    # output to alias, so donating it is a no-op that makes XLA warn
    # "Some donated buffers were not usable" on every dispatch
    return jax.jit(multi, donate_argnums=(0,))


@functools.cache
def make_arena_scan_step(config: PipelineConfig, capacity: int,
                         channels: int, k: int):
    """Consume ONE staging arena of ``k * capacity`` rows as a k-lane
    ``lax.scan``: each SoA column arrives as a single flat array and is
    reshaped to [k, capacity] INSIDE the jit (free relayout — no
    host-side packing or per-batch slicing copy, unlike
    :func:`make_packed_scan_step` whose K batches must first be
    concatenated by ``pack_batches``). This is the dispatch program of
    the zero-copy arena ingest path at ``scan_chunk`` > 1."""
    from sitewhere_tpu.core.types import AUX_LANES

    def multi(state: PipelineState, batch: EventBatch):
        stacked = EventBatch(
            valid=batch.valid.reshape(k, capacity),
            etype=batch.etype.reshape(k, capacity),
            token_id=batch.token_id.reshape(k, capacity),
            tenant_id=batch.tenant_id.reshape(k, capacity),
            ts_ms=batch.ts_ms.reshape(k, capacity),
            received_ms=batch.received_ms.reshape(k, capacity),
            values=batch.values.reshape(k, capacity, channels),
            vmask=batch.vmask.reshape(k, capacity, channels),
            aux=batch.aux.reshape(k, capacity, AUX_LANES),
            seq=batch.seq.reshape(k, capacity),
        )

        def body(st, b):
            return pipeline_step(st, b, config)

        return jax.lax.scan(body, state, stacked)

    # donate ONLY the state (see make_packed_scan_step: donating the
    # input batch would just warn — it has no same-shaped output)
    return jax.jit(multi, donate_argnums=(0,))


@functools.cache
def make_presence_sweep():
    """Compiled presence sweep (DevicePresenceManager analog)."""

    def sweep(state: PipelineState, now_ms: jax.Array, missing_ms: jax.Array):
        ds, newly_missing = presence_sweep(
            state.device_state, state.registry.device_active, now_ms, missing_ms
        )
        return dataclasses.replace(state, device_state=ds), newly_missing

    return jax.jit(sweep, donate_argnums=(0,))
