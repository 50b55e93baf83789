"""DistributedEngine: the full product runtime over the sharded ICI mesh.

``ShardedEngine`` (parallel/sharded.py) proves the collectives: it runs the
fused pipeline over a mesh, but consumes pre-interned integer batches. This
module is the *product* on top — everything the single-node ``Engine``
(engine.py) offers, running against stacked per-shard state:

  * string device tokens, interned once (native C++ interner when available)
    and hash-routed to an owning shard — the host-side analog of the
    reference's token-keyed Kafka partitioner
    (service-event-sources/.../manager/EventSourcesManager.java:183);
  * per-shard staging buffers feeding ONE stacked jit step (shard_map over
    the mesh), so every shard's fused pipeline runs in the same XLA program;
  * WAL durability + snapshot/recovery of the stacked state (the reference
    leans on Kafka offsets + k8s restarts, SURVEY.md §5.4/5.5);
  * admin CRUD, event queries, device-state reads, and presence sweeps
    served from the sharded state — the surface the REST gateway
    (web/rest.py) binds to, mirroring how the reference's REST controllers
    fan out to per-partition services over gRPC;
  * fair multi-tenant batch formation per shard.

Token routing: the global interner hands out dense ids; shard
``gid % n_shards`` owns the token and its local id is ``gid // n_shards``
(round-robin => balanced shards by construction). Global device ids are
``local_id * n_shards + shard`` — bijective, so host mirrors stay flat
dicts like the single-node engine's.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.core.events import EpochBase, EventBatch
from sitewhere_tpu.core.registry import MAX_ACTIVE_ASSIGNMENTS, TokenInterner
from sitewhere_tpu.core.types import (
    AUX_LANES,
    DEFAULT_VALUE_CHANNELS,
    NULL_ID,
    DeviceAssignmentStatus,
    EventType,
    PresenceState,
)
from sitewhere_tpu.engine import (
    WAL_BINARY,
    WAL_JSON,
    AssignmentInfo,
    ChannelMap,
    DeviceInfo,
    IngestHostMixin,
)
from sitewhere_tpu.parallel.sharded import ShardedEngine, _stacked_query
from sitewhere_tpu.pipeline import PipelineConfig, PipelineState, StepOutput


@dataclasses.dataclass
class DistributedConfig:
    """Per-shard capacities + the host-side engine knobs (EngineConfig
    analog). Global token capacity is n_shards * token_capacity_per_shard."""

    n_shards: int | None = None            # default: all local devices
    device_capacity_per_shard: int = 1 << 14
    token_capacity_per_shard: int = 1 << 15
    assignment_capacity_per_shard: int = 1 << 15
    store_capacity_per_shard: int = 1 << 16
    channels: int = DEFAULT_VALUE_CHANNELS
    batch_capacity_per_shard: int = 2048
    flush_interval_s: float = 0.05
    auto_register: bool = True
    default_device_type: str = "default"
    presence_missing_s: float = 8 * 3600.0
    use_native: bool = True
    strict_channels: bool = False
    fair_tenancy: bool = False
    wal_dir: str | None = None
    archive_dir: str | None = None     # long-term retention: spill each
                                       # (shard, arena) sub-ring to disk
                                       # before overwrite (utils/archive.py)
    archive_segment_rows: int = 4096
    archive_max_rows: int | None = None  # per-(shard,arena) retention cap
    archive_max_age_ms: int | None = None  # event-time retention horizon
    archive_cache_segments: int = 8    # LRU segment-decode cache depth
    flight_recorder: bool = True       # batch-lifecycle flight recorder
    flight_capacity: int = 1024        # lifecycle records retained
    span_trace: bool = True            # hierarchical span tracer (ISSUE
                                       # 10) — same contract as
                                       # EngineConfig.span_trace
    span_capacity: int = 4096          # completed spans retained
    span_sample: float = 1.0           # head-based keep fraction
    span_seed: int = 0                 # sampling hash seed
    qos: bool = False                  # overload discipline (utils/qos.py):
                                       # per-tenant token-bucket admission
                                       # consulted at the ingest EDGES
                                       # (REST/RPC/cluster forward), plus
                                       # weighted-fair ingest turns —
                                       # same contract as EngineConfig.qos
    tenant_rates: dict | None = None   # tenant -> admitted events/s
    qos_default_rate_eps: float = 0.0  # rate for unlisted tenants (0 = off)
    qos_burst_s: float = 2.0           # bucket depth, seconds of rate
    tenant_weights: dict | None = None # WFQ weights (default equal)
    shed_threshold: int = 0            # staged-row saturation valve (0 =
                                       # auto: 4 * batch_capacity_per_shard
                                       # * n_shards)
    qos_min_retry_after_s: float = 0.05
    conservation: bool = True          # event conservation ledger
                                       # (ISSUE 14) — same contract as
                                       # EngineConfig.conservation


class _StackedBuffer:
    """Host staging for all shards at once: [S, B, ...] numpy arrays with a
    per-shard fill count. ``emit()`` converts to ONE stacked EventBatch (one
    host->device transfer for the whole mesh step, not one per shard)."""

    def __init__(self, n_shards: int, capacity: int, channels: int):
        self.n_shards = n_shards
        self.capacity = capacity
        self.channels = channels
        self._alloc()

    def _alloc(self) -> None:
        s, b, c = self.n_shards, self.capacity, self.channels
        self.counts = np.zeros(s, np.int64)
        self.etype = np.zeros((s, b), np.int32)
        self.token_id = np.full((s, b), NULL_ID, np.int32)
        self.tenant_id = np.full((s, b), NULL_ID, np.int32)
        self.ts_ms = np.zeros((s, b), np.int32)
        self.received_ms = np.zeros((s, b), np.int32)
        self.values = np.zeros((s, b, c), np.float32)
        self.vmask = np.zeros((s, b, c), np.bool_)
        self.aux = np.full((s, b, AUX_LANES), NULL_ID, np.int32)

    def total(self) -> int:
        return int(self.counts.sum())

    def room(self, shard: int) -> int:
        return self.capacity - int(self.counts[shard])

    def append_row(self, shard: int, etype: int, local_token: int,
                   tenant_id: int, ts: int, recv: int,
                   values: np.ndarray | None, vmask: np.ndarray | None,
                   aux0: int, aux1: int) -> bool:
        i = int(self.counts[shard])
        if i >= self.capacity:
            return False
        self.etype[shard, i] = etype
        self.token_id[shard, i] = local_token
        self.tenant_id[shard, i] = tenant_id
        self.ts_ms[shard, i] = ts
        self.received_ms[shard, i] = recv
        if vmask is not None:
            self.values[shard, i] = values
            self.vmask[shard, i] = vmask
        self.aux[shard, i, 0] = aux0
        self.aux[shard, i, 1] = aux1
        self.counts[shard] = i + 1
        return True

    def emit(self) -> EventBatch:
        s, b = self.n_shards, self.capacity
        valid = np.arange(b)[None, :] < self.counts[:, None]
        # numpy-backed: the sharded jit dispatch transfers all leaves in one
        # grouped hop (no per-field device round trips)
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
            seq=np.broadcast_to(np.arange(b, dtype=np.int32), (s, b)).copy(),
        )
        self._alloc()
        return batch


class _FairChunk:
    """A run of staged rows for one (shard, tenant) awaiting fair batch
    formation (engine.py _FairChunk analog, shard-local)."""

    __slots__ = ("etype", "token", "ts", "recv", "values", "vmask",
                 "aux0", "aux1", "pos")

    def __init__(self, etype, token, ts, recv, values, vmask, aux0, aux1):
        self.etype = etype
        self.token = token
        self.ts = ts
        self.recv = recv
        self.values = values
        self.vmask = vmask
        self.aux0 = aux0
        self.aux1 = aux1
        self.pos = 0

    @property
    def remaining(self) -> int:
        return len(self.etype) - self.pos


# --------------------------------------------------------------------------
# admin-path jit updaters over the STACKED state (leading shard axis). Used
# on the REST/API path only; the hot path registers on-device in the step.
@jax.jit
def _admin_create_device_stacked(state: PipelineState, shard, token_local,
                                 did, aid, type_id, tenant_id, area_id,
                                 customer_id):
    reg = state.registry
    reg = dataclasses.replace(
        reg,
        token_to_device=reg.token_to_device.at[shard, token_local].set(did),
        device_active=reg.device_active.at[shard, did].set(True),
        device_type=reg.device_type.at[shard, did].set(type_id),
        device_tenant=reg.device_tenant.at[shard, did].set(tenant_id),
        device_area=reg.device_area.at[shard, did].set(area_id),
        device_customer=reg.device_customer.at[shard, did].set(customer_id),
        device_assignments=reg.device_assignments.at[shard, did, 0].set(aid),
        assignment_active=reg.assignment_active.at[shard, aid].set(True),
        assignment_status=reg.assignment_status.at[shard, aid].set(
            jnp.int32(DeviceAssignmentStatus.ACTIVE)),
        assignment_device=reg.assignment_device.at[shard, aid].set(did),
        assignment_area=reg.assignment_area.at[shard, aid].set(area_id),
        assignment_customer=reg.assignment_customer.at[shard, aid].set(customer_id),
    )
    return dataclasses.replace(
        state,
        registry=reg,
        next_device=state.next_device.at[shard].max(did + 1),
        next_assignment=state.next_assignment.at[shard].max(aid + 1),
    )


@jax.jit
def _admin_set_device_active_stacked(state: PipelineState, shard, did, active):
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg, device_active=reg.device_active.at[shard, did].set(active)))


@jax.jit
def _admin_update_device_stacked(state: PipelineState, shard, did, type_id,
                                 area_id, customer_id):
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg,
            device_type=reg.device_type.at[shard, did].set(type_id),
            device_area=reg.device_area.at[shard, did].set(area_id),
            device_customer=reg.device_customer.at[shard, did].set(customer_id),
        ))


@jax.jit
def _admin_set_parent_stacked(state: PipelineState, shard, did, parent_did):
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg, device_parent=reg.device_parent.at[shard, did].set(parent_did)))


@jax.jit
def _admin_add_assignment_stacked(state: PipelineState, shard, did, aid, slot,
                                  asset_id, area_id, customer_id):
    reg = state.registry
    reg = dataclasses.replace(
        reg,
        device_assignments=reg.device_assignments.at[shard, did, slot].set(aid),
        assignment_active=reg.assignment_active.at[shard, aid].set(True),
        assignment_status=reg.assignment_status.at[shard, aid].set(
            jnp.int32(DeviceAssignmentStatus.ACTIVE)),
        assignment_device=reg.assignment_device.at[shard, aid].set(did),
        assignment_asset=reg.assignment_asset.at[shard, aid].set(asset_id),
        assignment_area=reg.assignment_area.at[shard, aid].set(area_id),
        assignment_customer=reg.assignment_customer.at[shard, aid].set(customer_id),
    )
    return dataclasses.replace(
        state, registry=reg,
        next_assignment=state.next_assignment.at[shard].max(aid + 1))


@jax.jit
def _admin_update_assignment_stacked(state: PipelineState, shard, aid,
                                     asset_id, area_id, customer_id):
    """Stacked-axis analog of engine._admin_update_assignment (REST PUT
    path; reference: Assignments.java:144 -> updateDeviceAssignment)."""
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg,
            assignment_asset=reg.assignment_asset.at[shard, aid].set(asset_id),
            assignment_area=reg.assignment_area.at[shard, aid].set(area_id),
            assignment_customer=reg.assignment_customer.at[shard, aid].set(
                customer_id),
        ))


@jax.jit
def _admin_set_assignment_status_stacked(state: PipelineState, shard, aid,
                                         status, active):
    reg = state.registry
    did = reg.assignment_device[shard, aid]
    row = reg.device_assignments[shard, did]
    new_row = jnp.where((row == aid) & ~active, jnp.int32(NULL_ID), row)
    reg = dataclasses.replace(
        reg,
        assignment_status=reg.assignment_status.at[shard, aid].set(status),
        assignment_active=reg.assignment_active.at[shard, aid].set(active),
        device_assignments=reg.device_assignments.at[shard, did].set(new_row),
    )
    return dataclasses.replace(state, registry=reg)


def _watch_stacked_admin_jits() -> None:
    """Devicewatch (ISSUE 11): the stacked admin updaters report
    compiles under one ``distributed.admin`` family — unbudgeted, like
    the single-node admin family (shared across every mesh config in
    the process)."""
    from sitewhere_tpu.utils.devicewatch import watched_jit

    g = globals()
    for name in ("_admin_create_device_stacked",
                 "_admin_set_device_active_stacked",
                 "_admin_update_device_stacked",
                 "_admin_set_parent_stacked",
                 "_admin_add_assignment_stacked",
                 "_admin_update_assignment_stacked",
                 "_admin_set_assignment_status_stacked"):
        g[name] = watched_jit(g[name], family="distributed.admin")


_watch_stacked_admin_jits()


class DistributedEngine(IngestHostMixin):
    """Multi-shard product engine: one object per host serving the whole
    mesh. All mutations serialize through one lock (single-writer semantics,
    like the single-node engine); the step itself is one stacked jit. WAL
    and strict-channel behavior come from IngestHostMixin — identical
    semantics to the single-node Engine by construction."""

    def __init__(self, config: DistributedConfig | None = None):
        self.config = c = config or DistributedConfig()
        self.sharded = ShardedEngine(
            n_shards=c.n_shards,
            device_capacity_per_shard=c.device_capacity_per_shard,
            token_capacity_per_shard=c.token_capacity_per_shard,
            assignment_capacity_per_shard=c.assignment_capacity_per_shard,
            store_capacity_per_shard=c.store_capacity_per_shard,
            channels=c.channels,
            config=PipelineConfig(auto_register=c.auto_register,
                                  default_device_type=0),
        )
        self.n_shards = self.sharded.n_shards
        self.epoch = EpochBase()
        self.lock = threading.RLock()
        self.host_counters: dict[str, int] = {}
        token_capacity = c.token_capacity_per_shard * self.n_shards
        self._native_decoder = None
        if c.use_native:
            try:
                from sitewhere_tpu.ingest.fast_decode import NativeBatchDecoder
                from sitewhere_tpu.native.binding import NativeInterner

                self.tokens = NativeInterner(token_capacity)
                self._native_decoder = NativeBatchDecoder(self.tokens, c.channels)
            except (RuntimeError, OSError):
                self._native_decoder = None
        if self._native_decoder is not None:
            self.channel_map = ChannelMap(c.channels, self._native_decoder.names,
                                          strict=c.strict_channels)
            self.alert_types = self._native_decoder.alert_types
        else:
            self.tokens = TokenInterner(token_capacity)
            self.channel_map = ChannelMap(c.channels, strict=c.strict_channels)
            self.alert_types = TokenInterner(1 << 20)
        self.tenants = TokenInterner(1 << 16)
        self.tenants.intern("default")
        self.device_types = TokenInterner(1 << 16)
        self.device_types.intern(c.default_device_type)
        self.areas = TokenInterner(1 << 16)
        self.customers = TokenInterner(1 << 16)
        self.assets = TokenInterner(1 << 16)
        # adopt the native decoder's event-id interner (alternate ids,
        # aux1) so batch-decoded and per-request rows share one id space
        self.event_ids = (self._native_decoder.event_ids
                          if self._native_decoder is not None
                          else TokenInterner(1 << 22))

        self._buf = _StackedBuffer(self.n_shards, c.batch_capacity_per_shard,
                                   c.channels)
        self._last_flush = time.monotonic()
        # host mirrors — flat dicts over GLOBAL ids (local * n_shards + shard)
        self.devices: dict[int, DeviceInfo] = {}
        self.token_device: dict[int, int] = {}        # gid -> global did
        self.assignments: dict[int, AssignmentInfo] = {}
        self.assignment_tokens: dict[str, int] = {}
        self.device_slots: dict[int, list[int]] = {}
        self._next_device = np.zeros(self.n_shards, np.int64)   # per shard
        self._next_assignment = np.zeros(self.n_shards, np.int64)
        self.dead_letters: list[str] = []             # unregistered tokens
        self.outputs: list[dict] = []
        self._pending_outs: list[StepOutput] = []
        self._pending_tenant_fixups: list[tuple[int, int, int]] = []
        # flight recorder (utils/flight.py): Engine-parity lifecycle
        # records for every ingest batch; the mixin's _ingest_batch binds
        # records, flush_async/drain stamp dispatch/device_ready/readback
        from sitewhere_tpu.utils.flight import FlightRecorder

        self.flight = FlightRecorder(capacity=c.flight_capacity,
                                     enabled=c.flight_recorder)
        self._staged_traces: list = []
        self._pending_traces: list[list] = []
        # span tracer + process-unique engine label (ISSUE 10) — same
        # wiring as the single-node Engine; ClusterEngine re-stamps
        # .rank exactly like it does for the flight recorder
        from sitewhere_tpu.utils.metrics import next_engine_label
        from sitewhere_tpu.utils.tracing import SpanTracer

        self.tracer = SpanTracer(capacity=c.span_capacity,
                                 enabled=c.span_trace,
                                 sample=c.span_sample, seed=c.span_seed)
        self.metrics_label = next_engine_label()
        # event conservation ledger (ISSUE 14) — Engine-parity flow
        # counters at the staging/dispatch boundaries of the mesh
        from sitewhere_tpu.utils.conservation import FlowLedger

        self.ledger = FlowLedger(enabled=c.conservation)
        self.conservation_auditor = None
        # fair tenancy: per-shard {tenant_id: deque[_FairChunk]}
        self._fair_queues: list[dict[int, collections.deque]] = [
            {} for _ in range(self.n_shards)]
        self._fair_queued = np.zeros(self.n_shards, np.int64)
        self.wal = None
        self._wal_local = threading.local()
        if c.wal_dir:
            from sitewhere_tpu.utils.ingestlog import IngestLog

            self.wal = IngestLog(c.wal_dir)
        # long-term retention: every (shard, arena) sub-ring spills to one
        # archive partition before its rows can be overwritten
        self.archive = None
        self._rows_since_spool = 0
        if c.archive_dir:
            from sitewhere_tpu.utils.archive import EventArchive, mesh_topology

            arenas = self.state.store.cursor.shape[-1]
            acap = c.store_capacity_per_shard // arenas
            self.archive = EventArchive(
                c.archive_dir,
                segment_rows=max(1, min(c.archive_segment_rows, acap // 4)),
                max_rows_per_part=c.archive_max_rows,
                topology=mesh_topology(self.n_shards, arenas),
                max_age_ms=c.archive_max_age_ms,
                cache_segments=c.archive_cache_segments)
            self._spool_trigger = max(self.archive.segment_rows,
                                      acap // 2 - c.batch_capacity_per_shard)
        # overload discipline (ISSUE 9): same contract as the single-node
        # engine — admission at the edges (the cluster RPC ingest
        # handlers consult engine.qos at the OWNER), WFQ turns on the
        # batch-ingest critical section. The replica applier and WAL
        # recovery call the ingest methods directly and therefore can
        # never shed a durable event.
        if getattr(c, "qos", False):
            from sitewhere_tpu.utils.qos import (AdmissionController,
                                                 WeightedFairGate)

            self.qos = AdmissionController(
                tenant_rates=c.tenant_rates,
                default_rate_eps=c.qos_default_rate_eps,
                burst_s=c.qos_burst_s,
                shed_threshold=(c.shed_threshold
                                or 4 * c.batch_capacity_per_shard
                                * self.n_shards),
                backlog_fn=lambda: self.staged_count,
                min_retry_after_s=c.qos_min_retry_after_s)
            self._wfq_gate = WeightedFairGate(c.tenant_weights)

    # ---------------------------------------------------------------- routing
    def _route(self, gid: int) -> tuple[int, int]:
        """(shard, local_token) for a global interner id."""
        return gid % self.n_shards, gid // self.n_shards

    def _gdid(self, shard: int, local_did: int) -> int:
        return local_did * self.n_shards + shard

    def _split_gdid(self, gdid: int) -> tuple[int, int]:
        return gdid % self.n_shards, gdid // self.n_shards

    @property
    def state(self) -> PipelineState:
        return self.sharded.state

    @property
    def staged_count(self) -> int:
        return self._buf.total() + int(self._fair_queued.sum())

    def _sync_mirrors(self) -> None:
        while self._buf.total() or self._fair_queued.sum():
            self.flush_async()
        if self._pending_outs:
            self.drain()

    # ---------------------------------------------------------------- ingest
    # process() comes from IngestHostMixin; it converts the request to one
    # SoA row and calls _stage_row, which routes it to its owning shard.
    def _stage_row(self, et, token_id, tenant_id, ts, now, values, mask,
                   aux0, aux1):
        """Stage one converted event row into its owning shard's buffer
        (``token_id`` is the GLOBAL interner id). Caller holds the lock."""
        shard, local = self._route(token_id)
        self.ledger.add("staged_rows", 1)
        has_vals = mask is not None and mask.any()
        if self.config.fair_tenancy:
            i32 = np.int32
            self._fair_enqueue(shard, tenant_id, _FairChunk(
                etype=np.array([et], i32),
                token=np.array([local], i32),
                ts=np.array([ts], i32),
                recv=np.array([now], i32),
                values=values[None].copy() if has_vals else None,
                vmask=mask[None].copy() if has_vals else None,
                aux0=np.array([aux0], i32),
                aux1=np.array([aux1], i32),
            ))
            return
        if not self._buf.append_row(shard, et, local, tenant_id, ts, now,
                                    values if has_vals else None,
                                    mask if has_vals else None, aux0, aux1):
            self.flush_async()
            self._buf.append_row(shard, et, local, tenant_id, ts, now,
                                 values if has_vals else None,
                                 mask if has_vals else None, aux0, aux1)
        if self._buf.room(shard) == 0:
            self.flush_async()

    def ingest_json_batch(self, payloads: list[bytes],
                          tenant: str = "default",
                          traceparent: str | None = None) -> dict:
        """Fast path: one native decode call for the batch, vectorized
        shard routing + staging (no per-event Python)."""
        from sitewhere_tpu.ingest.decoders import JsonDeviceRequestDecoder

        return self._ingest_batch(
            payloads, tenant, WAL_JSON, JsonDeviceRequestDecoder(),
            self._native_decoder.decode if self._native_decoder else None,
            traceparent=traceparent)

    def ingest_binary_batch(self, payloads: list[bytes],
                            tenant: str = "default",
                            traceparent: str | None = None) -> dict:
        from sitewhere_tpu.ingest.decoders import BinaryEventDecoder

        return self._ingest_batch(
            payloads, tenant, WAL_BINARY, BinaryEventDecoder(),
            self._native_decoder.decode_binary if self._native_decoder
            else None, traceparent=traceparent)

    def _ingest_decoded(self, res, payloads, tenant, reg_decoder) -> dict:
        """Stage a natively decoded SoA batch, grouped by owning shard with
        one argsort (the vectorized Kafka-partitioner hop)."""
        with self.lock:
            now = self._staging_now()
            base_ms = int(self.epoch.base_unix_s * 1000)
            etype, ok, ts_rel, values, failed, n_reg_ok = \
                self._decode_prologue(res, payloads, tenant, reg_decoder,
                                      now, base_ms)
            idxs = np.nonzero(ok)[0]
            tenant_id = self.tenants.intern(tenant)
            gids = res.token_id[idxs]
            shards = gids % self.n_shards
            locals_ = gids // self.n_shards
            order = np.argsort(shards, kind="stable")
            sidx, sshard, slocal = idxs[order], shards[order], locals_[order]
            bounds = np.searchsorted(sshard, np.arange(self.n_shards + 1))
            staged = 0
            for s in range(self.n_shards):
                rows = sidx[bounds[s]:bounds[s + 1]]
                toks = slocal[bounds[s]:bounds[s + 1]]
                if not len(rows):
                    continue
                if self.config.fair_tenancy:
                    self._fair_enqueue(s, tenant_id, _FairChunk(
                        etype=etype[rows],
                        token=toks.astype(np.int32),
                        ts=ts_rel[rows],
                        recv=np.full(len(rows), now, np.int32),
                        values=values[rows],
                        vmask=res.chmask[rows],
                        aux0=res.aux0[rows],
                        aux1=np.full(len(rows), NULL_ID, np.int32),
                    ))
                    staged += len(rows)
                    continue
                pos = 0
                b = self._buf
                while pos < len(rows):
                    room = b.room(s)
                    if room == 0:
                        self.flush_async()
                        room = b.capacity
                    chunk = rows[pos:pos + room]
                    tchunk = toks[pos:pos + room]
                    lo = int(b.counts[s])
                    hi = lo + len(chunk)
                    b.etype[s, lo:hi] = etype[chunk]
                    b.token_id[s, lo:hi] = tchunk
                    b.tenant_id[s, lo:hi] = tenant_id
                    b.ts_ms[s, lo:hi] = ts_rel[chunk]
                    b.received_ms[s, lo:hi] = now
                    b.values[s, lo:hi] = values[chunk]
                    b.vmask[s, lo:hi] = res.chmask[chunk]
                    b.aux[s, lo:hi, 0] = res.aux0[chunk]
                    b.counts[s] = hi
                    staged += len(chunk)
                    pos += len(chunk)
                if b.room(s) == 0:
                    self.flush_async()
            self.channel_map.collisions += res.collisions
            self.ledger.add("staged_rows", staged)
            return {"decoded": int(np.sum(ok)) + n_reg_ok, "failed": failed,
                    "staged": staged}

    # ----------------------------------------------------------- fair tenancy
    def _fair_enqueue(self, shard: int, tenant_id: int, chunk: _FairChunk) -> None:
        q = self._fair_queues[shard].get(tenant_id)
        if q is None:
            q = self._fair_queues[shard][tenant_id] = collections.deque()
        q.append(chunk)
        self._fair_queued[shard] += chunk.remaining
        if self._fair_queued[shard] >= self.config.batch_capacity_per_shard:
            self.flush_async()

    def fair_backlog(self, tenant: str) -> int:
        with self.lock:
            tid = self.tenants.lookup(tenant)
            return sum(
                c.remaining
                for queues in self._fair_queues
                for c in queues.get(tid, ()))

    def _form_fair_batch(self, shard: int) -> None:
        """Quota-sliced per-shard batch formation across tenants (engine.py
        _form_fair_batch per shard). Caller holds the lock."""
        b = self._buf
        queues = self._fair_queues[shard]
        while self._fair_queued[shard] and b.room(shard):
            active = [t for t, q in queues.items() if q]
            if not active:
                break
            quota = max(1, b.room(shard) // len(active))
            for tid in active:
                q = queues[tid]
                take = quota
                while take > 0 and q and b.room(shard):
                    ch = q[0]
                    k = min(take, ch.remaining, b.room(shard))
                    lo = int(b.counts[shard])
                    hi, p = lo + k, ch.pos
                    b.etype[shard, lo:hi] = ch.etype[p:p + k]
                    b.token_id[shard, lo:hi] = ch.token[p:p + k]
                    b.tenant_id[shard, lo:hi] = tid
                    b.ts_ms[shard, lo:hi] = ch.ts[p:p + k]
                    b.received_ms[shard, lo:hi] = ch.recv[p:p + k]
                    if ch.values is not None:
                        b.values[shard, lo:hi] = ch.values[p:p + k]
                        b.vmask[shard, lo:hi] = ch.vmask[p:p + k]
                    b.aux[shard, lo:hi, 0] = ch.aux0[p:p + k]
                    b.aux[shard, lo:hi, 1] = ch.aux1[p:p + k]
                    b.counts[shard] = hi
                    ch.pos += k
                    take -= k
                    self._fair_queued[shard] -= k
                    if ch.remaining == 0:
                        q.popleft()
        for tid in [t for t, q in queues.items() if not q]:
            del queues[tid]

    # ------------------------------------------------------------------ step
    def maybe_flush(self) -> dict | None:
        with self.lock:
            expired = (time.monotonic() - self._last_flush
                       >= self.config.flush_interval_s)
            if (self._buf.total() or self._fair_queued.sum()) and expired:
                return self.flush()
            if self._pending_outs and expired:
                return self.drain()[-1]
            return None

    def flush(self) -> dict:
        import logging

        from sitewhere_tpu.utils.tracing import stage

        try:
            with self.lock, stage("sharded_step"):
                self.flush_async()
                while self._fair_queued.sum():
                    self.flush_async()
                return self.drain()[-1]
        except Exception:
            self.flight.dump_error(logging.getLogger(__name__))
            raise

    def flush_async(self) -> None:
        """Dispatch one stacked step (no host sync); outputs queue for
        drain()."""
        with self.lock:
            if self._fair_queued.sum():
                for s in range(self.n_shards):
                    if self._fair_queued[s]:
                        self._form_fair_batch(s)
            if not self._buf.total():
                return
            n_staged = int(max(self._buf.counts))  # worst shard's rows
            self.ledger.add("dispatched_rows", self._buf.total())
            batch = self._buf.emit()
            traces, self._staged_traces = self._staged_traces, []
            for rec in traces:
                rec.mark("dispatch")
            out = self.sharded.step(batch)
            self._pending_outs.append(out)
            self._pending_traces.append(traces)
            self._last_flush = time.monotonic()
            if self.archive is not None:
                # per-shard bound: each staged row persists at most one
                # event per active assignment
                self._rows_since_spool += n_staged * MAX_ACTIVE_ASSIGNMENTS
                if self._rows_since_spool >= self._spool_trigger:
                    self._spool()

    def ring_heads(self) -> dict[int, int]:
        """Absolute ring write head per archive partition (part =
        shard * arenas + arena) — the ONE definition shared by the
        archive spooler and the conservation audit plane (ISSUE 14).
        Caller holds the lock (one small device readback)."""
        store = self.state.store
        arenas = store.cursor.shape[-1]
        acap = self.ring_arena_capacity()
        ep = np.asarray(jax.device_get(store.epoch)).astype(np.int64)
        cu = np.asarray(jax.device_get(store.cursor)).astype(np.int64)
        heads = ep * acap + cu
        return {s * arenas + a: int(heads[s, a])
                for s in range(self.n_shards) for a in range(arenas)}

    def ring_arena_capacity(self) -> int:
        """Rows one (shard, arena) sub-ring holds before wrapping."""
        return (self.config.store_capacity_per_shard
                // self.state.store.cursor.shape[-1])

    def _spool(self) -> None:
        """Spill full archive segments from every (shard, arena) sub-ring.
        Caller holds the lock. One fixed-count ``read_range`` program per
        segment (reused across shards via the per-shard tree slice)."""
        from sitewhere_tpu.ops.readback import read_range

        store = self.state.store
        arenas = store.cursor.shape[-1]
        acap = self.ring_arena_capacity()
        rows = self.archive.segment_rows
        heads = self.ring_heads()
        for s in range(self.n_shards):
            shard_store = None
            for a in range(arenas):
                part = s * arenas + a
                head = heads[part]
                start = self.archive.spilled(part)
                if head - start > acap:   # wrapped before we got here
                    self.archive.note_lost(head - acap - start)
                    start = head - acap
                while head - start >= rows:
                    if shard_store is None:
                        shard_store = jax.tree_util.tree_map(
                            lambda x: x[s], store)
                    sl = jax.device_get(read_range(
                        shard_store, jnp.int32(start % acap), rows,
                        arena=a))
                    self.archive.append_segment(part, start, sl)
                    start += rows
        self._rows_since_spool = 0

    def drain(self) -> list[dict]:
        """Absorb queued stacked outputs. Only the [S] scalar counter lanes
        are fetched for the whole backlog; per-shard token lists stay on
        device and are sliced to their actual lengths only for shards that
        registered or dead-lettered (readback bytes proportional to real
        occurrences)."""
        with self.lock:
            if not self._pending_outs:
                return [{"found": 0, "missed": 0, "registered": 0,
                         "persisted": 0, "new_tokens": [], "dead_tokens": []}]
            outs, self._pending_outs = self._pending_outs, []
            trace_lists, self._pending_traces = self._pending_traces, []
            scalars = jax.device_get([
                (o.n_found, o.n_missed, o.n_registered, o.n_persisted)
                for o in outs])
            for recs in trace_lists:   # the device_get observed completion
                for rec in recs:
                    if "device_ready" not in rec.stages:
                        rec.mark("device_ready")
                    rec.mark("readback")
            summaries = [self._absorb_output(o, s)
                         for o, s in zip(outs, scalars)]
            self._mirror_new_device_tenants()
            return summaries

    def _absorb_output(self, out: StepOutput, scalars) -> dict:
        """Mirror one stacked step output: per-shard device-side allocation
        order == compacted new_tokens order, exactly like the single-node
        engine's contract."""
        n_found_s, n_missed_s, n_reg_s, n_pers_s = (
            np.asarray(x) for x in scalars)
        new_all: list[str] = []
        dead_all: list[str] = []
        for s in range(self.n_shards):
            k = int(n_reg_s[s])
            if k:
                toks = jax.device_get(out.new_tokens[s, :k])
                for local_tok in (int(t) for t in toks):
                    gid = local_tok * self.n_shards + s
                    did = int(self._next_device[s])
                    aid = int(self._next_assignment[s])
                    self._next_device[s] += 1
                    self._next_assignment[s] += 1
                    gdid = self._gdid(s, did)
                    self.token_device[gid] = gdid
                    token = self.tokens.token(gid)
                    self.devices[gdid] = DeviceInfo(
                        token=token,
                        device_type=self.config.default_device_type,
                        tenant="default",  # fixed up from device column below
                        auto_registered=True,
                    )
                    self._pending_tenant_fixups.append((gdid, s, did))
                    self._record_assignment(self._gdid(s, aid), gdid, slot=0)
                    new_all.append(token)
            dk = min(int(n_missed_s[s]), out.dead_tokens.shape[1])
            if dk:
                for t in jax.device_get(out.dead_tokens[s, :dk]):
                    if int(t) != NULL_ID:
                        dead_all.append(self.tokens.token(
                            int(t) * self.n_shards + s))
        self.dead_letters.extend(dead_all)
        summary = {
            "found": int(n_found_s.sum()),
            "missed": int(n_missed_s.sum()),
            "registered": int(n_reg_s.sum()),
            "persisted": int(n_pers_s.sum()),
            "new_tokens": new_all,
            "dead_tokens": dead_all,
        }
        self.outputs.append(summary)
        del self.outputs[:-256]
        return summary

    def _mirror_new_device_tenants(self) -> None:
        """One gather for every auto-registered device's tenant column
        (instead of a device->host transfer per device)."""
        if not self._pending_tenant_fixups:
            return
        fix, self._pending_tenant_fixups = self._pending_tenant_fixups, []
        sh = jnp.asarray([f[1] for f in fix], jnp.int32)
        dd = jnp.asarray([f[2] for f in fix], jnp.int32)
        tens = np.asarray(jax.device_get(
            self.state.registry.device_tenant[sh, dd]))
        for (gdid, _, _), ten in zip(fix, tens):
            if int(ten) != NULL_ID:
                info = self.devices.get(gdid)
                if info is not None:
                    info.tenant = self.tenants.token(int(ten))
                    aid = (self.device_slots.get(gdid) or [NULL_ID])[0]
                    if aid != NULL_ID and aid in self.assignments:
                        self.assignments[aid].tenant = info.tenant

    # ------------------------------------------------------------------ admin
    def register_device(self, token: str, device_type: str | None = None,
                        tenant: str = "default", area: str | None = None,
                        customer: str | None = None,
                        metadata: dict | None = None) -> int:
        """API-path device creation (get-or-create); returns the GLOBAL
        device id."""
        with self.lock:
            self._sync_mirrors()
            gid = self.tokens.intern(token)
            existing = self.token_device.get(gid)
            if existing is not None:
                return existing
            shard, local_tok = self._route(gid)
            did = int(self._next_device[shard])
            aid = int(self._next_assignment[shard])
            if did >= self.config.device_capacity_per_shard:
                raise RuntimeError(f"device capacity exhausted on shard {shard}")
            type_name = device_type or self.config.default_device_type
            # admin-path registrations ride the WAL + replica feed as
            # their wire-form envelope (standby visibility; PR-6 limit)
            self._wal_admin_register(token, type_name, tenant, area,
                                     customer)
            self._next_device[shard] += 1
            self._next_assignment[shard] += 1
            self.sharded.state = _admin_create_device_stacked(
                self.sharded.state,
                jnp.int32(shard), jnp.int32(local_tok),
                jnp.int32(did), jnp.int32(aid),
                jnp.int32(self.device_types.intern(type_name)),
                jnp.int32(self.tenants.intern(tenant)),
                jnp.int32(self.areas.intern(area) if area else NULL_ID),
                jnp.int32(self.customers.intern(customer) if customer else NULL_ID),
            )
            gdid = self._gdid(shard, did)
            self.token_device[gid] = gdid
            self.devices[gdid] = DeviceInfo(
                token=token, device_type=type_name, tenant=tenant,
                area=area, customer=customer, metadata=metadata or {},
            )
            self._record_assignment(self._gdid(shard, aid), gdid, slot=0,
                                    area=area, customer=customer)
            return gdid

    def delete_device(self, token: str) -> bool:
        with self.lock:
            self._sync_mirrors()
            gid = self.tokens.lookup(token)
            gdid = self.token_device.get(gid)
            if gdid is None:
                return False
            shard, did = self._split_gdid(gdid)
            self.sharded.state = _admin_set_device_active_stacked(
                self.sharded.state, jnp.int32(shard), jnp.int32(did), False)
            return True

    def map_device(self, child_token: str, parent_token: str) -> DeviceInfo:
        """Gateway/composite mapping. The on-device parent column is
        shard-local, so it is set only when parent and child land on the
        same shard; the host mirror always records the mapping (command
        routing uses the mirror)."""
        with self.lock:
            self._sync_mirrors()
            cgid = self.tokens.lookup(child_token)
            cdid = self.token_device.get(cgid)
            if cdid is None:
                raise KeyError(f"device {child_token!r} not registered")
            pgid = self.tokens.lookup(parent_token)
            pdid = self.token_device.get(pgid)
            if pdid is None:
                raise KeyError(f"parent device {parent_token!r} not registered")
            if cdid == pdid:
                raise ValueError("device cannot be its own parent")
            info = self.devices[cdid]
            info.metadata = dict(info.metadata) | {"parentToken": parent_token}
            cs, cd = self._split_gdid(cdid)
            ps, pd = self._split_gdid(pdid)
            if cs == ps:
                self.sharded.state = _admin_set_parent_stacked(
                    self.sharded.state, jnp.int32(cs), jnp.int32(cd),
                    jnp.int32(pd))
            return info

    def _record_assignment(self, gaid: int, gdid: int, slot: int,
                           token: str | None = None, asset: str | None = None,
                           area: str | None = None, customer: str | None = None,
                           metadata: dict | None = None) -> AssignmentInfo:
        dev = self.devices[gdid]
        tok = token or f"{dev.token}:a{gaid}"
        info = AssignmentInfo(
            token=tok, id=gaid, device_token=dev.token, tenant=dev.tenant,
            asset=asset, area=area or dev.area,
            customer=customer or dev.customer,
            metadata=metadata or {}, created_ms=self.epoch.now_ms(),
        )
        self.assignments[gaid] = info
        self.assignment_tokens[tok] = gaid
        slots = self.device_slots.setdefault(
            gdid, [NULL_ID] * MAX_ACTIVE_ASSIGNMENTS)
        slots[slot] = gaid
        return info

    def create_assignment(self, device_token: str, token: str | None = None,
                          asset: str | None = None, area: str | None = None,
                          customer: str | None = None,
                          metadata: dict | None = None) -> AssignmentInfo:
        with self.lock:
            self._sync_mirrors()
            gid = self.tokens.lookup(device_token)
            gdid = self.token_device.get(gid)
            if gdid is None:
                raise KeyError(f"device {device_token!r} not registered")
            if token is not None and token in self.assignment_tokens:
                raise ValueError(f"assignment token {token!r} already exists")
            slots = self.device_slots.setdefault(
                gdid, [NULL_ID] * MAX_ACTIVE_ASSIGNMENTS)
            try:
                slot = slots.index(NULL_ID)
            except ValueError:
                raise ValueError(
                    f"device {device_token!r} already has "
                    f"{MAX_ACTIVE_ASSIGNMENTS} active assignments") from None
            shard, did = self._split_gdid(gdid)
            aid = int(self._next_assignment[shard])
            if aid >= self.config.assignment_capacity_per_shard:
                raise RuntimeError("assignment capacity exhausted")
            self._next_assignment[shard] += 1
            self.sharded.state = _admin_add_assignment_stacked(
                self.sharded.state, jnp.int32(shard), jnp.int32(did),
                jnp.int32(aid), jnp.int32(slot),
                jnp.int32(self.assets.intern(asset) if asset else NULL_ID),
                jnp.int32(self.areas.intern(area) if area else NULL_ID),
                jnp.int32(self.customers.intern(customer) if customer else NULL_ID),
            )
            return self._record_assignment(
                self._gdid(shard, aid), gdid, slot, token=token, asset=asset,
                area=area, customer=customer, metadata=metadata)

    def update_device(self, token: str, device_type: str | None = None,
                      area: str | None = None, customer: str | None = None,
                      metadata: dict | None = None) -> DeviceInfo:
        """Update device columns + host metadata on the owning shard
        (Engine.update_device parity for the REST surface)."""
        with self.lock:
            self._sync_mirrors()
            gid = self.tokens.lookup(token)
            gdid = self.token_device.get(gid)
            if gdid is None:
                raise KeyError(f"device {token!r} not registered")
            info = self.devices[gdid]
            shard, did = self._split_gdid(gdid)
            type_id = jnp.int32(self.device_types.intern(
                device_type if device_type is not None else info.device_type))
            new_area = area if area is not None else info.area
            area_id = jnp.int32(
                self.areas.intern(new_area) if new_area else NULL_ID)
            new_customer = customer if customer is not None else info.customer
            customer_id = jnp.int32(
                self.customers.intern(new_customer) if new_customer else NULL_ID)
            self.sharded.state = _admin_update_device_stacked(
                self.sharded.state, jnp.int32(shard), jnp.int32(did),
                type_id, area_id, customer_id)
            if device_type is not None:
                info.device_type = device_type
            if area is not None:
                info.area = area
            if customer is not None:
                info.customer = customer
            if metadata is not None:
                info.metadata = metadata
            return info

    def get_assignment(self, token: str) -> AssignmentInfo | None:
        aid = self.assignment_tokens.get(token)
        return self.assignments.get(aid) if aid is not None else None

    def list_assignments(self, device_token: str | None = None,
                         status: str | None = None,
                         area: str | None = None,
                         asset: str | None = None,
                         customer: str | None = None) -> list[AssignmentInfo]:
        with self.lock:
            out = [
                a for a in self.assignments.values()
                if (device_token is None or a.device_token == device_token)
                and (status is None or a.status == status)
                and (area is None or a.area == area)
                and (asset is None or a.asset == asset)
                and (customer is None or a.customer == customer)
            ]
            return sorted(out, key=lambda a: a.id)

    def _set_assignment_status(self, token: str,
                               status: DeviceAssignmentStatus) -> AssignmentInfo:
        with self.lock:
            self._sync_mirrors()
            gaid = self.assignment_tokens.get(token)
            if gaid is None:
                raise KeyError(f"assignment {token!r} not found")
            shard, aid = self._split_gdid(gaid)
            active = status is not DeviceAssignmentStatus.RELEASED
            self.sharded.state = _admin_set_assignment_status_stacked(
                self.sharded.state, jnp.int32(shard), jnp.int32(aid),
                jnp.int32(status), active)
            info = self.assignments[gaid]
            info.status = status.name
            if not active:
                info.released_ms = self.epoch.now_ms()
                gdid = self.token_device.get(
                    self.tokens.lookup(info.device_token))
                if gdid is not None and gdid in self.device_slots:
                    self.device_slots[gdid] = [
                        NULL_ID if a == gaid else a
                        for a in self.device_slots[gdid]]
            return info

    def release_assignment(self, token: str) -> AssignmentInfo:
        return self._set_assignment_status(
            token, DeviceAssignmentStatus.RELEASED)

    def mark_assignment_missing(self, token: str) -> AssignmentInfo:
        """Flag an assignment MISSING (reference: Assignments.java
        /assignments/{token}/missing); it stays active so events still
        expand to it — Engine parity for the REST surface."""
        return self._set_assignment_status(
            token, DeviceAssignmentStatus.MISSING)

    def update_assignment(self, token: str, asset: str | None = None,
                          area: str | None = None,
                          customer: str | None = None,
                          metadata: dict | None = None) -> AssignmentInfo:
        """Update an assignment's association columns on its owning shard +
        host metadata (Engine.update_assignment parity; reference:
        Assignments.java:144 PUT)."""
        with self.lock:
            self._sync_mirrors()
            gaid = self.assignment_tokens.get(token)
            if gaid is None:
                raise KeyError(f"assignment {token!r} not found")
            info = self.assignments[gaid]
            shard, aid = self._split_gdid(gaid)
            new_asset = asset if asset is not None else info.asset
            new_area = area if area is not None else info.area
            new_customer = customer if customer is not None else info.customer
            # intern before mutating so a capacity error never half-applies
            asset_id = jnp.int32(
                self.assets.intern(new_asset) if new_asset else NULL_ID)
            area_id = jnp.int32(
                self.areas.intern(new_area) if new_area else NULL_ID)
            customer_id = jnp.int32(
                self.customers.intern(new_customer)
                if new_customer else NULL_ID)
            self.sharded.state = _admin_update_assignment_stacked(
                self.sharded.state, jnp.int32(shard), jnp.int32(aid),
                asset_id, area_id, customer_id)
            info.asset, info.area, info.customer = (
                new_asset, new_area, new_customer)
            if metadata is not None:
                info.metadata = metadata
            return info

    def delete_assignment(self, token: str) -> bool:
        """Delete an assignment (reference: Assignments.java DELETE):
        detach on-device (release semantics) and drop the host record;
        persisted events keep the id — deletes don't rewrite history."""
        with self.lock:
            self._sync_mirrors()
            gaid = self.assignment_tokens.get(token)
            if gaid is None:
                return False
            if self.assignments[gaid].status != "RELEASED":
                self._set_assignment_status(
                    token, DeviceAssignmentStatus.RELEASED)
            del self.assignments[gaid]
            del self.assignment_tokens[token]
            return True

    # ------------------------------------------------------------------ queries
    def get_device(self, token: str) -> DeviceInfo | None:
        if self._pending_outs:
            with self.lock:
                self._sync_mirrors()
        gid = self.tokens.lookup(token)
        gdid = self.token_device.get(gid)
        return self.devices.get(gdid) if gdid is not None else None

    def get_device_state(self, token: str) -> dict | None:
        """One device's aggregated state from its owning shard."""
        from sitewhere_tpu.core.state import RECENT_DEPTH

        with self.lock:
            self._sync_mirrors()
            gid = self.tokens.lookup(token)
            gdid = self.token_device.get(gid)
            if gdid is None:
                return None
            shard, d = self._split_gdid(gdid)
            ds = self.state.device_state
            # slice this device's rows in one device_get
            row = jax.device_get({
                "presence": ds.presence[shard, d],
                "last": ds.last_interaction_ms[shard, d],
                "meas_last": ds.meas_last[shard, d],
                "meas_last_ms": ds.meas_last_ms[shard, d],
                "recent_loc": ds.recent_loc[shard, d],
                "recent_loc_ms": ds.recent_loc_ms[shard, d],
                "recent_loc_valid": ds.recent_loc_valid[shard, d],
                "recent_alert_level": ds.recent_alert_level[shard, d],
                "recent_alert_type": ds.recent_alert_type[shard, d],
                "recent_alert_ms": ds.recent_alert_ms[shard, d],
                "recent_alert_valid": ds.recent_alert_valid[shard, d],
                "event_counts": ds.event_counts[shard, d],
            })
            chans = {}
            for name, nid in self.channel_map.names.items():
                ch = nid % self.config.channels
                ts = int(row["meas_last_ms"][ch])
                if ts > -(2**31) + 10:
                    chans[name] = {"value": float(row["meas_last"][ch]),
                                   "ts_ms": ts}
            recent_locs = [
                {
                    "latitude": float(row["recent_loc"][r, 0]),
                    "longitude": float(row["recent_loc"][r, 1]),
                    "elevation": float(row["recent_loc"][r, 2]),
                    "ts_ms": int(row["recent_loc_ms"][r]),
                }
                for r in range(RECENT_DEPTH)
                if bool(row["recent_loc_valid"][r])
            ]
            recent_alerts = [
                {
                    "level": int(row["recent_alert_level"][r]),
                    "type": self.alert_types.token(int(row["recent_alert_type"][r])),
                    "ts_ms": int(row["recent_alert_ms"][r]),
                }
                for r in range(RECENT_DEPTH)
                if bool(row["recent_alert_valid"][r])
            ]
            return {
                "device": self.devices[gdid].token,
                "shard": shard,
                "presence": PresenceState(int(row["presence"])).name,
                "last_interaction_ms": int(row["last"]),
                "measurements": chans,
                "recent_locations": recent_locs,
                "recent_alerts": recent_alerts,
                "event_counts": {
                    EventType(e).name: int(row["event_counts"][e])
                    for e in range(6)
                },
            }

    def query_events(self, device_token: str | None = None,
                     etype: EventType | None = None,
                     tenant: str | None = None,
                     since_ms: int | None = None,
                     until_ms: int | None = None,
                     limit: int = 100,
                     assignment_id: int | None = None,
                     aux0: int | None = None,
                     area: str | None = None,
                     customer: str | None = None,
                     alternate_id: str | None = None) -> dict:
        """Global newest-first query: every shard scans its ring on its own
        device (vmapped filter + top-k), host merges the per-shard pages
        with one vectorized argsort (scatter-gather across partitions).
        Filter surface matches Engine.query_events so the REST gateway
        serves identically from the sharded state. (``assignment_id`` is a
        GLOBAL id; its shard-local row filters on the owning shard.)"""
        with self.lock:
            self._sync_mirrors()
            dev_filter = NULL_ID
            shard_filter = None
            if device_token is not None:
                gid = self.tokens.lookup(device_token)
                gdid = self.token_device.get(gid, None)
                if gdid is None:
                    return {"total": 0, "events": []}
                shard_filter, dev_filter = self._split_gdid(gdid)
            ten = NULL_ID
            if tenant is not None:
                ten = self.tenants.lookup(tenant)
                if ten == NULL_ID:   # unknown tenant matches NOTHING —
                    return {"total": 0, "events": []}   # never all tenants
            area_id = customer_id = aux1 = None
            if area is not None:
                area_id = self.areas.lookup(area)
                if area_id == NULL_ID:
                    return {"total": 0, "events": []}
            if customer is not None:
                customer_id = self.customers.lookup(customer)
                if customer_id == NULL_ID:
                    return {"total": 0, "events": []}
            if alternate_id is not None:
                aux1 = self.event_ids.lookup(alternate_id)
                if aux1 == NULL_ID:
                    return {"total": 0, "events": []}
            a_local = None
            if assignment_id is not None:
                # global assignment id -> its owning shard's local row;
                # restrict the scan to that shard like the device filter
                a_shard, a_local = self._split_gdid(assignment_id)
                if shard_filter is not None and shard_filter != a_shard:
                    return {"total": 0, "events": []}
                shard_filter = a_shard
            res = _stacked_query(
                self.state.store,
                jnp.int32(int(etype) if etype is not None else NULL_ID),
                jnp.int32(ten),
                jnp.int32(since_ms if since_ms is not None else -(2**31)),
                jnp.int32(until_ms if until_ms is not None else 2**31 - 1),
                limit=limit,
                device=jnp.int32(dev_filter),
                device_shard=(jnp.int32(shard_filter)
                              if shard_filter is not None else None),
                assignment=(jnp.int32(a_local)
                            if a_local is not None else None),
                assignment_shard=(jnp.int32(shard_filter)
                                  if a_local is not None else None),
                aux0=jnp.int32(aux0) if aux0 is not None else None,
                aux1=jnp.int32(aux1) if aux1 is not None else None,
                area=jnp.int32(area_id) if area_id is not None else None,
                customer=(jnp.int32(customer_id)
                          if customer_id is not None else None),
            )
            res = jax.device_get(res)
            ns = np.asarray(res.n)
            ts = np.asarray(res.ts_ms)
            valid = np.arange(ts.shape[1])[None, :] < ns[:, None]
            s_idx, i_idx = np.nonzero(valid)
            order = np.argsort(-ts[s_idx, i_idx], kind="stable")[:limit]
            sel_s, sel_i = s_idx[order], i_idx[order]
            lane_names = self._lane_names()
            events = [
                self._format_event(
                    int(res.etype[s, i]), int(s), int(res.device[s, i]),
                    int(res.assignment[s, i]), int(res.ts_ms[s, i]),
                    int(res.received_ms[s, i]), res.values[s, i],
                    res.vmask[s, i], res.aux[s, i], lane_names)
                for s, i in zip(sel_s, sel_i)
            ]
            total = int(np.sum(np.asarray(res.total)))
            if self.archive is not None and self.archive.segments:
                arenas = self.state.store.cursor.shape[-1]
                parts_of = (
                    frozenset(shard_filter * arenas + a
                              for a in range(arenas))
                    if shard_filter is not None else None)
                total, events = self._merge_archive(
                    total, events, limit, lane_names,
                    device=int(dev_filter) if dev_filter != NULL_ID else None,
                    device_parts=parts_of,
                    etype=int(etype) if etype is not None else None,
                    tenant=ten if ten != NULL_ID else None,
                    since_ms=since_ms, until_ms=until_ms,
                    assignment=a_local,
                    assignment_parts=(parts_of if a_local is not None
                                      else None),
                    aux0=aux0, aux1=aux1, area=area_id,
                    customer=customer_id)
            return {"total": total, "events": events}

    def _merge_archive(self, total: int, events: list[dict], limit: int,
                       lane_names: dict[int, str],
                       **filters) -> tuple[int, list[dict]]:
        """Fold archived (evicted-from-ring) history into a mesh query
        result — same no-overlap cap as Engine._merge_archive, per
        (shard, arena) partition. Caller holds the lock."""
        store = self.state.store
        arenas = store.cursor.shape[-1]
        acap = self.config.store_capacity_per_shard // arenas
        ep = np.asarray(jax.device_get(store.epoch)).astype(np.int64)
        cu = np.asarray(jax.device_get(store.cursor)).astype(np.int64)
        heads = ep * acap + cu
        max_pos = {s * arenas + a: int(heads[s, a]) - acap
                   for s in range(self.n_shards) for a in range(arenas)}
        if all(v <= 0 for v in max_pos.values()):
            return total, events
        a_total, rows = self.archive.query(max_pos=max_pos, limit=limit,
                                           **filters)
        if not a_total:
            return total, events
        a_events = [
            self._format_event(
                int(r["etype"]), int(r["part"]) // arenas, int(r["device"]),
                int(r["assignment"]), int(r["ts_ms"]),
                int(r["received_ms"]), r["values"], r["vmask"], r["aux"],
                lane_names)
            for r in rows
        ]
        merged = sorted(events + a_events,
                        key=lambda e: -e["eventDateMs"])[:limit]
        return total + a_total, merged

    def _lane_names(self) -> dict[int, str]:
        lane_names: dict[int, str] = {}
        for name, nid in self.channel_map.names.items():
            lane_names.setdefault(nid % self.config.channels, name)
        return lane_names

    def _format_event(self, et_i: int, shard: int, device: int,
                      assignment: int, ts: int, received: int, values,
                      vmask, aux, lane_names: dict[int, str]) -> dict:
        """One persisted store row (shard-local ids) -> the REST event dict
        — the single formatter behind the ring query, the archive merge,
        and the by-id lookup, full six-type coverage matching
        Engine._format_event."""
        et = EventType(et_i)
        gdid = self._gdid(shard, device)
        info = self.devices.get(gdid)
        ev = {
            "type": et.name,
            "deviceToken": info.token if info else None,
            "shard": shard,
            "assignmentId": self._gdid(shard, assignment),
            "eventDateMs": ts,
            "receivedDateMs": received,
        }
        if et is EventType.MEASUREMENT:
            ev["measurements"] = {
                lane_names.get(int(c), f"ch{c}"): float(values[c])
                for c in np.nonzero(vmask)[0]
            }
        elif et is EventType.LOCATION:
            if vmask[0]:
                ev["latitude"] = float(values[0])
                ev["longitude"] = float(values[1])
                ev["elevation"] = float(values[2])
            else:
                ev["latitude"] = ev["longitude"] = ev["elevation"] = None
        elif et is EventType.ALERT:
            ev["level"] = int(values[0])
            atype = int(aux[0])
            ev["alertType"] = (
                self.alert_types.token(atype)
                if 0 <= atype < len(self.alert_types) else None)
        elif et is EventType.COMMAND_INVOCATION:
            ev["invocationId"] = int(aux[0])
        elif et is EventType.COMMAND_RESPONSE:
            oid = int(aux[0])
            ev["originatingEventId"] = (
                self.event_ids.token(oid)
                if 0 <= oid < len(self.event_ids) else None)
        elif et is EventType.STATE_CHANGE:
            sid = int(aux[0])
            if 0 <= sid < len(self.event_ids):
                attr, _, change = self.event_ids.token(sid).partition(":")
                ev["attribute"], ev["stateChange"] = attr, change
        return ev

    def search_device_states(self, last_interaction_before_ms: int | None = None,
                             presence: str | None = None,
                             limit: int = 100) -> list[dict]:
        """Vectorized device-state search over the stacked state columns."""
        with self.lock:
            self._sync_mirrors()
            ds = self.state.device_state
            last = np.asarray(jax.device_get(ds.last_interaction_ms))
            pres = np.asarray(jax.device_get(ds.presence))
            n_per = self._next_device
            mask = (np.arange(last.shape[1])[None, :] < n_per[:, None])
            if last_interaction_before_ms is not None:
                mask &= last < last_interaction_before_ms
            if presence is not None:
                mask &= pres == int(PresenceState[presence.upper()])
            out = []
            for s, d in zip(*np.nonzero(mask)):
                if len(out) >= limit:
                    break
                info = self.devices.get(self._gdid(int(s), int(d)))
                if info is None:
                    continue
                out.append({
                    "device": info.token,
                    "deviceType": info.device_type,
                    "tenant": info.tenant,
                    "shard": int(s),
                    "presence": PresenceState(int(pres[s, d])).name,
                    "lastInteractionMs": int(last[s, d]),
                })
            return out

    def presence_sweep(self) -> list[str]:
        """Mark stale devices MISSING on every shard; returns their tokens."""
        with self.lock:
            self._sync_mirrors()
            pairs = self.sharded.presence_sweep(
                self.epoch.now_ms(),
                int(self.config.presence_missing_s * 1000))
            out = []
            for s, d in pairs:
                info = self.devices.get(self._gdid(s, d))
                if info is not None:
                    out.append(info.token)
            return out

    # uniform "sweep THIS engine only" name (see Engine.presence_sweep_local)
    presence_sweep_local = presence_sweep

    def get_event(self, event_id: int,
                  tenant: str | None = None) -> dict | None:
        """Fetch one persisted event by its mesh-global id — the id layout
        DistributedFeedConsumer hands out (``pos * n_parts + shard * arenas
        + arena`` with ``n_parts = n_shards * arenas``), so the REST
        /api/events/id/{eventId} lookup works identically against the
        distributed engine (reference: DeviceEvents.java
        getDeviceEventById). Returns None when the id was never written or
        its ring slot has been overwritten. ``tenant`` scopes the lookup
        (rows of other tenants read as absent — ids are enumerable)."""
        from sitewhere_tpu.ops.readback import read_range

        with self.lock:
            self._sync_mirrors()
            ten = None
            if tenant is not None:
                ten = self.tenants.lookup(tenant)
                if ten == NULL_ID:
                    return None
            store = self.state.store
            if event_id < 0:
                return None
            arenas = store.cursor.shape[-1]
            pos, s, a = split_event_id(event_id, self.n_shards, arenas)
            acap = self.config.store_capacity_per_shard // arenas
            head = (int(jax.device_get(store.epoch[s, a])) * acap
                    + int(jax.device_get(store.cursor[s, a])))
            if pos >= head:
                return None
            if pos < head - acap:
                # evicted from the ring — resolve from the archive so the
                # by-id surface agrees with query_events
                if self.archive is None:
                    return None
                r = self.archive.get_row(s * arenas + a, pos)
                if r is None:
                    return None
                if ten is not None and int(r["tenant"]) != ten:
                    return None
                ev = self._format_event(
                    int(r["etype"]), s, int(r["device"]),
                    int(r["assignment"]), int(r["ts_ms"]),
                    int(r["received_ms"]), r["values"], r["vmask"],
                    r["aux"], self._lane_names())
                ev["eventId"] = event_id
                return ev
            shard_store = jax.tree_util.tree_map(lambda x: x[s], store)
            sl = jax.device_get(read_range(
                shard_store, jnp.int32(pos % acap), 1, arena=a))
            if not bool(sl.valid[0]):
                return None
            if ten is not None and int(sl.tenant[0]) != ten:
                return None
            ev = self._format_event(
                int(sl.etype[0]), s, int(sl.device[0]),
                int(sl.assignment[0]), int(sl.ts_ms[0]),
                int(sl.received_ms[0]), sl.values[0],
                np.asarray(sl.vmask[0]), np.asarray(sl.aux[0]),
                self._lane_names())
            ev["eventId"] = event_id
            return ev

    def make_feed_consumer(self, group_id: str, max_batch: int = 1024,
                           start_from_latest: bool = False):
        """Outbound consumer over the per-shard rings (Engine parity)."""
        return DistributedFeedConsumer(self, group_id, max_batch=max_batch,
                                       start_from_latest=start_from_latest)

    def metrics(self) -> dict:
        m = self.sharded.global_metrics()
        m["channel_collisions"] = self.channel_map.collisions
        m["staged"] = self.staged_count
        m["n_shards"] = self.n_shards
        m["devices"] = int(self._next_device.sum())
        if self.archive is not None:
            m["archived_rows"] = self.archive.total_rows()
            m["archive_lost_rows"] = self.archive.lost_rows
        # counters first would shadow nothing, but m is built from the
        # device metrics; guard the same way — core keys win
        m = dict(self.host_counters) | m
        return m

    def tenant_metrics(self) -> dict[str, dict[str, int]]:
        """Per-tenant event counts over ALL shards: vmap the single-state
        segment-sum (engine._tenant_event_counts) across the stacked
        state and reduce — tenant ids are engine-global, so summing the
        per-shard [t_cap, E] grids is exact (Engine.tenant_metrics
        parity for the Prometheus per-tenant series)."""
        from sitewhere_tpu.engine import (_tenant_event_counts, tenant_cap,
                                          tenant_counts_dict)

        with self.lock:
            self._sync_mirrors()
            n_tenants = len(self.tenants)
            t_cap = tenant_cap(n_tenants)
            per_shard = jax.vmap(
                lambda st: _tenant_event_counts(st, t_cap))(
                    self.sharded.state)                    # [S, T, E]
            counts = np.asarray(per_shard).sum(axis=0)
        return tenant_counts_dict(counts, self.tenants, n_tenants)

    def shard_metrics(self) -> list[dict]:
        """Per-shard counters (the per-partition consumer-lag analog).
        Only scalar counter fields report here; the packed per-tenant
        grid has its own accessor (tenant_pipeline_counters)."""
        mm = jax.device_get(self.state.metrics)
        fields = [f.name for f in dataclasses.fields(mm)
                  if np.ndim(getattr(mm, f.name)) == 1]   # [S] scalars only
        return [
            {name: int(np.asarray(getattr(mm, name))[s]) for name in fields}
            | {"devices": int(self._next_device[s])}
            for s in range(self.n_shards)
        ]

    def tenant_pipeline_counters(self) -> dict[str, dict[str, int]]:
        """Engine-parity device-side per-tenant counter grid, summed over
        shards (tenant ids are engine-global, so the per-shard [T, C]
        grids add exactly). Read back on the scrape path only."""
        from sitewhere_tpu.engine import format_tenant_counter_grid

        with self.lock:
            grid = np.asarray(jax.device_get(
                self.state.metrics.tenant_counters)).sum(axis=0)
            return format_tenant_counter_grid(grid, self.tenants)

    # ------------------------------------------------------------- durability
    def total_cursor(self) -> int:
        """Sum of per-shard absolute store cursors — monotone under appends,
        so it serves as the WAL watermark for the whole mesh."""
        st = self.state.store
        epochs = np.asarray(jax.device_get(st.epoch))   # [S, A]
        cursors = np.asarray(jax.device_get(st.cursor))
        acap = self.config.store_capacity_per_shard // epochs.shape[-1]
        return int(np.sum(epochs.astype(np.int64) * acap + cursors))

    def save(self, directory) -> dict:
        """Full mesh snapshot: stacked device state + host mirrors +
        interners. Pairs with the WAL for exact crash recovery
        (recover_distributed)."""
        import json
        import pathlib

        directory = pathlib.Path(directory)
        with self.lock:
            self._sync_mirrors()
            manifest = self.sharded.save(directory)
            cursor = self.total_cursor()
            host = {
                "format": 1,
                "config": dataclasses.asdict(self.config),
                "n_shards": self.n_shards,
                "epoch_base_unix_s": self.epoch.base_unix_s,
                "store_cursor": cursor,
                "next_device": [int(x) for x in self._next_device],
                "next_assignment": [int(x) for x in self._next_assignment],
                "tokens": [self.tokens.token(i)
                           for i in range(len(self.tokens))],
                "tenants": [self.tenants.token(i)
                            for i in range(len(self.tenants))],
                "device_types": [self.device_types.token(i)
                                 for i in range(len(self.device_types))],
                "channel_names": [self.channel_map.names.token(i)
                                  for i in range(len(self.channel_map.names))],
                "alert_types": [self.alert_types.token(i)
                                for i in range(len(self.alert_types))],
                "areas": [self.areas.token(i) for i in range(len(self.areas))],
                "customers": [self.customers.token(i)
                              for i in range(len(self.customers))],
                "assets": [self.assets.token(i)
                           for i in range(len(self.assets))],
                "event_ids": [self.event_ids.token(i)
                              for i in range(len(self.event_ids))],
                "token_device": {str(k): v for k, v in self.token_device.items()},
                "devices": {str(d): dataclasses.asdict(i)
                            for d, i in self.devices.items()},
                "assignments": {str(a): dataclasses.asdict(i)
                                for a, i in self.assignments.items()},
                "device_slots": {str(k): v
                                 for k, v in self.device_slots.items()},
                "dead_letters": self.dead_letters[-4096:],
            }
            (directory / "host_distributed.json").write_text(json.dumps(host))
            if self.wal is not None:
                self.wal.append_watermark(cursor)
                self.wal.sync()
            manifest["store_cursor"] = cursor
            return manifest


def encode_event_id(pos: int, shard: int, arena: int, n_shards: int,
                    arenas: int) -> int:
    """Mesh-global event id: ``pos * (n_shards*arenas) + shard*arenas +
    arena``. The single place the id layout lives — get_event and
    DistributedFeedConsumer.commit decode with :func:`split_event_id`."""
    return pos * (n_shards * arenas) + shard * arenas + arena


def split_event_id(event_id: int, n_shards: int,
                   arenas: int) -> tuple[int, int, int]:
    """Inverse of :func:`encode_event_id` -> (pos, shard, arena)."""
    parts = n_shards * arenas
    part = event_id % parts
    return event_id // parts, part // arenas, part % arenas


class DistributedFeedConsumer:
    """Outbound consumer group over the mesh engine's per-shard rings —
    the per-partition consumer-group analog (one committed offset per
    (shard, arena) sub-ring). Event ids encode (position, shard, arena)
    via :func:`encode_event_id` so commits are exact and ids stay unique
    across the mesh."""

    def __init__(self, engine: DistributedEngine, group_id: str,
                 max_batch: int = 1024, start_from_latest: bool = False):
        self.engine = engine
        self.group_id = group_id
        self.max_batch = max_batch
        store = engine.state.store
        self.n_shards = engine.n_shards
        self.arenas = store.cursor.shape[-1]
        self.offsets = np.zeros((self.n_shards, self.arenas), np.int64)
        if start_from_latest:
            self.offsets[:] = self._heads(store)
        self.lag_lost = 0

    def _heads(self, store) -> np.ndarray:
        acap = self.engine.config.store_capacity_per_shard // self.arenas
        ep = np.asarray(jax.device_get(store.epoch)).astype(np.int64)
        cu = np.asarray(jax.device_get(store.cursor)).astype(np.int64)
        return ep * acap + cu

    def _events_from_slice(self, sl, base: int, count: int, s: int, a: int,
                           lane_names: dict[int, str]) -> list:
        """Host-enrich one contiguous column slice (ring readback or
        archived segment — both carry the ring column layout)."""
        from sitewhere_tpu.outbound.feed import OutboundEvent

        eng = self.engine
        out = []
        for i in range(count):
            if not bool(sl.valid[i]):
                continue
            gdid = eng._gdid(s, int(sl.device[i]))
            info = eng.devices.get(gdid)
            et = EventType(int(sl.etype[i]))
            meas = {}
            lat = lon = None
            if et is EventType.MEASUREMENT:
                for ch in np.nonzero(np.asarray(sl.vmask[i]))[0]:
                    meas[lane_names.get(int(ch), f"ch{ch}")] = float(
                        sl.values[i, ch])
            elif et is EventType.LOCATION and bool(sl.vmask[i, 0]):
                lat = float(sl.values[i, 0])
                lon = float(sl.values[i, 1])
            out.append(OutboundEvent(
                latitude=lat,
                longitude=lon,
                event_id=encode_event_id(
                    base + i, s, a, self.n_shards, self.arenas),
                etype=et,
                device_token=info.token if info else f"#{gdid}",
                device_id=gdid,
                assignment_id=eng._gdid(s, int(sl.assignment[i])),
                tenant=(eng.tenants.token(int(sl.tenant[i]))
                        if int(sl.tenant[i]) != NULL_ID else "default"),
                area_id=int(sl.area[i]),
                customer_id=int(sl.customer[i]),
                asset_id=int(sl.asset[i]),
                ts_ms=int(sl.ts_ms[i]),
                received_ms=int(sl.received_ms[i]),
                measurements=meas,
                values=[float(v) for v in sl.values[i]],
                aux0=int(sl.aux[i, 0]),
                aux1=int(sl.aux[i, 1]),
            ))
        return out

    def poll(self) -> list:
        # whole-poll engine lock: stacked state is donated through every
        # step, so store references captured outside the lock die under a
        # concurrent flush, and a wrapped ring would serve new rows under
        # old positions (see outbound/feed.py:poll)
        with self.engine.lock:
            if self.engine._pending_outs:
                self.engine.drain()
            return self._poll_locked()

    def _poll_locked(self) -> list:
        """Poll body; caller MUST hold the engine lock (protects the
        donated stacked store AND the archive index)."""
        from sitewhere_tpu.ops.readback import read_range
        from sitewhere_tpu.outbound.feed import OutboundEvent

        store = self.engine.state.store
        acap = self.engine.config.store_capacity_per_shard // self.arenas
        heads = self._heads(store)
        out: list[OutboundEvent] = []
        eng = self.engine
        archive = getattr(eng, "archive", None)
        lane_names: dict[int, str] = {}
        for name, nid in eng.channel_map.names.items():
            lane_names.setdefault(nid % eng.config.channels, name)
        for s in range(self.n_shards):
            shard_store = None
            for a in range(self.arenas):
                head = int(heads[s, a])
                if head <= self.offsets[s, a]:
                    continue
                # a lagging consumer REPLAYS evicted rows from its archive
                # partition (Kafka-consumer at-least-once: falling behind
                # means reading older log segments, not losing events).
                # Replay does NOT advance committed offsets — redelivery
                # until commit(); only unrecoverable gaps advance + count
                # as lag_lost, and replay resumes at the next segment
                oldest = max(0, head - acap)
                budget = self.max_batch
                part = s * self.arenas + a
                if archive is None and self.offsets[s, a] < oldest:
                    self.lag_lost += oldest - int(self.offsets[s, a])
                    self.offsets[s, a] = oldest
                pos = int(self.offsets[s, a])
                while archive is not None and pos < oldest and budget > 0:
                    sl, n = archive.read_rows(
                        part, pos, min(oldest - pos, budget))
                    if n == 0:
                        # gap skip only when nothing replayed-but-
                        # uncommitted precedes it (else a pre-commit
                        # crash would drop those events)
                        if pos != int(self.offsets[s, a]):
                            break   # deliver pre-gap events first
                        nxt = archive.next_start(part, pos)
                        nxt = oldest if nxt is None else min(nxt, oldest)
                        # registered gaps (migration padding) never held
                        # data — skipping them is not loss
                        self.lag_lost += max(
                            0, nxt - pos - archive.gap_rows(part, pos, nxt))
                        self.offsets[s, a] = nxt
                        pos = nxt
                        continue
                    out.extend(self._events_from_slice(
                        sl, pos, n, s, a, lane_names))
                    pos += n
                    budget -= n
                if pos < oldest:
                    continue   # batch full mid-replay; resumes next poll
                count = min(head - pos, budget)
                if count <= 0:
                    continue
                if shard_store is None:
                    shard_store = jax.tree_util.tree_map(
                        lambda x, _s=s: x[_s], store)
                sl = jax.device_get(read_range(
                    shard_store, jnp.int32(pos % acap), count, arena=a))
                out.extend(self._events_from_slice(
                    sl, pos, count, s, a, lane_names))
        return out

    def commit(self, events: list) -> None:
        for ev in events:
            pos, s, a = split_event_id(ev.event_id, self.n_shards,
                                       self.arenas)
            self.offsets[s, a] = max(self.offsets[s, a], pos + 1)


def restore_distributed(directory) -> DistributedEngine:
    """Reconstruct a DistributedEngine from a snapshot directory (same
    shard count; use :func:`reshard_snapshot` to change it first)."""
    import json
    import pathlib

    directory = pathlib.Path(directory)
    host = json.loads((directory / "host_distributed.json").read_text())
    config = DistributedConfig(**host["config"])
    config.n_shards = host["n_shards"]
    eng = DistributedEngine(config)
    eng.sharded.restore(directory)
    eng.epoch = EpochBase(host["epoch_base_unix_s"])
    eng._next_device = np.asarray(host["next_device"], np.int64)
    eng._next_assignment = np.asarray(host["next_assignment"], np.int64)
    for tok in host["tokens"]:
        eng.tokens.intern(tok)
    for t in host["tenants"]:
        eng.tenants.intern(t)
    for t in host["device_types"]:
        eng.device_types.intern(t)
    for n in host["channel_names"]:
        eng.channel_map.names.intern(n)
    for a in host["alert_types"]:
        eng.alert_types.intern(a)
    for a in host["areas"]:
        eng.areas.intern(a)
    for cst in host["customers"]:
        eng.customers.intern(cst)
    for a in host["assets"]:
        eng.assets.intern(a)
    for e in host["event_ids"]:
        eng.event_ids.intern(e)
    eng.token_device = {int(k): v for k, v in host["token_device"].items()}
    eng.devices = {int(k): DeviceInfo(**v)
                   for k, v in host["devices"].items()}
    eng.assignments = {int(k): AssignmentInfo(**v)
                       for k, v in host["assignments"].items()}
    eng.assignment_tokens = {i.token: a for a, i in eng.assignments.items()}
    eng.device_slots = {int(k): list(v)
                        for k, v in host["device_slots"].items()}
    eng.dead_letters = list(host["dead_letters"])
    # conservation ledger (ISSUE 14): rebase over the restored device
    # counters BEFORE any WAL replay (engine.py restore_engine parity)
    eng.ledger.rebase(eng)
    return eng


def recover_distributed(snapshot_dir, wal_dir=None,
                        adopt_wal: bool = False) -> DistributedEngine:
    """Crash recovery for the mesh engine: restore the snapshot, replay the
    WAL tail past its watermark through the wire format that accepted each
    record (at-least-once; the sharded state merge is timestamp-idempotent
    like the single-node path). The replay mechanism is shared with
    recover_engine (utils/checkpoint.replay_wal_into).

    ``adopt_wal=True``: when the snapshot itself carries no WAL (migrated
    or resharded manifests set wal_dir=None), the engine ADOPTS ``wal_dir``
    as its live log after replaying it — the serving-rank boot path. The
    default keeps an explicitly named log READ-ONLY (a preserved recovery
    copy stays byte-identical)."""
    import json
    import pathlib

    from sitewhere_tpu.utils.checkpoint import replay_wal_into

    snapshot_dir = pathlib.Path(snapshot_dir)
    eng = restore_distributed(snapshot_dir)
    host = json.loads((snapshot_dir / "host_distributed.json").read_text())
    if wal_dir is None and eng.config.wal_dir is None:
        return eng
    if adopt_wal and eng.wal is None and wal_dir is not None:
        # the tail in wal_dir replays first, then new ingest journals
        # into the same log (replay never re-logs: replay_wal_into
        # detaches the live WAL while feeding records)
        from sitewhere_tpu.utils.ingestlog import IngestLog

        eng.config.wal_dir = str(wal_dir)
        eng.wal = IngestLog(wal_dir)
    replay_wal_into(eng, host["store_cursor"], wal_dir)
    return eng
