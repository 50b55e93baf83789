"""The host engine: single-node runtime tying ingest to the TPU pipeline.

This object is the deployment analog of the reference's whole service stack
(SURVEY.md §1): it owns the interners (device tokens, tenants, measurement
channels, alert types), the staging buffer and flush policy (the batch-size/
latency scheduler from SURVEY.md §7 "hard parts"), the compiled pipeline
step, and the host mirror of registry metadata (strings, types) that the
device tables don't carry.

Two registry write paths stay consistent by construction:
  * auto-registration happens ON DEVICE (ops/registration.py); the host
    mirrors it deterministically from the step's ``new_tokens`` readback
    (allocation order == batch order).
  * admin CRUD (REST/API path) allocates from the host counter and writes
    the device row explicitly via a tiny jit'd updater, then bumps the same
    counters the kernel uses.
All engine mutations are serialized through one lock, mirroring the
single-writer semantics the reference gets from Kafka partition ordering.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.core.events import EpochBase, HostEventBuffer
from sitewhere_tpu.core.registry import MAX_ACTIVE_ASSIGNMENTS, TokenInterner
from sitewhere_tpu.core.state import RECENT_DEPTH
from sitewhere_tpu.core.types import (
    DEFAULT_VALUE_CHANNELS,
    NULL_ID,
    DeviceAssignmentStatus,
    EventType,
    PresenceState,
)
from sitewhere_tpu.ingest.requests import DecodedRequest, RequestType
from sitewhere_tpu.pipeline import (
    FAMILY_PACKED_SCAN,
    FAMILY_STEP,
    FAMILY_SWEEP,
    PipelineConfig,
    PipelineState,
    StepOutput,
    make_packed_scan_step,
    make_pipeline_step,
    make_presence_sweep,
)
from sitewhere_tpu.utils.tracing import stage


# WAL record format tags (first byte of every logged payload): recovery
# replays each record through the decoder that originally accepted it
WAL_JSON = b"\x01"
WAL_BINARY = b"\x02"


class ChannelCapacityError(ValueError):
    """Raised in strict channel mode when distinct measurement names exceed
    the configured channel count (the config-time remedy for lane aliasing)."""


class ChannelMap:
    """Measurement-name -> channel-index interner (per engine).

    The reference stores named measurements as rows; the TPU layout is a
    fixed-width channel vector, so names map to channel lanes. Beyond
    ``channels`` distinct names the behavior is the ``strict`` knob's call:
    strict engines raise :class:`ChannelCapacityError` (no silent merging —
    the operator sizes ``channels`` up), lenient engines reuse lanes modulo
    with a collision counter surfaced in engine metrics, Prometheus
    (`swtpu_engine_channel_collisions`), and the REST metrics endpoints."""

    def __init__(self, channels: int, names=None, strict: bool = False):
        self.channels = channels
        self.names = names if names is not None else TokenInterner(1 << 20)
        self.collisions = 0
        self.strict = strict

    def channel_of(self, name: str) -> int:
        nid = self.names.intern(name)
        if nid >= self.channels:
            self.collisions += 1
            if self.strict:
                raise ChannelCapacityError(
                    f"measurement name {name!r} exceeds channel capacity "
                    f"{self.channels}; raise EngineConfig.channels or drop "
                    "strict_channels")
        return nid % self.channels

    def validate(self, names) -> None:
        """Strict-mode capacity check WITHOUT interning: a rejected
        request must not consume lanes, so names only intern once the
        request is accepted (channel_of on the staging pass)."""
        if not self.strict:
            return
        unseen: set[str] = set()
        for name in names:
            nid = self.names.lookup(name)
            if nid < 0:
                unseen.add(name)
            elif nid >= self.channels:
                self.collisions += 1
                raise ChannelCapacityError(
                    f"measurement name {name!r} exceeds channel capacity "
                    f"{self.channels}; raise EngineConfig.channels or drop "
                    "strict_channels")
        if len(self.names) + len(unseen) > self.channels:
            self.collisions += 1
            raise ChannelCapacityError(
                f"{len(unseen)} new measurement name(s) would exceed channel "
                f"capacity {self.channels}; raise EngineConfig.channels or "
                "drop strict_channels")


def _merge_summaries(summaries: list[dict]) -> dict:
    """Fold per-lane drain summaries into one (counts sum, token lists
    concatenate) — the summary a flush() caller sees."""
    out = {"found": 0, "missed": 0, "registered": 0, "persisted": 0,
           "new_tokens": [], "dead_tokens": []}
    for s in summaries:
        for k in ("found", "missed", "registered", "persisted"):
            out[k] += s[k]
        out["new_tokens"].extend(s["new_tokens"])
        out["dead_tokens"].extend(s["dead_tokens"])
    return out


def _empty_host_batch(capacity: int, channels: int):
    """All-invalid numpy EventBatch (tail-chunk padding for scan dispatch)."""
    from sitewhere_tpu.core.events import EventBatch
    from sitewhere_tpu.core.types import AUX_LANES

    return EventBatch(
        valid=np.zeros(capacity, np.bool_),
        etype=np.zeros(capacity, np.int32),
        token_id=np.full(capacity, NULL_ID, np.int32),
        tenant_id=np.full(capacity, NULL_ID, np.int32),
        ts_ms=np.zeros(capacity, np.int32),
        received_ms=np.zeros(capacity, np.int32),
        values=np.zeros((capacity, channels), np.float32),
        vmask=np.zeros((capacity, channels), np.bool_),
        aux=np.full((capacity, AUX_LANES), NULL_ID, np.int32),
        seq=np.arange(capacity, dtype=np.int32),
    )


class IngestHostMixin:
    """WAL durability + strict-channel machinery shared by the single-node
    ``Engine`` and the mesh ``DistributedEngine`` — one implementation so
    durability and strictness semantics can never diverge between them.
    Hosts provide: ``lock``, ``wal``, ``_wal_local``, ``channel_map``,
    ``config.strict_channels``, ``process()``, ``_ingest_decoded()``,
    ``flight`` (utils/flight.FlightRecorder), ``_staged_traces``."""

    # overload discipline (ISSUE 9): hosts that enable config.qos attach
    # an AdmissionController (consulted at the ingest EDGES, never here)
    # and a WeightedFairGate ordering the batch-ingest critical section
    # across tenants; both default off so recovery/standby replay and
    # non-QoS engines pay nothing
    qos = None
    _wfq_gate = None

    # staging-clock pin (event-plane replication): a replica feed ships
    # each WAL append's staging timestamp so the follower's standby
    # stages byte-identical rows; the follower's applier sets this
    # around its apply call, the leader sets it at publish time. The pin
    # is shared engine state: it is SET and CLEARED only under the
    # engine lock, within the same critical section that staged the
    # batch — an unlocked clear could null a concurrent batch's pin
    # between its publish and its staging.
    _now_override: int | None = None

    def _staging_now(self) -> int:
        ov = self._now_override
        return int(ov) if ov is not None else self.epoch.now_ms()

    def _clear_now_pin(self) -> None:
        """Drop the staging-clock pin (engine lock held). Nested
        process() calls (batch fallback, register/ack re-entry) keep the
        OUTER batch's pin — the whole batch must stage on one clock on
        both the leader and the follower."""
        if not getattr(self._wal_local, "depth", 0):
            self._now_override = None

    def _wal_append(self, tag: bytes, payloads: list[bytes],
                    tenant: str) -> None:
        """Log accepted payloads. MUST be called under the engine lock so a
        concurrent snapshot's watermark can never cover a record whose
        events were not yet staged. No-op while replaying or while an outer
        ingest path on this thread already logged the raw batch.

        Group-commit mode (the default): the append BUFFERS and returns a
        sequence ticket — the commit thread writes + fsyncs off the driver
        thread, and :meth:`_wal_gate` holds every dispatch until its
        batch's ticket is durable (WAL-before-dispatch preserved, fsync
        latency overlapped with next-batch decode). Non-group mode keeps
        the inline write+flush."""
        if self.wal is None or getattr(self._wal_local, "depth", 0):
            return
        head = tag + tenant.encode() + b"\x00"
        with stage("wal.append", mark="wal_append",
                   records=len(payloads)) as sp:
            b0 = self.wal.appended_bytes
            self._wal_last_seq = self.wal.append_many(payloads, head)
            if not self.wal.group_commit:
                # ONE buffered write for the whole group, then one flush:
                # an accepted event must survive a process crash (fsync
                # cadence stays the operator's sync() call)
                self.wal.flush()
            sp.set_metadata(bytes=self.wal.appended_bytes - b0)
        feed = getattr(self, "replica_feed", None)
        if feed is not None:
            # same critical section as the append: feed order == WAL
            # order. Pin the staging clock here and ship it, so leader
            # staging and follower replay stamp identical received_ms
            # (the byte-identity oracle). The sender still gates on
            # wait_durable(ticket) before the bytes leave this host.
            now_ms = self.epoch.now_ms()
            self._now_override = now_ms
            feed.publish(tag, payloads, tenant, self._wal_last_seq, now_ms)

    def _wal_gate(self, traces=()) -> None:
        """Block until every WAL record appended so far is DURABLE (group
        commit's fsync watermark) — called immediately before a device
        dispatch, under the engine lock. The append of the dispatching
        batch happened earlier on this same thread, so gating on the
        newest ticket covers it. No-op without a WAL (and inside
        wait_durable, when group commit is off)."""
        if self.wal is None or not self.wal.group_commit:
            # non-group mode flushes inline at append and never fsyncs at
            # dispatch — stamping wal_durable here would claim a
            # durability guarantee that mode does not provide
            return
        with stage("wal.gate", batches=len(traces)):
            self.wal.wait_durable(self._wal_last_seq)
            for rec in traces:
                rec.mark("wal_durable")

    # ------------------------------------------------------- flight recorder
    def get_trace(self, trace_id: str) -> dict:
        """Lifecycle records for one trace id (this engine's recorder;
        the cluster facade overrides with a rank fan-out)."""
        return {"traceId": trace_id,
                "records": self.flight.records_of(trace_id)}

    def recent_traces(self, limit: int = 50) -> list[dict]:
        return self.flight.recent(limit)

    def get_trace_timeline(self, trace_id: str) -> dict:
        """One trace as a Chrome-trace-event document (loads directly in
        Perfetto / chrome://tracing): flight-record lifecycle intervals
        merged with the span tracer's live spans. The cluster facade
        overrides with a rank fan-out so one trace id yields one
        multi-rank timeline."""
        from sitewhere_tpu.utils.tracing import (finish_timeline,
                                                 timeline_events)

        return finish_timeline(trace_id, timeline_events(self, trace_id))

    def slo_harvest(self) -> list:
        """Completed ingest lifecycles not yet exported to the SLO plane.
        Drained (exactly once each) by the Prometheus exporter at SCRAPE
        time: the per-tenant ``swtpu_ingest_e2e_seconds`` histograms are
        built entirely from flight records, so the ingest hot path pays
        ZERO extra device syncs for SLO latency — the same harvest rule
        bench.py's cluster leg and the autotuner's stage medians ride."""
        return self.flight.harvest_completed("ingest",
                                             terminal="device_ready")

    @contextlib.contextmanager
    def _wal_suppress(self):
        """Suppress WAL logging for nested process() calls on THIS thread
        (their raw batch is already logged)."""
        self._wal_local.depth = getattr(self._wal_local, "depth", 0) + 1
        try:
            yield
        finally:
            self._wal_local.depth -= 1

    def _ingest_batch(self, payloads: list[bytes], tenant: str, tag: bytes,
                      dec, native_fn, binary: bool = False,
                      traceparent: str | None = None) -> dict:
        """Common batch-ingest skeleton: strict validation -> WAL -> stage,
        wrapped in one flight-recorder lifecycle record (the batch's trace;
        ``traceparent`` — explicit or bound by the RPC server — joins a
        cross-rank trace instead of opening a new one). ``native_fn`` is
        the native SoA decoder call (None = Python path)."""
        from sitewhere_tpu.utils.tracing import current_traceparent

        rec = self.flight.begin(
            "ingest", tenant=tenant, n_payloads=len(payloads),
            traceparent=traceparent or current_traceparent())
        # the batch's root span: every span below shares its trace id
        with stage("ingest", payloads=len(payloads),
                   trace_id=rec.trace_id or ""):
            # weighted-fair turn: under multi-tenant contention the
            # gate orders which tenant's batch enters the ingest critical
            # section (and therefore acquires the next arena slot / staging
            # room) by virtual-time deficit, so one tenant's flood cannot
            # starve the others in lock-arrival order. The turn is ENTERED by
            # the inner skeleton immediately before its branch's critical
            # section, so work that deliberately runs outside the engine lock
            # (the lenient path's native decode) keeps overlapping across
            # threads with QoS on. Re-entrant callers (admin paths already
            # inside the engine lock) skip the gate — parking them would
            # deadlock against their own lock.
            gate = self._wfq_gate
            gate_ctx = (gate.turn(tenant, len(payloads))
                        if gate is not None and not self.lock._is_owned()
                        else contextlib.nullcontext())
            with self.flight.bind(rec):
                summary = self._ingest_batch_inner(payloads, tenant, tag,
                                                   dec, native_fn, binary,
                                                   rec, gate_ctx)
            if rec.trace_id is not None:
                rec.add_counts(summary)
                if rec.meta.get("path") != "arena" and summary.get("staged"):
                    with self.lock:
                        if self.staged_count:
                            # rows await dispatch via the shared buffer: the
                            # next flush stamps this record's dispatch
                            self._staged_traces.append(rec)
                        else:
                            # a mid-ingest buffer-fill flush already
                            # dispatched every row of this batch (the record
                            # was not yet queued): join the newest in-flight
                            # program so drain stamps the tail stages
                            # instead of stranding an incomplete trace
                            rec.mark("dispatch")
                            if self._pending_traces:
                                self._pending_traces[-1].append(rec)
                            else:
                                rec.mark("device_ready")
                summary["trace_id"] = rec.trace_id
            return summary

    def _ingest_batch_inner(self, payloads, tenant, tag, dec, native_fn,
                            binary, rec,
                            gate_ctx=contextlib.nullcontext()) -> dict:
        # gate_ctx is the batch's (single-use) weighted-fair turn; each
        # branch enters it immediately before its own critical section —
        # never around work that is designed to run outside the lock
        if native_fn is None:
            with gate_ctx, self.lock:
                try:
                    predecoded = self._strict_predecode(payloads, dec)
                    self._wal_append(tag, payloads, tenant)
                    # the Python path decodes and stages in one pass
                    with stage("ingest.commit", mark="commit") as sp:
                        summary = self._ingest_python_fallback(
                            payloads, tenant, dec, predecoded)
                        rec.mark("decode")
                        sp.set_metadata(staged=summary.get("staged", 0))
                    return summary
                finally:
                    self._clear_now_pin()
        if self.config.strict_channels:
            # strict serializes the native decode under the lock so a
            # rejected batch can roll back the names it interned without
            # clobbering a concurrent batch's newly-interned names
            with gate_ctx, self.lock:
                try:
                    names_before = len(self.channel_map.names)
                    res = self._native_decode(native_fn, payloads)
                    self._check_strict_native(res, names_before)
                    self._wal_append(tag, payloads, tenant)
                    return self._stage_decoded(res, payloads, tenant, dec)
                finally:
                    self._clear_now_pin()
        if getattr(self, "_arena_pool", None) is not None \
                and not self.config.fair_tenancy:
            # zero-copy path: the native scanner fills the staging arena
            # directly — no decode output arrays, no staging copy. Decode
            # runs UNDER the lock (the arena is shared mutable state);
            # cross-thread decode parallelism is the worker pool's job.
            with gate_ctx:
                return self._ingest_batch_arena(payloads, tenant, tag, dec,
                                                binary)
        # lenient fast path: decode OUTSIDE the lock (concurrent receivers
        # decode in parallel — and outside the WFQ turn, for the same
        # reason); log + stage atomically
        res = self._native_decode(native_fn, payloads)
        with gate_ctx, self.lock:
            try:
                self._wal_append(tag, payloads, tenant)
                return self._stage_decoded(res, payloads, tenant, dec)
            finally:
                self._clear_now_pin()

    @staticmethod
    def _native_decode(native_fn, payloads):
        """The native SoA decode of a whole batch, as one span."""
        with stage("ingest.decode", mark="decode",
                   rows=len(payloads)) as sp:
            res = native_fn(payloads)
            sp.set_metadata(failed=int(np.sum(res.rtype < 0)))
        return res

    def _stage_decoded(self, res, payloads, tenant, dec) -> dict:
        """Stage a natively decoded batch (caller holds the lock and has
        logged it), as one span."""
        with stage("ingest.commit", mark="commit") as sp:
            summary = self._ingest_decoded(res, payloads, tenant, dec)
            sp.set_metadata(staged=summary.get("staged", 0))
        return summary

    def _strict_predecode(self, payloads, dec):
        """Strict pre-pass for the Python-fallback path: decode ONCE and
        validate channel capacity without interning, so a rejected batch
        never leaks lanes. Returns per-payload request lists (None entries
        = decode failures) for reuse by _ingest_python_fallback; None when
        strict mode is off. Caller holds the lock."""
        if not self.channel_map.strict:
            return None
        decoded: list[list | None] = []
        names: list[str] = []
        for p in payloads:
            try:
                reqs = dec.decode(p, {})
            except Exception:
                decoded.append(None)   # counted failed on the ingest pass
                continue
            decoded.append(reqs)
            for req in reqs:
                names.extend(req.measurements or ())
        self.channel_map.validate(names)
        return decoded

    def _check_strict_native(self, res, names_before: int) -> None:
        """Strict native path: the C++ decoder interned names during decode;
        on any collision the whole batch is rejected BEFORE WAL/staging and
        the names it added roll back (interner truncate), so a refused
        batch never leaks lanes. Caller holds the lock."""
        if not self.config.strict_channels or not res.collisions:
            return
        self.channel_map.names.truncate(names_before)
        self.channel_map.collisions += res.collisions
        raise ChannelCapacityError(
            f"{res.collisions} measurement lane collision(s) in batch: "
            f"distinct names exceed channel capacity "
            f"{self.config.channels}; raise channels or drop strict_channels")

    def _ingest_python_fallback(self, payloads, tenant, dec,
                                predecoded=None) -> dict:
        """Per-request staging; reuses the strict pre-pass's decode when
        present (no double decode under the lock)."""
        failed = 0
        with self._wal_suppress():   # the raw batch is already logged
            if predecoded is not None:
                for reqs in predecoded:
                    if reqs is None:
                        failed += 1
                        continue
                    for req in reqs:
                        req.tenant = tenant
                        self.process(req)
            else:
                for p in payloads:
                    try:
                        for req in dec.decode(p, {}):
                            req.tenant = tenant
                            self.process(req)
                    except Exception:
                        failed += 1
        return {"decoded": len(payloads) - failed, "failed": failed}

    def _wal_admin_register(self, token: str, device_type: str,
                            tenant: str, area: str | None,
                            customer: str | None) -> None:
        """WAL-carry an ADMIN-path device registration as its wire-form
        REGISTER envelope, in the same critical section as the mutation —
        so the non-wire REST/RPC ``register_device`` becomes WAL-
        replayable AND replica-feed visible (a promoted standby serves
        the same registry; closes the PR-6 documented limit). The wire
        path already logged its own envelope and re-enters under
        ``_wal_suppress``, so this no-ops there; replay and standby apply
        run with no live WAL and no-op too."""
        if self.wal is None or getattr(self._wal_local, "depth", 0):
            return
        from sitewhere_tpu.ingest.decoders import encode_binary_request
        from sitewhere_tpu.ingest.requests import (DecodedRequest,
                                                   RequestType)

        extras = {"deviceTypeToken": device_type}
        if area:
            extras["areaToken"] = area
        if customer:
            extras["customerToken"] = customer
        req = DecodedRequest(type=RequestType.REGISTER_DEVICE,
                             device_token=token, tenant=tenant,
                             extras=extras)
        try:
            self._wal_append(WAL_BINARY, [encode_binary_request(req)],
                             tenant)
        finally:
            self._clear_now_pin()

    def process(self, req) -> None:
        """Stage one decoded request (the per-request / protocol-receiver
        path); flushes when the staging batch fills. Registration and
        mapping envelopes take the admin path; event requests convert to
        one staged SoA row via the engine's ``_stage_row``."""
        from sitewhere_tpu.ingest.requests import RequestType

        with self.lock:
            if self.channel_map.strict and req.measurements:
                # strict mode must reject BEFORE the WAL append so a refused
                # event is never durable — and WITHOUT interning, so the
                # refused names don't leak channel lanes
                self.channel_map.validate(req.measurements)
            if self.wal is not None:
                # per-request path: log the request in the binary wire form
                # when it carries one; unsupported types are snapshot-only
                from sitewhere_tpu.ingest.decoders import encode_binary_request

                try:
                    self._wal_append(WAL_BINARY,
                                     [encode_binary_request(req)], req.tenant)
                except KeyError:
                    pass
            if req.type is RequestType.REGISTER_DEVICE:
                # the envelope above IS this registration's WAL record:
                # suppress the admin path's own record or it double-logs
                with self._wal_suppress():
                    self.register_device(
                        req.device_token,
                        device_type=req.extras.get(
                            "deviceTypeToken",
                            self.config.default_device_type),
                        tenant=req.tenant,
                        area=req.extras.get("areaToken"),
                        customer=req.extras.get("customerToken"),
                    )
                self._clear_now_pin()
                return
            if req.type is RequestType.MAP_DEVICE:
                parent = (req.extras.get("parentToken")
                          or req.extras.get("parentHardwareId"))
                if parent:
                    self.map_device(req.device_token, parent)
                self._clear_now_pin()
                return
            et = req.event_type
            if et is None:
                self._clear_now_pin()
                return
            now = self._staging_now()
            # wire timestamps are absolute unix ms; device arrays carry int32
            # ms relative to the engine epoch base
            if req.event_ts_ms is not None:
                base_ms = int(self.epoch.base_unix_s * 1000)
                ts = int(np.clip(req.event_ts_ms - base_ms,
                                 -(2**31) + 1, 2**31 - 1))
            else:
                ts = now
            token_id = self.tokens.intern(req.device_token)
            tenant_id = self.tenants.intern(req.tenant)
            channels = self.config.channels
            values = np.zeros(channels, np.float32)
            mask = np.zeros(channels, np.bool_)
            aux0 = NULL_ID
            if et is EventType.MEASUREMENT and req.measurements:
                for name, val in req.measurements.items():
                    ch = self.channel_map.channel_of(name)
                    values[ch] = val
                    mask[ch] = True
            elif et is EventType.LOCATION:
                # lanes only when coordinates were provided — a location
                # request with null coords persists with no location lanes
                # (native decoder parity; no null-island (0,0) rows)
                if req.latitude is not None and req.longitude is not None:
                    values[0], values[1] = req.latitude, req.longitude
                    values[2] = req.elevation or 0.0
                    mask[:3] = True
            elif et is EventType.ALERT:
                values[0] = float(int(req.alert_level))
                mask[0] = True
                aux0 = self.alert_types.intern(req.alert_type or "alert")
            elif et is EventType.COMMAND_RESPONSE and req.originating_event_id:
                aux0 = self.event_ids.intern(req.originating_event_id)
            elif et is EventType.STATE_CHANGE and (req.attribute or req.state_type):
                # the change label travels in aux0 so consumers can tell
                # e.g. assignment.created from assignment.released
                aux0 = self.event_ids.intern(
                    f"{req.attribute or ''}:{req.state_type or ''}")
            aux1 = (self.event_ids.intern(req.alternate_id)
                    if req.alternate_id is not None else NULL_ID)
            self._stage_row(int(et), token_id, tenant_id, ts, now,
                            values, mask, aux0, aux1)
            # top-level per-request call: the pin set by _wal_append
            # (replica feed) covered exactly this request; nested calls
            # keep the outer batch's pin (_clear_now_pin checks depth)
            self._clear_now_pin()

    def _decode_prologue(self, res, payloads, tenant, reg_decoder,
                         now: int, base_ms: int):
        """Shared post-processing of a native SoA decode: map request types
        to event types, re-route registration/mapping/ack envelopes through
        the per-request path (they carry string payloads the fast columns
        don't extract), relativize timestamps, and fold alert levels into
        values lane 0. Returns (etype, ok, ts_rel, values, failed,
        n_reg_ok). Caller holds the lock."""
        from sitewhere_tpu.ingest.fast_decode import (
            RT_ACK,
            RT_MAP,
            RT_REGISTER,
            RTYPE_TO_ETYPE,
        )

        etype = RTYPE_TO_ETYPE[np.clip(res.rtype, -1, 7)]
        ok = (res.rtype >= 0) & (etype >= 0)
        regs = ((res.rtype == RT_REGISTER) | (res.rtype == RT_MAP)
                | (res.rtype == RT_ACK))
        ok &= ~regs   # slow-path rows must not also stage via fast path
        failed = int(np.sum(res.rtype < 0))
        n_reg_ok = 0
        if np.any(regs):
            with self._wal_suppress():   # raw batch already logged
                for i in np.nonzero(regs)[0]:
                    try:
                        for req in reg_decoder.decode(payloads[int(i)], {}):
                            req.tenant = tenant
                            self.process(req)
                        n_reg_ok += 1
                    except Exception:
                        failed += 1
        # relative int32 timestamps (absent -> now)
        ts_rel = np.where(
            res.ts_ms64 >= 0,
            np.clip(res.ts_ms64 - base_ms, -(2**31) + 1, 2**31 - 1),
            now,
        ).astype(np.int32)
        values = res.values
        # alert rows carry their level in values[:, 0]
        alert_rows = ok & (etype == int(EventType.ALERT))
        if np.any(alert_rows):
            values = values.copy()
            values[alert_rows, 0] = res.level[alert_rows]
        return etype, ok, ts_rel, values, failed, n_reg_ok


@dataclasses.dataclass
class EngineConfig:
    device_capacity: int = 1 << 17
    token_capacity: int = 1 << 18
    assignment_capacity: int = 1 << 18
    store_capacity: int = 1 << 18
    channels: int = DEFAULT_VALUE_CHANNELS
    batch_capacity: int = 8192
    flush_interval_s: float = 0.05     # max added latency before a forced flush
    auto_register: bool = True
    default_device_type: str = "default"
    presence_missing_s: float = 8 * 3600.0  # DevicePresenceManager default 8h
    use_native: bool = True            # C++ decode/interning data plane
    strict_channels: bool = False      # error (vs alias) past channel capacity
    fair_tenancy: bool = False         # round-robin batch formation across
                                       # tenants (multi-tenant fairness)
    assignment_triggers: bool = False  # emit STATE_CHANGE events on
                                       # assignment create/status change
                                       # (DeviceManagementTriggers analog)
    wal_dir: str | None = None         # write-ahead log directory; None
                                       # disables the durability log
    wal_group_commit: bool = True      # group-commit WAL: appends buffer,
                                       # a commit thread fsyncs once per
                                       # quiescent window, and dispatch
                                       # gates on the durability watermark
                                       # (fsync overlaps next-batch decode
                                       # instead of serializing the driver)
    wal_group_window_s: float = 0.002  # commit-thread quiescent window
    ingest_workers: int = 0            # sharded arena decode fan-out:
                                       # one wire batch splits across N
                                       # threads by payload bytes into
                                       # disjoint rows of one arena,
                                       # byte-identical to single-thread.
                                       # 0 = auto (os.cpu_count()),
                                       # 1 = single-threaded decode
    autotune: bool = False             # stage-time autotuner: adapt
                                       # dispatch_depth / decode fan-out
                                       # (and optionally scan_chunk)
                                       # toward the flight recorder's
                                       # measured bottleneck
    autotune_interval: int = 64        # dispatches between evaluations
    autotune_scan_chunk: bool = False  # allow the tuner to change
                                       # scan_chunk (recompiles the arena
                                       # scan program mid-run)
    archive_dir: str | None = None     # long-term retention tier: spill
                                       # ring segments to disk before
                                       # overwrite; query_events merges
                                       # ring + archive (utils/archive.py)
    archive_segment_rows: int = 4096   # rows per spilled segment (clamped
                                       # to arena_capacity // 4)
    archive_max_rows: int | None = None  # retention policy per arena: None
                                         # = unbounded history; else oldest
                                         # whole segments expire past this
                                         # (INFLUX_RETENTION_POLICY analog)
    archive_max_age_ms: int | None = None  # event-time retention horizon:
                                           # segments older than this (vs
                                           # the partition's newest event)
                                           # expire
    archive_cache_segments: int = 8    # LRU segment-decode cache depth
                                       # shared by archive queries, by-id
                                       # lookups, and feed replay (one
                                       # np.load per segment per working
                                       # set, not per call)
    archive_compress: bool = False     # per-column codecs on spilled
                                       # segments (ISSUE 19): delta+zigzag
                                       # packed ints / packbits bools /
                                       # deflated floats; decode cost is
                                       # charged in the planner, query
                                       # results stay byte-identical
    scan_chunk: int = 1                # >1: dispatch K emitted batches as
                                       # ONE lax.scan program (amortizes
                                       # dispatch/transfer per chunk; adds
                                       # up to K-1 batches of latency)
    dispatch_depth: int = 1            # outstanding device programs before
                                       # the dispatcher waits; >1 overlaps
                                       # host staging with device work.
                                       # The default 1 is unmeasured on
                                       # the chip
    analytics_devices: int = 0         # HBM telemetry windows for [0, M)
    analytics_window: int = 128        # W timesteps per window
    tenant_arenas: int = 1             # >1: partition the event ring into
                                       # per-tenant-hash arenas — one
                                       # tenant's burst can only evict its
                                       # own arena's rows (hard retention
                                       # isolation)
    ingest_arenas: int = 0             # staging-arena pool for the
                                       # zero-copy batch ingest path:
                                       # 0 = auto (dispatch_depth + 2),
                                       # -1 disables (legacy copy staging).
                                       # Each arena holds
                                       # batch_capacity * scan_chunk rows
    flight_recorder: bool = True       # batch-lifecycle flight recorder
                                       # (utils/flight.py); overhead is a
                                       # few dict writes per BATCH — bench
                                       # gates it at <= 3% of host e2e
    flight_capacity: int = 1024        # lifecycle records retained
    span_trace: bool = True            # hierarchical span tracer (ISSUE
                                       # 10, utils/tracing.SpanTracer):
                                       # live spans for forward hops,
                                       # replica send/apply, shard
                                       # decode, query rounds, scheduler
                                       # fires; ingest lifecycle spans
                                       # derive from flight records at
                                       # export — bench hard-gates the
                                       # on-vs-off delta <= 3%
    span_capacity: int = 4096          # completed spans retained
    span_sample: float = 1.0           # head-based keep fraction (seeded
                                       # deterministic per trace id);
                                       # the slowest decile per span
                                       # name is kept regardless
    span_seed: int = 0                 # sampling hash seed
    query_coalesce: int = 16           # max concurrent event queries fused
                                       # into ONE device program by the
                                       # shared-scan query batcher (1
                                       # effectively disables coalescing;
                                       # queries still run off the lock)
    qos: bool = False                  # overload discipline (utils/qos.py):
                                       # per-tenant token-bucket admission
                                       # + weighted-fair ingest/query
                                       # scheduling. Admission applies at
                                       # the EDGES (REST/RPC/cluster
                                       # forward/loadgen), never inside
                                       # the engine's own ingest — WAL
                                       # replay and replica apply must
                                       # never shed durable events
    tenant_rates: dict | None = None   # tenant -> admitted events/s
                                       # (token bucket); unlisted tenants
                                       # use qos_default_rate_eps
    qos_default_rate_eps: float = 0.0  # rate for unlisted tenants
                                       # (0 = no per-tenant rate cap)
    qos_burst_s: float = 2.0           # token-bucket depth, in seconds
                                       # of the tenant's rate
    tenant_weights: dict | None = None # weighted-fair-queuing weights for
                                       # arena-turn + query-round sharing
                                       # (default: equal, 1.0 each)
    shed_threshold: int = 0            # staged-row backlog at which every
                                       # tenant sheds "saturated" (0 =
                                       # auto: 4 * batch_capacity *
                                       # scan_chunk); the SLO autotuner
                                       # steers this knob
    qos_min_retry_after_s: float = 0.05  # Retry-After floor on sheds
    arena_stall_timeout_s: float | None = None  # bound ArenaPool.acquire:
                                       # a wedged in-flight dispatch
                                       # raises ArenaStallError (-> shed
                                       # + counter) instead of hanging
                                       # the ingest thread silently
    slo_p99_target_ms: float | None = None  # autotuner SLO objective:
                                       # steer workers/depth/chunk + the
                                       # shed threshold toward this
                                       # per-tenant ingest-e2e p99 target
                                       # instead of raw throughput
    rule_groups: int = 1024            # streaming-rules CEP tier (ISSUE
                                       # 13, rules/): group slots (device/
                                       # area/tenant ids) each rule and
                                       # rollup tracks on device; ids
                                       # beyond this count as out-of-band
                                       # (visible in rule counters)
    rollup_buckets: int = 32           # tumbling-window ring depth per
                                       # (rollup, group) — how much
                                       # materialized history a rollup
                                       # serves before windows recycle
    rule_pending: int = 4              # pending-fire ring depth per
                                       # (rule, group): fires surviving
                                       # between harvest polls (overflow
                                       # drops oldest, counted)
    devicewatch: bool = True           # device-plane telemetry (ISSUE
                                       # 11, utils/devicewatch.py): XLA
                                       # compile/retrace watchdog over
                                       # every program family, memory
                                       # ledger, per-program cost —
                                       # bench hard-gates the on-vs-off
                                       # delta <= 3% and zero excess
                                       # retraces across the smoke run
    conservation: bool = True          # event conservation ledger
                                       # (ISSUE 14, utils/conservation):
                                       # per-stage flow counters the
                                       # audit plane balances against
                                       # the device counters; cost is
                                       # one dict add per batch + one
                                       # np.sum per dispatch — bench
                                       # hard-gates the delta <= 3%


@dataclasses.dataclass
class DeviceInfo:
    """Host-side device metadata (strings); hot columns live on device."""

    token: str
    device_type: str
    tenant: str
    area: str | None = None
    customer: str | None = None
    metadata: dict[str, Any] = dataclasses.field(default_factory=dict)
    auto_registered: bool = False


def local_device_info(engine, device_id: int, default=None):
    """DeviceInfo for a rank-LOCAL device id — the lookup for records this
    engine produced itself (feed records, analytics tables, dead letters).
    Device ids are rank-scoped, so on a cluster facade this must read the
    local rank's mirror, never fan out (the same integer names a different
    device on every rank)."""
    return getattr(engine, "local", engine).devices.get(device_id, default)


class _FairChunk:
    """A run of staged rows for one tenant awaiting fair batch formation.
    ``pos`` advances as formation slices rows out; arrays are never copied
    after enqueue."""

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


@dataclasses.dataclass
class AssignmentInfo:
    """Host-side assignment metadata (reference: device assignments managed by
    RdbDeviceManagement + the Assignments REST controller); the hot columns
    (status/device/asset/area/customer) also live on-device for expansion."""

    token: str
    id: int
    device_token: str
    tenant: str
    status: str = "ACTIVE"                 # DeviceAssignmentStatus name
    asset: str | None = None
    area: str | None = None
    customer: str | None = None
    metadata: dict[str, Any] = dataclasses.field(default_factory=dict)
    created_ms: int = 0
    released_ms: int | None = None


@jax.jit
def _admin_create_device(state: PipelineState, token_id, device_id, assignment_id,
                         type_id, tenant_id, area_id, customer_id):
    """Write one device + ACTIVE assignment row (API-path creation)."""
    reg = state.registry
    reg = dataclasses.replace(
        reg,
        token_to_device=reg.token_to_device.at[token_id].set(device_id),
        device_active=reg.device_active.at[device_id].set(True),
        device_type=reg.device_type.at[device_id].set(type_id),
        device_tenant=reg.device_tenant.at[device_id].set(tenant_id),
        device_area=reg.device_area.at[device_id].set(area_id),
        device_customer=reg.device_customer.at[device_id].set(customer_id),
        device_assignments=reg.device_assignments.at[device_id, 0].set(assignment_id),
        assignment_active=reg.assignment_active.at[assignment_id].set(True),
        assignment_status=reg.assignment_status.at[assignment_id].set(
            jnp.int32(DeviceAssignmentStatus.ACTIVE)
        ),
        assignment_device=reg.assignment_device.at[assignment_id].set(device_id),
        assignment_area=reg.assignment_area.at[assignment_id].set(area_id),
        assignment_customer=reg.assignment_customer.at[assignment_id].set(customer_id),
    )
    return dataclasses.replace(
        state,
        registry=reg,
        next_device=jnp.maximum(state.next_device, device_id + 1),
        next_assignment=jnp.maximum(state.next_assignment, assignment_id + 1),
    )


@jax.jit
def _admin_set_device_active(state: PipelineState, device_id, active):
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg, device_active=reg.device_active.at[device_id].set(active)
        )
    )


def tenant_cap(n_tenants: int) -> int:
    """Static power-of-two tenant bucket for the segment-sum — one
    formula for every engine flavor so their per-tenant series agree."""
    return max(64, 1 << max(0, n_tenants - 1).bit_length())


def format_tenant_counter_grid(grid, tenants) -> dict[str, dict[str, int]]:
    """[T_BUCKETS, C] device counter grid -> {tenant: {lane: n}} (quiet
    buckets omitted; buckets past the named-tenant range label as
    ``bucketN``) — the ONE formatting rule behind Engine and
    DistributedEngine ``tenant_pipeline_counters`` and therefore the
    Prometheus ``swtpu_pipeline_*`` series shape."""
    from sitewhere_tpu.pipeline import (TENANT_COUNTER_BUCKETS,
                                        TENANT_COUNTER_LANES)

    names = {tid % TENANT_COUNTER_BUCKETS: tenants.token(tid)
             for tid in range(min(len(tenants), TENANT_COUNTER_BUCKETS))}
    return {
        names.get(b, f"bucket{b}"): {
            lane: int(grid[b, i])
            for i, lane in enumerate(TENANT_COUNTER_LANES)}
        for b in range(grid.shape[0]) if grid[b].any()
    }


def tenant_counts_dict(counts, tenants, n_tenants: int) -> dict:
    """[t_cap, E] count grid -> {tenant: {EventType: n}} (quiet tenants
    skipped) — shared by Engine and DistributedEngine tenant_metrics."""
    out: dict[str, dict[str, int]] = {}
    for tid in range(min(n_tenants, counts.shape[0])):
        if not counts[tid].any():
            continue
        out[tenants.token(tid)] = {
            EventType(e).name: int(counts[tid, e])
            for e in range(counts.shape[1])
        }
    return out


@functools.partial(jax.jit, static_argnames=("t_cap",))
def _tenant_event_counts(state: PipelineState, t_cap: int):
    """Segment-sum per-device event counters by tenant: [t_cap, E].
    ``t_cap`` is static (power-of-two bucket) so the program cache stays
    small as tenants grow; the reduction is a one-hot matmul (MXU-friendly,
    no scatter)."""
    reg = state.registry
    counts = state.device_state.event_counts              # [N, E]
    tenant = jnp.where(reg.device_active, reg.device_tenant, -1)
    t_ids = jnp.arange(t_cap)
    onehot = (tenant[:, None] == t_ids[None, :]).astype(jnp.int32)  # [N, T]
    return jnp.einsum("nt,ne->te", onehot, counts)


@jax.jit
def _admin_set_parent(state: PipelineState, device_id, parent_id):
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg, device_parent=reg.device_parent.at[device_id].set(parent_id)
        )
    )


@jax.jit
def _admin_update_device(state: PipelineState, device_id, type_id, area_id,
                         customer_id):
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg,
            device_type=reg.device_type.at[device_id].set(type_id),
            device_area=reg.device_area.at[device_id].set(area_id),
            device_customer=reg.device_customer.at[device_id].set(customer_id),
        )
    )


@jax.jit
def _admin_add_assignment(state: PipelineState, device_id, assignment_id, slot,
                          asset_id, area_id, customer_id):
    """Attach an additional ACTIVE assignment to a device slot (the
    RdbDeviceManagement.createDeviceAssignment analog; slots feed the
    per-assignment event expansion of DeviceAssignmentsLookupMapper)."""
    reg = state.registry
    reg = dataclasses.replace(
        reg,
        device_assignments=reg.device_assignments.at[device_id, slot].set(assignment_id),
        assignment_active=reg.assignment_active.at[assignment_id].set(True),
        assignment_status=reg.assignment_status.at[assignment_id].set(
            jnp.int32(DeviceAssignmentStatus.ACTIVE)
        ),
        assignment_device=reg.assignment_device.at[assignment_id].set(device_id),
        assignment_asset=reg.assignment_asset.at[assignment_id].set(asset_id),
        assignment_area=reg.assignment_area.at[assignment_id].set(area_id),
        assignment_customer=reg.assignment_customer.at[assignment_id].set(customer_id),
    )
    return dataclasses.replace(
        state, registry=reg,
        next_assignment=jnp.maximum(state.next_assignment, assignment_id + 1),
    )


@jax.jit
def _admin_update_assignment(state: PipelineState, assignment_id, asset_id,
                             area_id, customer_id):
    """Update the hot assignment columns (REST PUT path; reference:
    RdbDeviceManagement.updateDeviceAssignment via Assignments.java:144)."""
    reg = state.registry
    return dataclasses.replace(
        state, registry=dataclasses.replace(
            reg,
            assignment_asset=reg.assignment_asset.at[assignment_id].set(asset_id),
            assignment_area=reg.assignment_area.at[assignment_id].set(area_id),
            assignment_customer=reg.assignment_customer.at[assignment_id].set(customer_id),
        )
    )


@jax.jit
def _admin_set_assignment_status(state: PipelineState, assignment_id, status, active):
    """Update assignment status; when deactivated (release), also detach it
    from its device's slot row so event expansion stops targeting it."""
    reg = state.registry
    did = reg.assignment_device[assignment_id]
    row = reg.device_assignments[did]
    new_row = jnp.where((row == assignment_id) & ~active, jnp.int32(NULL_ID), row)
    reg = dataclasses.replace(
        reg,
        assignment_status=reg.assignment_status.at[assignment_id].set(status),
        assignment_active=reg.assignment_active.at[assignment_id].set(active),
        device_assignments=reg.device_assignments.at[did].set(new_row),
    )
    return dataclasses.replace(state, registry=reg)


def _watch_admin_jits() -> None:
    """Put every module-level admin updater under the devicewatch
    ``admin`` family (ISSUE 11): compiles counted/timed, no budget —
    these are shared by every engine in the process, so distinct engine
    shapes are legitimate distinct programs."""
    from sitewhere_tpu.utils.devicewatch import watched_jit

    g = globals()
    for name in ("_admin_create_device", "_admin_set_device_active",
                 "_admin_set_parent", "_admin_update_device",
                 "_admin_add_assignment", "_admin_update_assignment",
                 "_admin_set_assignment_status"):
        g[name] = watched_jit(g[name], family="admin")
    g["_tenant_event_counts"] = watched_jit(
        g["_tenant_event_counts"], family="admin",
        static_argnames=("t_cap",))


_watch_admin_jits()


def _fetch_query_result(tree):
    """Materialize a launched query program's outputs on the host. A
    module-level seam (not inlined at the call site) so tests can pin
    that the wait + readback happen WITHOUT the engine lock held."""
    return jax.device_get(tree)


class QueryBatcher:
    """Shared-scan micro-batcher for ``Engine.query_events``.

    Concurrent queries coalesce continuous-batching style (Orca): the
    first submitter becomes the leader and drains the queue in rounds;
    queries arriving while a round executes form the next round. Each
    round groups entries by their power-of-two ``limit`` bucket and runs
    ONE fused multi-predicate program per group (ops/query.
    query_store_batch) — Q queries share a single pass over the ring.

    Lock discipline: the leader takes the ENGINE lock only to snapshot
    ``state.store`` and enqueue the (async) device programs — the state is
    donated through every ingest step, so the program must capture the
    buffers before a later dispatch can recycle them. The device wait,
    result readback, and all host-side formatting happen outside the
    lock, so reads no longer block ingest dispatch or each other.
    Snapshot semantics: a query sees every row its caller's mirror sync
    dispatched, plus whatever concurrent ingest dispatched before the
    snapshot — one consistent store version, which may trail in-flight
    dispatches by at most ``dispatch_depth`` batches."""

    def __init__(self, engine, max_batch: int = 16):
        from sitewhere_tpu.utils.metrics import query_metrics

        self.engine = engine
        self.max_batch = max(1, int(max_batch))
        self._mu = threading.Lock()
        self._queue: list[dict] = []
        self._running = False
        self._wfq = None         # weighted-fair round membership (QoS):
                                 # attach_wfq installs a WFQPicker so an
                                 # overflowing round's slots follow
                                 # tenant weights, not arrival order
        self.programs = 0        # device programs launched
        self.coalesced = 0       # queries served through them
        self.max_coalesced = 0   # largest micro-batch observed
        self._metrics = query_metrics()
        # AOT-compiled executables per (Q bucket, limit bucket): compiling
        # from ShapeDtypeStructs needs no live buffers, so first-shape
        # compilation happens OUTSIDE the engine lock — a cold query must
        # not stall ingest dispatch for a compile. Store shapes are fixed
        # for the engine's lifetime (PipelineState.create).
        self._programs: dict[tuple[int, int], Any] = {}
        self._store_struct = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            engine.state.store)

    def _compiled_for(self, qpad: int, limit: int):
        from sitewhere_tpu.ops.query import QueryParams, query_store_batch

        key = (qpad, limit)
        fn = self._programs.get(key)
        if fn is None:
            pstruct = QueryParams(*(
                jax.ShapeDtypeStruct((qpad,), jnp.int32)
                for _ in QueryParams._fields))
            t0 = time.perf_counter()
            fn = query_store_batch.lower(self._store_struct, pstruct,
                                         limit=limit).compile()
            dt = time.perf_counter() - t0
            self._programs[key] = fn
            # devicewatch (ISSUE 11): exact AOT compile seconds + cost;
            # budget = one program per (Q bucket, limit bucket), the
            # shape invariant clamp_page_size/bucket_limit exist to hold
            watch = getattr(self.engine, "devicewatch", None)
            if watch is not None:
                watch.record_aot("query.batch", key=key, bucket=key,
                                 seconds=dt, compiled=fn)
        return fn

    def attach_wfq(self, weights: dict | None) -> None:
        """Enable weighted-fair round membership (ISSUE 9): when more
        queries are queued than one round holds, slots are granted in
        per-tenant virtual-time order instead of first-come."""
        from sitewhere_tpu.utils.qos import WFQPicker

        self._wfq = WFQPicker(weights)

    def observe_latency(self, seconds: float) -> None:
        self._metrics["latency"].observe(seconds)
        self._metrics["queries"].inc()

    def run(self, params: tuple, limit: int, archive: dict | None = None,
            tenant: str | None = None, trace_id: str | None = None):
        """Submit one predicate set (``ops.query.QueryParams`` field order,
        plain ints) at a bucketed ``limit``. ``archive`` — ``{"limit":
        exact_page, "filters": {...}}`` — asks the round to ALSO scan the
        retention tier for this query: the leader runs one shared
        planning/decode pass for every archive request it coalesced (one
        eviction-cap computation, planner tables reused, segment decodes
        shared through the archive's LRU cache) instead of each query
        re-scanning the disk tier behind the engine lock. Returns ``(row,
        cursors, q, archive_result)``: the query's numpy ``QueryResult``
        row, the snapshot's archive cursor capture (``(epoch, cursor,
        arena_capacity)`` or None), the micro-batch size it rode in, and
        the ``(total, rows)`` archive page (None when the tier is absent,
        empty, or fully covered by the ring)."""
        entry = {"params": params, "limit": int(limit),
                 "event": threading.Event(), "result": None,
                 "cursors": None, "q": 0, "error": None,
                 "archive": archive, "archive_result": None,
                 "tenant": tenant or "default", "trace": trace_id}
        if self.engine.lock._is_owned():
            # a caller already INSIDE the engine lock (RLock re-entrancy
            # was always legal on this path) must not park as a follower:
            # the leader would block acquiring the lock this thread holds.
            # Run its own single-query round re-entrantly instead.
            self._execute([entry])
            return (entry["result"], entry["cursors"], entry["q"],
                    entry["archive_result"])
        with self._mu:
            self._queue.append(entry)
            lead = not self._running
            if lead:
                self._running = True
        if lead:
            self._drain()
        else:
            wait_sp = self.engine.tracer.begin(
                "query.coalesce_wait", trace_id=trace_id)
            entry["event"].wait()
            wait_sp.end(q=entry["q"])
        if entry["error"] is not None:
            raise entry["error"]
        return (entry["result"], entry["cursors"], entry["q"],
                entry["archive_result"])

    def _drain(self) -> None:
        """Leader loop: execute rounds until the queue is empty. The empty
        check and the ``_running`` handoff are atomic, so a submitter that
        saw ``_running`` either lands in a round this leader takes or
        becomes the next leader itself — no entry can strand."""
        while True:
            with self._mu:
                if (self._wfq is not None
                        and len(self._queue) > self.max_batch):
                    # overflow round under QoS: membership follows
                    # tenant weights (virtual-time order, FIFO within a
                    # tenant) — a one-tenant read flood can no longer
                    # push every other tenant's queries behind its
                    # entire backlog
                    batch, self._queue = self._wfq.pick(
                        self._queue, self.max_batch)
                else:
                    batch = self._queue[: self.max_batch]
                    del self._queue[: len(batch)]
                if not batch:
                    self._running = False
                    return
            try:
                self._execute(batch)
            except Exception as e:   # fail every entry of the round loudly
                for entry in batch:
                    if not entry["event"].is_set():
                        entry["error"] = e
                        entry["event"].set()

    def _execute(self, batch: list[dict]) -> None:
        from sitewhere_tpu.ops.query import QueryParams, bucket_limit

        eng = self.engine
        groups: dict[int, list[dict]] = {}
        for entry in batch:
            groups.setdefault(entry["limit"], []).append(entry)
        # per group: pad Q to a power of two (repeating the last
        # predicate) so program shapes stay bounded — one compile per
        # (Q bucket, limit bucket), not per concurrency level — and
        # resolve/compile the executable BEFORE taking the engine lock
        staged = []
        for limit, entries in groups.items():
            qn = len(entries)
            qpad = bucket_limit(qn)
            cols = []
            for j in range(len(QueryParams._fields)):
                col = [e["params"][j] for e in entries]
                col.extend(col[-1:] * (qpad - qn))
                cols.append(jnp.asarray(np.asarray(col, np.int32)))
            staged.append((entries, self._compiled_for(qpad, limit),
                           QueryParams(*cols)))
        # round-level spans attribute to the round leader's first entry
        # trace (the round is one shared unit of work); per-query device
        # and format intervals live on each query's own flight record
        round_trace = next((e["trace"] for e in batch if e["trace"]), None)
        launched = []
        # span context managers (not bare begin/end): a device or archive
        # error in this round is caught by _drain and the round keeps
        # serving — an unclosed span would stay on the leader thread's
        # span stack and mis-parent every later span on that thread
        with eng.tracer.begin("query.round.snapshot",
                              trace_id=round_trace, q=len(batch)) as snap_sp:
            with eng.lock:
                store = eng.state.store
                cursors = None
                if eng.archive is not None:
                    # fresh buffers (eager add): the snapshot's own arrays
                    # are donated away by the next ingest dispatch, so the
                    # archive merge must not touch them after the lock is
                    # released
                    cursors = (store.epoch + 0, store.cursor + 0,
                               store.arena_capacity)
                for entries, compiled, params in staged:
                    # async enqueue only — the device executes (and is
                    # awaited) after the lock is released
                    res = compiled(store, params)
                    launched.append((entries, res))
                    qn = len(entries)
                    self.programs += 1
                    self.coalesced += qn
                    self.max_coalesced = max(self.max_coalesced, qn)
                    self._metrics["batch"].observe(float(qn))
                    self._metrics["programs"].inc()
            snap_sp.annotate(programs=len(launched))
        # batched tiered reads: while the fused ring programs execute on
        # device, the leader serves every archive request of the round in
        # ONE pass — the eviction cap is computed once from the round's
        # shared snapshot cursors, ONE SegmentPlanner call plans every
        # request against the shared zone-map/bloom tables
        # (EventArchive.query_batch; planner calls per round == 1, pinned
        # by test + exported as swtpu_archive_planner_calls_total), and
        # each surviving segment decodes at most once into the archive's
        # LRU cache no matter how many queries touch it. The engine lock
        # is held for the disk scan (archive files are mutated by
        # _spool/compact under it), exactly like the per-query merge it
        # replaces — but once per round instead of once per query.
        archive_entries = [e for e in batch if e["archive"] is not None]
        if archive_entries and eng.archive is not None and cursors is not None:
            with eng.lock:
                if eng.archive.segments:
                    ep, cu, acap = cursors
                    ep, cu = np.asarray(ep), np.asarray(cu)
                    max_pos = {a: int(ep[a]) * acap + int(cu[a]) - acap
                               for a in range(len(cu))}
                    if any(v > 0 for v in max_pos.values()):
                        with eng.tracer.begin(
                                "query.round.archive",
                                trace_id=round_trace,
                                queries=len(archive_entries)) as arch_sp:
                            decoded0 = eng.archive.plan_decoded
                            results = eng.archive.query_batch(
                                [e["archive"] for e in archive_entries],
                                max_pos=max_pos)
                            for e, res in zip(archive_entries, results):
                                e["archive_result"] = res
                            arch_sp.annotate(
                                segments_decoded=eng.archive.plan_decoded
                                - decoded0)
        with eng.tracer.begin("query.round.fetch", trace_id=round_trace):
            for entries, res in launched:
                self._unpack_round(entries, res, cursors)

    def _unpack_round(self, entries: list[dict], res, cursors) -> None:
        """Fetch one launched program's result and hand each entry its
        per-query row. Overridden by the SPMD batcher, whose program
        returns per-SHARD pages that merge on the host before rows are
        handed out."""
        host = _fetch_query_result(res)
        for q, entry in enumerate(entries):
            entry["result"] = type(host)(*(col[q] for col in host))
            entry["cursors"] = cursors
            entry["q"] = len(entries)
            entry["event"].set()


# rule/rollup PARAMETER columns (ops/rules.py table halves): a swap that
# keeps shapes AND static layout replaces exactly these and preserves
# the carried state (kind/scope/agg/op live in the static layout)
_RULE_PARAM_FIELDS = ("active", "etype", "tenant", "ch_a", "val_a",
                      "ch_b", "val_b", "window_ms")
_ROLLUP_PARAM_FIELDS = ("channel", "scope", "etype", "window_ms")


def _swap_sig(state: PipelineState) -> tuple:
    """Abstract signature of the SWAPPABLE state leaves (zones + rules —
    the only PipelineState subtrees whose shape can change at runtime).
    Two states with equal signatures dispatch through the same compiled
    program."""
    sub = (state.zones, state.rules)
    return (jax.tree_util.tree_structure(sub),
            tuple((leaf.shape, str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(sub)))


class _PrecompiledStep:
    """AOT-compiled dispatch program installed by a rule-set swap
    (compile-before-swap: the executable was built OFF the engine lock
    while the old program kept serving). Calls the executable while the
    engine's swap epoch matches the one it was installed under; a later
    declared shape change (zones install, rules clear) bumps the epoch
    and this shim falls back to the jit program, which compiles lazily
    under that change's own allowance. The epoch compare is one integer
    per dispatch — the hot path never walks the state pytree."""

    def __init__(self, compiled, jit_fn, family: str, sig: tuple):
        self.compiled = compiled
        self.jit_fn = jit_fn
        self.family = family
        self.sig = sig
        self._engine = None
        self._epoch = -1

    def bind(self, engine) -> "_PrecompiledStep":
        """Arm the shim against the engine's CURRENT swap epoch (called
        by set_rules at install time, after the swap bumped it)."""
        self._engine = engine
        self._epoch = engine._swap_epoch
        return self

    def __call__(self, state, batch):
        if (self._engine is not None
                and self._engine._swap_epoch == self._epoch):
            return self.compiled(state, batch)
        return self.jit_fn(state, batch)

    def lower(self, *args, **kwargs):
        return self.jit_fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.jit_fn, name)


class Engine(IngestHostMixin):
    """Single-node engine instance."""

    def __init__(self, config: EngineConfig | None = None):
        self.config = config or EngineConfig()
        c = self.config
        self.epoch = EpochBase()
        self.lock = threading.RLock()
        # host-side auxiliary counters merged into metrics() — e.g. the
        # DecodeWorkerPool's ambiguous-lane fallback count (VERDICT r3:
        # the exactness fallback must be visible, not just a log line)
        self.host_counters: dict[str, int] = {}
        # the native host data-plane (C++ decode + interning) is the default;
        # pure-Python fallback when no compiler is available
        self._native_decoder = None
        if c.use_native:
            try:
                from sitewhere_tpu.ingest.fast_decode import NativeBatchDecoder
                from sitewhere_tpu.native.binding import NativeInterner

                self.tokens = NativeInterner(c.token_capacity)
                self._native_decoder = NativeBatchDecoder(self.tokens, c.channels)
            except (RuntimeError, OSError):
                self._native_decoder = None
        if self._native_decoder is not None:
            self.channel_map = ChannelMap(c.channels, self._native_decoder.names,
                                          strict=c.strict_channels)
            self.alert_types = self._native_decoder.alert_types
        else:
            self.tokens = TokenInterner(c.token_capacity)
            self.channel_map = ChannelMap(c.channels, strict=c.strict_channels)
            self.alert_types = TokenInterner(1 << 20)
        self.tenants = TokenInterner(1 << 16)
        self.tenants.intern("default")
        self.device_types = TokenInterner(1 << 16)
        self.device_types.intern(c.default_device_type)
        self.areas = TokenInterner(1 << 16)
        self.customers = TokenInterner(1 << 16)
        # alternate/correlation ids (the aux1 lane). With a native
        # decoder the engine ADOPTS the decoder's event-id interner so
        # the batch decode path and the per-request process() path hand
        # out the same ids (alternate-id queries and the device-side
        # dedup counter agree across paths).
        self.event_ids = (self._native_decoder.event_ids
                          if self._native_decoder is not None
                          else TokenInterner(1 << 22))

        self.state = PipelineState.create(
            c.device_capacity, c.token_capacity, c.assignment_capacity,
            c.store_capacity, c.channels,
            analytics_devices=c.analytics_devices,
            analytics_window=c.analytics_window,
            store_arenas=c.tenant_arenas,
        )
        # device-plane watchdog (ISSUE 11): every program family this
        # engine dispatches goes through a passthrough shape-key watch —
        # compiles timed, retrace budgets enforced (one program per
        # family per engine; legitimate transitions grant allowance).
        # Created BEFORE the steps so the arena rebuild path can re-wrap.
        from sitewhere_tpu.utils.devicewatch import EngineWatch

        self.devicewatch = EngineWatch(enabled=c.devicewatch)
        self._backlog_hwm = 0   # staged-row high-watermark (reset on
                                # scrape via take_backlog_hwm)
        self._step = self.devicewatch.wrap(make_pipeline_step(
            PipelineConfig(auto_register=c.auto_register, default_device_type=0)
        ), FAMILY_STEP, cost=True)
        self._scan_step = self.devicewatch.wrap(make_packed_scan_step(
            PipelineConfig(auto_register=c.auto_register, default_device_type=0),
            c.batch_capacity, c.channels,
        ), FAMILY_PACKED_SCAN, cost=True)
        self._staged_batches: list = []   # emitted host batches awaiting a
                                          # scan-chunk dispatch
        self._sweep = self.devicewatch.wrap(make_presence_sweep(),
                                            FAMILY_SWEEP)
        self._buf = HostEventBuffer(c.batch_capacity, c.channels)
        # zero-copy arena ingest (native batch decode only): the scanner
        # writes straight into pooled SoA staging buffers that the jit
        # step transfers without any intermediate copy. At scan_chunk==1
        # an arena batch has the SAME shape as a legacy staged batch, so
        # both paths share ONE compiled program; scan_chunk>1 consumes a
        # whole K-lane arena with make_arena_scan_step.
        self._arena_pool = None
        self._arena_fill = None
        self._arena_step = None
        self._arena_committing = False
        self._arena_dispatches = 0
        if (self._native_decoder is not None and c.ingest_arenas >= 0
                and self._native_decoder.has_arena):
            self._build_arena_machinery(max(1, c.scan_chunk))
        # sharded multi-core decode: wire batches split across N threads
        # into disjoint rows of the fill arena, byte-identical to the
        # single-threaded path (tests/test_shard_decode.py). Degrades to
        # the plain decoder on 1 core / missing native entry points.
        self._sharder = None
        if self._arena_pool is not None:
            import os as _os

            n_workers = c.ingest_workers or (_os.cpu_count() or 1)
            if n_workers > 1 and self._native_decoder.has_shard:
                from sitewhere_tpu.ingest.workers import ShardedArenaDecoder

                self._sharder = ShardedArenaDecoder(self._native_decoder,
                                                    n_workers)
        self._last_flush = time.monotonic()
        # host mirrors
        self.devices: dict[int, DeviceInfo] = {}      # device_id -> info
        self.token_device: dict[int, int] = {}        # token_id -> device_id
        self.assignments: dict[int, AssignmentInfo] = {}   # assignment_id -> info
        self.assignment_tokens: dict[str, int] = {}        # token -> assignment_id
        self.device_slots: dict[int, list[int]] = {}       # device_id -> slot row
        self.assets = TokenInterner(1 << 16)
        self._next_device = 0
        self._next_assignment = 0
        self.dead_letters: list[int] = []             # unregistered token ids
        self.outputs: list[dict] = []                 # recent step summaries
        self._pending_outs: list[StepOutput] = []     # un-absorbed step outputs
        self._fair_queues: dict[int, list] = {}       # tenant_id -> staged rows
        self._fair_queued = 0
        # flight recorder: one lifecycle record per ingest batch
        # (utils/flight.py); _staged_traces holds records whose rows sit
        # in the copy-staging buffer awaiting dispatch, _pending_traces
        # parallels _pending_outs for readback stamping in drain()
        from sitewhere_tpu.utils.flight import FlightRecorder

        self.flight = FlightRecorder(capacity=c.flight_capacity,
                                     enabled=c.flight_recorder)
        self._staged_traces: list = []
        self._pending_traces: list[list] = []
        # hierarchical span tracer (ISSUE 10): live spans for the
        # operations flight records don't time (shard decode, query
        # rounds, forward hops, replication legs); a cluster facade
        # re-stamps .rank like it does for the flight recorder
        from sitewhere_tpu.utils.metrics import next_engine_label
        from sitewhere_tpu.utils.tracing import SpanTracer

        self.tracer = SpanTracer(capacity=c.span_capacity,
                                 enabled=c.span_trace,
                                 sample=c.span_sample, seed=c.span_seed)
        if self._sharder is not None:
            self._sharder.tracer = self.tracer
        # process-unique engine label scoping this engine's series on the
        # process-global registry (the SLO harvest writes under it, so
        # one in-process engine's autotuner can never steer on another's
        # tenants — ISSUE 10 satellite closing the PR-9 known limit)
        self.metrics_label = next_engine_label()
        # event conservation ledger (ISSUE 14): flow counters at the
        # staging and dispatch boundaries; everything else the audit
        # plane samples from counters that already exist. The auditor
        # (utils/conservation.ConservationAuditor) attaches itself here.
        from sitewhere_tpu.utils.conservation import FlowLedger

        self.ledger = FlowLedger(enabled=c.conservation)
        self.conservation_auditor = None
        # shared-scan batched query engine: concurrent query_events calls
        # coalesce into one fused multi-predicate device program; string
        # lookups and the store snapshot happen under the lock, the device
        # wait and row formatting outside it
        self._query_batcher = QueryBatcher(self, max_batch=c.query_coalesce)
        # durability: accepted payloads append to the WAL BEFORE staging,
        # tagged by wire format so recovery replays each through the right
        # decoder (utils/checkpoint.recover_engine)
        self.wal = None
        self._wal_local = threading.local()   # re-entrancy guard per thread
        self._wal_last_seq = 0   # newest append ticket; dispatch gates on it
        if c.wal_dir:
            from sitewhere_tpu.utils.ingestlog import IngestLog

            self.wal = IngestLog(c.wal_dir,
                                 group_commit=c.wal_group_commit,
                                 group_window_s=c.wal_group_window_s)
        # long-term retention tier: rows spill to disk before the ring can
        # overwrite them (the external-DB history of the reference)
        self.archive = None
        self._rows_since_spool = 0
        if c.archive_dir:
            from sitewhere_tpu.utils.archive import (EventArchive,
                                                     single_topology)

            acap = c.store_capacity // c.tenant_arenas
            self.archive = EventArchive(
                c.archive_dir,
                segment_rows=max(1, min(c.archive_segment_rows, acap // 4)),
                max_rows_per_part=c.archive_max_rows,
                topology=single_topology(c.tenant_arenas),
                max_age_ms=c.archive_max_age_ms,
                cache_segments=c.archive_cache_segments,
                compress=c.archive_compress)
            # spool whenever any arena could be halfway to overwrite; with
            # the worst case of every staged row landing in one arena this
            # keeps backlog + one batch < arena capacity
            self._spool_trigger = max(self.archive.segment_rows,
                                      acap // 2 - c.batch_capacity)
            # one scan-chunk dispatch advances the head by up to
            # K*batch*MAX_ACTIVE rows before the next spool check runs; if
            # that exceeds the arena's headroom no trigger can guarantee
            # loss-free spill (losses are still COUNTED via note_lost)
            worst = (max(1, c.scan_chunk) * c.batch_capacity
                     * MAX_ACTIVE_ASSIGNMENTS)
            if worst > acap - self.archive.segment_rows:
                logging.getLogger(__name__).warning(
                    "archive: one dispatch can write %d rows but arena "
                    "capacity is %d — ring may wrap before spooling; "
                    "raise store_capacity or lower scan_chunk/batch_capacity",
                    worst, acap)
        # streaming-rules CEP tier (ISSUE 13): the harvest program is
        # built lazily per rules shape; a rule-set swap resets it.
        # _swap_epoch counts declared state-shape changes (zones + rules
        # swaps); the precompiled-step shim compares it per dispatch
        self._rules_harvest_fn = None
        self._swap_epoch = 0
        # stage-time autotuner (opt-in): adapts dispatch_depth / decode
        # fan-out (and optionally scan_chunk) toward the flight
        # recorder's measured bottleneck, one knob per evaluation
        self._autotuner = None
        if c.autotune:
            from sitewhere_tpu.utils.autotune import StageTimeAutotuner

            self._autotuner = StageTimeAutotuner(
                self, interval=c.autotune_interval,
                adapt_scan_chunk=c.autotune_scan_chunk)
        # overload discipline (ISSUE 9): per-tenant token-bucket admission
        # (consulted by the REST/RPC/cluster/loadgen EDGES — never by the
        # engine's own ingest, so WAL replay and replica apply can never
        # shed durable events) + weighted-fair scheduling of the ingest
        # critical section and query-round membership
        self._stall_sheds = 0     # arena-stall sheds (plain attribute:
                                  # NOT a metrics() key — dispatch-shape
                                  # equality; mirrored in swtpu_qos_*)
        if c.qos:
            from sitewhere_tpu.utils.qos import (AdmissionController,
                                                 WeightedFairGate)

            self.qos = AdmissionController(
                tenant_rates=c.tenant_rates,
                default_rate_eps=c.qos_default_rate_eps,
                burst_s=c.qos_burst_s,
                shed_threshold=(c.shed_threshold
                                or 4 * c.batch_capacity
                                * max(1, c.scan_chunk)),
                backlog_fn=lambda: self.staged_count,
                min_retry_after_s=c.qos_min_retry_after_s)
            self._wfq_gate = WeightedFairGate(c.tenant_weights)
            self._query_batcher.attach_wfq(c.tenant_weights)
        # persistent-connection wire edges (ingest/wire_edge.py) register
        # here so the conservation ledger's "wire" stage and the
        # swtpu_wire_* scrape exporter can find them. Plain attribute —
        # deliberately NOT a metrics() key (dispatch-shape equality pin).
        self.wire_edges: list = []

    def _build_arena_machinery(self, k: int) -> None:
        """(Re)build the staging-arena pool and, for k > 1, the K-lane
        arena scan step — the ONE constructor shared by __init__ and
        runtime scan_chunk retuning, so the sizing heuristics can never
        diverge between a fresh and a retuned engine."""
        from sitewhere_tpu.ingest.arena import ArenaPool

        c = self.config
        n_arenas = c.ingest_arenas or max(1, c.dispatch_depth) + 2
        self._arena_pool = ArenaPool(
            n_arenas, c.batch_capacity * k, c.channels, lanes=k)
        self._arena_step = None
        if k > 1:
            from sitewhere_tpu.pipeline import (FAMILY_ARENA_SCAN,
                                                make_arena_scan_step)

            # fresh watch scope per rebuild: a scan-chunk retune is a
            # DECLARED program change, not shape churn
            self._arena_step = self.devicewatch.wrap(make_arena_scan_step(
                PipelineConfig(auto_register=c.auto_register,
                               default_device_type=0),
                c.batch_capacity, c.channels, k), FAMILY_ARENA_SCAN,
                cost=True)

    def set_ingest_tuning(self, *, scan_chunk: int | None = None,
                          dispatch_depth: int | None = None,
                          ingest_workers: int | None = None,
                          shed_threshold: int | None = None) -> dict:
        """Apply ingest-tuning knobs at runtime — the single choke point
        the autotuner (and operators, via REST/config reload) go through,
        because each knob invalidates different machinery:

          dispatch_depth   takes effect at the next dispatch, free
          ingest_workers   clamps the sharded-decode fan-out, free
          shed_threshold   moves the QoS saturation valve (no-op with
                           QoS off), free
          scan_chunk       REBUILDS the arena pool + scan step (drains
                           in-flight dispatches first; the new program
                           compiles on next dispatch)

        Returns the applied values."""
        with self.lock:
            c = self.config
            if dispatch_depth is not None:
                c.dispatch_depth = max(1, int(dispatch_depth))
            if ingest_workers is not None and self._sharder is not None:
                self._sharder.set_active_workers(ingest_workers)
            if shed_threshold is not None and self.qos is not None:
                c.shed_threshold = max(1, int(shed_threshold))
                self.qos.shed_threshold = c.shed_threshold
            if scan_chunk is not None:
                k = max(1, int(scan_chunk))
                if k != max(1, c.scan_chunk) and self._arena_pool is not None:
                    # quiesce: dispatch the fill arena and staged batches,
                    # then wait out in-flight programs so no arena of the
                    # old shape is still feeding a transfer
                    self._dispatch_arena()
                    self._dispatch_staged(all_batches=True)
                    self._arena_pool.drain()
                    self._build_arena_machinery(k)
                    c.scan_chunk = k
            applied = {"scan_chunk": c.scan_chunk,
                       "dispatch_depth": c.dispatch_depth,
                       "ingest_workers": (self._sharder.active_workers
                                          if self._sharder else 1)}
            if self.qos is not None:
                applied["shed_threshold"] = self.qos.shed_threshold
            return applied

    @property
    def staged_count(self) -> int:
        return (len(self._buf) + self._fair_queued
                + (self._arena_fill.cursor if self._arena_fill is not None
                   else 0)
                + sum(int(np.sum(b.valid)) for b in self._staged_batches))

    def take_backlog_hwm(self, reset: bool = True) -> int:
        """Max staged-row backlog observed since the last reset (ISSUE 11
        satellite). The Prometheus scrape resets it — each sample is
        "worst case this scrape window"; peeks (REST ledger, debug
        bundle) pass ``reset=False``."""
        hwm = max(self._backlog_hwm, self.staged_count)
        if reset:
            self._backlog_hwm = self.staged_count
        return hwm

    def _sync_mirrors(self) -> None:
        """Make host mirrors current: run any staged batch and absorb any
        pending async outputs. Caller holds the lock. The fill arena is
        NOT waited on mid-commit (a registration envelope's admin path
        re-enters here while the arena's valid mask is still being
        built — flush_async refuses to dispatch it, so waiting would
        spin forever); the committed rows dispatch when the commit
        finishes."""
        while (len(self._buf) or self._fair_queued
               or (self._arena_fill is not None and self._arena_fill.cursor
                   and not self._arena_committing)):
            self.flush_async()
        if self._staged_batches:
            self._dispatch_staged(all_batches=True)
        if self._pending_outs:
            self.drain()

    # ------------------------------------------------------------------ ingest
    def _stage_row(self, et, token_id, tenant_id, ts, now, values, mask,
                   aux0, aux1):
        """Stage one converted event row (called by the mixin's process());
        flushes when the batch fills. Caller holds the lock."""
        self.host_counters["staged_copy_rows"] = \
            self.host_counters.get("staged_copy_rows", 0) + 1
        self.ledger.add("staged_rows", 1)
        if self.config.fair_tenancy:
            i32 = np.int32
            has_vals = mask is not None and (mask.any() or values.any())
            self._fair_enqueue(tenant_id, _FairChunk(
                etype=np.array([et], i32),
                token=np.array([token_id], i32),
                ts=np.array([ts], i32),
                recv=np.array([now], i32),
                values=values[None].copy() if has_vals else None,
                vmask=mask[None].copy() if has_vals else None,
                aux0=np.array([aux0], i32),
                aux1=np.array([aux1], i32),
            ))
            return
        i = len(self._buf)
        if not self._buf.append(et, token_id, tenant_id, ts, now, (), aux0, aux1):
            self.flush_async()
            i = len(self._buf)
            self._buf.append(et, token_id, tenant_id, ts, now, (), aux0, aux1)
        if mask is not None and mask.any():
            self._buf.values[i, :] = values
            self._buf.vmask[i, :] = mask
        if self._buf.full:
            self.flush_async()

    def _fair_enqueue(self, tenant_id: int, chunk: "_FairChunk") -> None:
        """Queue a chunk of staged rows under its tenant (O(1) per chunk —
        the fast path enqueues a whole decode batch at once). Caller holds
        the lock."""
        import collections

        q = self._fair_queues.get(tenant_id)
        if q is None:
            q = self._fair_queues[tenant_id] = collections.deque()
        q.append(chunk)
        self._fair_queued += chunk.remaining
        if self._fair_queued >= self.config.batch_capacity:
            self.flush_async()

    def fair_backlog(self, tenant: str) -> int:
        """Rows queued but not yet batched for one tenant (fair mode)."""
        with self.lock:
            tid = self.tenants.lookup(tenant)
            return sum(c.remaining for c in self._fair_queues.get(tid, ()))

    def _form_fair_batch(self) -> None:
        """Quota-sliced batch formation across tenants — fairness in batch
        formation (SURVEY.md §7 'hard parts': a tenant's burst must not
        starve the others' latency). Each pass gives every tenant with
        backlog an equal share of the remaining room, copied as vectorized
        slices. Caller holds the lock."""
        b = self._buf
        while self._fair_queued and not b.full:
            active = [t for t, q in self._fair_queues.items() if q]
            if not active:
                break
            quota = max(1, (b.capacity - len(b)) // len(active))
            for tid in active:
                q = self._fair_queues[tid]
                take = quota
                while take > 0 and q and not b.full:
                    ch = q[0]
                    k = min(take, ch.remaining, b.capacity - len(b))
                    lo, hi, p = b._n, b._n + k, ch.pos
                    b.etype[lo:hi] = ch.etype[p:p + k]
                    b.token_id[lo:hi] = ch.token[p:p + k]
                    b.tenant_id[lo:hi] = tid
                    b.ts_ms[lo:hi] = ch.ts[p:p + k]
                    b.received_ms[lo:hi] = ch.recv[p:p + k]
                    if ch.values is not None:
                        b.values[lo:hi] = ch.values[p:p + k]
                        b.vmask[lo:hi] = ch.vmask[p:p + k]
                    b.aux[lo:hi, 0] = ch.aux0[p:p + k]
                    b.aux[lo:hi, 1] = ch.aux1[p:p + k]
                    b._n = hi
                    ch.pos += k
                    take -= k
                    self._fair_queued -= k
                    if ch.remaining == 0:
                        q.popleft()
        for tid in [t for t, q in self._fair_queues.items() if not q]:
            del self._fair_queues[tid]

    def ingest_json_batch(self, payloads: list[bytes],
                          tenant: str = "default",
                          traceparent: str | None = None) -> dict:
        """Fast path: decode a batch of JSON device-request payloads in one
        native call and stage them vectorized (no per-event Python). Returns
        a summary with decode failures (failed-decode DLQ analog) and the
        batch's flight-recorder ``trace_id``. Registration envelopes fall
        back to the per-request path (they carry string metadata the hot
        path doesn't extract)."""
        from sitewhere_tpu.ingest.decoders import JsonDeviceRequestDecoder

        return self._ingest_batch(
            payloads, tenant, WAL_JSON, JsonDeviceRequestDecoder(),
            self._native_decoder.decode if self._native_decoder else None,
            binary=False, traceparent=traceparent)

    def ingest_binary_batch(self, payloads: list[bytes],
                            tenant: str = "default",
                            traceparent: str | None = None) -> dict:
        """Fast path for the flat-binary wire format (the "protobuf" ingest
        slot): one native C call decodes the whole batch."""
        from sitewhere_tpu.ingest.decoders import BinaryEventDecoder

        return self._ingest_batch(
            payloads, tenant, WAL_BINARY, BinaryEventDecoder(),
            self._native_decoder.decode_binary if self._native_decoder
            else None, binary=True, traceparent=traceparent)

    # ------------------------------------------------------------ arena ingest
    def _acquire_arena(self, tenant: str, n_remaining: int):
        """Pool acquire bounded by ``arena_stall_timeout_s``: a wedged
        in-flight dispatch raises a typed stall instead of hanging the
        ingest thread under the engine lock forever; the stall translates
        to an explicit shed (counted in ``swtpu_qos_shed_total`` with
        reason="stall" when QoS is on) that the edges surface as
        429/Retry-After. Chunks of the batch staged BEFORE the stall are
        already WAL-durable and dispatch normally."""
        from sitewhere_tpu.ingest.arena import ArenaStallError

        try:
            return self._arena_pool.acquire(
                timeout_s=self.config.arena_stall_timeout_s)
        except ArenaStallError as e:
            self._stall_sheds += 1
            if self.qos is not None:
                self.qos.note_shed(tenant, n_remaining, "stall")
            from sitewhere_tpu.utils.qos import ShedError

            raise ShedError(
                f"ingest shed: {e}", tenant=tenant,
                retry_after_s=max(
                    1.0, self.config.arena_stall_timeout_s or 1.0),
                reason="stall") from e

    def _ingest_batch_arena(self, payloads, tenant, tag, reg_decoder,
                            binary: bool) -> dict:
        """Zero-copy batch ingest: the native scanner decodes straight
        into the fill arena at its cursor, the commit pass runs a few
        vectorized in-place transforms, and full arenas dispatch without
        any staging copy. WAL-before-stage ordering is preserved: the
        group append (one write + one flush per chunk) lands before any
        row of the chunk can dispatch."""
        summary = {"decoded": 0, "failed": 0, "staged": 0}
        n = len(payloads)
        rec = self.flight.current()
        rec.add("path", "arena")
        with self.lock:
            now = self.epoch.now_ms()
            base_ms = int(self.epoch.base_unix_s * 1000)
            pos = 0
            while pos < n:
                arena = self._arena_fill
                if arena is None:
                    arena = self._arena_fill = \
                        self._acquire_arena(tenant, n - pos)
                take = min(n - pos, arena.room)
                chunk = (payloads if take == n
                         else payloads[pos:pos + take])
                lo = arena.cursor
                dec = self._sharder or self._native_decoder
                if dec is self._sharder:
                    # per-shard decode spans (ISSUE 10) attribute to this
                    # batch's trace; the engine lock serializes arena
                    # decode, so a plain attribute is race-free
                    dec.current_trace = rec.trace_id
                with stage("ingest.decode", mark="decode",
                           rows=take) as sp:
                    n_ok, collisions = dec.decode_into(
                        chunk, arena, lo, binary=binary)
                    sp.set_metadata(failed=take - n_ok)
                rec.mark("arena_fill")
                if self._sharder is not None:
                    rec.add("ingest_workers", self._sharder.last_workers)
                self._wal_append(tag, chunk, tenant)
                with stage("ingest.commit", mark="commit") as sp:
                    staged0 = summary["staged"]
                    self._arena_commit(arena, lo, take, chunk, tenant,
                                       reg_decoder, now, base_ms, summary)
                    sp.set_metadata(staged=summary["staged"] - staged0)
                if rec.trace_id is not None:
                    arena.traces.append(rec)
                self.channel_map.collisions += collisions
                arena.cursor = lo + take
                if arena.room == 0:
                    self._dispatch_arena()
                pos += take
        return summary

    def _ingest_decoded_arena(self, res, payloads, tenant,
                              reg_decoder) -> dict:
        """Stage an externally decoded SoA batch (the worker pool's
        shared-memory outputs, or the in-process fallback) through the
        arena path: ONE vectorized copy of the decode columns into the
        fill arena, then the shared commit — no DecodedArrays copies, no
        HostEventBuffer, no emit-time reallocation. Caller has already
        WAL-logged the raw batch."""
        summary = {"decoded": 0, "failed": 0, "staged": 0}
        n = len(res.rtype)
        rec = self.flight.current()
        rec.add("path", "arena")
        with self.lock:
            now = self.epoch.now_ms()
            base_ms = int(self.epoch.base_unix_s * 1000)
            pos = 0
            while pos < n:
                arena = self._arena_fill
                if arena is None:
                    arena = self._arena_fill = \
                        self._acquire_arena(tenant, n - pos)
                take = min(n - pos, arena.room)
                lo, hi = arena.cursor, arena.cursor + take
                sl = slice(pos, pos + take)
                arena.rtype[lo:hi] = res.rtype[sl]
                arena.token_id[lo:hi] = res.token_id[sl]
                arena.ts64[lo:hi] = res.ts_ms64[sl]
                arena.values[lo:hi] = res.values[sl]
                arena.vmask[lo:hi] = res.chmask[sl]
                arena.aux[lo:hi, 0] = res.aux0[sl]
                arena.aux[lo:hi, 1] = res.aux1[sl]
                arena.level[lo:hi] = res.level[sl]
                rec.mark("arena_fill")
                self._arena_commit(arena, lo, take,
                                   payloads[pos:pos + take], tenant,
                                   reg_decoder, now, base_ms, summary)
                rec.mark("commit")
                if rec.trace_id is not None:
                    arena.traces.append(rec)
                arena.cursor = hi
                if arena.room == 0:
                    self._dispatch_arena()
                pos += take
            self.channel_map.collisions += res.collisions
        return summary

    def _arena_commit(self, arena, lo, n, payloads, tenant, reg_decoder,
                      now, base_ms, summary) -> None:
        """Make arena rows [lo, lo+n) live: map request types to event
        types, relativize timestamps, fold alert levels, fill the
        batch-constant columns — all vectorized, in place, no row-level
        Python. Registration/mapping/ack envelopes re-route through the
        per-request path (they carry string payloads the fast columns
        don't extract). Caller holds the lock."""
        from sitewhere_tpu.ingest.fast_decode import (
            RT_ACK,
            RT_MAP,
            RT_REGISTER,
            RTYPE_TO_ETYPE,
        )

        hi = lo + n
        rt = arena.rtype[lo:hi]
        etype = arena.etype[lo:hi]
        np.take(RTYPE_TO_ETYPE, np.clip(rt, -1, 7), out=etype)
        ok = (rt >= 0) & (etype >= 0)
        regs = ((rt == RT_REGISTER) | (rt == RT_MAP) | (rt == RT_ACK))
        ok &= ~regs
        failed = int(np.sum(rt < 0))
        n_reg_ok = 0
        if regs.any():
            # slow-path envelopes may stage per-request rows into _buf,
            # whose fill-triggered flush must NOT dispatch this arena
            # mid-commit (its valid mask is not set yet)
            self._arena_committing = True
            try:
                with self._wal_suppress():   # raw batch already logged
                    for i in np.nonzero(regs)[0]:
                        try:
                            for req in reg_decoder.decode(
                                    payloads[int(i)], {}):
                                req.tenant = tenant
                                self.process(req)
                            n_reg_ok += 1
                        except Exception:
                            failed += 1
            finally:
                self._arena_committing = False
        ts64 = arena.ts64[lo:hi]
        # relative int32 timestamps (absent -> now); the clip bounds the
        # int64->int32 cast of the slice assignment
        rel = np.clip(ts64 - base_ms, -(2**31) + 1, 2**31 - 1)
        arena.ts_ms[lo:hi] = np.where(ts64 >= 0, rel, now)
        arena.received_ms[lo:hi] = now
        arena.tenant_id[lo:hi] = self.tenants.intern(tenant)
        # aux0 (alert type) AND aux1 (alternate id) were written by the
        # native decoder — the device-side dedup counter sees batch rows
        alert_rows = ok & (etype == int(EventType.ALERT))
        if alert_rows.any():
            # alert rows carry their level in values[:, 0]
            arena.values[lo:hi][alert_rows, 0] = \
                arena.level[lo:hi][alert_rows]
        arena.valid[lo:hi] = ok
        staged = int(np.sum(ok))
        summary["decoded"] += staged + n_reg_ok
        summary["failed"] += failed
        summary["staged"] += staged
        self.host_counters["arena_rows"] = \
            self.host_counters.get("arena_rows", 0) + staged
        self.ledger.add("staged_rows", staged)

    def _dispatch_arena(self) -> None:
        """Dispatch the fill arena (full or partial — rows past the
        cursor are masked invalid, free padding) and retire it to the
        pool; it recycles once its step output is ready, which proves
        the host->device transfer of its buffers completed. Caller holds
        the lock."""
        arena = self._arena_fill
        if arena is None or arena.cursor == 0:
            return
        arena.valid[arena.cursor:] = False
        # conservation ledger: valid rows leaving the staging tier (the
        # failed-decode padding below the cursor never dispatches)
        self.ledger.add("dispatched_rows", int(np.sum(arena.valid)))
        traces, arena.traces = arena.traces, []
        # durability watermark: every WAL record of this arena's batches
        # must be fsync'd before the device program runs (group commit
        # moved the fsync off-thread; the ORDER guarantee stays here)
        self._wal_gate(traces)
        for rec in traces:
            rec.mark("dispatch")
        step = self._arena_step or self._step
        with stage("step.dispatch", rows=arena.cursor):
            self.state, out = step(self.state, arena.view_batch())
        self._enqueue_out(out, traces)
        # the recycle wait that proves the transfer completed ALSO proves
        # the device program ran: device_ready harvests there, free
        self._arena_pool.retire(arena, out.n_persisted, traces)
        self._archive_account(arena.cursor * MAX_ACTIVE_ASSIGNMENTS)
        self._arena_fill = None
        # plain attribute, NOT a metrics key: dispatch counts differ by
        # batching shape (scan_chunk), and metrics() equality across
        # dispatch configs is a tested parity property
        self._arena_dispatches += 1
        self._last_flush = time.monotonic()
        if self._autotuner is not None:
            self._autotuner.note_dispatch()

    def _ingest_decoded(self, res, payloads, tenant, reg_decoder) -> dict:
        """Stage a natively decoded SoA batch (shared by the JSON and binary
        fast paths); registration envelopes re-decode on the slow path for
        their string metadata."""
        if (getattr(self, "_arena_pool", None) is not None
                and not self.config.fair_tenancy):
            return self._ingest_decoded_arena(res, payloads, tenant,
                                              reg_decoder)
        with self.lock:
            now = self.epoch.now_ms()
            base_ms = int(self.epoch.base_unix_s * 1000)
            etype, ok, ts_rel, values, failed, n_reg_ok = \
                self._decode_prologue(res, payloads, tenant, reg_decoder,
                                      now, base_ms)
            idxs = np.nonzero(ok)[0]
            tenant_id = self.tenants.intern(tenant)
            if self.config.fair_tenancy:
                # fair mode: the fast path must honor the same per-tenant
                # quota as process(). The whole call shares one tenant, so
                # the entire decode batch enqueues as ONE chunk (array
                # slices — no per-row Python). ``values`` goes in whole:
                # alert rows carry their level there with chmask unset.
                if len(idxs):
                    self._fair_enqueue(tenant_id, _FairChunk(
                        etype=etype[idxs],
                        token=res.token_id[idxs],
                        ts=ts_rel[idxs],
                        recv=np.full(len(idxs), now, np.int32),
                        values=values[idxs],
                        vmask=res.chmask[idxs],
                        aux0=res.aux0[idxs],
                        aux1=res.aux1[idxs],
                    ))
                self.channel_map.collisions += res.collisions
                self.ledger.add("staged_rows", len(idxs))
                return {"decoded": int(np.sum(ok)) + n_reg_ok, "failed": failed,
                        "staged": int(len(idxs))}
            staged = 0
            pos = 0
            # all-rows-decoded batches (the steady state) stage with plain
            # slices — contiguous memcpy instead of a fancy-index gather
            # per column (~0.5ms/16k-batch on the 1-core host)
            contiguous = len(idxs) == len(ok)
            while pos < len(idxs):
                room = self.config.batch_capacity - len(self._buf)
                if room == 0:
                    self.flush_async()
                    room = self.config.batch_capacity
                chunk = (slice(pos, min(pos + room, len(idxs)))
                         if contiguous else idxs[pos: pos + room])
                n_chunk = (chunk.stop - chunk.start if contiguous
                           else len(chunk))
                b = self._buf
                lo = b._n
                hi = lo + n_chunk
                b.etype[lo:hi] = etype[chunk]
                b.token_id[lo:hi] = res.token_id[chunk]
                b.tenant_id[lo:hi] = tenant_id
                b.ts_ms[lo:hi] = ts_rel[chunk]
                b.received_ms[lo:hi] = now
                b.values[lo:hi] = values[chunk]
                b.vmask[lo:hi] = res.chmask[chunk]
                b.aux[lo:hi, 0] = res.aux0[chunk]
                b.aux[lo:hi, 1] = res.aux1[chunk]
                b._n = hi
                staged += n_chunk
                pos += room
            if self._buf.full:
                self.flush_async()
            self.channel_map.collisions += res.collisions
            # rows that took the copy-staging path (bench reports these
            # per batch to prove the arena path stays copy-free)
            self.host_counters["staged_copy_rows"] = \
                self.host_counters.get("staged_copy_rows", 0) + staged
            self.ledger.add("staged_rows", staged)
            return {"decoded": int(np.sum(ok)) + n_reg_ok, "failed": failed,
                    "staged": staged}

    def maybe_flush(self) -> dict | None:
        """Flush if the latency budget expired (call from a timer loop).
        Also drains async-flushed outputs so mirror staleness is bounded by
        the same interval."""
        with self.lock:
            expired = (time.monotonic() - self._last_flush
                       >= self.config.flush_interval_s)
            if (len(self._buf) or self._fair_queued or self._staged_batches
                    or (self._arena_fill is not None
                        and self._arena_fill.cursor)) and expired:
                with stage("flush"):
                    return self.flush()
            if self._pending_outs and expired:
                with stage("flush"):
                    return _merge_summaries(self.drain())
            return None

    def flush(self) -> dict:
        """Run the staged work through the pipeline and sync host mirrors;
        returns the AGGREGATE summary of everything drained (a flush may
        cover several scan lanes, including empty padding lanes). On a
        pipeline error the flight recorder dumps the recent batch
        lifecycles before the error propagates."""
        try:
            with self.lock, stage("pipeline_step"):
                self.flush_async()
                while self._fair_queued:  # fair mode: one batch per dispatch
                    self.flush_async()
                self._dispatch_staged(all_batches=True)
                return _merge_summaries(self.drain())
        except Exception:
            self.flight.dump_error(logging.getLogger(__name__))
            raise

    def flush_async(self) -> None:
        """Dispatch a step on the staged batch WITHOUT a mirror readback:
        the step output queues for :meth:`drain`. This is the steady-state
        ingest path; host mirrors lag until the next drain/flush, which
        every host-facing query performs first. Outstanding device programs
        are bounded by ``dispatch_depth`` (the dispatcher may wait for an
        older program — never a readback). No-op on an empty buffer.

        With ``scan_chunk > 1``, emitted batches accumulate and dispatch as
        ONE ``lax.scan`` program per chunk — one transfer group + one
        dispatch per K batches."""
        with self.lock:
            # staged-backlog high-watermark (ISSUE 11 satellite): sample
            # at the dispatch entry, where the backlog peaks — scrape
            # reads "worst case this window", not the instantaneous 0 a
            # drained engine shows (reset on scrape)
            staged = self.staged_count
            if staged > self._backlog_hwm:
                self._backlog_hwm = staged
            # drain fair queues whenever rows are queued (even if the flag
            # was toggled off afterwards — queued rows must never strand)
            if self._fair_queued:
                self._form_fair_batch()
            # a partially filled arena flushes too — but never mid-commit
            # (its valid mask is not final) — so the latency budget bounds
            # the arena path exactly like the legacy buffer
            if (self._arena_fill is not None and self._arena_fill.cursor
                    and not self._arena_committing):
                self._dispatch_arena()
            if not len(self._buf):
                return
            n_staged = len(self._buf)
            batch = self._buf.emit()
            if self.config.scan_chunk > 1:
                self._staged_batches.append(batch)
                self._dispatch_staged(all_batches=False)
            else:
                traces, self._staged_traces = self._staged_traces, []
                self._wal_gate(traces)
                for rec in traces:
                    rec.mark("dispatch")
                self.ledger.add("dispatched_rows", n_staged)
                with stage("step.dispatch", rows=n_staged):
                    self.state, out = self._step(self.state, batch)
                self._enqueue_out(out, traces)
                # ring head has advanced: each staged row persists up to
                # one event per active assignment — count the upper bound
                # so rows always spill before the ring wraps over them
                self._archive_account(n_staged * MAX_ACTIVE_ASSIGNMENTS)
            self._last_flush = time.monotonic()

    def _dispatch_staged(self, all_batches: bool) -> None:
        """Dispatch accumulated batches as scanned K-chunks (one packed
        transfer + one program per chunk). With ``all_batches`` a partial
        tail chunk is PADDED with empty batches to K rather than dispatched
        through the single-step program: the steady-state loop must run ONE
        compiled program, because alternating programs over the donated
        state forces repeated state relayout/conversion. Empty padding batches are free
        (valid=False rows, zero-count outputs)."""
        from sitewhere_tpu.core.events import pack_batches

        k = self.config.scan_chunk
        while self._staged_batches:
            if len(self._staged_batches) < k and not all_batches:
                return
            chunk, self._staged_batches = (self._staged_batches[:k],
                                           self._staged_batches[k:])
            while len(chunk) < k:   # pad the tail chunk with empty batches
                chunk.append(_empty_host_batch(self.config.batch_capacity,
                                               self.config.channels))
            # records for every batch in the chunk (K-batch granularity:
            # the chunk IS the dispatch unit)
            traces, self._staged_traces = self._staged_traces, []
            self._wal_gate(traces)
            for rec in traces:
                rec.mark("dispatch")
            rows = sum(int(np.sum(b.valid)) for b in chunk)
            self.ledger.add("dispatched_rows", rows)
            with stage("step.dispatch", rows=rows):
                self.state, outs = self._scan_step(self.state,
                                                   pack_batches(chunk))
            self._enqueue_out(outs, traces)
            # spool accounting happens HERE, where the ring head actually
            # advances — NOT at staging time (a staged-but-undispatched
            # batch would reset the counter while contributing no rows,
            # letting the chunk dispatch wrap the ring untracked)
            self._archive_account(
                k * self.config.batch_capacity * MAX_ACTIVE_ASSIGNMENTS)

    def _enqueue_out(self, out: StepOutput, traces: list = ()) -> None:
        """Queue a step output for drain, bounding outstanding device
        programs to ``dispatch_depth``. At the default depth 1 the wait
        lands on the just-dispatched program; a higher depth overlaps host
        staging with device execution (neither is measured on the chip)."""
        self._pending_outs.append(out)
        self._pending_traces.append(list(traces))
        d = max(1, self.config.dispatch_depth)
        if len(self._pending_outs) >= d:
            with stage("step.wait", depth=d):
                jax.block_until_ready(self._pending_outs[-d].n_persisted)
                # the wait observed that program's completion — stamp
                # device_ready on its batches at zero extra sync cost
                # (overwrite: a multi-chunk batch keeps its LAST chunk)
                for rec in self._pending_traces[-d]:
                    rec.mark("device_ready")

    def barrier(self) -> None:
        """Dispatch ALL staged work and wait for completion WITHOUT any
        device->host readback: the steady-state ingest loop synchronizes
        with this barrier and defers drain() — which does read — to
        reporting boundaries."""
        with self.lock:
            while (len(self._buf) or self._fair_queued
                   or (self._arena_fill is not None
                       and self._arena_fill.cursor)):
                self.flush_async()
            self._dispatch_staged(all_batches=True)
            if self._pending_outs:
                # depth 0: no program may stay outstanding (the waits of
                # _enqueue_out carry the dispatch depth)
                with stage("step.wait", depth=0):
                    jax.block_until_ready(
                        self._pending_outs[-1].n_persisted)

    def _archive_account(self, max_new_rows: int) -> None:
        """Track the upper bound of ring rows written by a dispatch; spool
        when any arena could be approaching overwrite. Caller holds the
        lock. No-op without an archive."""
        if self.archive is None:
            return
        self._rows_since_spool += max_new_rows
        if self._rows_since_spool >= self._spool_trigger:
            self._spool()

    def ring_heads(self) -> dict[int, int]:
        """Absolute ring write head per archive partition (= arena) —
        the ONE definition shared by the archive spooler and the
        conservation audit plane (ISSUE 14), so spill cursors are
        always compared against the heads the spooler advances to.
        Caller holds the lock (small device readback)."""
        from sitewhere_tpu.ops.readback import arena_cursor

        store = self.state.store
        return {a: arena_cursor(store, a) for a in range(store.arenas)}

    def ring_arena_capacity(self) -> int:
        """Rows one archive partition's ring holds before wrapping —
        the capacity bound of the conservation archive-spill equation."""
        return int(self.state.store.arena_capacity)

    def _spool(self) -> None:
        """Spill full segments of not-yet-archived ring rows to disk.
        Caller holds the lock. Reads use ONE compiled ``read_range``
        program (fixed ``segment_rows`` count) per segment; partial tails
        stay in the ring (still queryable there), so the archive only ever
        holds whole segments."""
        from sitewhere_tpu.ops.readback import read_range

        store = self.state.store
        acap = self.ring_arena_capacity()
        rows = self.archive.segment_rows
        for a, head in self.ring_heads().items():
            start = self.archive.spilled(a)
            if head - start > acap:   # wrapped before we got here
                self.archive.note_lost(head - acap - start)
                start = head - acap
            while head - start >= rows:
                sl = jax.device_get(read_range(
                    store, jnp.int32(start % acap), rows, arena=a))
                self.archive.append_segment(a, start, sl)
                start += rows
        self._rows_since_spool = 0

    def drain(self) -> list[dict]:
        """Absorb every queued step output into the host mirrors. ONLY the
        scalar counters are fetched for the whole backlog; the [B]-sized
        token lists stay on device and are sliced to their actual lengths
        for the (rare) steps that registered or dead-lettered — readback
        bytes stay proportional to real occurrences, never batch capacity."""
        with self.lock:
            if not self._pending_outs:
                return [{"found": 0, "missed": 0, "registered": 0,
                         "persisted": 0, "new_tokens": [], "dead_tokens": []}]
            outs, self._pending_outs = self._pending_outs, []
            trace_lists, self._pending_traces = self._pending_traces, []
            scalars = jax.device_get([
                (o.n_found, o.n_missed, o.n_registered, o.n_persisted)
                for o in outs])
            # the device_get above observed every drained program: stamp
            # readback (and device_ready for batches whose arena was
            # never recycled before this point) on their records
            for recs in trace_lists:
                for rec in recs:
                    if "device_ready" not in rec.stages:
                        rec.mark("device_ready")
                    rec.mark("readback")
            summaries = []
            for out, s in zip(outs, scalars):
                if np.ndim(s[0]) == 0:           # single step
                    summaries.append(self._absorb_output(
                        out, *(int(x) for x in s)))
                else:                             # scanned chunk: [K] lanes
                    for kk in range(np.shape(s[0])[0]):
                        sub = jax.tree_util.tree_map(lambda x: x[kk], out)
                        summaries.append(self._absorb_output(
                            sub, *(int(x[kk]) for x in s)))
            return summaries

    def _absorb_output(self, out: StepOutput, n_found: int, n_missed: int,
                       n_registered: int, n_persisted: int) -> dict:
        # token lists are front-compacted on device: fetch exactly the
        # occupied prefix (zero fetches in the common no-registration case)
        new_tokens = []
        if n_registered:
            new_tokens = [int(t) for t in
                          jax.device_get(out.new_tokens[:n_registered])]
        # mirror device-side auto-registration: allocation order == list order
        new_dids = []
        new_aids = []
        for tid in new_tokens:
            did = self._next_device
            aid = self._next_assignment
            self._next_device += 1
            self._next_assignment += 1
            self.token_device[tid] = did
            new_dids.append(did)
            new_aids.append(aid)
        if new_dids:
            tenants = np.asarray(jax.device_get(
                self.state.registry.device_tenant[np.asarray(new_dids)]))
            for tid, did, aid, ten in zip(new_tokens, new_dids, new_aids, tenants):
                tenant = self.tenants.token(int(ten)) if int(ten) != NULL_ID else "default"
                self.devices[did] = DeviceInfo(
                    token=self.tokens.token(tid),
                    device_type=self.config.default_device_type,
                    tenant=tenant,
                    auto_registered=True,
                )
                self._record_assignment(aid, did, slot=0)
        dead = []
        if n_missed:
            dead = [int(t) for t in jax.device_get(out.dead_tokens[:n_missed])]
        self.dead_letters.extend(dead)
        summary = {
            "found": n_found,
            "missed": n_missed,
            "registered": n_registered,
            "persisted": n_persisted,
            "new_tokens": new_tokens,
            "dead_tokens": dead,
        }
        self.outputs.append(summary)
        del self.outputs[:-256]
        return summary

    # ------------------------------------------------------------------ admin
    def register_device(
        self,
        token: str,
        device_type: str | None = None,
        tenant: str = "default",
        area: str | None = None,
        customer: str | None = None,
        metadata: dict | None = None,
    ) -> int:
        """API-path device creation (get-or-create), with explicit metadata —
        the RegisterDevice / RdbDeviceManagement.createDevice analog."""
        with self.lock:
            # staged events may still reference tokens about to be registered
            self._sync_mirrors()
            token_id = self.tokens.intern(token)
            existing = self.token_device.get(token_id)
            if existing is not None:
                return existing
            did = self._next_device
            aid = self._next_assignment
            if did >= self.config.device_capacity:
                raise RuntimeError("device capacity exhausted")
            type_name = device_type or self.config.default_device_type
            # admin-path registrations ride the WAL + replica feed as
            # their wire-form envelope (standby visibility; PR-6 limit)
            self._wal_admin_register(token, type_name, tenant, area,
                                     customer)
            self._next_device += 1
            self._next_assignment += 1
            self.state = _admin_create_device(
                self.state,
                jnp.int32(token_id), jnp.int32(did), jnp.int32(aid),
                jnp.int32(self.device_types.intern(type_name)),
                jnp.int32(self.tenants.intern(tenant)),
                jnp.int32(self.areas.intern(area) if area else NULL_ID),
                jnp.int32(self.customers.intern(customer) if customer else NULL_ID),
            )
            self.token_device[token_id] = did
            self.devices[did] = DeviceInfo(
                token=token, device_type=type_name, tenant=tenant,
                area=area, customer=customer, metadata=metadata or {},
            )
            self._record_assignment(aid, did, slot=0, area=area, customer=customer)
            return did

    def delete_device(self, token: str) -> bool:
        with self.lock:
            tid = self.tokens.lookup(token)
            did = self.token_device.get(tid)
            if did is None:
                return False
            self.state = _admin_set_device_active(self.state, jnp.int32(did), False)
            return True

    def map_device(self, child_token: str, parent_token: str) -> DeviceInfo:
        """Map a device under a gateway/composite parent (the reference's
        MapDevice request + DeviceMappings REST path; the parent feeds
        NestedDeviceSupport command routing and the on-device
        device_parent column)."""
        with self.lock:
            self._sync_mirrors()
            ctid = self.tokens.lookup(child_token)
            cdid = self.token_device.get(ctid)
            if cdid is None:
                raise KeyError(f"device {child_token!r} not registered")
            ptid = self.tokens.lookup(parent_token)
            pdid = self.token_device.get(ptid)
            if pdid is None:
                raise KeyError(f"parent device {parent_token!r} not registered")
            if cdid == pdid:
                raise ValueError("device cannot be its own parent")
            info = self.devices[cdid]
            info.metadata = dict(info.metadata) | {"parentToken": parent_token}
            self.state = _admin_set_parent(
                self.state, jnp.int32(cdid), jnp.int32(pdid))
            return info

    def update_device(self, token: str, device_type: str | None = None,
                      area: str | None = None, customer: str | None = None,
                      metadata: dict | None = None) -> DeviceInfo:
        """Update device columns + host metadata (RdbDeviceManagement.updateDevice)."""
        with self.lock:
            self._sync_mirrors()
            tid = self.tokens.lookup(token)
            did = self.token_device.get(tid)
            if did is None:
                raise KeyError(f"device {token!r} not registered")
            info = self.devices[did]
            # validate EVERYTHING (including interning, which can exhaust
            # capacity) before mutating either view, so a failed update
            # never leaves host and device state half-applied
            type_id = jnp.int32(self.device_types.intern(
                device_type if device_type is not None else info.device_type))
            new_area = area if area is not None else info.area
            area_id = jnp.int32(
                self.areas.intern(new_area) if new_area else NULL_ID)
            new_customer = customer if customer is not None else info.customer
            customer_id = jnp.int32(
                self.customers.intern(new_customer) if new_customer else NULL_ID)
            parent_update = None   # (new metadata dict, parent did or NULL)
            if metadata is not None:
                # the gateway mapping lives in metadata AND the on-device
                # parent column; keep the two views in lockstep:
                #   key absent        -> preserve the existing mapping
                #   key set to a token-> remap (on-device column follows)
                #   key set to None   -> unmap (column cleared)
                old_parent = info.metadata.get("parentToken")
                metadata = dict(metadata)
                if "parentToken" not in metadata and old_parent is not None:
                    metadata["parentToken"] = old_parent
                new_parent = metadata.get("parentToken")
                if new_parent != old_parent:
                    if new_parent is None:
                        metadata.pop("parentToken", None)
                        parent_update = (metadata, NULL_ID)
                    else:
                        pdid = self.token_device.get(
                            self.tokens.lookup(new_parent))
                        if pdid is None:
                            raise KeyError(
                                f"parent device {new_parent!r} not registered")
                        if pdid == did:
                            raise ValueError(
                                "device cannot be its own parent")
                        parent_update = (metadata, pdid)
                else:
                    if new_parent is None:
                        metadata.pop("parentToken", None)
                    parent_update = (metadata, None)   # no column change
            if device_type is not None:
                info.device_type = device_type
            if area is not None:
                info.area = area
            if customer is not None:
                info.customer = customer
            if parent_update is not None:
                info.metadata, pdid = parent_update
                if pdid is not None:
                    self.state = _admin_set_parent(
                        self.state, jnp.int32(did), jnp.int32(pdid))
            self.state = _admin_update_device(
                self.state, jnp.int32(did), type_id, area_id, customer_id)
            return info

    # ------------------------------------------------------------- assignments
    def _record_assignment(self, aid: int, did: int, slot: int,
                           token: str | None = None, asset: str | None = None,
                           area: str | None = None, customer: str | None = None,
                           metadata: dict | None = None) -> AssignmentInfo:
        """Record host metadata for an assignment already written on-device
        (by _admin_create_device / _admin_add_assignment / the registration
        kernel). Caller holds the engine lock."""
        dev = self.devices[did]
        tok = token or f"{dev.token}:a{aid}"
        info = AssignmentInfo(
            token=tok, id=aid, device_token=dev.token, tenant=dev.tenant,
            asset=asset, area=area or dev.area, customer=customer or dev.customer,
            metadata=metadata or {}, created_ms=self.epoch.now_ms(),
        )
        self.assignments[aid] = info
        self.assignment_tokens[tok] = aid
        slots = self.device_slots.setdefault(did, [NULL_ID] * MAX_ACTIVE_ASSIGNMENTS)
        slots[slot] = aid
        return info

    def create_assignment(self, device_token: str, token: str | None = None,
                          asset: str | None = None, area: str | None = None,
                          customer: str | None = None,
                          metadata: dict | None = None) -> AssignmentInfo:
        """Attach an additional ACTIVE assignment to a registered device
        (reference: RdbDeviceManagement.createDeviceAssignment via the
        Assignments REST controller)."""
        with self.lock:
            self._sync_mirrors()
            tid = self.tokens.lookup(device_token)
            did = self.token_device.get(tid)
            if did is None:
                raise KeyError(f"device {device_token!r} not registered")
            if token is not None and token in self.assignment_tokens:
                raise ValueError(f"assignment token {token!r} already exists")
            slots = self.device_slots.setdefault(
                did, [NULL_ID] * MAX_ACTIVE_ASSIGNMENTS)
            try:
                slot = slots.index(NULL_ID)
            except ValueError:
                # client-correctable conflict, not an engine fault
                raise ValueError(
                    f"device {device_token!r} already has "
                    f"{MAX_ACTIVE_ASSIGNMENTS} active assignments") from None
            aid = self._next_assignment
            if aid >= self.config.assignment_capacity:
                raise RuntimeError("assignment capacity exhausted")
            self._next_assignment += 1
            self.state = _admin_add_assignment(
                self.state, jnp.int32(did), jnp.int32(aid), jnp.int32(slot),
                jnp.int32(self.assets.intern(asset) if asset else NULL_ID),
                jnp.int32(self.areas.intern(area) if area else NULL_ID),
                jnp.int32(self.customers.intern(customer) if customer else NULL_ID),
            )
            info = self._record_assignment(
                aid, did, slot, token=token, asset=asset, area=area,
                customer=customer, metadata=metadata)
            self._assignment_trigger(device_token, "assignment.created",
                                     info.tenant)
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

    def update_assignment(self, token: str, asset: str | None = None,
                          area: str | None = None,
                          customer: str | None = None,
                          metadata: dict | None = None) -> AssignmentInfo:
        """Update an assignment's association columns + host metadata
        (reference: Assignments.java:144 PUT -> updateDeviceAssignment)."""
        with self.lock:
            self._sync_mirrors()
            aid = self.assignment_tokens.get(token)
            if aid is None:
                raise KeyError(f"assignment {token!r} not found")
            info = self.assignments[aid]
            new_asset = asset if asset is not None else info.asset
            new_area = area if area is not None else info.area
            new_customer = customer if customer is not None else info.customer
            # intern before mutating so a capacity error never half-applies
            asset_id = jnp.int32(
                self.assets.intern(new_asset) if new_asset else NULL_ID)
            area_id = jnp.int32(
                self.areas.intern(new_area) if new_area else NULL_ID)
            customer_id = jnp.int32(
                self.customers.intern(new_customer) if new_customer else NULL_ID)
            self.state = _admin_update_assignment(
                self.state, jnp.int32(aid), asset_id, area_id, customer_id)
            info.asset, info.area, info.customer = new_asset, new_area, new_customer
            if metadata is not None:
                info.metadata = metadata
            return info

    def delete_assignment(self, token: str) -> bool:
        """Delete an assignment (reference: Assignments.java DELETE ->
        deleteDeviceAssignment): detach it on-device (release semantics) and
        drop the host record. Persisted events that referenced the id stay
        in the ring — like the reference, deletes don't rewrite history."""
        with self.lock:
            self._sync_mirrors()
            aid = self.assignment_tokens.get(token)
            if aid is None:
                return False
            if self.assignments[aid].status != "RELEASED":
                self._set_assignment_status(token, DeviceAssignmentStatus.RELEASED)
            del self.assignments[aid]
            del self.assignment_tokens[token]
            return True

    def _set_assignment_status(self, token: str,
                               status: DeviceAssignmentStatus) -> AssignmentInfo:
        with self.lock:
            self._sync_mirrors()
            aid = self.assignment_tokens.get(token)
            if aid is None:
                raise KeyError(f"assignment {token!r} not found")
            active = status is not DeviceAssignmentStatus.RELEASED
            self.state = _admin_set_assignment_status(
                self.state, jnp.int32(aid), jnp.int32(status), active)
            info = self.assignments[aid]
            info.status = status.name
            if not active:
                info.released_ms = self.epoch.now_ms()
                tid = self.tokens.lookup(info.device_token)
                did = self.token_device.get(tid)
                if did is not None and did in self.device_slots:
                    slots = self.device_slots[did]
                    self.device_slots[did] = [
                        NULL_ID if s == aid else s for s in slots]
            self._assignment_trigger(
                info.device_token, f"assignment.{status.name.lower()}",
                info.tenant)
            return info

    def _assignment_trigger(self, device_token: str, change: str,
                            tenant: str) -> None:
        """Emit a system STATE_CHANGE event on assignment lifecycle changes
        (reference: DeviceManagementTriggers.java:30-62 pushes device
        state-change events to Kafka on assignment create). Opt-in so event
        streams stay pure device telemetry by default. Caller holds the
        lock."""
        if not self.config.assignment_triggers:
            return
        self.process(DecodedRequest(
            type=RequestType.DEVICE_STATE_CHANGE,
            device_token=device_token,
            tenant=tenant,
            attribute="assignment",
            state_type=change,
        ))

    def release_assignment(self, token: str) -> AssignmentInfo:
        """End an assignment (reference: Assignments controller
        /assignments/{token}/end -> endDeviceAssignment)."""
        return self._set_assignment_status(token, DeviceAssignmentStatus.RELEASED)

    def mark_assignment_missing(self, token: str) -> AssignmentInfo:
        """Flag an assignment MISSING (reference: /assignments/{token}/missing);
        it stays active so events still expand to it."""
        return self._set_assignment_status(token, DeviceAssignmentStatus.MISSING)

    # ------------------------------------------------------------------ queries
    def get_device(self, token: str) -> DeviceInfo | None:
        if self._pending_outs:
            with self.lock:
                self._sync_mirrors()
        tid = self.tokens.lookup(token)
        did = self.token_device.get(tid)
        return self.devices.get(did) if did is not None else None

    def get_device_state(self, token: str) -> dict | None:
        """Read back one device's aggregated state (REST device-state API)."""
        with self.lock:
            self._sync_mirrors()
            tid = self.tokens.lookup(token)
            did = self.token_device.get(tid)
            if did is None:
                return None
            ds = self.state.device_state
            d = did
            chans = {}
            for name, nid in self.channel_map.names.items():
                ch = nid % self.config.channels
                ts = int(ds.meas_last_ms[d, ch])
                if ts > -(2**31) + 10:
                    chans[name] = {
                        "value": float(ds.meas_last[d, ch]),
                        "ts_ms": ts,
                    }
            recent_locs = [
                {
                    "latitude": float(ds.recent_loc[d, r, 0]),
                    "longitude": float(ds.recent_loc[d, r, 1]),
                    "elevation": float(ds.recent_loc[d, r, 2]),
                    "ts_ms": int(ds.recent_loc_ms[d, r]),
                }
                for r in range(RECENT_DEPTH)
                if bool(ds.recent_loc_valid[d, r])
            ]
            recent_alerts = [
                {
                    "level": int(ds.recent_alert_level[d, r]),
                    "type": self.alert_types.token(int(ds.recent_alert_type[d, r])),
                    "ts_ms": int(ds.recent_alert_ms[d, r]),
                }
                for r in range(RECENT_DEPTH)
                if bool(ds.recent_alert_valid[d, r])
            ]
            return {
                "device": self.devices[did].token,
                "presence": PresenceState(int(ds.presence[d])).name,
                "last_interaction_ms": int(ds.last_interaction_ms[d]),
                "measurements": chans,
                "recent_locations": recent_locs,
                "recent_alerts": recent_alerts,
                "event_counts": {
                    EventType(e).name: int(ds.event_counts[d, e]) for e in range(6)
                },
            }

    def search_device_states(
        self,
        last_interaction_before_ms: int | None = None,
        presence: str | None = None,
        device_tokens: list[str] | None = None,
        area: str | None = None,
        device_type: str | None = None,
        limit: int = 100,
    ) -> list[dict]:
        """Filtered device-state search (reference: DeviceStates controller
        POST /devicestates/search -> searchDeviceStates with
        lastInteractionDateBefore / presenceMissingDateBefore criteria).
        Filters run vectorized over the device-resident state columns."""
        with self.lock:
            self._sync_mirrors()
            n = self._next_device
            if n == 0:
                return []
            ds = self.state.device_state
            last = np.asarray(ds.last_interaction_ms[:n])
            pres = np.asarray(ds.presence[:n])
            mask = np.ones(n, np.bool_)
            if last_interaction_before_ms is not None:
                mask &= last < last_interaction_before_ms
            if presence is not None:
                mask &= pres == int(PresenceState[presence.upper()])
            if device_tokens is not None:
                wanted = {
                    self.token_device.get(self.tokens.lookup(t)) for t in device_tokens
                }
                sel = np.zeros(n, np.bool_)
                for d in wanted:
                    if d is not None and d < n:
                        sel[d] = True
                mask &= sel
            if area is not None or device_type is not None:
                # the hot area/type columns live on device (admin writes
                # mirror them): one id-array fetch + vectorized compare
                # replaces the per-device dict-lookup loop
                reg = self.state.registry
                if area is not None:
                    aid = self.areas.lookup(area)
                    if aid == NULL_ID:   # unknown area matches nothing
                        mask[:] = False
                    else:
                        mask &= np.asarray(reg.device_area[:n]) == aid
                if device_type is not None:
                    ty = self.device_types.lookup(device_type)
                    if ty == NULL_ID:
                        mask[:] = False
                    else:
                        mask &= np.asarray(reg.device_type[:n]) == ty
            out = []
            for d in np.nonzero(mask)[0][:limit]:
                info = self.devices.get(int(d))
                if info is None:
                    continue
                out.append({
                    "device": info.token,
                    "deviceType": info.device_type,
                    "tenant": info.tenant,
                    "presence": PresenceState(int(pres[d])).name,
                    "lastInteractionMs": int(last[d]),
                })
            return out

    def query_events(
        self,
        device_token: str | None = None,
        etype: EventType | None = None,
        tenant: str | None = None,
        since_ms: int | None = None,
        until_ms: int | None = None,
        limit: int = 100,
        assignment_id: int | None = None,
        aux0: int | None = None,
        area: str | None = None,
        customer: str | None = None,
        alternate_id: str | None = None,
    ) -> dict:
        """Filtered, newest-first event query over the HBM ring store — the
        REST listDeviceEvents/searchDeviceEvents surface (TPU-side scan,
        only the top rows travel to the host). All filters apply on-device
        so the limit applies after filtering; ``area``/``customer`` cover
        the reference's per-area/per-customer event rollups
        (Areas.java /{token}/measurements..., Customers.java ditto) and
        ``alternate_id`` the /events/alternate/{id} lookup.

        Read path (shared-scan batched): only the mirror sync and the
        string->id resolution run under the engine lock. The device
        program — coalesced with any concurrent queries into one fused
        multi-predicate pass — and all row formatting run OUTSIDE it, so
        queries block neither ingest dispatch nor each other. ``limit``
        buckets to the next power of two for the compile cache; the
        result slices back to the exact page."""
        from sitewhere_tpu.ops.query import bucket_limit

        t_q0 = time.perf_counter()
        limit = max(1, int(limit))
        rec = self.flight.begin("query", tenant=tenant or "all")
        miss = False   # any unknown string filter matches NOTHING — an
                       # unknown tenant must never widen to all tenants
        with self.lock:
            self._sync_mirrors()
            dev = NULL_ID
            if device_token is not None:
                tid = self.tokens.lookup(device_token)
                dev = self.token_device.get(tid, NULL_ID)
                miss |= dev == NULL_ID
            ten = NULL_ID
            if not miss and tenant is not None:
                ten = self.tenants.lookup(tenant)
                miss |= ten == NULL_ID
            area_id = customer_id = aux1 = None
            if not miss and area is not None:
                area_id = self.areas.lookup(area)
                miss |= area_id == NULL_ID
            if not miss and customer is not None:
                customer_id = self.customers.lookup(customer)
                miss |= customer_id == NULL_ID
            if not miss and alternate_id is not None:
                aux1 = self.event_ids.lookup(alternate_id)
                miss |= aux1 == NULL_ID
            lane_names = None if miss else self._lane_names()
        rec.mark("lookup")
        if miss:
            # still a served query: count it and close its record so
            # high miss-rate polling shows up in the read metrics
            self._query_batcher.observe_latency(time.perf_counter() - t_q0)
            return {"total": 0, "events": []}
        imin, imax = -(2**31), 2**31 - 1
        params = (  # ops.query.QueryParams field order
            dev,
            int(etype) if etype is not None else NULL_ID,
            ten,
            int(since_ms) if since_ms is not None else imin,
            int(until_ms) if until_ms is not None else imax,
            int(assignment_id) if assignment_id is not None else NULL_ID,
            int(aux0) if aux0 is not None else NULL_ID,
            int(aux1) if aux1 is not None else NULL_ID,
            int(area_id) if area_id is not None else NULL_ID,
            int(customer_id) if customer_id is not None else NULL_ID,
        )
        archive_req = None
        if self.archive is not None:
            # predicate pushdown request for the retention tier: the
            # batcher round scans it ONCE for every coalesced query, with
            # the same resolved ids the device predicates use and the
            # caller's EXACT page size (not the bucketed one)
            archive_req = {"limit": limit, "filters": dict(
                device=dev if device_token is not None else None,
                etype=int(etype) if etype is not None else None,
                tenant=ten if tenant is not None else None,
                since_ms=since_ms, until_ms=until_ms,
                assignment=assignment_id, aux0=aux0, aux1=aux1,
                area=area_id, customer=customer_id)}
        row, cursors, coalesced, archive_res = self._query_batcher.run(
            params, bucket_limit(limit), archive=archive_req,
            tenant=tenant, trace_id=rec.trace_id)
        rec.mark("device")
        rec.add("coalesced", coalesced)
        # every result column is already ONE host numpy array (the
        # batcher's single readback) — per-row formatting never touches
        # the device again
        total = int(row.total)
        n = min(total, limit)
        events = [
            self._format_event(
                int(row.etype[i]), int(row.device[i]),
                int(row.assignment[i]), int(row.ts_ms[i]),
                int(row.received_ms[i]), row.values[i], row.vmask[i],
                row.aux[i], lane_names)
            for i in range(n)
        ]
        rec.mark("format")
        if archive_res is not None:
            # two-tier merge from the round's shared archive pass: the
            # disk scan already ran inside the batcher round (capped by
            # the SAME snapshot cursors the ring scan saw, so the tiers
            # never overlap); formatting the pre-fetched rows needs no
            # lock, like the ring-side formatting above
            total, events = self._merge_archive(total, events, limit,
                                                archive_res)
            rec.mark("archive")
        self._query_batcher.observe_latency(time.perf_counter() - t_q0)
        return {"total": total, "events": events}

    def _lane_names(self) -> dict[int, str]:
        lane_names: dict[int, str] = {}
        for name, nid in self.channel_map.names.items():
            lane_names.setdefault(nid % self.config.channels, name)
        return lane_names

    def _format_event(self, et_i: int, device_id: int, assignment: int,
                      ts: int, received: int, values, vmask, aux,
                      lane_names: dict[int, str]) -> dict:
        """One persisted store row -> the REST event dict (shared by the
        ring query and the archive merge so both tiers serve identical
        shapes)."""
        et = EventType(et_i)
        info = self.devices.get(device_id)
        ev = {
            "type": et.name,
            "deviceToken": info.token if info else None,
            "assignmentId": assignment,
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
                ev["latitude"], ev["longitude"], ev["elevation"] = (
                    float(values[0]), float(values[1]), float(values[2]))
            else:  # decoded without coordinates — never null island
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

    def _merge_archive(self, total: int, events: list[dict], limit: int,
                       archive_res: tuple[int, list[dict]],
                       ) -> tuple[int, list[dict]]:
        """Fold archived history into a ring query result. The archive
        scan itself ran inside the batcher round (pushdown + shared
        decode, capped at rows already EVICTED from each arena — absolute
        pos < head - capacity at the round's snapshot — so the two tiers
        never overlap); this merge only formats the pre-fetched rows and
        interleaves them newest-first, byte-identical to the pre-pushdown
        per-query scan. The reference's unbounded date-range search
        (InfluxDbDeviceEventManagement.java:63-161) falls out of ring +
        archive union."""
        a_total, rows = archive_res
        if not a_total:
            return total, events
        lane_names = self._lane_names()
        a_events = [
            self._format_event(
                int(r["etype"]), int(r["device"]), int(r["assignment"]),
                int(r["ts_ms"]), int(r["received_ms"]), r["values"],
                r["vmask"], r["aux"], lane_names)
            for r in rows
        ]
        merged = sorted(events + a_events,
                        key=lambda e: -e["eventDateMs"])[:limit]
        return total + a_total, merged

    def get_event(self, event_id: int,
                  tenant: str | None = None) -> dict | None:
        """Fetch one persisted event by its absolute store position — the
        stable event id handed out by the outbound feed and the
        /api/events/id/{eventId} lookup (reference: DeviceEvents.java
        getDeviceEventById). Returns None when the id was never written or
        its ring slot has been overwritten. ``tenant`` scopes the lookup:
        a row belonging to another tenant reads as absent (ids are
        enumerable ring positions, so tenant-bound callers must not be
        able to walk other tenants' history)."""
        from sitewhere_tpu.ops.readback import arena_cursor, read_range

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
            arena = event_id % store.arenas
            pos = event_id // store.arenas
            head = arena_cursor(store, arena)
            if pos >= head:
                return None
            if pos < head - store.arena_capacity:
                # evicted from the ring: the id must resolve from the
                # archive so the by-id surface agrees with query_events
                if self.archive is None:
                    return None
                r = self.archive.get_row(arena, pos)
                if r is None:
                    return None
                if ten is not None and int(r["tenant"]) != ten:
                    return None
                ev = self._format_event(
                    int(r["etype"]), int(r["device"]), int(r["assignment"]),
                    int(r["ts_ms"]), int(r["received_ms"]), r["values"],
                    r["vmask"], r["aux"], self._lane_names())
                ev["eventId"] = event_id
                return ev
            sl = jax.device_get(read_range(
                store, jnp.int32(pos % store.arena_capacity), 1,
                arena=arena))
            if not bool(sl.valid[0]):
                return None
            if ten is not None and int(sl.tenant[0]) != ten:
                return None
            ev = self._format_event(
                int(sl.etype[0]), int(sl.device[0]), int(sl.assignment[0]),
                int(sl.ts_ms[0]), int(sl.received_ms[0]), sl.values[0],
                np.asarray(sl.vmask[0]), np.asarray(sl.aux[0]),
                self._lane_names())
            ev["eventId"] = event_id
            return ev

    def make_feed_consumer(self, group_id: str, max_batch: int = 1024,
                           start_from_latest: bool = False):
        """Factory for outbound consumers over this engine's event store —
        the single constructor the outbound services (connectors, command
        delivery, zone monitor) use, so the same wiring works against the
        single-node and the distributed engine."""
        from sitewhere_tpu.outbound.feed import FeedConsumer

        return FeedConsumer(self, group_id, max_batch=max_batch,
                            start_from_latest=start_from_latest)

    def presence_sweep(self) -> list[str]:
        """Mark stale devices MISSING; returns their tokens (notification
        hook — PresenceNotificationStrategies.SendOnce analog)."""
        with self.lock:
            self._sync_mirrors()   # async-registered devices must be mirrored
                                   # or their one-shot notification is lost
            now = jnp.int32(self.epoch.now_ms())
            missing_ms = jnp.int32(int(self.config.presence_missing_s * 1000))
            self.state, newly = self._sweep(self.state, now, missing_ms)
            idxs = np.nonzero(np.asarray(newly))[0]
            return [self.devices[int(i)].token for i in idxs if int(i) in self.devices]

    def tenant_metrics(self) -> dict[str, dict[str, int]]:
        """Per-tenant event counts — one on-device segment-sum of the
        per-device counters over the tenant column (the reference labels
        every Prometheus metric per tenant via buildLabels())."""
        with self.lock:
            self._sync_mirrors()
            n_tenants = len(self.tenants)
            counts = np.asarray(_tenant_event_counts(
                self.state, tenant_cap(n_tenants)))
        return tenant_counts_dict(counts, self.tenants, n_tenants)

    # uniform name for "sweep THIS engine only" — the cluster facade
    # overrides presence_sweep with a fan-out but keeps this local form,
    # so per-rank background loops never trigger N^2 sweeps
    presence_sweep_local = presence_sweep

    def set_geofence_zones(self, polygons, max_vertices: int = 16) -> None:
        """Install geofence polygons into the pipeline state so the jit
        step counts zone containment per tenant (the ``geofence_hit``
        counter lane) inside the already-running program — no extra
        dispatch, no host round trip per batch. Pass an empty list to
        remove the zones (the lane freezes at its cumulative value)."""
        from sitewhere_tpu.ops.geofence import pack_zones
        from sitewhere_tpu.pipeline import ZoneTable

        with self.lock:
            # a zone install/remove that CHANGES the zones leaf's
            # abstract shape (None <-> ZoneTable, or a different zone
            # count/vertex capacity) is a DECLARED recompile of every
            # step family — grant the watchdog budgets one more shape.
            # A no-op (clearing already-None zones, reinstalling the
            # same shape) must NOT grant: leaked allowance would let
            # genuine shape churn pass the retrace budget unflagged.
            old = self.state.zones
            if not polygons:
                if old is not None:
                    self.devicewatch.allow(1)
                    self._swap_epoch += 1
                    self.state = dataclasses.replace(self.state,
                                                     zones=None)
                return
            verts, valid = pack_zones(polygons, max_vertices)
            if old is None or tuple(old.verts.shape) != verts.shape:
                self.devicewatch.allow(1)
                self._swap_epoch += 1
            self.state = dataclasses.replace(
                self.state, zones=ZoneTable(jnp.asarray(verts),
                                            jnp.asarray(valid)))

    # ------------------------------------------------- streaming rules
    def precompile_rules(self, rules_state):
        """AOT-compile the HOT dispatch program (single-step or k-lane
        arena scan — whichever this engine actually dispatches) for a
        CANDIDATE rules subtree, from ShapeDtypeStructs so no buffers are
        touched and the engine lock is held only to snapshot shapes. The
        compile-before-swap half of a rule-set install: ingest keeps
        serving the old program until this returns, and the first
        post-swap dispatch is compile-free."""
        from sitewhere_tpu.core.events import EventBatch
        from sitewhere_tpu.pipeline import (FAMILY_ARENA_SCAN,
                                            make_arena_scan_step)

        c = self.config
        with self.lock:
            base = dataclasses.replace(self.state, rules=rules_state)
            state_struct = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), base)
            sig = _swap_sig(base)
            k = max(1, c.scan_chunk)
            arena_scan = self._arena_step is not None
        cfg = PipelineConfig(auto_register=c.auto_register,
                             default_device_type=0)
        if arena_scan:
            fn = make_arena_scan_step(cfg, c.batch_capacity, c.channels, k)
            rows, family = c.batch_capacity * k, FAMILY_ARENA_SCAN
        else:
            fn = make_pipeline_step(cfg)
            rows, family = c.batch_capacity, FAMILY_STEP
        bstruct = jax.eval_shape(
            lambda: EventBatch.zeros(rows, c.channels))
        t0 = time.perf_counter()
        compiled = fn.lower(state_struct, bstruct).compile()
        logging.getLogger(__name__).info(
            "rules precompile (%s): %.2fs", family,
            time.perf_counter() - t0)
        return _PrecompiledStep(compiled, fn, family, sig)

    def set_rules(self, rules_state, *, precompiled=None,
                  preserve_state: bool = False) -> None:
        """Install/replace/remove the streaming-rules subtree. A shape
        change is a DECLARED recompile of every step family — the
        watchdog budgets are granted one shape, exactly like
        ``set_geofence_zones`` — and installs ``precompiled`` (from
        :meth:`precompile_rules`) as the hot program so the swap never
        stalls a dispatch. ``preserve_state=True`` (same-shaped rule
        tables, e.g. a threshold tweak) keeps the carried accumulators
        and recompiles nothing."""
        with self.lock:
            old = self.state.rules
            if (preserve_state and old is not None
                    and rules_state is not None):
                merged_rules = old.rules
                if old.rules is not None and rules_state.rules is not None:
                    merged_rules = dataclasses.replace(old.rules, **{
                        f: getattr(rules_state.rules, f)
                        for f in _RULE_PARAM_FIELDS})
                merged_rollups = old.rollups
                if (old.rollups is not None
                        and rules_state.rollups is not None):
                    merged_rollups = dataclasses.replace(old.rollups, **{
                        f: getattr(rules_state.rollups, f)
                        for f in _ROLLUP_PARAM_FIELDS})
                rules_state = dataclasses.replace(
                    rules_state, rules=merged_rules,
                    rollups=merged_rollups)
            changed = (_swap_sig(self.state)
                       != _swap_sig(dataclasses.replace(
                           self.state, rules=rules_state)))
            if changed:
                # declared program change: one shape of allowance for
                # every wrapped family, and the lazily-built harvest
                # program starts over with the new shape
                self.devicewatch.allow(1)
                self._swap_epoch += 1
                self._rules_harvest_fn = None
            self.state = dataclasses.replace(self.state,
                                             rules=rules_state)
            if not changed:
                return
            cfg = PipelineConfig(auto_register=self.config.auto_register,
                                 default_device_type=0)
            if precompiled is not None:
                # fresh watch scope: a rule-set swap is a declared
                # program change (the scan-chunk-retune discipline)
                precompiled.bind(self)
                if precompiled.family == FAMILY_STEP:
                    self._step = self.devicewatch.wrap(
                        precompiled, FAMILY_STEP, cost=True)
                else:
                    self._arena_step = self.devicewatch.wrap(
                        precompiled, precompiled.family, cost=True)
            else:
                # rules removed (or swapped without precompile): drop
                # any stale AOT shim — on WHICHEVER family it was
                # installed — and return to the shared jit programs
                if isinstance(getattr(self._step, "fn", self._step),
                              _PrecompiledStep):
                    self._step = self.devicewatch.wrap(
                        make_pipeline_step(cfg), FAMILY_STEP, cost=True)
                if (self._arena_step is not None and isinstance(
                        getattr(self._arena_step, "fn",
                                self._arena_step), _PrecompiledStep)):
                    from sitewhere_tpu.pipeline import (
                        FAMILY_ARENA_SCAN, make_arena_scan_step)

                    self._arena_step = self.devicewatch.wrap(
                        make_arena_scan_step(
                            cfg, self.config.batch_capacity,
                            self.config.channels,
                            max(1, self.config.scan_chunk)),
                        FAMILY_ARENA_SCAN, cost=True)

    def poll_rule_fires(self):
        """Harvest pending rule fires: ONE donated-state device program
        (``rules.harvest`` family) that advances the harvest cursors,
        then a single readback. Returns numpy ``(pend_key[R, G, K],
        pend_val[R, G, K], pend_w[R, G], pend_h[R, G])`` — the
        ``harvest_fires`` ring contract (each group's ``min(w - h, K)``
        newest entries, oldest-first at ``(w - n .. w - 1) % K``) — or
        None when no rules are installed. Reporting-cadence only — the
        ingest hot loop never calls this."""
        from sitewhere_tpu.ops.rules import harvest_fires
        from sitewhere_tpu.pipeline import FAMILY_RULES_HARVEST

        with self.lock:
            rs = self.state.rules
            if rs is None or rs.rules is None:
                return None
            self._sync_mirrors()
            if self._rules_harvest_fn is None:
                def _harvest(state: PipelineState):
                    new_rules, *fires = harvest_fires(state.rules)
                    return (dataclasses.replace(state, rules=new_rules),
                            tuple(fires))

                self._rules_harvest_fn = self.devicewatch.wrap(
                    jax.jit(_harvest, donate_argnums=(0,)),
                    FAMILY_RULES_HARVEST)
            self.state, out = self._rules_harvest_fn(self.state)
            return jax.device_get(out)

    def rule_counters(self) -> dict:
        """Device-side CEP counters (status/REST surface; NOT part of
        metrics() — ``missed``/``late`` depend on harvest cadence and
        batch partitioning, so they would break the dispatch-shape
        metrics-equality invariant that ``rule_fires`` preserves)."""
        with self.lock:
            rs = getattr(self.state, "rules", None)
            out: dict = {}
            if rs is not None and rs.rules is not None:
                rb = rs.rules
                f, m, l, o = jax.device_get(
                    (rb.fires, rb.missed, rb.late, rb.oob))
                out.update(ruleFires=int(np.sum(f)),
                           ruleMissedFires=int(np.sum(m)),
                           ruleLateEvents=int(np.sum(l)),
                           ruleOobGroups=int(np.sum(o)),
                           rulesActive=int(rb.n_rules))
            if rs is not None and rs.rollups is not None:
                out.update(
                    rollupLateEvents=int(np.sum(
                        jax.device_get(rs.rollups.late))),
                    rollupsActive=int(rs.rollups.n_rollups))
            return out

    def _rollup_tables(self, p: int, scope: str):
        """One rollup's materialized tables as host arrays
        ``(wid, cnt, vsum, vmin, vmax)``, each ``[G, B]`` — the seam the
        rules manager reads through (the SPMD engine overrides this to
        fold its per-shard tables into the same single-chip layout)."""
        ro = self.state.rules.rollups
        return tuple(np.asarray(a) for a in jax.device_get(
            (ro.wid[p], ro.cnt[p], ro.vsum[p], ro.vmin[p], ro.vmax[p])))

    def tenant_pipeline_counters(self) -> dict[str, dict[str, int]]:
        """The device-side per-tenant counter grid (accepted /
        dedup_dropped / geofence_hit / invalid), accumulated inside the
        jit step and read back here on the SCRAPE path only — the ingest
        hot loop never syncs for it. Tenants bucket by ``id % 64``
        (pipeline.TENANT_COUNTER_BUCKETS); quiet buckets are omitted."""
        with self.lock:
            grid = np.asarray(jax.device_get(
                self.state.metrics.tenant_counters))
            if grid.ndim == 3:        # SPMD stacked state: sum over shards
                grid = grid.sum(axis=0)
            return format_tenant_counter_grid(grid, self.tenants)

    def metrics(self) -> dict:
        m = self.state.metrics
        # np.sum-style casts: on the single-chip engine every counter is
        # 0-d (sum is identity); an SPMD engine's stacked [S] counters
        # total over shards, keeping the metrics dict shape identical
        def tot(x) -> int:
            return int(np.asarray(jax.device_get(x)).sum())

        return {
            # host_counters first: a counter can never shadow a core key
            **self.host_counters,
            "processed": tot(m.processed),
            "found": tot(m.found),
            "missed": tot(m.missed),
            "registered": tot(m.registered),
            "persisted": tot(m.persisted),
            "reg_overflow": tot(m.reg_overflow),
            "channel_collisions": self.channel_map.collisions,
            "staged": len(self._buf),
            **({"arena_pool_waits": self._arena_pool.waits,
                "arena_pool_size": self._arena_pool.n_arenas}
               if self._arena_pool is not None else {}),
            **({"ingest_workers": self._sharder.active_workers,
                "sharded_batches": self._sharder.sharded_batches}
               if self._sharder is not None else {}),
            **({"wal_fsyncs": self.wal.fsyncs,
                "wal_commit_groups": self.wal.commit_groups}
               if self.wal is not None and self.wal.group_commit else {}),
            **({"archived_rows": self.archive.total_rows(),
                "archive_lost_rows": self.archive.lost_rows}
               if self.archive is not None else {}),
            # CEP tier: only the PARTITION-INVARIANT counters (fires is
            # a pure function of the event stream; missed/late depend on
            # harvest cadence and live in rule_counters() instead), so
            # metrics() equality across dispatch shapes holds WITH rules
            **({"rule_fires": tot(self.state.rules.rules.fires),
                "rules_active": self.state.rules.rules.n_rules}
               if self.state.rules is not None
               and self.state.rules.rules is not None else {}),
        }
