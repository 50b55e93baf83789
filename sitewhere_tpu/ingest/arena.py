"""Zero-copy ingest staging arenas.

The legacy batch path moves every decoded event through three host
buffers before the chip sees it: the decoder allocates fresh SoA output
arrays, ``_ingest_decoded`` copies the accepted rows into the
``HostEventBuffer``, and ``emit()`` re-allocates the buffer for the next
batch. On a 1-core driver those copies and allocations are a large slice
of the ~30x gap between the fused device step and the host e2e rate
(ISSUE 2; measured before PR 2 on a runtime that is gone).

A :class:`StagingArena` is ONE preallocated SoA buffer holding both the
decoder's scratch columns (``rtype``/``ts64``/``level``) and the final
``EventBatch`` columns. The native scanner writes straight into the
final columns (``swtpu_decode_arena_*`` entry points take the arena's
column slices, including a strided ``aux[:, 0]`` lane), the commit pass
runs a handful of vectorized in-place transforms (type map, timestamp
relativization, alert-level fold), and the dispatch hands the SAME
arrays to the jit step — zero row-level Python, zero staging copies,
zero per-batch allocation.

The :class:`ArenaPool` rotates a small fixed set of arenas through
in-flight dispatches: an arena is recycled only once the step output it
fed reports ready (``jax.block_until_ready``), which guarantees the
host->device transfer of its arrays has completed — mutating a numpy
buffer while a transfer is still reading it would corrupt the batch.
With ``dispatch_depth`` >= 2 and ``n_arenas`` > depth, decode of batch
N+1 overlaps transfer/execution of batch N. An exhausted pool blocks on
the OLDEST in-flight dispatch (backpressure, counted in
``waits``) rather than allocating.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from sitewhere_tpu.core.events import EventBatch
from sitewhere_tpu.core.types import AUX_LANES, NULL_ID


class ArenaStallError(RuntimeError):
    """``ArenaPool.acquire`` gave up waiting on a wedged in-flight
    dispatch (``timeout_s`` exceeded). Raised LOUDLY instead of hanging
    the ingest thread under the engine lock forever; the engine
    translates it to a shed + counter (ISSUE 9)."""


class StagingArena:
    """One preallocated SoA staging buffer of ``rows`` event slots.

    ``rows`` is ``batch_capacity * scan_chunk``: with ``scan_chunk`` K > 1
    the arena is consumed as K scan lanes of ``rows // K`` by the arena
    scan step (``pipeline.make_arena_scan_step``) — the ``seq`` column is
    pre-tiled per lane. ``cursor`` is the fill position; rows past the
    cursor at dispatch are masked invalid (free padding)."""

    __slots__ = ("rows", "channels", "lanes", "cursor", "traces",
                 "valid", "etype", "token_id", "tenant_id", "ts_ms",
                 "received_ms", "values", "vmask", "aux", "seq",
                 "rtype", "ts64", "level")

    def __init__(self, rows: int, channels: int, lanes: int = 1):
        if rows % max(1, lanes):
            raise ValueError(f"arena rows {rows} not divisible by "
                             f"{lanes} scan lanes")
        self.rows = rows
        self.channels = channels
        self.lanes = max(1, lanes)
        self.cursor = 0
        self.traces: list = []   # flight records of batches staged here
        # final EventBatch columns (the decoder + commit write these)
        self.valid = np.zeros(rows, np.bool_)
        self.etype = np.zeros(rows, np.int32)
        self.token_id = np.full(rows, NULL_ID, np.int32)
        self.tenant_id = np.full(rows, NULL_ID, np.int32)
        self.ts_ms = np.zeros(rows, np.int32)
        self.received_ms = np.zeros(rows, np.int32)
        self.values = np.zeros((rows, channels), np.float32)
        # uint8 storage, viewed as bool for the EventBatch (same layout);
        # the native decoder ABI wants uint8
        self.vmask = np.zeros((rows, channels), np.uint8)
        self.aux = np.full((rows, AUX_LANES), NULL_ID, np.int32)
        self.seq = np.tile(np.arange(rows // self.lanes, dtype=np.int32),
                           self.lanes)
        # decoder scratch columns (host-only, never transferred)
        self.rtype = np.empty(rows, np.int32)
        self.ts64 = np.empty(rows, np.int64)
        self.level = np.empty(rows, np.int32)

    @property
    def room(self) -> int:
        return self.rows - self.cursor

    @property
    def nbytes(self) -> int:
        """Host bytes this arena pins (data columns AND decoder scratch —
        all preallocated for the arena's lifetime; the memory ledger's
        per-arena unit). Derived from the array-valued slots so a future
        column is counted the day it is added."""
        return sum(v.nbytes for name in self.__slots__
                   if isinstance((v := getattr(self, name)), np.ndarray))

    def view_batch(self) -> EventBatch:
        """The full-capacity numpy-backed EventBatch over the arena's
        arrays (no copies; rows past the cursor must already be masked
        invalid by the dispatcher)."""
        return EventBatch(
            valid=self.valid,
            etype=self.etype,
            token_id=self.token_id,
            tenant_id=self.tenant_id,
            ts_ms=self.ts_ms,
            received_ms=self.received_ms,
            values=self.values,
            vmask=self.vmask.view(np.bool_),
            aux=self.aux,
            seq=self.seq,
        )

    def reset(self) -> None:
        """Make the arena fillable again. Stale column contents are inert
        (every row is dead until the next commit sets its ``valid``); the
        valid mask itself is cleared so a stale True can never leak
        through a partial dispatch."""
        self.cursor = 0
        self.valid[:] = False
        self.traces = []


class ShardedStagingArena:
    """Stacked ``[n_shards, rows]`` staging arena for the SPMD engine.

    Each shard owns one contiguous lane of ``rows`` slots (C-order, so a
    lane is one flat memcpy-able slab) with its own fill ``cursors[s]``;
    the batch-decode path scatters routed rows into the lanes and
    ``view_batch()`` hands the SAME arrays to the shard_mapped fused step
    as a stacked EventBatch whose leading axis matches the mesh sharding.
    With ``lanes`` (= scan_chunk) K > 1 each shard's lane is consumed as
    K scan chunks of ``rows // K`` by the packed sharded scan step.

    No decoder scratch columns: the SPMD path runs the commit transforms
    on the decoder's flat SoA output BEFORE the scatter, so only final
    EventBatch columns live here."""

    __slots__ = ("n_shards", "rows", "channels", "lanes", "cursors",
                 "traces", "valid", "etype", "token_id", "tenant_id",
                 "ts_ms", "received_ms", "values", "vmask", "aux", "seq")

    def __init__(self, n_shards: int, rows: int, channels: int,
                 lanes: int = 1):
        if rows % max(1, lanes):
            raise ValueError(f"arena rows {rows} not divisible by "
                             f"{lanes} scan lanes")
        self.n_shards = n_shards
        self.rows = rows
        self.channels = channels
        self.lanes = max(1, lanes)
        self.cursors = np.zeros(n_shards, np.int64)
        self.traces: list = []
        s = n_shards
        self.valid = np.zeros((s, rows), np.bool_)
        self.etype = np.zeros((s, rows), np.int32)
        self.token_id = np.full((s, rows), NULL_ID, np.int32)
        self.tenant_id = np.full((s, rows), NULL_ID, np.int32)
        self.ts_ms = np.zeros((s, rows), np.int32)
        self.received_ms = np.zeros((s, rows), np.int32)
        self.values = np.zeros((s, rows, channels), np.float32)
        self.vmask = np.zeros((s, rows, channels), np.uint8)
        self.aux = np.full((s, rows, AUX_LANES), NULL_ID, np.int32)
        self.seq = np.tile(
            np.tile(np.arange(rows // self.lanes, dtype=np.int32),
                    self.lanes), (s, 1))

    @property
    def cursor(self) -> int:
        """Total staged rows across every shard lane (the single-arena
        ``cursor`` seam: flush/quiesce callers only test truthiness)."""
        return int(self.cursors.sum())

    @property
    def nbytes(self) -> int:
        return sum(v.nbytes for name in self.__slots__
                   if isinstance((v := getattr(self, name)), np.ndarray))

    def view_batch(self) -> EventBatch:
        """The stacked ``[n_shards, rows]`` EventBatch over the arena's
        arrays (no copies; lanes past each shard's cursor must already be
        masked invalid by the dispatcher)."""
        return EventBatch(
            valid=self.valid,
            etype=self.etype,
            token_id=self.token_id,
            tenant_id=self.tenant_id,
            ts_ms=self.ts_ms,
            received_ms=self.received_ms,
            values=self.values,
            vmask=self.vmask.view(np.bool_),
            aux=self.aux,
            seq=self.seq,
        )

    def reset(self) -> None:
        self.cursors[:] = 0
        self.valid[:] = False
        self.traces = []


class ArenaPool:
    """Fixed pool of staging arenas rotating through in-flight dispatches.

    Not thread-safe by itself — the engine serializes acquire/retire
    under its lock (the same discipline as every other staging mutation).
    ``factory`` swaps the arena type (the SPMD engine pools
    :class:`ShardedStagingArena`); the pool itself only needs ``reset()``
    and ``nbytes`` from its arenas."""

    def __init__(self, n_arenas: int, rows: int, channels: int,
                 lanes: int = 1, factory=None):
        if n_arenas < 1:
            raise ValueError("arena pool needs at least one arena")
        self.n_arenas = n_arenas
        make = factory or (lambda: StagingArena(rows, channels, lanes))
        self._free: list = [make() for _ in range(n_arenas)]
        # (arena, ticket): ticket is any array from the dispatch that fed
        # on the arena; ticket-ready implies the transfer out of the
        # arena's host buffers has completed
        self._inflight: collections.deque = collections.deque()
        self.waits = 0   # times acquire had to block on the oldest dispatch
        self._occupancy_hwm = 0   # max arenas simultaneously out of the
                                  # free list (capacity headroom, ISSUE 11)
        # per-arena footprint cached at construction: nbytes must hold
        # even at the instant every arena is checked out (fill arena +
        # in-flight dispatches can empty both lists)
        self._arena_nbytes = self._free[0].nbytes

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def inflight_count(self) -> int:
        return len(self._inflight)

    @property
    def nbytes(self) -> int:
        """Host bytes held by the pool's staging buffers (free, filling
        and in-flight arenas all stay allocated for the pool's lifetime
        — the memory-ledger component; sized from construction-time
        geometry, so it holds even when every arena is checked out)."""
        return self.n_arenas * self._arena_nbytes

    def take_occupancy_hwm(self, reset: bool = True) -> int:
        """Max arenas simultaneously out of the free pool since the last
        reset. The Prometheus scrape resets it (each sample = worst case
        this window); peeks pass ``reset=False``."""
        current = self.n_arenas - len(self._free)
        hwm = max(self._occupancy_hwm, current)
        if reset:
            self._occupancy_hwm = current
        return hwm

    def acquire(self, timeout_s: float | None = None):
        """A fillable arena; blocks on the oldest in-flight dispatch when
        every arena is tied up (ingest backpressure). With ``timeout_s``
        the block is BOUNDED: a dispatch that never completes (wedged
        device runtime, dead transfer stream) raises a typed
        :class:`ArenaStallError` instead of hanging the ingest thread
        silently — the caller sheds the batch and the failure is
        visible."""
        self._reclaim_ready()
        if not self._free:
            self.waits += 1
            self._reclaim_oldest(timeout_s)
        arena = self._free.pop()
        occupied = self.n_arenas - len(self._free)
        if occupied > self._occupancy_hwm:
            self._occupancy_hwm = occupied
        return arena

    def retire(self, arena, ticket, traces: list = ()) -> None:
        """Hand a dispatched arena back; it recycles once ``ticket`` is
        ready. ``traces`` are the flight records of the batches it
        carried — the recycle wait already observes the step output, so
        stamping their ``device_ready`` here costs no extra sync."""
        self._inflight.append((arena, ticket, tuple(traces)))

    @staticmethod
    def _mark_ready(traces) -> None:
        # overwrite, like every other stage mark: a batch spanning
        # several arenas keeps the LAST chunk's readiness, matching its
        # last-dispatch stamp (drain's backfill, by contrast, only fills
        # the stage when no reclaim ever observed it)
        for rec in traces:
            rec.mark("device_ready")

    def _reclaim_oldest(self, timeout_s: float | None = None) -> None:
        import jax

        if timeout_s is not None:
            # bounded wait: poll the ticket's readiness (jax has no timed
            # block) and refuse to pop an arena we may never get back. A
            # ticket without is_ready (plain numpy in tests) is treated
            # as ready — block_until_ready returns immediately for it.
            ticket = self._inflight[0][1]
            is_ready = getattr(ticket, "is_ready", None)
            deadline = time.monotonic() + timeout_s
            while is_ready is not None and not is_ready():
                if time.monotonic() >= deadline:
                    raise ArenaStallError(
                        f"arena recycle stalled: oldest of "
                        f"{len(self._inflight)} in-flight dispatch(es) "
                        f"not ready after {timeout_s:.3f}s")
                time.sleep(min(0.001, timeout_s / 10))
        arena, ticket, traces = self._inflight.popleft()
        jax.block_until_ready(ticket)
        self._mark_ready(traces)
        arena.reset()
        self._free.append(arena)

    def _reclaim_ready(self) -> None:
        """Opportunistically recycle arenas whose dispatches already
        finished (no blocking)."""
        while self._inflight:
            ticket = self._inflight[0][1]
            is_ready = getattr(ticket, "is_ready", None)
            if is_ready is None or not is_ready():
                return
            arena, _, traces = self._inflight.popleft()
            self._mark_ready(traces)
            arena.reset()
            self._free.append(arena)

    def drain(self) -> None:
        """Block until every in-flight arena is reclaimable (shutdown /
        test barrier)."""
        while self._inflight:
            self._reclaim_oldest()
