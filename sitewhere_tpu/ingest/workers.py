"""Multi-core host decode: one wire batch split across decode threads.

SURVEY.md §2.9 maps the reference's replica parallelism (each microservice
scales horizontally behind partitioned Kafka consumer groups) to "multiple
host ingest workers feeding a fixed chip mesh". The single-threaded
ingest path tops out at one core's JSON-scan rate; the
:class:`ShardedArenaDecoder` below cuts each wire batch by payload bytes
and runs the C++ scanner (native/src/swtpu.cpp) on every slice in its own
thread, straight into disjoint rows of the engine's fill arena. The
native scans release the GIL, so the slices decode in parallel; the
engine sizes the fan-out with ``EngineConfig.ingest_workers``.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time

import numpy as np

# One process-wide thread pool behind every engine's sharded decode:
# shard scans release the GIL inside the native call, so the threads are
# fungible across engines, and a shared pool keeps "many engines in one
# test process" from accumulating idle thread stacks.
_shard_pool = None
_shard_pool_lock = threading.Lock()


def _shard_executor():
    global _shard_pool
    if _shard_pool is None:
        with _shard_pool_lock:
            if _shard_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                _shard_pool = ThreadPoolExecutor(
                    max_workers=max(1, (os.cpu_count() or 2) - 1),
                    thread_name_prefix="swtpu-shard")
    return _shard_pool


class ShardedArenaDecoder:
    """In-process sharded arena decode: one wire batch splits across N
    decode workers by payload BYTES (not counts), each worker decoding a
    contiguous payload range into the matching disjoint row range of the
    same :class:`StagingArena` via ``swtpu_shard_decode_arena_pylist``.
    The native scans release the GIL, so shards genuinely parallelize
    across cores; the engine lock (held by the caller) keeps the shared
    interners read-only for the whole call.

    Determinism contract (pinned by tests/test_shard_decode.py): arena
    contents — including interner id assignment — are byte-identical to
    the single-threaded ``NativeBatchDecoder.decode_into`` path. Strings
    not yet in the shared interners go to per-shard OVERLAY tables and
    their uses become patch records; the serial merge interns overlay
    tails in shard order, which IS first-occurrence row order (shards
    are ordered contiguous row ranges, and each overlay assigns local
    ids in first-occurrence order), then applies the patches as
    vectorized scatters. Known divergence: within ONE row, a first-seen
    measurement name whose final lane collides with an already-known
    name's lane applies after the scan (patch order) instead of in key
    order — reachable only under lane aliasing, which the single path
    also mishandles (by aliasing).
    """

    # below this many payloads per shard, thread + merge overhead beats
    # the parallel scan win — the batch decodes single-threaded instead
    min_shard_payloads = 64

    def __init__(self, decoder, n_workers: int):
        if not decoder.has_shard:
            raise RuntimeError("sharded decode entry points unavailable")
        if n_workers < 1:
            raise ValueError("need at least one decode worker")
        self.decoder = decoder
        self.lib = decoder.lib
        self.py_lib = decoder.py_lib
        self.n_workers = n_workers
        self.active_workers = n_workers   # autotuner-adjustable fan-out
        self.last_workers = 1             # shards used by the last batch
        self.sharded_batches = 0
        # span plumbing (ISSUE 10): the engine sets ``tracer`` once and
        # ``current_trace`` per batch (under its lock, which serializes
        # arena decode) so each shard's native scan records a span on
        # the batch's trace — both default off for direct constructors
        self.tracer = None
        self.current_trace: str | None = None
        self._ctxs = [self.lib.swtpu_shard_create(decoder.handle)
                      for _ in range(n_workers)]

    def set_active_workers(self, n: int) -> int:
        """Clamp and apply a new shard fan-out (autotuner hook)."""
        self.active_workers = max(1, min(int(n), self.n_workers))
        return self.active_workers

    # ------------------------------------------------------------- decode
    def decode_into(self, payloads, arena, lo: int,
                    *, binary: bool = False) -> tuple[int, int]:
        """Drop-in for ``NativeBatchDecoder.decode_into`` — same outputs,
        same contract, decoded by up to ``active_workers`` shards."""
        n = len(payloads)
        if lo + n > arena.rows:
            # same guard as the single-threaded contract: short column
            # slices would hand the native scanner pointers it writes past
            raise ValueError(f"{n} payloads exceed arena room "
                             f"{arena.rows - lo}")
        k = min(self.active_workers, n // self.min_shard_payloads)
        if k <= 1 or type(payloads) is not list:
            self.last_workers = 1
            return self.decoder.decode_into(payloads, arena, lo,
                                            binary=binary)
        lens = np.fromiter(map(len, payloads), np.int64, n)
        cum = np.cumsum(lens)
        total = int(cum[-1])
        # contiguous payload ranges cut at ~equal BYTE boundaries: the
        # scan cost tracks bytes, not message counts, and contiguity is
        # what makes shard order == row order (the determinism argument)
        targets = (total * np.arange(1, k)) // k
        cuts = np.searchsorted(cum, targets, side="left") + 1
        bounds = [0]
        for b in cuts:
            b = int(min(b, n))
            if b > bounds[-1]:
                bounds.append(b)
        if bounds[-1] != n:
            bounds.append(n)
        used = len(bounds) - 1
        if used <= 1:
            self.last_workers = 1
            return self.decoder.decode_into(payloads, arena, lo,
                                            binary=binary)
        pool = _shard_executor()
        futs = [
            pool.submit(self._decode_shard, w, payloads, bounds[w],
                        bounds[w + 1] - bounds[w], arena,
                        lo + bounds[w], binary)
            for w in range(1, used)
        ]
        first = self._decode_shard(0, payloads, 0, bounds[1], arena, lo,
                                   binary)
        results = [first] + [f.result() for f in futs]
        if any(r is None for r in results):
            # a shard saw a non-bytes item: redo the whole range through
            # the single path (shards never touched the shared interners,
            # so the retry is side-effect free)
            self.last_workers = 1
            return self.decoder.decode_into(payloads, arena, lo,
                                            binary=binary)
        n_ok = sum(r[0] for r in results)
        collisions = sum(r[1] for r in results)
        ok_drop, extra_coll = self._merge(used, arena, bounds, lo)
        self.last_workers = used
        self.sharded_batches += 1
        return n_ok - ok_drop, collisions + extra_coll

    def _decode_shard(self, w: int, payloads, start: int, cnt: int,
                      arena, row0: int, binary: bool):
        c = ctypes
        collisions = c.c_int32(0)
        t0 = time.perf_counter_ns()
        args = self.decoder.arena_out_args(arena, row0, row0 + cnt,
                                           collisions)
        n_ok = int(self.py_lib.swtpu_shard_decode_arena_pylist(
            self._ctxs[w], payloads, np.int32(start), np.int32(cnt),
            np.int32(self.decoder.channels), *args,
            np.int32(1 if binary else 0)))
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            tracer.record("ingest.shard_decode", t0,
                          time.perf_counter_ns(),
                          trace_id=self.current_trace, shard=w,
                          payloads=cnt)
        if n_ok < 0:
            return None
        return n_ok, int(collisions.value)

    # -------------------------------------------------------------- merge
    def _merge(self, used: int, arena, bounds, lo: int) -> tuple[int, int]:
        """Interner-tail merge + patch application. Serial, under the
        engine lock. Walks shards in order; each shard's first-seen
        strings intern in local-id order — together, exactly the
        single-threaded first-occurrence order. Patch scatters only
        overwrite cells still holding the matching provisional id
        (-2 - idx): a later occurrence of the key may have replaced it.
        Returns (ok_rows_dropped, extra_lane_collisions)."""
        c = ctypes
        lib = self.lib
        dec = self.decoder
        handles = (dec.tokens.handle, dec.names.handle,
                   dec.alert_types.handle, dec.event_ids.handle)
        channels = dec.channels
        sbuf = c.create_string_buffer(1024)
        ok_drop = 0
        extra_coll = 0

        def ptr(a, t):
            return a.ctypes.data_as(c.POINTER(t))

        for w in range(used):
            ctx = self._ctxs[w]
            row0 = lo + bounds[w]
            maps = []
            for kind in range(4):
                cnt = int(lib.swtpu_shard_new_count(ctx, np.int32(kind)))
                m = np.empty(cnt, np.int32)
                for i in range(cnt):
                    ln = int(lib.swtpu_shard_new_string(
                        ctx, np.int32(kind), np.int32(i), sbuf, 1024))
                    m[i] = int(lib.swtpu_intern(
                        handles[kind], sbuf.raw[:ln], np.int32(ln)))
                maps.append(m)
            for kind in range(4):
                pc = int(lib.swtpu_shard_patch_count(ctx, np.int32(kind)))
                if not pc:
                    continue
                rows = np.empty(pc, np.int32)
                idxs = np.empty(pc, np.int32)
                vals = np.empty(pc, np.float32)
                lib.swtpu_shard_patch_fetch(
                    ctx, np.int32(kind), ptr(rows, c.c_int32),
                    ptr(idxs, c.c_int32), ptr(vals, c.c_float))
                rows = rows + np.int32(row0)
                if kind == 0:      # device tokens
                    fin = maps[kind][idxs]
                    cur = arena.token_id[rows]
                    hit = cur == (-2 - idxs)
                    r, f = rows[hit], fin[hit]
                    arena.token_id[r] = f
                    bad = f < 0
                    if bad.any():
                        # interner capacity exhausted during the merge:
                        # the row becomes a decode failure, like the
                        # direct path's interner-full rejection
                        rb = r[bad]
                        ok_drop += int(np.sum(arena.rtype[rb] >= 0))
                        arena.rtype[rb] = -1
                        arena.token_id[rb] = -1
                elif kind == 1:    # measurement names -> value lanes
                    # idx >= 0: overlay id (map via the merged tail,
                    # collision counted here against the final id);
                    # idx < 0: a known name deferred for key-order
                    # replay, final id rides bit-inverted and its
                    # collision was already counted at scan time
                    direct = idxs < 0
                    fin = np.where(direct, ~idxs,
                                   maps[kind][np.where(direct, 0, idxs)])
                    good = fin >= 0
                    extra_coll += int(np.sum(fin[good & ~direct]
                                             >= channels))
                    f = fin[good]
                    # in-order scatter: repeated (row, lane) pairs keep
                    # the LAST write, matching single-threaded key order
                    arena.values[rows[good], f % channels] = vals[good]
                    arena.vmask[rows[good], f % channels] = 1
                else:              # alert types (aux0) / alternate ids (aux1)
                    fin = maps[kind][idxs]
                    lane = 0 if kind == 2 else 1
                    cur = arena.aux[rows, lane]
                    hit = cur == (-2 - idxs)
                    arena.aux[rows[hit], lane] = np.where(
                        fin[hit] >= 0, fin[hit], -1)
        return ok_drop, extra_coll

    def close(self) -> None:
        for ctx in self._ctxs:
            self.lib.swtpu_shard_destroy(ctx)
        self._ctxs = []
