"""Native batch JSON decoder: list-of-payloads -> SoA arrays in one C call.

This is the performance path for the ingest edge (SURVEY.md §3.2 hot loop
"decode" — the reference runs Jackson per message on the JVM). Payloads are
concatenated into one buffer, the C++ scanner fills numpy arrays directly,
and device tokens / measurement names / alert types come back as interned
int32 ids ready for EventBatch packing. Falls back to the pure-Python
JsonDeviceRequestDecoder when the native library is unavailable.
"""

from __future__ import annotations

import ctypes
from typing import NamedTuple

import numpy as np

from sitewhere_tpu.native.binding import (NativeInterner, load_library,
                                          load_py_library)

# native rtype codes (swtpu.cpp ReqType) -> core EventType / registration
RT_REGISTER = 0
RT_MEASUREMENT = 1
RT_LOCATION = 2
RT_ALERT = 3
RT_STATE_CHANGE = 4
RT_ACK = 5
RT_MAP = 6   # MapDevice envelopes take the host slow path (like REGISTER)

# map native rtype -> core EventType ordinal (EventType in core/types.py)
RTYPE_TO_ETYPE = np.full(8, -1, np.int32)
RTYPE_TO_ETYPE[RT_MEASUREMENT] = 0
RTYPE_TO_ETYPE[RT_LOCATION] = 1
RTYPE_TO_ETYPE[RT_ALERT] = 2
RTYPE_TO_ETYPE[RT_ACK] = 4
RTYPE_TO_ETYPE[RT_STATE_CHANGE] = 5


class DecodedArrays(NamedTuple):
    n_ok: int
    rtype: np.ndarray      # int32[N] native request type (-1 = decode failed)
    token_id: np.ndarray   # int32[N]
    ts_ms64: np.ndarray    # int64[N] epoch ms (-1 = absent)
    values: np.ndarray     # float32[N, C]
    chmask: np.ndarray     # bool[N, C]
    aux0: np.ndarray       # int32[N] alert-type id
    aux1: np.ndarray       # int32[N] alternate-id (event-id interner; -1 none)
    level: np.ndarray      # int32[N] alert level
    collisions: int


class NativeBatchDecoder:
    """Holds the C++ decoder + its interners. The token interner is shared
    with the engine (ids must be the engine's ids); the event-id interner
    (alternate/correlation ids, the aux1 lane) is decoder-owned and the
    engine ADOPTS it as ``event_ids`` so the batch path and the
    per-request path assign the same ids."""

    def __init__(self, token_interner: NativeInterner, channels: int,
                 name_capacity: int = 1 << 20, alert_capacity: int = 1 << 16,
                 event_capacity: int = 1 << 22):
        self.lib = load_library()
        if self.lib is None:
            raise RuntimeError("native library unavailable")
        self.tokens = token_interner
        self.channels = channels
        self.handle = self.lib.swtpu_decoder_create(
            token_interner.handle, name_capacity, alert_capacity,
            event_capacity
        )
        self.names = NativeInterner(
            name_capacity, self.lib, self.lib.swtpu_decoder_names(self.handle)
        )
        self.alert_types = NativeInterner(
            alert_capacity, self.lib, self.lib.swtpu_decoder_alert_types(self.handle)
        )
        self.event_ids = NativeInterner(
            event_capacity, self.lib,
            self.lib.swtpu_decoder_event_ids(self.handle)
        )
        # zero-copy list[bytes] entry point (libswtpu_py.so): skips the
        # b"".join + per-payload length scan + offsets cumsum the packed
        # ABI makes Python pay per batch (~1ms of a 16k batch on the
        # 1-core host). None -> packed path.
        self.py_lib = load_py_library()

    def decode(self, payloads: list[bytes]) -> DecodedArrays:
        """Batched JSON DeviceRequest decode. No thread may mutate
        ``payloads`` until the call returns (the zero-copy path scans
        the payload buffers in place)."""
        return self._decode(payloads, binary=False)

    def decode_binary(self, payloads: list[bytes]) -> DecodedArrays:
        """Batched flat-binary decode (the "protobuf" ingest slot; wire
        format of ingest/decoders.py encode_binary_request). Same
        no-concurrent-mutation contract as :meth:`decode`."""
        return self._decode(payloads, binary=True)

    def _decode_pylist(self, payloads: list[bytes],
                       binary: bool) -> "DecodedArrays | None":
        """List-direct decode; None = not eligible (fall back packed)."""
        if self.py_lib is None or type(payloads) is not list:
            return None
        n = len(payloads)
        c = self.channels
        rtype = np.empty(n, np.int32)
        token = np.empty(n, np.int32)
        ts = np.empty(n, np.int64)
        values = np.empty((n, c), np.float32)
        chmask = np.empty((n, c), np.uint8)
        aux0 = np.empty(n, np.int32)
        aux1 = np.empty(n, np.int32)
        level = np.empty(n, np.int32)
        collisions = ctypes.c_int32(0)

        def ptr(a, t):
            return a.ctypes.data_as(ctypes.POINTER(t))

        n_ok = int(self.py_lib.swtpu_decode_pylist(
            self.handle, payloads, np.int32(n), np.int32(c),
            ptr(rtype, ctypes.c_int32), ptr(token, ctypes.c_int32),
            ptr(ts, ctypes.c_int64), ptr(values, ctypes.c_float),
            ptr(chmask, ctypes.c_uint8), ptr(aux0, ctypes.c_int32),
            ptr(aux1, ctypes.c_int32),
            ptr(level, ctypes.c_int32), ctypes.byref(collisions),
            np.int32(1 if binary else 0)))
        if n_ok < 0:
            return None   # non-bytes item: packed path handles/raises
        return DecodedArrays(
            n_ok=n_ok, rtype=rtype, token_id=token, ts_ms64=ts,
            values=values, chmask=chmask.view(bool), aux0=aux0, aux1=aux1,
            level=level, collisions=int(collisions.value))

    def decode_packed(self, buf, offsets: np.ndarray, n: int,
                      rtype: np.ndarray, token: np.ndarray, ts: np.ndarray,
                      values: np.ndarray, chmask: np.ndarray,
                      aux0: np.ndarray, aux1: np.ndarray, level: np.ndarray,
                      *, binary: bool = False) -> tuple[int, int]:
        """One scanner call over an already-concatenated wire batch
        (``offsets`` int64[>=n+1]; output arrays sized >= n rows). THE
        single marshalling site for swtpu_decode_*_batch, so a signature
        change has one home.
        Returns (n_ok, channel_collisions)."""
        collisions = ctypes.c_int32(0)

        def ptr(a, t):
            return a.ctypes.data_as(ctypes.POINTER(t))

        fn = (self.lib.swtpu_decode_binary_batch if binary
              else self.lib.swtpu_decode_batch)
        n_ok = int(fn(
            self.handle, buf, ptr(offsets, ctypes.c_int64),
            np.int32(n), np.int32(self.channels),
            ptr(rtype, ctypes.c_int32), ptr(token, ctypes.c_int32),
            ptr(ts, ctypes.c_int64),
            ptr(values, ctypes.c_float), ptr(chmask, ctypes.c_uint8),
            ptr(aux0, ctypes.c_int32), ptr(aux1, ctypes.c_int32),
            ptr(level, ctypes.c_int32),
            ctypes.byref(collisions),
        ))
        return n_ok, int(collisions.value)

    @property
    def has_arena(self) -> bool:
        """Arena-fill entry points present in the loaded libraries."""
        return bool(getattr(self.lib, "_swtpu_has_arena", False))

    @property
    def has_shard(self) -> bool:
        """Sharded (multi-worker) arena-decode entry points present in
        BOTH libraries (the ShardCtx ABI lives in libswtpu.so, the
        ranged list decode in libswtpu_py.so)."""
        return bool(getattr(self.lib, "_swtpu_has_shard", False)
                    and self.py_lib is not None
                    and getattr(self.py_lib, "_swtpu_has_shard", False))

    @staticmethod
    def arena_out_args(arena, lo: int, hi: int, collisions):
        """The output-pointer argument tail shared by the arena and
        shard decode entry points: every output aims at the arena's own
        column slices for rows [lo, hi), with the aux lanes strided."""
        c = ctypes

        def ptr(a, t):
            return a.ctypes.data_as(c.POINTER(t))

        stride = c.c_int64(arena.aux.shape[1])
        return (
            ptr(arena.rtype[lo:hi], c.c_int32),
            ptr(arena.token_id[lo:hi], c.c_int32),
            ptr(arena.ts64[lo:hi], c.c_int64),
            ptr(arena.values[lo:hi], c.c_float),
            ptr(arena.vmask[lo:hi], c.c_uint8),
            ptr(arena.aux[lo:hi], c.c_int32), stride,
            ptr(arena.aux[lo:hi, 1:], c.c_int32), stride,
            ptr(arena.level[lo:hi], c.c_int32),
            c.byref(collisions),
        )

    def decode_into(self, payloads: list[bytes], arena, lo: int,
                    *, binary: bool = False) -> tuple[int, int]:
        """Decode ``payloads`` straight into ``arena`` rows
        [lo, lo + len(payloads)): the scanner's outputs are the arena's
        own column slices (zero-copy staging; the aux[:, 0] / aux[:, 1]
        lanes are written strided in place). Same no-concurrent-mutation
        contract as :meth:`decode`. Returns (n_ok, channel_collisions)."""
        n = len(payloads)
        hi = lo + n
        if hi > arena.rows:
            raise ValueError(f"{n} payloads exceed arena room "
                             f"{arena.rows - lo}")
        c = ctypes
        collisions = c.c_int32(0)

        def ptr(a, t):
            return a.ctypes.data_as(c.POINTER(t))

        args = self.arena_out_args(arena, lo, hi, collisions) \
            + (np.int32(1 if binary else 0),)
        if (self.py_lib is not None and type(payloads) is list
                and getattr(self.py_lib, "_swtpu_has_arena", False)):
            n_ok = int(self.py_lib.swtpu_decode_arena_pylist(
                self.handle, payloads, np.int32(n),
                np.int32(self.channels), *args))
            if n_ok >= 0:
                return n_ok, int(collisions.value)
        # packed fallback (also covers non-list iterables of bytes)
        payloads = list(payloads)
        buf = b"".join(payloads)
        offsets = np.zeros(n + 1, np.int64)
        np.cumsum(np.fromiter(map(len, payloads), np.int64, n),
                  out=offsets[1:])
        n_ok = int(self.lib.swtpu_decode_arena_batch(
            self.handle, buf, ptr(offsets, c.c_int64), np.int32(n),
            np.int32(self.channels), *args))
        return n_ok, int(collisions.value)

    def _decode(self, payloads: list[bytes], binary: bool) -> DecodedArrays:
        fast = self._decode_pylist(payloads, binary=binary)
        if fast is not None:
            return fast
        n = len(payloads)
        c = self.channels
        buf = b"".join(payloads)
        offsets = np.zeros(n + 1, np.int64)
        # map(len) iterates at C level; fromiter keeps cumsum on the fast
        # ndarray path (a list argument routes numpy through the boxed
        # _wrapit fallback — measured ~20% of the non-scanner decode
        # overhead at 16k-payload batches)
        np.cumsum(np.fromiter(map(len, payloads), np.int64, n),
                  out=offsets[1:])
        rtype = np.empty(n, np.int32)
        token = np.empty(n, np.int32)
        ts = np.empty(n, np.int64)
        values = np.empty((n, c), np.float32)
        chmask = np.empty((n, c), np.uint8)
        aux0 = np.empty(n, np.int32)
        aux1 = np.empty(n, np.int32)
        level = np.empty(n, np.int32)
        n_ok, collisions = self.decode_packed(
            buf, offsets, n, rtype, token, ts, values, chmask, aux0, aux1,
            level, binary=binary)
        return DecodedArrays(
            n_ok=n_ok, rtype=rtype, token_id=token, ts_ms64=ts,
            values=values, chmask=chmask.view(bool), aux0=aux0, aux1=aux1,
            level=level, collisions=collisions,
        )


def native_available() -> bool:
    return load_library() is not None
