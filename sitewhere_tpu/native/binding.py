"""ctypes binding + on-demand build of the native host data-plane (swtpu).

Builds native/src/swtpu.cpp with g++ -O3 on first use (cached in
native/build/ under a key of sources, command, compiler and host CPU).
Falls back cleanly: ``load_library()`` returns None when no compiler is
available, and callers (ingest/fast_decode.py, engine interners)
use the pure-Python path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import pathlib
import platform
import subprocess
import threading

logger = logging.getLogger(__name__)

_REPO = pathlib.Path(__file__).resolve().parents[2]
_SRC = _REPO / "native" / "src" / "swtpu.cpp"
_PY_SRC = _REPO / "native" / "src" / "swtpu_py.cpp"
_BUILD = _REPO / "native" / "build"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
_py_lib = None
_py_tried = False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.swtpu_interner_create.restype = c.c_void_p
    lib.swtpu_interner_create.argtypes = [c.c_int32]
    lib.swtpu_interner_destroy.argtypes = [c.c_void_p]
    lib.swtpu_intern.restype = c.c_int32
    lib.swtpu_intern.argtypes = [c.c_void_p, c.c_char_p, c.c_int32]
    lib.swtpu_interner_lookup.restype = c.c_int32
    lib.swtpu_interner_lookup.argtypes = [c.c_void_p, c.c_char_p, c.c_int32]
    lib.swtpu_interner_size.restype = c.c_int32
    lib.swtpu_interner_size.argtypes = [c.c_void_p]
    lib.swtpu_interner_get.restype = c.c_int32
    lib.swtpu_interner_get.argtypes = [c.c_void_p, c.c_int32, c.c_char_p, c.c_int32]
    lib.swtpu_interner_truncate.argtypes = [c.c_void_p, c.c_int32]
    lib.swtpu_decoder_create.restype = c.c_void_p
    lib.swtpu_decoder_create.argtypes = [c.c_void_p, c.c_int32, c.c_int32,
                                         c.c_int32]
    lib.swtpu_decoder_destroy.argtypes = [c.c_void_p]
    lib.swtpu_decoder_names.restype = c.c_void_p
    lib.swtpu_decoder_names.argtypes = [c.c_void_p]
    lib.swtpu_decoder_alert_types.restype = c.c_void_p
    lib.swtpu_decoder_alert_types.argtypes = [c.c_void_p]
    lib.swtpu_decoder_event_ids.restype = c.c_void_p
    lib.swtpu_decoder_event_ids.argtypes = [c.c_void_p]
    lib.swtpu_decode_batch.restype = c.c_int32
    lib.swtpu_decode_batch.argtypes = [
        c.c_void_p,                      # decoder
        c.c_char_p,                      # buf
        c.POINTER(c.c_int64),            # offsets
        c.c_int32, c.c_int32,            # n_msgs, channels
        c.POINTER(c.c_int32),            # out_rtype
        c.POINTER(c.c_int32),            # out_token
        c.POINTER(c.c_int64),            # out_ts
        c.POINTER(c.c_float),            # out_values
        c.POINTER(c.c_uint8),            # out_chmask
        c.POINTER(c.c_int32),            # out_aux0
        c.POINTER(c.c_int32),            # out_aux1
        c.POINTER(c.c_int32),            # out_level
        c.POINTER(c.c_int32),            # out_collisions
    ]
    lib.swtpu_decode_binary_batch.restype = c.c_int32
    lib.swtpu_decode_binary_batch.argtypes = lib.swtpu_decode_batch.argtypes
    try:
        # arena-fill entry point (strided aux columns + json/binary flag);
        # absent only in a stale prebuilt library — the arena ingest path
        # then stays off while everything else keeps working
        lib.swtpu_decode_arena_batch.restype = c.c_int32
        lib.swtpu_decode_arena_batch.argtypes = [
            c.c_void_p, c.c_char_p, c.POINTER(c.c_int64),
            c.c_int32, c.c_int32,
            c.POINTER(c.c_int32), c.POINTER(c.c_int32),
            c.POINTER(c.c_int64), c.POINTER(c.c_float),
            c.POINTER(c.c_uint8),
            c.POINTER(c.c_int32), c.c_int64,     # aux0 + stride
            c.POINTER(c.c_int32), c.c_int64,     # aux1 + stride
            c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.c_int32,
        ]
        lib._swtpu_has_arena = True
    except AttributeError:
        lib._swtpu_has_arena = False
    try:
        # sharded-decode context ABI (multi-worker arena decode)
        lib.swtpu_shard_create.restype = c.c_void_p
        lib.swtpu_shard_create.argtypes = [c.c_void_p]
        lib.swtpu_shard_destroy.argtypes = [c.c_void_p]
        lib.swtpu_shard_reset.argtypes = [c.c_void_p]
        lib.swtpu_shard_new_count.restype = c.c_int32
        lib.swtpu_shard_new_count.argtypes = [c.c_void_p, c.c_int32]
        lib.swtpu_shard_new_string.restype = c.c_int32
        lib.swtpu_shard_new_string.argtypes = [
            c.c_void_p, c.c_int32, c.c_int32, c.c_char_p, c.c_int32]
        lib.swtpu_shard_patch_count.restype = c.c_int32
        lib.swtpu_shard_patch_count.argtypes = [c.c_void_p, c.c_int32]
        lib.swtpu_shard_patch_fetch.argtypes = [
            c.c_void_p, c.c_int32, c.POINTER(c.c_int32),
            c.POINTER(c.c_int32), c.POINTER(c.c_float)]
        lib._swtpu_has_shard = True
    except AttributeError:
        lib._swtpu_has_shard = False
    return lib


def _host_cpu() -> str:
    """What ``-march=native`` resolves from: the CPU's vendor, model and
    feature flags (first core of /proc/cpuinfo)."""
    seen: dict[str, str] = {}
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key, _, val = line.partition(":")
                key = key.strip()
                if key in ("vendor_id", "model name", "flags", "Features",
                           "CPU implementer", "CPU part"):
                    seen.setdefault(key, val.strip())
    except OSError:
        pass
    return f"{platform.machine()}|{sorted(seen.items())}"


def _build(stem: str, cmd: list[str], fail_level: int):
    """Compile ``cmd`` into ``native/build/<stem>-<key>.so``. The key hashes
    every source under native/src, the command, the compiler's version and
    the host CPU, so a library built from other sources, by another
    compiler or for another CPU is never loaded: it is rebuilt. The link
    writes a temp file that RENAMES over the target: a process that
    already dlopen'd an older build keeps its mapping of that inode."""
    h = hashlib.sha256()
    for src in sorted(_SRC.parent.iterdir()):
        if src.is_file():
            h.update(src.name.encode() + b"\0" + src.read_bytes())
    h.update("\0".join(cmd).encode())
    try:
        h.update(subprocess.run([cmd[0], "--version"], capture_output=True,
                                check=True).stdout)
    except (subprocess.CalledProcessError, OSError) as e:
        logger.log(fail_level, "native build unavailable (%s); "
                   "using Python fallback", e)
        return None
    h.update(_host_cpu().encode())
    so = _BUILD / f"{stem}-{h.hexdigest()[:16]}.so"
    if so.exists():
        return so
    _BUILD.mkdir(parents=True, exist_ok=True)
    tmp = so.with_suffix(f".tmp{os.getpid()}.so")
    try:
        subprocess.run(cmd + ["-o", str(tmp)], check=True,
                       capture_output=True, text=True)
        tmp.rename(so)
    except (subprocess.CalledProcessError, OSError) as e:
        logger.log(fail_level, "native build of %s failed (%s); using "
                   "Python fallback", stem, getattr(e, "stderr", e))
        tmp.unlink(missing_ok=True)
        return None
    return so


def build_library() -> pathlib.Path | None:
    """Build (or find the matching build of) the shared library."""
    return _build("libswtpu", ["g++", "-O3", "-march=native", "-shared",
                               "-fPIC", "-std=c++17", str(_SRC)],
                  logging.WARNING)


def build_py_library() -> pathlib.Path | None:
    """Build the CPython-aware variant (list[bytes] decode entry point;
    native/src/swtpu_py.cpp). Optional: failure only loses the
    zero-copy path, never the base library."""
    import sysconfig

    if not _PY_SRC.exists():
        return None
    return _build("libswtpu_py", [
        "g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
        f"-I{sysconfig.get_path('include')}", f"-I{_SRC.parent}",
        str(_PY_SRC)], logging.INFO)


def load_library() -> ctypes.CDLL | None:
    """Build (if needed) and load libswtpu; None when unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = build_library()
        if so is None:
            return None
        try:
            _lib = _configure(ctypes.CDLL(str(so)))
        except OSError as e:
            logger.warning("failed to load %s: %s", so, e)
            _lib = None
        return _lib


def load_py_library() -> "ctypes.PyDLL | None":
    """The CPython-aware lib, loaded as PyDLL (its list entry point runs
    under the GIL until it drops it itself). None = use the packed ABI."""
    global _py_lib, _py_tried
    with _lock:
        if _py_lib is not None or _py_tried:
            return _py_lib
        _py_tried = True
        so = build_py_library()
        if so is None:
            return None
        try:
            # configure ONLY the list entry point: this handle holds the
            # GIL for every call, so the packed batch functions must
            # never be reached through it (they'd serialize the whole
            # scan under the GIL — use the CDLL handle for those)
            lib = ctypes.PyDLL(str(so))
            c = ctypes
            lib.swtpu_decode_pylist.restype = c.c_int32
            lib.swtpu_decode_pylist.argtypes = [
                c.c_void_p, c.py_object, c.c_int32, c.c_int32,
                c.POINTER(c.c_int32), c.POINTER(c.c_int32),
                c.POINTER(c.c_int64), c.POINTER(c.c_float),
                c.POINTER(c.c_uint8), c.POINTER(c.c_int32),
                c.POINTER(c.c_int32),
                c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.c_int32]
            lib.swtpu_route_pylist.restype = c.c_int32
            lib.swtpu_route_pylist.argtypes = [
                c.py_object, c.c_int32, c.c_int32,
                c.POINTER(c.c_int32), c.c_int32]
            try:
                lib.swtpu_decode_arena_pylist.restype = c.c_int32
                lib.swtpu_decode_arena_pylist.argtypes = [
                    c.c_void_p, c.py_object, c.c_int32, c.c_int32,
                    c.POINTER(c.c_int32), c.POINTER(c.c_int32),
                    c.POINTER(c.c_int64), c.POINTER(c.c_float),
                    c.POINTER(c.c_uint8),
                    c.POINTER(c.c_int32), c.c_int64,   # aux0 + stride
                    c.POINTER(c.c_int32), c.c_int64,   # aux1 + stride
                    c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.c_int32]
                lib._swtpu_has_arena = True
            except AttributeError:
                lib._swtpu_has_arena = False
            try:
                # ranged shard decode: list slice [start, start+n) into a
                # disjoint arena row range through a ShardCtx (created by
                # the CDLL handle — pointers are shared across the libs,
                # the established Decoder*-passing pattern)
                lib.swtpu_shard_decode_arena_pylist.restype = c.c_int32
                lib.swtpu_shard_decode_arena_pylist.argtypes = [
                    c.c_void_p, c.py_object, c.c_int32, c.c_int32,
                    c.c_int32,
                    c.POINTER(c.c_int32), c.POINTER(c.c_int32),
                    c.POINTER(c.c_int64), c.POINTER(c.c_float),
                    c.POINTER(c.c_uint8),
                    c.POINTER(c.c_int32), c.c_int64,
                    c.POINTER(c.c_int32), c.c_int64,
                    c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.c_int32]
                lib._swtpu_has_shard = True
            except AttributeError:
                lib._swtpu_has_shard = False
            _py_lib = lib
        except OSError as e:
            logger.info("py-bridge load failed (%s); packed path only", e)
            _py_lib = None
        return _py_lib


def route_payloads(payloads: list[bytes], n_ranks: int,
                   binary: bool = False):
    """Owning rank per payload via the native token-hash router (one C
    call over the whole batch, no decode). Returns an int32 ndarray
    (-1 = unroutable, caller keeps local), or None when the native list
    path is unavailable — the caller falls back to the Python
    partitioner. Byte-exact with parallel/cluster.py:owner_rank."""
    import numpy as np

    lib = load_py_library()
    if lib is None or type(payloads) is not list:
        return None
    n = len(payloads)
    out = np.empty(n, np.int32)
    rc = int(lib.swtpu_route_pylist(
        payloads, np.int32(n), np.int32(n_ranks),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        np.int32(1 if binary else 0)))
    return out if rc == 0 else None


class NativeInterner:
    """TokenInterner-compatible wrapper over the C++ open-addressing table.

    Keeps a lazily-synced Python-side list of strings (ids are dense and
    append-only, so syncing pulls only the tail)."""

    def __init__(self, capacity: int, lib: ctypes.CDLL | None = None,
                 handle: int | None = None):
        self.capacity = capacity
        self.lib = lib or load_library()
        if self.lib is None:
            raise RuntimeError("native library unavailable")
        self.handle = handle if handle is not None else self.lib.swtpu_interner_create(capacity)
        self._tokens: list[str] = []

    def __len__(self) -> int:
        return int(self.lib.swtpu_interner_size(self.handle))

    def intern(self, token: str) -> int:
        b = token.encode()
        tid = int(self.lib.swtpu_intern(self.handle, b, len(b)))
        if tid < 0:
            raise RuntimeError(f"token capacity {self.capacity} exhausted")
        return tid

    def lookup(self, token: str) -> int:
        b = token.encode()
        return int(self.lib.swtpu_interner_lookup(self.handle, b, len(b)))

    def _sync(self) -> None:
        n = len(self)
        buf = ctypes.create_string_buffer(1024)
        while len(self._tokens) < n:
            i = len(self._tokens)
            ln = int(self.lib.swtpu_interner_get(self.handle, i, buf, 1024))
            self._tokens.append(buf.raw[: min(ln, 1024)].decode(errors="replace"))

    def token(self, tid: int) -> str:
        if tid >= len(self._tokens):
            self._sync()
        return self._tokens[tid]

    def truncate(self, n: int) -> None:
        """Roll back to the first ``n`` entries (rejected-batch cleanup)."""
        self.lib.swtpu_interner_truncate(self.handle, n)
        del self._tokens[n:]

    def items(self):
        self._sync()
        return ((s, i) for i, s in enumerate(self._tokens))
