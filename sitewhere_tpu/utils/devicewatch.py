"""Device-plane telemetry (ISSUE 11): the XLA side of the microscope.

PRs 3/7/10 instrumented the HOST (flight records, federated metrics,
span timelines, thread profiler); the XLA programs themselves stayed a
black box — nothing counted compiles or retraces, nothing accounted for
HBM occupancy, nothing exported per-program cost. This module adds the
three missing legs:

* **Compile/retrace watchdog** — :func:`watched_jit` wraps a jitted
  callable in a PASSTHROUGH shim (dispatch semantics untouched; jax's
  own jit cache keeps serving) that tracks the distinct abstract shape
  keys flowing through it. The first dispatch of a globally-new key is
  a compile: its wall time lands in ``swtpu_xla_compile_seconds`` and,
  for cost-enabled families, a lower-only pass captures
  ``cost_analysis`` flops/bytes (once per family by default — the pass
  re-traces, roughly doubling a compiling dispatch; set
  ``SWTPU_XLA_COST=all`` to re-capture on every compile. The AOT query
  path always captures exactly, from its own executable). Every :class:`WatchScope` declares an
  expected-distinct-shape budget (one program per bucket — e.g. one per
  ``(Q bucket, limit bucket)`` for the query path, one per scan_chunk
  program for ingest); a key beyond the budget increments the loud
  ``swtpu_xla_retrace_excess_total``, logs the offending shape diff,
  and in strict mode (``SWTPU_XLA_STRICT=1`` or
  :func:`strict_retraces`) raises :class:`RetraceError` BEFORE
  dispatching — a standing guard for the shape invariants PR 5/10 pin
  by hand.

* **Memory ledger** — :func:`memory_ledger` sizes the ring store,
  registry/state tables, staging-arena pool, archive segment cache and
  process-wide live jax arrays at scrape time (``nbytes`` walk;
  ``device.memory_stats()`` where the backend provides it — TPU yes,
  CPU returns None), exported as ``swtpu_device_mem_*`` gauges and
  served at ``GET /api/instance/device/memory``. High-watermarks
  (arena occupancy, staged backlog) reset on scrape so each sample
  reads "worst case this window".

* **Per-program cost & device time** — ``cost_analysis()`` captured
  once per compile and exported per family; device execution-time
  histograms harvested from the existing flight records at scrape time
  (the hot path pays nothing — see ``metrics.harvest_slo``); and
  :func:`capture_device_profile` wraps ``jax.profiler`` for the
  ``GET /api/instance/profile/device`` endpoint so hardware runs can
  pull real TPU timelines next to the PR-10 Perfetto export.

Nothing here touches ``engine.metrics()`` — the dispatch-shape equality
pin holds with the watchdog enabled, like every plane before it.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
import tempfile
import threading
import time
from typing import Any

import jax

from sitewhere_tpu.utils.metrics import REGISTRY, devicewatch_metrics

log = logging.getLogger(__name__)


class RetraceError(RuntimeError):
    """Strict-mode watchdog verdict: a program family compiled a shape
    beyond its declared budget (shape churn). Raised BEFORE the dispatch
    runs, so donated engine state is never consumed by the offending
    call."""


# --------------------------------------------------------------------------
# Abstract shape keys
# --------------------------------------------------------------------------

def _leaf_desc(leaf):
    """A cheap, stable descriptor for one call-tree leaf: the abstract
    value's (shape, dtype, weak_type) tuple — exactly what decides a jit
    retrace — for arrays and scalars, ``repr`` for static leaves jax
    would hash by value (meshes, configs). Tuples, not formatted
    strings: keys are computed on every watched dispatch, the readable
    form only when a budget violation needs a log line."""
    try:
        aval = jax.core.get_aval(leaf)
        return (tuple(aval.shape), aval.dtype.name,
                bool(getattr(aval, "weak_type", False)))
    except Exception:
        return repr(leaf)[:120]


def _fmt_desc(desc) -> str:
    """Human form of a :func:`_leaf_desc` descriptor for diff logging."""
    if isinstance(desc, tuple) and len(desc) == 3:
        shape, dtype, weak = desc
        dims = ",".join(str(d) for d in shape)
        return f"{dtype}[{dims}]" + ("~weak" if weak else "")
    return str(desc)


def abstract_key(args: tuple, kwargs: dict,
                 statics: tuple = ()) -> tuple | None:
    """The watchdog's shape key for one call: pytree structure hash +
    per-leaf abstract descriptors (+ static values by repr). Returns
    None when any leaf is a tracer — the call is being inlined into an
    enclosing jit trace and must pass through untouched."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    for leaf in leaves:
        if isinstance(leaf, jax.core.Tracer):
            return None
    return (hash(treedef), tuple(_leaf_desc(l) for l in leaves),
            tuple(repr(s) for s in statics))


def _key_diff(old: tuple, new: tuple) -> str:
    """First differing leaves between two shape keys — the "what churned"
    payload of the watchdog's log line."""
    olds, news = old[1], new[1]
    if old[0] != new[0]:
        return ("pytree STRUCTURE changed "
                f"({len(olds)} -> {len(news)} leaves)")
    diffs = [f"leaf[{i}]: {_fmt_desc(a)} -> {_fmt_desc(b)}"
             for i, (a, b) in enumerate(zip(olds, news)) if a != b]
    diffs += [f"static[{i}]: {a} -> {b}"
              for i, (a, b) in enumerate(zip(old[2], new[2])) if a != b]
    return "; ".join(diffs[:6]) + (" ..." if len(diffs) > 6 else "")


def _cost_dict(raw) -> dict | None:
    """Normalize a ``cost_analysis()`` result (dict on some jax builds,
    [dict] on others) to ``{"flops": f, "bytes_accessed": b}``."""
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else None
    if not isinstance(raw, dict):
        return None
    out = {}
    if "flops" in raw:
        out["flops"] = float(raw["flops"])
    if "bytes accessed" in raw:
        out["bytes_accessed"] = float(raw["bytes accessed"])
    return out or None


# --------------------------------------------------------------------------
# Watch core
# --------------------------------------------------------------------------

class _Family:
    """Process-global per-family aggregate: counters, last compile cost,
    the globally-compiled key set (so a second engine reusing jax's warm
    cache counts a HIT, not a compile), and the live scopes whose
    distinct keys sum into ``swtpu_xla_programs_live``."""

    __slots__ = ("name", "compiles", "hits", "excess", "last_cost",
                 "last_compile_s", "keys", "scopes")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.hits = 0
        self.excess = 0
        self.last_cost: dict | None = None
        self.last_compile_s: float | None = None
        self.keys: set = set()           # (fn_id, shape_key) ever compiled
        self.scopes: list = []           # weakrefs, pruned on snapshot


class WatchScope:
    """One watched seam's program book-keeping (per engine program, per
    QueryBatcher, or per module-level kernel): distinct shape keys seen,
    grouped into budget buckets with a per-bucket allowance. The scope —
    not the family — owns the budget, so two engines with different
    store shapes can never trip each other's watchdog."""

    def __init__(self, watch: "DeviceWatch", family: str,
                 allowance: int = 1):
        import weakref

        self.watch = watch
        self.family = family
        self.default_allowance = max(1, int(allowance))
        self._keys: dict[tuple, Any] = {}        # key -> bucket
        self._buckets: dict[Any, list] = {}      # bucket -> [keys]
        self._extra: dict[Any, int] = {}         # bucket -> extra allowance
        fam = watch._family(family)
        fam.scopes.append(weakref.ref(self))

    # ------------------------------------------------------------- budget
    def allow(self, n: int = 1, bucket: Any = "program") -> None:
        """Raise one bucket's allowance — the declaration hook a
        legitimate shape transition calls (``set_geofence_zones``
        recompiles every step family on purpose)."""
        self._extra[bucket] = self._extra.get(bucket, 0) + int(n)

    def _allowance(self, bucket) -> int:
        return self.default_allowance + self._extra.get(bucket, 0)

    @property
    def live_programs(self) -> int:
        return len(self._keys)

    # ------------------------------------------------------------ observe
    def observe(self, key: tuple, bucket: Any, fn_id: int = 0) -> str:
        """Classify one watched call: ``"seen"`` (scope already holds the
        key), ``"hit"`` (new to this scope, but some scope already
        compiled it — jax's cache is warm), or ``"compile"``. Applies the
        budget on scope-new keys; strict violations raise before the key
        registers (so the caller never dispatches)."""
        watch = self.watch
        fam = watch._family(self.family)
        with watch._lock:
            if key in self._keys:
                fam.hits += 1
                watch._inst["hits"].inc(family=self.family)
                return "seen"
            over = None
            if bucket is not None:
                held = self._buckets.setdefault(bucket, [])
                if len(held) >= self._allowance(bucket):
                    over = held[0]
            if over is not None:
                fam.excess += 1
                watch._inst["excess"].inc(family=self.family)
                diff = _key_diff(over, key)
                log.warning(
                    "devicewatch: retrace budget exceeded for family %r "
                    "bucket %r (%d program(s) allowed): %s",
                    self.family, bucket, self._allowance(bucket), diff)
                if watch.strict:
                    raise RetraceError(
                        f"family {self.family!r} bucket {bucket!r} "
                        f"exceeded its {self._allowance(bucket)}-program "
                        f"shape budget: {diff}")
            self._keys[key] = bucket
            if bucket is not None and over is None:
                # excess keys do NOT consume budget: a later allow()
                # re-arms the bucket, and every further distinct churn
                # shape warns again (a storm stays loud per shape)
                self._buckets[bucket].append(key)
            gkey = (fn_id, key)
            if gkey in fam.keys:
                fam.hits += 1
                watch._inst["hits"].inc(family=self.family)
                return "hit"
            fam.keys.add(gkey)
            return "compile"

    def note_compile(self, seconds: float, cost: dict | None) -> None:
        watch = self.watch
        fam = watch._family(self.family)
        with watch._lock:
            fam.compiles += 1
            fam.last_compile_s = seconds
            if cost is not None:
                fam.last_cost = cost
        watch._inst["compiles"].inc(family=self.family)
        watch._inst["compile"].observe(seconds, family=self.family)

    def record_aot(self, key: Any, bucket: Any, seconds: float,
                   compiled=None) -> None:
        """Record an explicit ``lower().compile()`` the caller already
        timed (the QueryBatcher's AOT path) — exact compile seconds and
        cost from the same executable."""
        cost = None
        if compiled is not None:
            try:
                cost = _cost_dict(compiled.cost_analysis())
            except Exception:
                cost = None
        # scope-unique key: every AOT compile is a REAL compile (the
        # caller just ran lower().compile()), so it must never dedup
        # against another engine's same-bucket program
        self.observe(("aot", id(self), key), bucket)
        self.note_compile(seconds, cost)


class WatchedProgram:
    """Passthrough wrapper around one jitted callable. Dispatch goes to
    the wrapped function verbatim (jax's jit cache unchanged); the shim
    only classifies each call's shape key and, on a genuine compile,
    times the dispatch and optionally captures a lower-only cost
    analysis. ``.lower`` and every other attribute pass through, so AOT
    users (the QueryBatcher) and introspection keep working."""

    __slots__ = ("fn", "scope", "bucket", "cost", "static_argnames",
                 "_sig")

    def __init__(self, fn, scope: WatchScope, bucket: Any = "program",
                 cost: bool = False, static_argnames: tuple = ()):
        self.fn = fn
        self.scope = scope
        self.bucket = bucket
        self.cost = cost
        self.static_argnames = tuple(static_argnames)
        self._sig = None
        if self.static_argnames:
            import inspect

            try:
                self._sig = inspect.signature(fn)
            except (TypeError, ValueError):
                self._sig = None

    def _statics(self, args, kwargs) -> tuple:
        """Static argument VALUES for the key (two ``limit`` values share
        one weak-int32 aval — only the value tells the programs apart)."""
        if self._sig is None:
            return ()
        try:
            bound = self._sig.bind(*args, **kwargs)
        except TypeError:
            return ()
        return tuple(bound.arguments.get(n) for n in self.static_argnames)

    def __call__(self, *args, **kwargs):
        watch = self.scope.watch
        if not watch.enabled:
            return self.fn(*args, **kwargs)
        statics = self._statics(args, kwargs)
        key = abstract_key(args, kwargs, statics)
        if key is None:      # tracer-staged: inlining into an outer jit
            return self.fn(*args, **kwargs)
        bucket = self.bucket
        if bucket is BY_STATICS:
            # one budget bucket per static-argument tuple: a family whose
            # static (e.g. ``limit``) legitimately takes several values
            # gets one program per value, not one program total
            bucket = ("statics",) + tuple(repr(s) for s in statics)
        verdict = self.scope.observe(key, bucket, fn_id=id(self.fn))
        if verdict != "compile":
            return self.fn(*args, **kwargs)
        cost = None
        if self.cost and (watch.cost_all or watch._family(
                self.scope.family).last_cost is None):
            try:
                cost = _cost_dict(
                    self.fn.lower(*args, **kwargs).cost_analysis())
            except Exception:
                cost = None
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        self.scope.note_compile(time.perf_counter() - t0, cost)
        return out

    def lower(self, *args, **kwargs):
        return self.fn.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.fn, name)


class DeviceWatch:
    """Process-global watchdog state (one XLA compile cache per process,
    one watch). ``enabled`` gates ALL per-dispatch work; ``strict`` turns budget
    violations into :class:`RetraceError` (tests, CI)."""

    def __init__(self):
        self.enabled = True
        self.strict = os.environ.get("SWTPU_XLA_STRICT") == "1"
        # cost capture for jit-watched families needs a lower-only pass
        # (re-trace, no backend compile) — roughly doubling a compiling
        # dispatch. Default: once per family (the AOT query path always
        # captures exactly, from its own executable); SWTPU_XLA_COST=all
        # re-captures on every compile.
        self.cost_all = os.environ.get("SWTPU_XLA_COST") == "all"
        self._lock = threading.RLock()
        self._families: dict[str, _Family] = {}
        self._inst = devicewatch_metrics(REGISTRY)

    def _family(self, name: str) -> _Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(name)
            return fam

    def scope(self, family: str, allowance: int = 1) -> WatchScope:
        return WatchScope(self, family, allowance)

    # ----------------------------------------------------------- posture
    def compile_totals(self) -> dict[str, int]:
        """family -> programs compiled so far (the loadgen's delta
        source for "recompiles during this run")."""
        with self._lock:
            return {n: f.compiles for n, f in self._families.items()}

    def excess_total(self) -> int:
        with self._lock:
            return sum(f.excess for f in self._families.values())

    def posture(self) -> dict:
        """Per-family compile posture for the debug bundle and the
        ``/api/instance/device/memory`` breakdown."""
        out = {}
        with self._lock:
            for name, fam in self._families.items():
                live = 0
                alive = []
                for ref in fam.scopes:
                    sc = ref()
                    if sc is not None:
                        alive.append(ref)
                        live += sc.live_programs
                fam.scopes[:] = alive
                out[name] = {
                    "programsLive": live,
                    "compiles": fam.compiles,
                    "cacheHits": fam.hits,
                    "retraceExcess": fam.excess,
                    "lastCompileS": fam.last_compile_s,
                    "lastCost": fam.last_cost,
                }
        return out


WATCH = DeviceWatch()

# Bucket sentinel: derive the budget bucket per call from the watched
# program's STATIC argument values (see WatchedProgram.__call__).
BY_STATICS = object()


def watched_jit(fn, family: str, static_argnames: tuple = (),
                bucket: Any = None, cost: bool = False,
                allowance: int = 1) -> WatchedProgram:
    """Wrap an already-jitted module-level kernel in a process-global
    watch scope. ``bucket=None`` leaves the family unbudgeted (metrics
    only) — module kernels legitimately serve many shapes across
    engines; per-engine seams get budgets via :class:`EngineWatch`."""
    return WatchedProgram(fn, WATCH.scope(family, allowance),
                          bucket=bucket, cost=cost,
                          static_argnames=static_argnames)


def compile_totals() -> dict[str, int]:
    return WATCH.compile_totals()


def compile_posture() -> dict:
    return WATCH.posture()


@contextlib.contextmanager
def strict_retraces():
    """Strict mode for the enclosed block: budget violations raise
    :class:`RetraceError` instead of counting — the test-suite form of
    ``SWTPU_XLA_STRICT=1``."""
    prev = WATCH.strict
    WATCH.strict = True
    try:
        yield WATCH
    finally:
        WATCH.strict = prev


class EngineWatch:
    """Per-engine watchdog handle: one fresh :class:`WatchScope` per
    wrapped program (so a scan-chunk rebuild starts a clean budget) plus
    the AOT scope the QueryBatcher records into. ``enabled=False``
    (EngineConfig.devicewatch) returns callables unwrapped — zero
    dispatch-path change."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._wrapped: dict[str, WatchedProgram] = {}
        self._aot: dict[str, WatchScope] = {}

    def wrap(self, fn, family: str, cost: bool = False,
             static_argnames: tuple = (), bucket: Any = "program"):
        if not self.enabled:
            return fn
        w = WatchedProgram(fn, WATCH.scope(family), bucket=bucket,
                           cost=cost, static_argnames=static_argnames)
        self._wrapped[family] = w
        return w

    def allow(self, n: int = 1) -> None:
        """Grant every wrapped program +n shapes — called by seams that
        legitimately change the state's abstract shape (geofence zone
        installs swap a pytree leaf in/out)."""
        for w in self._wrapped.values():
            w.scope.allow(n)

    def record_aot(self, family: str, key: Any, bucket: Any,
                   seconds: float, compiled=None) -> None:
        if not self.enabled:
            return
        scope = self._aot.get(family)
        if scope is None:
            scope = self._aot[family] = WATCH.scope(family)
        scope.record_aot(key, bucket, seconds, compiled)


# --------------------------------------------------------------------------
# Memory ledger
# --------------------------------------------------------------------------

def _tree_nbytes(tree) -> int:
    """Byte size of a pytree's array leaves from shape/dtype metadata —
    safe on DONATED (deleted) jax arrays, whose data is gone but whose
    aval survives."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(math.prod(shape)) * dtype.itemsize
    return total


def live_array_stats() -> dict | None:
    """Process-wide live jax buffers (count + bytes) — the closest CPU
    analog of ``device.memory_stats()``; on TPU both are exported and
    should roughly reconcile."""
    try:
        arrs = jax.live_arrays()
        total = 0
        for a in arrs:
            try:
                total += int(a.nbytes)
            except Exception:
                continue          # deleted between listing and sizing
        return {"count": len(arrs), "bytes": total}
    except Exception:
        return None


def backend_memory_stats() -> dict | None:
    """Per-device allocator stats where the backend provides them (TPU:
    bytes_in_use / peak_bytes_in_use / largest_free_block; CPU: None)."""
    out = {}
    for d in jax.devices():
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            out[str(d)] = {k: int(v) for k, v in ms.items()
                           if isinstance(v, (int, float))}
    return out or None


def memory_ledger(engine, reset_hwm: bool = False) -> dict:
    """Scrape-time accounting of everything this engine keeps resident:
    device-side state tables (computed from avals, so donation can't
    break the walk), host staging arenas, the archive's decoded-segment
    cache, and process-wide live arrays. ``reset_hwm`` drains the
    high-watermarks (the SCRAPE semantics — "worst case since the last
    scrape"); peeks (REST endpoint, debug bundle) leave them intact."""
    eng = getattr(engine, "local", engine)   # cluster facade -> rank local
    comp: dict[str, int] = {}
    st = getattr(eng, "state", None)
    if st is not None:
        comp["ring_store"] = _tree_nbytes(st.store)
        comp["registry"] = _tree_nbytes(st.registry)
        comp["device_state"] = _tree_nbytes(st.device_state)
        comp["pipeline_metrics"] = _tree_nbytes(st.metrics)
        if getattr(st, "windows", None) is not None:
            comp["telemetry_windows"] = _tree_nbytes(st.windows)
        if getattr(st, "zones", None) is not None:
            comp["geofence_zones"] = _tree_nbytes(st.zones)
    pool = getattr(eng, "_arena_pool", None)
    if pool is not None and hasattr(pool, "nbytes"):
        comp["arena_pool"] = int(pool.nbytes)
    arch = getattr(eng, "archive", None)
    cache = getattr(arch, "cache", None) if arch is not None else None
    if cache is not None and hasattr(cache, "nbytes"):
        comp["segment_cache"] = int(cache.nbytes)
    hwm: dict[str, int] = {}
    if pool is not None and hasattr(pool, "take_occupancy_hwm"):
        hwm["arena_occupancy"] = int(
            pool.take_occupancy_hwm(reset=reset_hwm))
    take_backlog = getattr(eng, "take_backlog_hwm", None)
    if take_backlog is not None:
        hwm["staged_backlog_rows"] = int(take_backlog(reset=reset_hwm))
    return {
        "components": comp,
        "totalBytes": sum(comp.values()),
        "inflightPrograms": len(getattr(eng, "_pending_outs", ()) or ()),
        "highWatermarks": hwm,
        "liveArrays": live_array_stats(),
        "deviceMemoryStats": backend_memory_stats(),
    }


def device_memory_payload(engine) -> dict:
    """THE document behind ``GET /api/instance/device/memory`` and the
    ``Instance.deviceMemory`` RPC: the ledger breakdown plus per-family
    compile posture (a peek — high-watermarks are NOT reset; only the
    Prometheus scrape drains them)."""
    return {**memory_ledger(engine, reset_hwm=False),
            "compileFamilies": compile_posture()}


def export_devicewatch(engine, registry=None) -> None:
    """Scrape-time export: per-family watchdog counters are already live
    in the registry — this syncs the scrape-time views (live program
    gauge, last-compile cost, the per-engine memory ledger with
    reset-on-scrape high-watermarks) and drains the query-path flight
    records into the device execution-time histogram."""
    reg = registry or REGISTRY
    inst = devicewatch_metrics(reg)
    for name, fam in WATCH.posture().items():
        inst["live"].set(fam["programsLive"], family=name)
        cost = fam["lastCost"] or {}
        if "flops" in cost:
            inst["flops"].set(cost["flops"], family=name)
        if "bytes_accessed" in cost:
            inst["bytes"].set(cost["bytes_accessed"], family=name)
    led = memory_ledger(engine, reset_hwm=True)
    lbl = getattr(engine, "metrics_label",
                  getattr(getattr(engine, "local", None), "metrics_label",
                          "e?"))
    mem = inst["mem"]
    written: set[tuple] = set()
    for comp, nbytes in led["components"].items():
        mem.set(nbytes, component=comp, engine=lbl)
        written.add(tuple(sorted({"component": comp,
                                  "engine": lbl}.items())))
    la = led["liveArrays"]
    if la is not None:
        mem.set(la["bytes"], component="live_arrays", engine=lbl)
        written.add(tuple(sorted({"component": "live_arrays",
                                  "engine": lbl}.items())))
    mem.retain(written, engine=lbl)
    mh = inst["mem_hwm"]
    kept: set[tuple] = set()
    for comp, v in led["highWatermarks"].items():
        mh.set(v, component=comp, engine=lbl)
        kept.add(tuple(sorted({"component": comp, "engine": lbl}.items())))
    mh.retain(kept, engine=lbl)
    # query-path device time: drain completed query lifecycles (the
    # ingest drain lives in metrics.harvest_slo, on the shared
    # consume-once records)
    flight = getattr(engine, "flight", None)
    if flight is not None:
        exec_hist = inst["exec"]
        for rec in flight.harvest_completed("query", terminal="device"):
            t0 = rec.stages.get("lookup", rec.t0_ns)
            t1 = rec.stages["device"]
            if t1 >= t0:
                exec_hist.observe((t1 - t0) / 1e9, family="query")


# --------------------------------------------------------------------------
# Device profiler capture
# --------------------------------------------------------------------------

_PROFILE_LOCK = threading.Lock()
_PROFILE_SEQ = [0]


def capture_device_profile(ms: float, base_dir: str | None = None) -> dict:
    """Capture a ``jax.profiler`` trace for ~``ms`` milliseconds into a
    fresh named directory and return its location + file listing. The
    profiler is a process singleton, so captures serialize on a lock;
    ``ms`` clamps to [50, 10000]. On TPU the trace carries real device
    timelines (XLA ops, HBM transfers); on CPU it still captures the
    host-side runtime — either loads in TensorBoard/Perfetto."""
    ms = max(50.0, min(float(ms), 10_000.0))
    base = base_dir or os.path.join(tempfile.gettempdir(),
                                    "swtpu-device-profiles")
    os.makedirs(base, exist_ok=True)
    with _PROFILE_LOCK:
        _PROFILE_SEQ[0] += 1
        out = os.path.join(
            base, time.strftime("prof-%Y%m%d-%H%M%S")
            + f"-p{os.getpid()}-{_PROFILE_SEQ[0]}")
        jax.profiler.start_trace(out)
        try:
            time.sleep(ms / 1000.0)
        finally:
            jax.profiler.stop_trace()
    files = []
    total = 0
    for root, _dirs, names in os.walk(out):
        for name in names:
            p = os.path.join(root, name)
            try:
                total += os.path.getsize(p)
            except OSError:
                continue
            files.append(os.path.relpath(p, out))
    return {"dir": out, "ms": ms, "files": sorted(files),
            "bytes": total}
