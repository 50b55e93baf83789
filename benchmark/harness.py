"""The benchmark harness: one cell, one seed, one run.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the deployment file (``configs[].file``), the traffic mix
(``benchmark/traffic/<traffic>.json``) and one reader per per-layer
metric (``benchmark/metrics/<name>.py``; a metric named
``<base>.<part>``, one quantity reported apart for some cells, is read
by ``<base>.py`` where it has no file of its own). Adding a cell, a mix,
a deployment or a metric adds files and entries; nothing here changes.

A run: build the engine the deployment describes, make every input from
the seed, register the fleet and stamp the backlog's passes (set-up),
then drain the backlog for ``--seconds`` through the public engine API
(``ingest_json_batch``, ``maybe_flush`` on the deployment's cadence),
then hold what the engine answers (``get_device_state``,
``query_events``, ``get_device``, ``metrics``) and the log it wrote
against the plain reference (``reference.py``, ``wal_reader.py``).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

from benchmark import traffic_gen as tg
from benchmark import wal_reader
from benchmark.reference import Reference, page_view

SAMPLE_DEVICES = 192      # seeded sample of devices whose state is compared
TAIL_DEVICES = 64         # ... plus the devices of the newest arrivals
DEVICE_PAGES = 64         # device event pages compared after the window


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: str, workload: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, cell, deployment, mix) for a cell name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, conf["file"]))
    mix = load_json(os.path.join(root, "benchmark", "traffic",
                                 cell["traffic"] + ".json"))
    if mix["kind"] != "backlog":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return bench, cell, cfg, mix


def metrics_for(bench: dict, section: str, workload: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(root: str, name: str):
    d = os.path.join(root, "benchmark", "metrics")
    path = os.path.join(d, name + ".py")
    if not os.path.exists(path):
        path = os.path.join(d, name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def process_start_unix() -> float:
    """This process's start, from the kernel's record of it."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return time.time()


class CompileMeter:
    """Backend compile seconds, compiles and persistent-cache hits from
    JAX's own monitoring events (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


# ------------------------------------------------------------- deployment
@dataclasses.dataclass
class Deployment:
    cfg: dict
    mix: dict
    tenants: list
    tokens: list
    dev_tenant: np.ndarray
    devices: np.ndarray       # device indices, registered by first sight
    table: tg.EventTable      # onboarding rows, then the backlog pool
    n_onboard: int
    frame_ten: np.ndarray     # tenant of each pool frame
    passes: list              # passes[s][f]: frame f stamped for pass s


def build_deployment(cfg: dict, mix: dict, seed: int) -> Deployment:
    """Every input of a run from the seed: the fleet, one first-sight
    measurement per device (onboarding, oldest), and the backlog pool
    stamped for each of its ``stamps`` passes."""
    n_ten = int(cfg["tenants"])
    tenants = ([cfg["tenant_name"]] if n_ten == 1
               else tg.tenant_names(n_ten))
    n_dev = int(cfg["registered_devices"])
    tokens = tg.device_tokens(cfg["token_prefix"], n_dev)
    dev_tenant = (np.arange(n_dev) % n_ten).astype(np.int16)
    # onboarding rows in call order, tenant by tenant
    onb_dev = np.argsort(dev_tenant, kind="stable")
    pool_n = int(mix["pool_events"])
    # every stamp lies in the past: the backlog built up while the
    # consumer was away
    t0 = int(time.time() * 1000) - int(mix["stamps"]) * pool_n - 60_000
    onboard = tg.make_events(
        np.random.default_rng([seed, 0x0B]), onb_dev, dev_tenant[onb_dev],
        t0 - len(onb_dev) - 1000 + np.arange(len(onb_dev)), cfg, tokens,
        kinds=np.zeros(len(onb_dev), np.int8))
    devices = np.arange(n_dev)
    pool, frame_ten = tg.backlog_pool(cfg, mix, seed, t0, tokens,
                                      dev_tenant, n_ten, devices)
    fe = int(mix["frame_events"])
    passes = []
    for s in range(int(mix["stamps"])):
        pl = tg.stamp(pool.payloads, pool.ts_abs + tg.pass_shift_ms(mix, s))
        passes.append([pl[i * fe:(i + 1) * fe]
                       for i in range(len(frame_ten))])
    return Deployment(cfg, mix, tenants, tokens, dev_tenant, devices,
                      tg.concat_tables([onboard, pool]),
                      len(onboard), frame_ten, passes)


def make_engine(cfg: dict, wal_dir: str):
    from sitewhere_tpu.engine import Engine, EngineConfig

    return Engine(EngineConfig(**cfg["engine"],
                               wal_dir=wal_dir if cfg["durable"] else None))


class FlushTimer:
    """``maybe_flush()`` on the deployment's cadence, as a serving loop
    calls it."""

    def __init__(self, eng, interval_s: float):
        self.eng, self.interval = eng, interval_s
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        import jax

        while not self.stop.wait(self.interval):
            with jax.profiler.TraceAnnotation("bench.flush_timer"):
                self.eng.maybe_flush()

    def close(self):
        self.stop.set()
        self.thread.join()


# ----------------------------------------------------------------- set-up
def setup(eng, dep: Deployment) -> int:
    """Register the fleet by first sight through the ingest path, tenant
    by tenant. Returns decode failures."""
    fe = int(dep.mix["frame_events"])
    ten = dep.table.ten[:dep.n_onboard]
    failed = 0
    lo = 0
    while lo < dep.n_onboard:
        t = int(ten[lo])
        hi = min(int(np.searchsorted(ten, t, side="right")), lo + fe)
        failed += eng.ingest_json_batch(dep.table.payloads[lo:hi],
                                        dep.tenants[t])["failed"]
        lo = hi
    eng.flush()
    return failed


# ----------------------------------------------------------------- window
@dataclasses.dataclass
class WindowResult:
    frames: int               # frames sent inside the window
    t0: float                 # window open (perf_counter)
    t1: float                 # window close (perf_counter)
    t0_unix: float
    failed: int


def backlog_window(eng, dep: Deployment, seconds: float) -> WindowResult:
    """Drain a standing backlog as fast as the engine takes it, frame by
    frame, pass after pass over the pool; the window ends once every
    event sent in it is visible in device state (``barrier``)."""
    import jax

    n_frames = len(dep.frame_ten)
    n_stamps = len(dep.passes)
    eng.barrier()
    failed = 0
    k = 0
    t0_unix = time.time()
    t0 = time.perf_counter()
    end = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() < end:
            s, f = divmod(k, n_frames)
            with jax.profiler.TraceAnnotation("bench.ingest"):
                failed += eng.ingest_json_batch(
                    dep.passes[s % n_stamps][f],
                    dep.tenants[int(dep.frame_ten[f])])["failed"]
            k += 1
        with jax.profiler.TraceAnnotation("bench.barrier"):
            eng.barrier()
    return WindowResult(k, t0, time.perf_counter(), t0_unix, failed)


def arrivals(dep: Deployment, frames: int) -> tuple[np.ndarray, np.ndarray]:
    """(event-table rows, eventDate sent) of every arrival in order: the
    onboarding rows, then ``frames`` frames of the backlog's passes."""
    fe = int(dep.mix["frame_events"])
    pool_n = len(dep.frame_ten) * fe
    j = np.arange(frames * fe, dtype=np.int64)
    rows = dep.n_onboard + j % pool_n
    shift = (j // pool_n) % int(dep.mix["stamps"]) * int(
        dep.mix["pool_events"])
    onb = np.arange(dep.n_onboard, dtype=np.int64)
    return (np.concatenate([onb, rows]),
            np.concatenate([dep.table.ts_abs[onb],
                            dep.table.ts_abs[rows] + shift]))


# ----------------------------------------------------------------- checks
def collect_answers(eng, dep: Deployment, seed: int, tail_devs) -> dict:
    """What the engine answers once the window has closed and everything
    is flushed: counters, the state of a seeded sample of devices (with
    the devices of the newest arrivals), event pages of devices and of
    every tenant, and the devices' registrations."""
    eng.flush()
    rng = np.random.default_rng([seed, 0x5E])
    devs = rng.choice(dep.devices, size=min(SAMPLE_DEVICES,
                                            len(dep.devices)), replace=False)
    plan = {"states": sorted(set(devs.tolist()) | set(tail_devs)),
            "pages": devs[:DEVICE_PAGES].tolist()}
    m = eng.metrics()
    ans = {"plan": plan, "persisted": int(m["persisted"]),
           "registered": int(m["registered"]),
           "states": {d: eng.get_device_state(dep.tokens[d])
                      for d in plan["states"]},
           "tenant_of": {}}
    for d in plan["states"]:
        info = eng.get_device(dep.tokens[d])
        ans["tenant_of"][d] = None if info is None else info.tenant
    # pages from several clients at once, as dashboards send them (the
    # engine coalesces them into shared scans)
    with concurrent.futures.ThreadPoolExecutor(16) as pool:
        dev_pages = pool.map(lambda d: eng.query_events(
            device_token=dep.tokens[d], limit=20), plan["pages"])
        ten_pages = pool.map(lambda t: eng.query_events(tenant=t, limit=100),
                             dep.tenants)
        ans["dev_pages"] = dict(zip(plan["pages"], dev_pages))
        ans["ten_pages"] = list(ten_pages)
    return ans


def answers_from(ref: Reference, dep: Deployment, plan: dict) -> dict:
    """The same answers, as a reference (or a control put in the
    program's place) gives them."""
    states = plan["states"]
    return {
        "persisted": ref.n, "registered": len(dep.devices),
        "states": {d: ref.final_state(d) for d in states},
        "tenant_of": {d: dep.tenants[int(dep.dev_tenant[d])]
                      for d in states},
        "dev_pages": {d: ref.query(20, device=d) for d in plan["pages"]},
        "ten_pages": [ref.query(100, tenant=t)
                      for t in range(len(dep.tenants))]}


def compare(ans: dict, ref: Reference, dep: Deployment) -> dict:
    want = answers_from(ref, dep, ans["plan"])
    return {
        "count_mismatch": abs(ans["persisted"] - want["persisted"])
        + abs(ans["registered"] - want["registered"]),
        "state_mismatch": sum(ans["states"].get(d) != s
                              for d, s in want["states"].items()),
        "registry_mismatch": sum(ans["tenant_of"].get(d) != t
                                 for d, t in want["tenant_of"].items()),
        "page_mismatch": sum(
            page_view(ans["dev_pages"][d]) != p
            for d, p in want["dev_pages"].items()) + sum(
            page_view(a) != p for a, p in zip(ans["ten_pages"],
                                               want["ten_pages"]))}


def _sent(dep: Deployment, rows: np.ndarray, ts: np.ndarray) -> tuple:
    """(tenant, payload bytes) of the given arrivals, as they were sent."""
    pl = dep.table.payloads
    return ([dep.tenants[int(t)] for t in dep.table.ten[rows]],
            tg.stamp([pl[r] for r in rows.tolist()], ts))


def check_wal(wal_dir: str, dep: Deployment, rows: np.ndarray,
              ts: np.ndarray) -> int:
    """The log on disk holds every sent event, in order, once."""
    rec = wal_reader.read_records(wal_reader.segments(wal_dir))
    tens, pls = _sent(dep, rows, ts)
    sent = np.array([tg.payload_crc(t, p) for t, p in zip(tens, pls)],
                    np.uint32)
    bad = abs(len(rec["crc"]) - len(sent))
    n = min(len(sent), len(rec["crc"]))
    return bad + int(np.sum(rec["crc"][:n] != sent[:n]))


def wal_tail_check(wal_dir: str, dep: Deployment, rows: np.ndarray,
                   ts: np.ndarray) -> int:
    """For a log too large to read whole in the check's time: the bytes
    on disk are exactly the framed records sent (a stamp keeps a
    payload's length), and the newest segment holds exactly the newest
    records, in order."""
    segs = wal_reader.segments(wal_dir)
    lens = np.array([8 + 2 + len(dep.tenants[int(dep.table.ten[r])])
                     + len(dep.table.payloads[r])
                     for r in range(len(dep.table))], np.int64)
    want = int(lens[rows].sum()) + len(wal_reader.MAGIC) * len(segs)
    bad = int(sum(os.path.getsize(p) for p in segs) != want)
    rec = wal_reader.read_records(segs[-1:])
    n = len(rec["crc"])
    tens, pls = _sent(dep, rows[len(rows) - n:], ts[len(ts) - n:])
    crc = np.array([tg.payload_crc(t, p) for t, p in zip(tens, pls)],
                   np.uint32)
    return bad + int(np.sum(crc != rec["crc"]))


def bf16_round(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 (nearest even), NaN kept: a control's storage."""
    f = np.asarray(x, np.float32)
    b = f.view(np.uint32).astype(np.uint64)
    b = ((b + 0x7FFF + ((b >> 16) & 1)) >> 16) << 16
    out = b.astype(np.uint32).view(np.float32).astype(np.float64)
    return np.where(np.isnan(x), np.nan, out)


# ------------------------------------------------------------- per-layer
@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader may read. ``view`` is the reduced
    device trace; ``w0``/``w1`` bound the measured window on the trace's
    clock; ``flight`` holds the flight-recorder records of the window's
    ingest batches; ``events`` counts the window's events."""

    view: object
    w0: int
    w1: int
    flight: list
    events: dict
    cfg: dict
    mix: dict
    peaks: dict


def chip_peaks(root: str, kind: str) -> dict:
    table = load_json(os.path.join(root, "benchmark", "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# -------------------------------------------------------------------- run
def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             controls: bool = False, engine_hook=None) -> dict:
    """One run of one cell; returns the result line as a dict. Raises
    :class:`NoChip` before any work where the chip is missing."""
    t_proc = process_start_unix()
    bench, cell, cfg, mix = find_cell(root, workload)
    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if require_chip and (dev0.platform != "tpu"
                         or len(devices) < int(cell["chips"])):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX sees {len(devices)} {dev0.platform} device(s)")
    if require_chip:
        from sitewhere_tpu.utils.compile_cache import configure_compile_cache

        cache_dir = configure_compile_cache()
        # cache every program, not only the slow ones: the many small read
        # programs are what a warm set-up would otherwise compile again
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"compile cache: {cache_dir}")
    meter = CompileMeter()
    e2e = metrics_for(bench, "end_to_end", workload)
    per_layer = metrics_for(bench, "per_layer", workload)
    readers = ({m["name"]: load_reader(root, m["name"]) for m in per_layer}
               if trace else {})
    peaks = chip_peaks(root, dev0.device_kind) if (trace and require_chip) \
        else {}
    work = os.path.join(root, ".bench")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        return _run(seed, seconds, trace, controls, engine_hook,
                    require_chip, t_proc, cfg, mix, e2e, per_layer, readers,
                    peaks, meter, os.path.join(work, "wal"),
                    os.path.join(work, "trace"), devices)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run(seed, seconds, trace, controls, engine_hook, require_chip, t_proc,
         cfg, mix, e2e, per_layer, readers, peaks, meter, wal_dir,
         trace_dir, devices) -> dict:
    import jax

    from benchmark import trace_reduce as tr

    dep = build_deployment(cfg, mix, seed)
    eng = make_engine(cfg, wal_dir)
    decode_failed = setup(eng, dep)
    if engine_hook is not None:   # tests: plant a fault in the timed path
        engine_hook(eng)
    win_s = min(seconds, float(mix["trace_s"])) if trace else seconds
    c0 = meter.snapshot()
    if trace:
        jax.profiler.start_trace(trace_dir)
    timer = FlushTimer(eng, float(cfg["engine"]["flush_interval_s"]))
    wr = backlog_window(eng, dep, win_s)
    timer.close()
    if trace:
        jax.profiler.stop_trace()
    c1 = meter.snapshot()
    decode_failed += wr.failed
    fe = int(mix["frame_events"])
    n_window = wr.frames * fe
    setup_s = wr.t0_unix - t_proc
    t1_unix = wr.t0_unix + (wr.t1 - wr.t0)
    log(f"window: {wr.t1 - wr.t0:.3f} s, events sent in it {n_window}, "
        f"compiles in it {c1['compiles'] - c0['compiles']} "
        f"({c1['compile_s'] - c0['compile_s']:.3f} s), set-up compiles "
        f"{c0['compiles']} ({c0['compile_s']:.3f} s), cache hits "
        f"{c0['cache_hits']} misses {c0['cache_misses']}")

    # printed for the reader of a run, compared with nothing
    pool_passes = wr.frames / len(dep.frame_ten)
    diag = {"frames": wr.frames, "pool_passes": pool_passes,
            "stamps_repeated": pool_passes > int(mix["stamps"]),
            "compiles_in_window": c1["compiles"] - c0["compiles"],
            "setup_compiles": c0["compiles"],
            "setup_compile_s": c0["compile_s"]}
    values = {"setup_s": setup_s, "ingest_eps": n_window / (wr.t1 - wr.t0)}

    metrics = {}
    breakdown = None
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if trace:
        pd = tr.load(trace_dir)
        view = tr.tpu_view(pd) if require_chip else tr.cpu_device_view(pd)
        win = [(s, e) for n, s, e in view.host if n == "bench.window"]
        w0, w1 = win[0] if win else (0, 0)
        flight = [r for r in eng.recent_traces(eng.flight.capacity)
                  if r.get("kind") == "ingest"
                  and wr.t0_unix * 1000 <= r.get("startedMs", 0)
                  <= t1_unix * 1000]
        ctx = LayerContext(view, w0, w1, flight, {"total": n_window}, cfg,
                           mix, peaks)
        for m in per_layer:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if view.chips:
            busy = [tr.busy_ns(c, w0, w1) for c in view.chips.values()]
            device["busy_s"] = float(np.mean(busy)) / 1e9
            device["window_s"] = (w1 - w0) / 1e9
            fullest = max(view.chips, key=lambda c: tr.busy_ns(
                view.chips[c], w0, w1))
            breakdown = {"device_ops": tr.top_ops(view, w0, w1),
                         "idle_gaps": tr.gaps_by_host(view, fullest, w0,
                                                      w1)}
    else:
        # an end-to-end metric may be one quantity named apart per cell
        # (``ingest_eps.plant``): its value is the base quantity's
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"].split(".")[0]],
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices())

    # ---- the check: engine answers first, then free the engine, then
    # the reference on the host
    rows, ts = arrivals(dep, wr.frames)
    base_ms = int(eng.epoch.base_unix_s * 1000)
    ref = Reference(dep.table, rows, ts, dep.tokens,
                    cfg["measurement_names"], cfg["alert_types"], base_ms,
                    cfg["engine"]["store_capacity"])
    tail_devs = sorted(set(dep.table.dev[rows[-TAIL_DEVICES:]].tolist()))
    t_check = time.perf_counter()
    ans = collect_answers(eng, dep, seed, tail_devs)
    t_ans = time.perf_counter()
    if eng.wal is not None:
        eng.wal.close()
    del eng
    gc.collect()
    checks = {"decode_failed": decode_failed}
    checks.update(compare(ans, ref, dep))
    if cfg["durable"]:
        check = (check_wal if wal_reader.total_bytes(wal_dir)
                 <= int(mix["wal_full_check_bytes"]) else wal_tail_check)
        checks["wal_mismatch"] = check(wal_dir, dep, rows, ts)
    diag.update(check_answers_s=t_ans - t_check,
                check_reference_s=time.perf_counter() - t_ans)
    log(f"diag: {json.dumps(diag)}")
    result = {"correct": all(v == 0 for v in checks.values()),
              "attempted": int(n_window), "failed": int(decode_failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if controls:
        result["controls"] = run_controls(ans, ref, dep, cfg, rows, ts,
                                          base_ms)
    result["diag"] = diag
    result["checks"] = {k: {"value": int(v), "limit": 0}
                        for k, v in checks.items()}
    return result


def run_controls(ans: dict, ref: Reference, dep: Deployment, cfg: dict,
                 rows: np.ndarray, ts: np.ndarray, base_ms: int) -> dict:
    """Readings of the controls, each put in the program's place and held
    to the same comparison: the values stored in bfloat16, and the
    newest frame of arrivals lost (at-most-once delivery)."""
    fe = int(dep.mix["frame_events"])
    kw = dict(tokens=dep.tokens, names=cfg["measurement_names"],
              atypes=cfg["alert_types"], base_ms=base_ms,
              store_capacity=cfg["engine"]["store_capacity"])
    ctls = {"bf16_values": Reference(dep.table, rows, ts,
                                     value_round=bf16_round, **kw),
            "lost_tail": Reference(dep.table, rows[:-fe], ts[:-fe], **kw)}
    out = {}
    for name, ctl in ctls.items():
        a = answers_from(ctl, dep, ans["plan"])
        a["plan"] = ans["plan"]
        out[name] = compare(a, ref, dep)
    return out
