"""Load generator: the benchmark driver for the ingest→device-state path.

The reference's only load tooling is a manual JMS sender — 5 threads x 100
hard-coded JSON measurement messages aimed at a live instance
(service-event-sources/src/test/java/com/sitewhere/sources/
EventSourceTests.java:49-71, payloads built by EventsHelper.java). This
module is the CI-runnable equivalent (SURVEY.md §4d): it generates the same
canonical DeviceRequest measurement JSON, drives either the engine's native
host path or a live REST gateway, and reports throughput plus end-to-end
ingest→device-state latency percentiles — the BASELINE.md north-star metrics
(events/sec/chip, inbound→state p99 < 50 ms).

Modes:
  * engine — payload bytes → C++ batch decode → staging → fused TPU step →
    state merged. Latency is measured per batch from first submit to the
    flush return that made the batch's events visible in device state
    (CLOSED loop: the next batch waits for the previous one).
  * open loop — a seeded, deterministic schedule of per-tenant Poisson
    arrivals carrying a MIXED ingest/query/entity-mutation workload
    (``build_open_loop_schedule`` + ``run_open_loop``). The generator
    fires on the schedule's clock, never the engine's: when the engine
    falls behind, events queue and their measured latency GROWS — the
    queueing delay a closed-loop driver structurally hides, and exactly
    what per-tenant SLO measurement must see. Per-event wire→state
    latencies sample into log-bucketed histograms (p50/p99/p99.9).
  * rest — HTTP POSTs against a running gateway (wire-level e2e).

CLI: ``python -m sitewhere_tpu.loadgen --batches 50 --batch-size 4096``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import json
import time

import numpy as np


def generate_measurements_message(token: str, seq: int,
                                  name: str = "engine.temperature",
                                  value: float | None = None) -> bytes:
    """Canonical JSON measurement DeviceRequest
    (EventsHelper.generateJsonMeasurementsMessage analog)."""
    payload = {
        "deviceToken": token,
        "type": "DeviceMeasurement",
        "request": {
            "name": name,
            "value": value if value is not None else round(20.0 + (seq % 80) * 0.5, 2),
            "eventDate": None,
            "updateState": True,
            "metadata": {"seq": str(seq)},
        },
    }
    return json.dumps(payload).encode()


@dataclasses.dataclass
class LoadStats:
    events_sent: int
    events_decoded: int
    events_failed: int
    wall_s: float
    events_per_s: float
    latency_p50_ms: float
    latency_p99_ms: float
    latency_max_ms: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _percentiles(lat_ms: list[float]) -> tuple[float, float, float]:
    arr = np.asarray(lat_ms)
    return (float(np.percentile(arr, 50)), float(np.percentile(arr, 99)),
            float(arr.max()))


def run_engine_load(engine, n_batches: int = 50, batch_size: int = 4096,
                    n_devices: int = 10_000, seed: int = 0,
                    warmup_batches: int = 3,
                    pipelined: bool = False,
                    sample_every: int = 8) -> LoadStats:
    """Drive the full host path: JSON bytes → native decode → staged → fused
    step → device state.

    pipelined=False — per-batch latency = submit → flush return (state
    merged and visible on the host), the inbound→device-state span of
    SURVEY.md §3.2-3.3.
    pipelined=True — steady-state throughput: batches dispatch as scanned
    chunks; every chunk dispatch is completion-synchronous inside the
    engine (depth-1), so each batch's e2e latency — submit → its chunk's
    state merge completed — is observed WITHOUT any device->host readback.
    The timed window ends at a readback-free ``barrier()``; mirror drain is
    teardown/reporting, not ingest.
    """
    rng = np.random.default_rng(seed)
    toks = [f"lg-{i}" for i in range(n_devices)]

    def make_batch(b: int) -> list[bytes]:
        picks = rng.integers(0, n_devices, batch_size)
        return [generate_measurements_message(toks[d], b * batch_size + i)
                for i, d in enumerate(picks)]

    for w in range(warmup_batches):          # compile + interner warm:
        engine.ingest_json_batch(make_batch(w))
        if not pipelined:
            engine.flush()
    if pipelined:
        # warmup compiles the scan-chunk program (incl. the padded-tail
        # shape) without a mirror readback
        engine.barrier()
    else:
        engine.flush()

    # pre-build payloads so the generator itself stays out of the timing
    prebuilt = [make_batch(b) for b in range(n_batches)]
    latencies: list[float] = []
    decoded = failed = 0
    submits: list[float] = []
    t0 = time.perf_counter()
    for i, payloads in enumerate(prebuilt):
        s0 = time.perf_counter()
        res = engine.ingest_json_batch(payloads)
        if pipelined:
            submits.append(s0)
            if engine.staged_count:
                engine.flush_async()
            if engine.staged_count == 0:
                # the chunk holding every pending submit just completed
                # (dispatch blocks until the state merge finished)
                done = time.perf_counter()
                latencies.extend((done - s) * 1e3 for s in submits)
                submits.clear()
        else:
            engine.flush()                    # state merged on return
            latencies.append((time.perf_counter() - s0) * 1e3)
        decoded += res["decoded"]
        failed += res["failed"]
    if pipelined:
        engine.barrier()                      # tail chunk, no readback
        done = time.perf_counter()
        latencies.extend((done - s) * 1e3 for s in submits)
    wall = time.perf_counter() - t0
    p50, p99, mx = _percentiles(latencies)
    sent = n_batches * batch_size
    return LoadStats(sent, decoded, failed, wall, sent / wall, p50, p99, mx)


# ---------------------------------------------------------------------------
# Open-loop mixed-workload harness (ISSUE 7).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TenantLoad:
    """One tenant's arrival process and workload mix."""

    tenant: str
    rate_eps: float                    # mean event arrival rate (Poisson)
    n_devices: int = 64
    device_prefix: str | None = None   # default "<tenant>-dev"
    query_every: int = 0               # one query per N ingest frames
    mutate_every: int = 0              # one entity mutation per N frames
    history_every: int = 0             # one HISTORICAL query per N frames:
                                       # a date range ending history_age_ms
                                       # in the past, so an archive-primed
                                       # engine serves it from the tiered
                                       # (ring + disk) read path
    history_age_ms: int = 60_000       # how far behind "now" the range ends
    analytics_every: int = 0           # one historical SCORING JOB per N
                                       # frames (ISSUE 19): a deterministic
                                       # marker, mirror of history_every —
                                       # the schedule stays a pure function
                                       # of the spec (the driver resolves
                                       # it against engine.analytics_jobs
                                       # at fire time; engines without the
                                       # manager skip it), and with the
                                       # knob OFF the schedule is
                                       # byte-identical to pre-knob runs
    abusive_mult: float = 1.0          # noisy-neighbor knob (ISSUE 9):
                                       # during burst windows the tenant
                                       # offers rate_eps * abusive_mult.
                                       # Extra arrivals come from a
                                       # SEPARATE seeded stream, so a
                                       # schedule with the knob OFF stays
                                       # byte-identical to pre-knob runs
    abusive_period_s: float = 0.0      # burst window period; 0 (with
                                       # mult > 1) = the whole horizon
    abusive_burst_s: float = 0.0       # burst length within each period
    abusive_device: int | None = None  # hotspot knob (ISSUE 18): pin
                                       # every EXTRA (abusive-stream)
                                       # event onto this one device
                                       # index, concentrating the burst
                                       # on a single placement slot /
                                       # shard lane so the heat plane
                                       # has a known-hot target. None
                                       # (default) keeps the extra
                                       # stream's device picks from the
                                       # base RNG — byte-identical to
                                       # pre-knob schedules
    rule_trigger_eps: float = 0.0      # rule-trigger traffic (ISSUE 13):
                                       # a SEPARATE seeded Poisson stream
                                       # of threshold-crossing
                                       # measurements (value =
                                       # rule_value on rule_channel)
                                       # superimposed on the base load —
                                       # same additivity/fingerprint
                                       # discipline as the abusive knob:
                                       # with the knob OFF (rate 0) the
                                       # schedule is byte-identical to a
                                       # pre-knob run
    rule_period_s: float = 0.0         # trigger burst period; 0 (with
                                       # eps > 0) = the whole horizon
    rule_burst_s: float = 0.0          # burst length within each period
    rule_channel: str = "engine.temperature"   # channel the crossings hit
    rule_value: float = 96.5           # crossing value (exactly f32-
                                       # representable so sum-rollup
                                       # parity is rounding-order-free)


@dataclasses.dataclass(frozen=True)
class OpenLoopSpec:
    """A complete, seed-determined load description: same spec + same
    seed => byte-identical payload stream and identical arrival
    schedule (pinned by tests/test_loadgen.py)."""

    tenants: tuple
    duration_s: float = 1.0
    frame_size: int = 64               # events per ingest submission
    seed: int = 0


@dataclasses.dataclass
class ScheduledOp:
    """One scheduled action. ``t_s`` is the arrival offset from schedule
    start; ingest frames also carry each event's OWN arrival offset so
    latency is measured per event, from the moment it notionally hit
    the wire — not from whenever the backlogged driver got to it."""

    t_s: float
    kind: str                          # "ingest" | "query" | "mutate"
    tenant: str
    payloads: list | None = None
    arrivals: tuple | None = None
    query: dict | None = None
    mutate: tuple | None = None        # (op, token, metadata)
    analytics: dict | None = None      # AnalyticsJobSpec kwargs (ISSUE 19)


_KIND_ORDER = {"ingest": 0, "query": 1, "mutate": 2, "analytics": 3}


def build_open_loop_schedule(spec: OpenLoopSpec) -> list[ScheduledOp]:
    """Deterministic open-loop schedule: per-tenant Poisson arrivals
    (seeded per tenant index), events grouped into frames of
    ``frame_size`` (a frame departs when its LAST event has arrived),
    with query and entity-mutation ops interleaved at each tenant's
    configured cadence. Pure function of the spec — no wall clock, no
    global RNG."""
    ops: list[ScheduledOp] = []
    for ti, tl in enumerate(spec.tenants):
        rng = np.random.default_rng([spec.seed, ti])
        prefix = tl.device_prefix or f"{tl.tenant}-dev"
        if tl.rate_eps <= 0:
            continue
        # draw inter-arrival gaps in chunks until past the horizon
        gaps: list[np.ndarray] = []
        total = 0.0
        while total < spec.duration_s:
            g = rng.exponential(1.0 / tl.rate_eps,
                                size=max(64, int(tl.rate_eps * 0.25) or 64))
            gaps.append(g)
            total += float(g.sum())
        arr = np.cumsum(np.concatenate(gaps))
        arr = arr[arr < spec.duration_s]
        if tl.abusive_mult > 1.0:
            # noisy-neighbor bursts: superimpose an EXTRA Poisson stream
            # at rate * (mult - 1), thinned to the burst windows — the
            # union of Poisson processes is Poisson at the summed rate,
            # so inside a window the tenant offers rate * mult. The
            # extra stream draws from its own seeded generator: the base
            # stream's draws (and every other tenant's schedule) are
            # untouched, keeping non-abusive fingerprints stable.
            xrng = np.random.default_rng([spec.seed, ti, 0xAB])
            xrate = tl.rate_eps * (tl.abusive_mult - 1.0)
            xgaps: list[np.ndarray] = []
            xtotal = 0.0
            while xtotal < spec.duration_s:
                g = xrng.exponential(
                    1.0 / xrate, size=max(64, int(xrate * 0.25) or 64))
                xgaps.append(g)
                xtotal += float(g.sum())
            xarr = np.cumsum(np.concatenate(xgaps))
            xarr = xarr[xarr < spec.duration_s]
            if tl.abusive_period_s > 0 and tl.abusive_burst_s > 0:
                xarr = xarr[(xarr % tl.abusive_period_s)
                            < tl.abusive_burst_s]
            # stable argsort == np.sort(kind="stable") on the times,
            # while also carrying WHICH rows came from the extra stream
            # (the hotspot knob needs the provenance; the merged arrival
            # array is byte-identical either way)
            n_base = len(arr)
            both = np.concatenate([arr, xarr])
            order = np.argsort(both, kind="stable")
            arr = both[order]
            abusive_at = order >= n_base
        else:
            abusive_at = None
        picks = rng.integers(0, tl.n_devices, len(arr))
        if abusive_at is not None and tl.abusive_device is not None:
            # hotspot: the extra stream's events all land on one device
            # (one slot, one shard). picks is drawn BEFORE this with the
            # same count either way, so base-stream devices — and every
            # abusive_device=None schedule — keep their fingerprints
            picks = picks.copy()
            picks[abusive_at] = int(tl.abusive_device) % tl.n_devices
        is_rule = np.zeros(len(arr), bool)
        if tl.rule_trigger_eps > 0:
            # rule-trigger traffic (ISSUE 13): threshold-crossing
            # measurements from their OWN seeded stream, merged after the
            # base draws — the base stream's draws (and every other
            # tenant's schedule) are untouched, so a schedule with the
            # knob OFF keeps its pre-knob fingerprint (the abusive-knob
            # additivity discipline)
            rrng = np.random.default_rng([spec.seed, ti, 0x51])
            rgaps: list[np.ndarray] = []
            rtotal = 0.0
            while rtotal < spec.duration_s:
                g = rrng.exponential(
                    1.0 / tl.rule_trigger_eps,
                    size=max(64, int(tl.rule_trigger_eps * 0.25) or 64))
                rgaps.append(g)
                rtotal += float(g.sum())
            rarr = np.cumsum(np.concatenate(rgaps))
            rarr = rarr[rarr < spec.duration_s]
            if tl.rule_period_s > 0 and tl.rule_burst_s > 0:
                rarr = rarr[(rarr % tl.rule_period_s) < tl.rule_burst_s]
            rpicks = rrng.integers(0, tl.n_devices, len(rarr))
            order = np.argsort(np.concatenate([arr, rarr]), kind="stable")
            arr = np.concatenate([arr, rarr])[order]
            picks = np.concatenate([picks, rpicks])[order]
            is_rule = np.concatenate(
                [is_rule, np.ones(len(rarr), bool)])[order]
        mut_registered: set[str] = set()
        n_frames = 0
        for lo in range(0, len(arr), spec.frame_size):
            hi = min(lo + spec.frame_size, len(arr))
            payloads = [generate_measurements_message(
                f"{prefix}-{int(picks[k])}", ti * 10_000_000 + k,
                **({"name": tl.rule_channel, "value": tl.rule_value}
                   if is_rule[k] else {}))
                for k in range(lo, hi)]
            frame_t = float(arr[hi - 1])
            ops.append(ScheduledOp(
                t_s=frame_t, kind="ingest", tenant=tl.tenant,
                payloads=payloads,
                arrivals=tuple(float(a) for a in arr[lo:hi])))
            n_frames += 1
            if tl.query_every and n_frames % tl.query_every == 0:
                variant = (n_frames // tl.query_every) % 3
                if variant == 0:
                    q = {"limit": 20}
                elif variant == 1:
                    q = {"device_token":
                         f"{prefix}-{int(picks[lo])}", "limit": 20}
                else:
                    q = {"since_ms": 0, "limit": 20}
                ops.append(ScheduledOp(t_s=frame_t, kind="query",
                                       tenant=tl.tenant, query=q))
            if tl.history_every and n_frames % tl.history_every == 0:
                # deterministic MARKER, not a concrete range: the schedule
                # is a pure function of the spec (no wall clock), so the
                # driver resolves the range against the engine's epoch at
                # fire time — "everything up to history_age_ms ago", which
                # on an archive-primed engine lands beyond the ring
                hv = (n_frames // tl.history_every) % 2
                q = {"history_age_ms": tl.history_age_ms, "limit": 20}
                if hv == 1:
                    q["device_token"] = f"{prefix}-{int(picks[lo])}"
                ops.append(ScheduledOp(t_s=frame_t, kind="query",
                                       tenant=tl.tenant, query=q))
            if tl.analytics_every and n_frames % tl.analytics_every == 0:
                # deterministic scoring-job MARKER (ISSUE 19), the
                # history_every mirror: a pure function of the spec — the
                # driver resolves it into an archive->device batched
                # scoring job at fire time. emit=False keeps the measured
                # ingest stream closed (scores don't feed back into the
                # event counts the run asserts on); the name pins the
                # job's dedup-key lineage per marker
                j = n_frames // tl.analytics_every
                a = {"window": 8, "min_fill": 1, "batch_devices": 8,
                     "emit": False, "name": f"lg-{tl.tenant}-{j}"}
                ops.append(ScheduledOp(t_s=frame_t, kind="analytics",
                                       tenant=tl.tenant, analytics=a))
            if tl.mutate_every and n_frames % tl.mutate_every == 0:
                j = n_frames // tl.mutate_every
                token = f"{prefix}-m{j % 8}"
                if token not in mut_registered:
                    mut_registered.add(token)
                    mut = ("register", token, None)
                else:
                    mut = ("update", token, {"rev": str(j)})
                ops.append(ScheduledOp(t_s=frame_t, kind="mutate",
                                       tenant=tl.tenant, mutate=mut))
    ops.sort(key=lambda op: (op.t_s, op.tenant, _KIND_ORDER[op.kind]))
    return ops


def schedule_fingerprint(schedule: list[ScheduledOp]) -> str:
    """SHA-256 over the canonical byte form of a schedule — the
    determinism pin (same seed => same fingerprint) and the provenance
    field a run records next to its measured numbers."""
    h = hashlib.sha256()
    for op in schedule:
        h.update(f"{op.kind}|{op.tenant}|{op.t_s!r}\n".encode())
        for p in op.payloads or ():
            h.update(p)
        for a in op.arrivals or ():
            h.update(repr(a).encode())
        if op.query is not None:
            h.update(json.dumps(op.query, sort_keys=True).encode())
        if op.mutate is not None:
            h.update(repr(op.mutate).encode())
        if op.analytics is not None:
            h.update(json.dumps(op.analytics, sort_keys=True).encode())
    return h.hexdigest()


def _pcts(lat_ms: list[float]) -> dict:
    if not lat_ms:
        return {"p50_ms": None, "p99_ms": None, "p999_ms": None,
                "max_ms": None}
    a = np.asarray(lat_ms)
    return {"p50_ms": round(float(np.percentile(a, 50)), 3),
            "p99_ms": round(float(np.percentile(a, 99)), 3),
            "p999_ms": round(float(np.percentile(a, 99.9)), 3),
            "max_ms": round(float(a.max()), 3)}


@dataclasses.dataclass
class OpenLoopResult:
    """Per-tenant SLO view of one open-loop run. For each tenant,
    ``per_tenant[t]`` carries two latency families:

      e2e_*      scheduled arrival -> visible in device state. THE SLO
                 number: includes queueing delay whenever the engine
                 (or the driver) fell behind the arrival process.
      service_*  submit -> visible. The engine-side span comparable to
                 the flight-recorder-harvested swtpu_ingest_e2e_seconds
                 histogram (same start edge as the batch's flight
                 record). e2e == service when the run kept pace.

    With QoS enabled on the engine (``engine.qos``), the driver acts as
    the admission EDGE: shed frames are counted per tenant (``shed`` in
    ``per_tenant``, ``shed_events`` in total) and never submitted —
    ``events`` is the ADMITTED count, the denominator of any
    zero-admitted-loss check.
    """

    wall_s: float
    events: int
    events_per_s: float
    offered_eps: float
    queries: int
    query_p99_ms: float | None
    history_queries: int
    history_p99_ms: float | None
    scoring_jobs: int
    scoring_p50_ms: float | None
    scoring_p99_ms: float | None
    mutations: int
    max_lateness_s: float
    per_tenant: dict
    shed_events: int = 0
    # span/trace coverage (ISSUE 10): fraction of a sample of this run's
    # ingest trace ids that still resolve on the engine (flight records
    # or spans) after the run — the observability plane's own SLO. None
    # when the run ingested nothing.
    trace_coverage: float | None = None
    # device plane (ISSUE 11): XLA programs compiled per watched family
    # DURING this run (devicewatch totals delta). A warm steady-state
    # run should show {} — any entry is a latency cliff the SLO
    # histograms would otherwise launder into "one slow frame". None
    # when devicewatch is unavailable/disabled.
    compile_counts: dict | None = None
    # ingest-path provenance (ISSUE 17): host_counters deltas over the
    # run — ``arena_rows`` (rows scattered zero-copy into staging
    # arenas) vs ``staged_copy_rows`` (rows that took a per-row host
    # copy). On an SpmdEngine in its default arena mode every measured
    # event should land in arena_rows, pinning that open-loop --shards
    # numbers exercise the batch ingest edge, not per-event staging.
    ingest_path: dict | None = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def run_open_loop(engine, schedule: list[ScheduledOp], *,
                  checkpoint_frames: int = 4,
                  time_scale: float = 1.0) -> OpenLoopResult:
    """Replay a schedule against a live engine (Engine, DistributedEngine,
    ClusterEngine or the mesh-sharded SpmdEngine — anything with
    ingest_json_batch / query_events / flush; the driver never looks
    inside, so the SPMD router's slot fan-out is exercised exactly as
    production traffic would). Ops fire at their scheduled time; a late
    driver fires
    immediately and the lateness lands in the measured latency (open
    loop). Completion checkpoints every ``checkpoint_frames`` ingest
    frames call ``engine.flush()`` — on a cluster facade that fans out,
    so forwarded events count only once visible at their OWNER."""
    pending: list[tuple[str, list[float], float]] = []
    per: dict[str, tuple[list, list]] = {}
    qlat: list[float] = []
    hlat: list[float] = []
    alat: list[float] = []
    epoch = getattr(engine, "epoch", None)
    # the driver is an ingest EDGE: with QoS on, every frame faces the
    # engine's admission controller here — shed frames count per tenant
    # and are never submitted (the client saw an explicit 429)
    qos = getattr(engine, "qos", None)
    shed: dict[str, int] = {}
    trace_sample: list[str] = []   # first few ingest trace ids: span
    #                                coverage is checked after the run
    mutations = 0
    max_late = 0.0
    frames = 0
    events = 0
    # devicewatch (ISSUE 11): snapshot per-family compile totals so the
    # result reports compiles observed DURING the run
    compiles0 = None
    try:
        from sitewhere_tpu.utils.devicewatch import WATCH, compile_totals

        if WATCH.enabled:
            compiles0 = compile_totals()
    except ImportError:
        pass
    hc0 = dict(getattr(engine, "host_counters", None) or {})
    t0 = time.perf_counter()

    def checkpoint():
        nonlocal frames
        frames = 0
        if not pending:
            return
        engine.flush()
        t_done = time.perf_counter()
        for tenant, arrivals, submit in pending:
            e2e, svc = per.setdefault(tenant, ([], []))
            e2e.extend((t_done - a) * 1e3 for a in arrivals)
            svc.extend([(t_done - submit) * 1e3] * len(arrivals))
        pending.clear()

    for op in schedule:
        target = t0 + op.t_s * time_scale
        now = time.perf_counter()
        if now < target:
            time.sleep(target - now)
        else:
            max_late = max(max_late, now - target)
        if op.kind == "ingest":
            if qos is not None:
                d = qos.admit(op.tenant, len(op.payloads))
                if not d.admitted:
                    shed[op.tenant] = (shed.get(op.tenant, 0)
                                       + len(op.payloads))
                    continue
            submit = time.perf_counter()
            summary = engine.ingest_json_batch(op.payloads, op.tenant)
            tid = (summary or {}).get("trace_id")
            if tid and len(trace_sample) < 16:
                trace_sample.append(tid)
            pending.append((op.tenant,
                            [t0 + a * time_scale for a in op.arrivals],
                            submit))
            events += len(op.payloads)
            frames += 1
            if frames >= checkpoint_frames:
                checkpoint()
        elif op.kind == "query":
            q = dict(op.query)
            age = q.pop("history_age_ms", None)
            if age is not None:
                # resolve the historical marker at fire time: a range from
                # the beginning of history (unbounded start — backfilled
                # events can sit at negative epoch-relative ms) to ``age``
                # before now — older than the ring on any archive-primed
                # run, so the tiered read path serves it
                now_rel = (int(epoch.now_ms()) if epoch is not None
                           else 0)
                q["until_ms"] = now_rel - int(age)
            t1 = time.perf_counter()
            engine.query_events(**q)
            (hlat if age is not None
             else qlat).append((time.perf_counter() - t1) * 1e3)
        elif op.kind == "analytics":
            # archive->device scoring-job marker (ISSUE 19): resolved
            # against the engine's job manager at fire time; engines
            # without the manager (or without an archive to stream from)
            # skip it, so plain-store schedules replay unchanged
            aj = getattr(engine, "analytics_jobs", None)
            if aj is not None and getattr(engine, "archive", None) is not None:
                t1 = time.perf_counter()
                aj.run_job(dict(op.analytics, tenant=op.tenant))
                alat.append((time.perf_counter() - t1) * 1e3)
        else:
            kind, token, md = op.mutate
            if kind == "register":
                engine.register_device(token, tenant=op.tenant)
            else:
                try:
                    engine.update_device(token, metadata=md)
                except KeyError:
                    engine.register_device(token, tenant=op.tenant)
            mutations += 1
    checkpoint()
    wall = time.perf_counter() - t0
    # span/trace coverage (ISSUE 10): every sampled ingest trace id must
    # still resolve to a non-empty timeline (flight-record intervals or
    # live spans) — the observability plane's own SLO
    coverage = None
    get_tl = getattr(engine, "get_trace_timeline", None)
    if trace_sample and get_tl is not None:
        hits = 0
        for tid in trace_sample:
            try:
                doc = get_tl(tid)
            except Exception:
                continue
            if any(e.get("ph") == "X" for e in doc.get("traceEvents", ())):
                hits += 1
        coverage = round(hits / len(trace_sample), 3)
    horizon = max((op.t_s for op in schedule), default=0.0) * time_scale
    per_tenant = {}
    for tenant in sorted(set(per) | set(shed)):
        e2e, svc = per.get(tenant, ([], []))
        per_tenant[tenant] = {
            "events": len(e2e),
            "shed": shed.get(tenant, 0),
            **{f"e2e_{k}": v for k, v in _pcts(e2e).items()},
            **{f"service_{k}": v for k, v in _pcts(svc).items()},
        }
    compile_counts = None
    if compiles0 is not None:
        from sitewhere_tpu.utils.devicewatch import compile_totals

        compile_counts = {
            fam: n - compiles0.get(fam, 0)
            for fam, n in compile_totals().items()
            if n - compiles0.get(fam, 0)}
    hc1 = getattr(engine, "host_counters", None) or {}
    ingest_path = {k: int(hc1.get(k, 0)) - int(hc0.get(k, 0))
                   for k in ("arena_rows", "staged_copy_rows")}
    qp = _pcts(qlat)
    hp = _pcts(hlat)
    ap = _pcts(alat)
    return OpenLoopResult(
        wall_s=round(wall, 3), events=events,
        events_per_s=round(events / wall, 1) if wall else 0.0,
        offered_eps=round((events + sum(shed.values())) / horizon, 1)
        if horizon else 0.0,
        queries=len(qlat), query_p99_ms=qp["p99_ms"],
        history_queries=len(hlat), history_p99_ms=hp["p99_ms"],
        scoring_jobs=len(alat), scoring_p50_ms=ap["p50_ms"],
        scoring_p99_ms=ap["p99_ms"],
        mutations=mutations, max_lateness_s=round(max_late, 4),
        per_tenant=per_tenant, shed_events=sum(shed.values()),
        trace_coverage=coverage, compile_counts=compile_counts,
        ingest_path=ingest_path)


async def run_rest_load(base_url: str, jwt: str, n_workers: int = 5,
                        msgs_per_worker: int = 100,
                        device_prefix: str = "rest-lg") -> LoadStats:
    """Wire-level driver: N concurrent workers x M posts each (the 5x100
    pattern of EventSourceTests.java:50-53) against /api/devices/{t}/events."""
    import asyncio

    import aiohttp

    latencies: list[float] = []
    failed = 0
    headers = {"Authorization": f"Bearer {jwt}"}

    async def worker(w: int, session: aiohttp.ClientSession):
        nonlocal failed
        token = f"{device_prefix}-{w}"
        for i in range(msgs_per_worker):
            body = json.loads(generate_measurements_message(token, i))
            s0 = time.perf_counter()
            async with session.post(
                f"{base_url}/api/devices/{token}/events",
                json=body, headers=headers,
            ) as r:
                if r.status != 201:
                    failed += 1
                await r.read()
            latencies.append((time.perf_counter() - s0) * 1e3)

    t0 = time.perf_counter()
    async with aiohttp.ClientSession() as session:
        await asyncio.gather(*(worker(w, session) for w in range(n_workers)))
    wall = time.perf_counter() - t0
    sent = n_workers * msgs_per_worker
    p50, p99, mx = _percentiles(latencies)
    return LoadStats(sent, sent - failed, failed, wall, sent / wall, p50, p99, mx)


def main() -> None:
    import argparse

    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batches", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=4096)
    ap.add_argument("--devices", type=int, default=10_000)
    ap.add_argument("--open-loop", action="store_true",
                    help="seeded open-loop mixed workload instead of the "
                         "closed-loop batch driver")
    ap.add_argument("--rate", type=float, default=5000.0,
                    help="open-loop arrival rate (events/s)")
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=0,
                    help="drive the mesh-sharded SPMD engine with N "
                         "shards instead of a single-chip engine "
                         "(0 = single-chip; requires >= N attached "
                         "devices). Wire frames go through the batch "
                         "ingest edge (arena scatter), never per-event "
                         "staging — the result's ingest_path counters "
                         "pin it")
    args = ap.parse_args()

    cfg = EngineConfig(
        device_capacity=max(1 << 15, 1 << (args.devices - 1).bit_length()),
        token_capacity=1 << 17, assignment_capacity=1 << 17,
        store_capacity=1 << 18, batch_capacity=args.batch_size,
    )
    if args.shards:
        from sitewhere_tpu.parallel.sharded import SpmdEngine

        engine = SpmdEngine(cfg, n_shards=args.shards)
    else:
        engine = Engine(cfg)
    if args.open_loop:
        # warm OUTSIDE the measured schedule: the first flush pays the
        # fused-step jit compile (seconds), which would otherwise land
        # in — and, open-loop, cascade through — every reported latency
        run_engine_load(engine, n_batches=1, batch_size=args.batch_size,
                        n_devices=min(args.devices, 4096),
                        warmup_batches=1)
        spec = OpenLoopSpec(
            tenants=(TenantLoad("default", args.rate,
                                n_devices=min(args.devices, 4096),
                                query_every=8, mutate_every=16),),
            duration_s=args.duration,
            frame_size=min(args.batch_size, 512), seed=args.seed)
        schedule = build_open_loop_schedule(spec)
        res = run_open_loop(engine, schedule)
        print(json.dumps({
            "schedule_fingerprint": schedule_fingerprint(schedule),
            **res.to_dict()}))
        return
    stats = run_engine_load(engine, args.batches, args.batch_size, args.devices)
    print(json.dumps(stats.to_dict()))


if __name__ == "__main__":
    main()
