"""Device-plane observability (ISSUE 11): compile/retrace watchdog,
memory ledger, per-program cost, device profiler capture.

Pins the acceptance surface: every engine program family reports its
compiles (timed, with cost analysis) and holds a retrace budget that a
deliberate shape churn trips — loudly in normal mode, as a typed
:class:`RetraceError` BEFORE dispatch in strict mode; the memory
ledger's ring/arena byte totals reconcile with independently recomputed
capacities; the capacity high-watermarks reset on scrape; the REST
surfaces (``/api/instance/device/memory``,
``/api/instance/profile/device``) and the debug bundle's ``device``
section serve the same breakdown; and none of it leaks into
``engine.metrics()`` — the dispatch-shape equality pin runs WITH
devicewatch enabled (it defaults on), the test_ingest ~line 872
pattern."""

import json

import numpy as np
import pytest

from sitewhere_tpu.engine import Engine, EngineConfig, _empty_host_batch
from sitewhere_tpu.loadgen import generate_measurements_message
from sitewhere_tpu.utils.devicewatch import (WATCH, RetraceError,
                                             WatchScope, compile_posture,
                                             compile_totals,
                                             device_memory_payload,
                                             memory_ledger,
                                             strict_retraces)

SMALL = dict(device_capacity=64, token_capacity=128,
             assignment_capacity=128, store_capacity=4096,
             batch_capacity=16, channels=4)


def _engine(**kw) -> Engine:
    cfg = dict(SMALL)
    cfg.update(kw)
    return Engine(EngineConfig(**cfg))


def _batch(prefix="dw", n=16, base=0):
    return [generate_measurements_message(f"{prefix}-{i % 8}", base + i)
            for i in range(n)]


# ===================================================================
# Watchdog: compiles counted/timed/cost-analyzed, budgets enforced
# ===================================================================

def test_ingest_family_compiles_once_with_cost_and_timing():
    before = compile_totals().get("ingest.step", 0)
    # a shape combination no other test uses: under the full suite the
    # SMALL shape is already in jax's (and the watch's global) cache,
    # which would make this engine's first dispatch a HIT by design
    eng = _engine(store_capacity=8192, batch_capacity=48)
    eng.ingest_json_batch(_batch())
    eng.flush()
    eng.ingest_json_batch(_batch(base=100))
    eng.flush()
    post = compile_posture()["ingest.step"]
    # exactly one program for this engine, hit on the second dispatch
    assert compile_totals()["ingest.step"] == before + 1
    assert post["lastCompileS"] is not None and post["lastCompileS"] > 0
    assert post["retraceExcess"] == 0
    cost = post["lastCost"]
    assert cost and cost["flops"] > 0 and cost["bytes_accessed"] > 0


def test_warm_cache_second_engine_counts_hit_not_compile():
    """Two engines with identical shapes share jax's jit cache — the
    second engine's first dispatch must count as a cache HIT, or the
    compile counters would claim work XLA never did."""
    a = _engine()
    a.ingest_json_batch(_batch(prefix="wc"))
    a.flush()
    n0 = compile_totals().get("ingest.step", 0)
    hits0 = compile_posture()["ingest.step"]["cacheHits"]
    b = _engine()
    b.ingest_json_batch(_batch(prefix="wd"))
    b.flush()
    assert compile_totals()["ingest.step"] == n0
    assert compile_posture()["ingest.step"]["cacheHits"] > hits0


def test_retrace_budget_fires_on_shape_churn_and_strict_raises(caplog):
    """The watchdog's reason to exist: a batch whose shape drifted (here:
    capacity 24 against a 16-capacity engine) is a retrace beyond the
    engine's one-program budget — counted + shape-diff-logged in normal
    mode, raised as RetraceError BEFORE dispatch in strict mode."""
    import logging

    eng = _engine()
    eng.ingest_json_batch(_batch())
    eng.flush()
    fam0 = compile_posture()["ingest.step"]["retraceExcess"]
    churned = _empty_host_batch(24, 4)
    with caplog.at_level(logging.WARNING,
                         logger="sitewhere_tpu.utils.devicewatch"):
        eng.state, _ = eng._step(eng.state, churned)   # executes, loudly
    assert compile_posture()["ingest.step"]["retraceExcess"] == fam0 + 1
    assert any("retrace budget exceeded" in r.message for r in caplog.records)
    assert any("bool[16] -> bool[24]" in r.message
               for r in caplog.records), "shape diff not logged"
    # strict mode: raises BEFORE the jitted call — engine state is NOT
    # donated away by the refused dispatch
    churned32 = _empty_host_batch(32, 4)
    with strict_retraces():
        with pytest.raises(RetraceError):
            eng._step(eng.state, churned32)
    # the engine still works (state untouched by the strict refusal)
    eng.ingest_json_batch(_batch(base=50))
    assert eng.flush()["persisted"] > 0


def test_declared_transitions_do_not_trip_the_budget():
    """set_geofence_zones and a scan_chunk retune are DECLARED program
    changes — allowance granted / fresh scope — so legitimate operation
    never looks like churn."""
    eng = _engine(scan_chunk=2)
    eng.ingest_json_batch(_batch(n=32))
    eng.flush()
    excess0 = WATCH.excess_total()
    eng.set_geofence_zones([[(0.0, 0.0), (0.0, 1.0), (1.0, 1.0)]])
    eng.ingest_json_batch(_batch(n=32, base=100))
    eng.flush()
    eng.set_ingest_tuning(scan_chunk=4)
    eng.ingest_json_batch(_batch(n=64, base=200))
    eng.flush()
    eng.presence_sweep()
    assert WATCH.excess_total() == excess0
    # the converse guard: a NO-OP declaration (clearing already-None
    # zones, reinstalling the same zone shape) must NOT leak allowance —
    # genuine churn right after still trips the strict watchdog
    eng2 = _engine()
    eng2.ingest_json_batch(_batch(prefix="nz"))
    eng2.flush()
    eng2.set_geofence_zones([])            # zones already None: no grant
    with strict_retraces():
        with pytest.raises(RetraceError):
            eng2._step(eng2.state, _empty_host_batch(24, 4))


def test_query_batcher_records_aot_compiles_per_bucket():
    eng = _engine()
    eng.ingest_json_batch(_batch(prefix="qb"))
    eng.flush()
    before = compile_totals().get("query.batch", 0)
    eng.query_events(device_token="qb-1", limit=5)
    eng.query_events(device_token="qb-2", limit=5)    # same bucket: cached
    after1 = compile_totals()["query.batch"]
    assert after1 == before + 1
    eng.query_events(device_token="qb-1", limit=200)  # new limit bucket
    assert compile_totals()["query.batch"] == after1 + 1
    post = compile_posture()["query.batch"]
    assert post["retraceExcess"] == 0
    assert post["lastCost"] and post["lastCost"]["flops"] > 0


def test_scope_budget_allowance_semantics():
    """WatchScope unit pin: one program per bucket by default, allow()
    raises the cap, unbudgeted (bucket=None) scopes never fire."""
    scope = WatchScope(WATCH, "unit.test")
    k1 = (1, ("f32[4]",), ())
    k2 = (1, ("f32[8]",), ())
    k3 = (1, ("f32[16]",), ())
    assert scope.observe(k1, "b") == "compile"
    assert scope.observe(k1, "b") == "seen"
    fam0 = compile_posture()["unit.test"]["retraceExcess"]
    scope.observe(k2, "b")                      # beyond budget: counted
    assert compile_posture()["unit.test"]["retraceExcess"] == fam0 + 1
    scope.allow(1, "b")
    scope.observe(k3, "b")                      # granted: no new excess
    assert compile_posture()["unit.test"]["retraceExcess"] == fam0 + 1
    free = WatchScope(WATCH, "unit.free")
    for i in range(5):                          # unbudgeted: never fires
        free.observe((1, (f"f32[{i}]",), ()), None)
    assert compile_posture()["unit.free"]["retraceExcess"] == 0


def test_device_exec_histogram_harvests_from_flight_records():
    """Ingest and query device intervals land in swtpu_device_exec_seconds
    at scrape time, riding the existing consume-once flight drains — and
    repeated scrapes don't double-count."""
    from sitewhere_tpu.utils.metrics import (MetricsRegistry,
                                             devicewatch_metrics,
                                             export_engine_metrics)

    reg = MetricsRegistry()
    eng = _engine()
    eng.ingest_json_batch(_batch(prefix="ex"))
    eng.flush()
    eng.query_events(device_token="ex-1", limit=5)
    export_engine_metrics(eng, reg)
    h = devicewatch_metrics(reg)["exec"]
    n_ing = h.count(family="ingest")
    n_q = h.count(family="query")
    assert n_ing >= 1 and n_q >= 1
    export_engine_metrics(eng, reg)              # nothing new to drain
    assert h.count(family="ingest") == n_ing
    assert h.count(family="query") == n_q


# ===================================================================
# The standing pin: metrics() dispatch-shape equality WITH devicewatch
# ===================================================================

def test_metrics_dict_equality_across_dispatch_shapes_with_devicewatch():
    """The test_ingest ~line 872 pin, run explicitly WITH devicewatch on
    (its default): scan_chunk 1 vs 4 produce byte-equal metrics dicts
    and zero excess retraces — no watchdog key leaks into
    engine.metrics()."""
    def build(chunk):
        return Engine(EngineConfig(
            device_capacity=256, token_capacity=512,
            assignment_capacity=512, store_capacity=4096,
            batch_capacity=16, channels=4, scan_chunk=chunk,
            devicewatch=True))

    excess0 = WATCH.excess_total()
    a, b = build(1), build(4)
    b.epoch = a.epoch
    base = int(a.epoch.base_unix_s * 1000)
    payloads = [json.dumps(
        {"deviceToken": f"dwsc-{i % 40}", "type": "DeviceMeasurements",
         "eventDate": base + i,
         "request": {"measurements": {"t": float(i)}}}).encode()
        for i in range(160)]
    for eng in (a, b):
        for lo in range(0, 160, 16):
            eng.ingest_json_batch(payloads[lo:lo + 16])
        eng.flush()
    assert a.metrics() == b.metrics()
    assert a.metrics()["persisted"] == 160
    assert WATCH.excess_total() == excess0


# ===================================================================
# Memory ledger
# ===================================================================

def test_memory_ledger_reconciles_with_configured_capacities():
    """Ring-store bytes equal
    the eval_shape-derived size of the configured EventStore, arena-pool
    bytes equal n_arenas x a freshly built arena of the configured
    geometry."""
    import jax

    from sitewhere_tpu.core.store import EventStore
    from sitewhere_tpu.ingest.arena import StagingArena

    eng = _engine()
    led = memory_ledger(eng)
    comp = led["components"]
    exp_store = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: EventStore.zeros(4096, 4, 1))))
    assert comp["ring_store"] == exp_store
    if eng._arena_pool is not None:
        assert comp["arena_pool"] == (
            eng._arena_pool.n_arenas * StagingArena(16, 4, lanes=1).nbytes)
    assert led["totalBytes"] == sum(comp.values())
    assert led["liveArrays"] is None or led["liveArrays"]["bytes"] > 0


def test_high_watermarks_track_peaks_and_reset_on_scrape():
    eng = _engine()
    eng.ingest_json_batch(_batch(prefix="hw", n=16))
    eng.flush()
    # peek (no reset): the ingest drove at least one arena out of the
    # pool / rows through the backlog sample point
    led = memory_ledger(eng, reset_hwm=False)
    hwm = led["highWatermarks"]
    if eng._arena_pool is not None:
        assert hwm["arena_occupancy"] >= 1
        # scrape semantics: reset drains the peak back to "current"
        assert eng._arena_pool.take_occupancy_hwm(reset=True) >= 1
        assert eng._arena_pool.take_occupancy_hwm(reset=False) \
            == eng._arena_pool.n_arenas - eng._arena_pool.free_count
    assert eng.take_backlog_hwm(reset=True) >= 0
    assert eng.take_backlog_hwm(reset=False) == eng.staged_count


# ===================================================================
# Surfaces: REST endpoints, debug bundle, open-loop compile counts
# ===================================================================

def _rest_roundtrip(paths_params):
    """Start a real instance server, GET each (path, params), return
    bodies (json)."""
    import asyncio
    import base64

    from sitewhere_tpu.instance.instance import (InstanceConfig,
                                                 SiteWhereTpuInstance)
    from sitewhere_tpu.web.rest import start_server

    async def go():
        import aiohttp

        inst = SiteWhereTpuInstance(InstanceConfig(
            engine=EngineConfig(**SMALL)))
        inst.engine.ingest_json_batch(_batch(prefix="rest"))
        inst.engine.flush()
        server = await start_server(inst)
        base = f"http://127.0.0.1:{server.port}"
        try:
            async with aiohttp.ClientSession() as s:
                basic = base64.b64encode(b"admin:password").decode()
                async with s.get(
                        f"{base}/api/authapi/jwt",
                        headers={"Authorization": f"Basic {basic}"}) as r:
                    jwt = (await r.json())["token"]
                out = []
                for path, params in paths_params:
                    async with s.get(
                            base + path, params=params,
                            headers={"Authorization":
                                     f"Bearer {jwt}"}) as r:
                        out.append((r.status, await r.json()))
                return out
        finally:
            await server.cleanup()

    return asyncio.new_event_loop().run_until_complete(go())


def test_rest_device_memory_endpoint():
    (status, body), = _rest_roundtrip(
        [("/api/instance/device/memory", None)])
    assert status == 200
    assert body["components"]["ring_store"] > 0
    assert "highWatermarks" in body and "totalBytes" in body
    fams = body["compileFamilies"]
    assert fams["ingest.step"]["compiles"] >= 1


def test_rest_device_profile_endpoint(tmp_path):
    """GET /api/instance/profile/device?ms=N captures a jax profiler
    trace into a named directory (CPU captures host runtime; TPU runs
    get real device timelines) — or degrades to 503 if this backend has
    no profiler."""
    import os

    (status, body), = _rest_roundtrip(
        [("/api/instance/profile/device", {"ms": "60"})])
    if status == 503:
        pytest.skip(f"profiler unavailable: {body}")
    assert status == 200
    assert os.path.isdir(body["dir"])
    assert body["files"], "profiler capture produced no files"
    assert body["bytes"] > 0


def test_debug_bundle_carries_device_section():
    from sitewhere_tpu.utils.tracing import debug_bundle

    eng = _engine()
    eng.ingest_json_batch(_batch(prefix="db"))
    eng.flush()
    bundle = debug_bundle(eng)
    dev = bundle["device"]
    assert dev["components"]["ring_store"] > 0
    assert dev["compileFamilies"]["ingest.step"]["compiles"] >= 1
    json.dumps(bundle)                     # the bundle stays one document


def test_open_loop_reports_compile_counts():
    from sitewhere_tpu.loadgen import (OpenLoopSpec, TenantLoad,
                                       build_open_loop_schedule,
                                       run_open_loop)

    eng = _engine()
    spec = OpenLoopSpec(
        tenants=(TenantLoad("dwol", 400.0, n_devices=8),),
        duration_s=0.3, frame_size=16, seed=7)
    res = run_open_loop(eng, build_open_loop_schedule(spec))
    assert res.compile_counts is not None
    # a COLD engine compiles its step during the run; a second identical
    # run is steady-state and must report no ingest compiles
    res2 = run_open_loop(eng, build_open_loop_schedule(spec))
    assert not any(f.startswith("ingest.")
                   for f in (res2.compile_counts or {}))
