#!/usr/bin/env python3
"""Multi-chip SPMD store bench leg (ISSUE 16), run as a SUBPROCESS of
bench.py: the parent process initializes JAX before the leg runs, so a
multi-device mesh (virtual CPU devices in smoke, the real slice on
hardware) must be configured in a fresh interpreter.

Drives the mesh-sharded real engine (parallel.sharded.SpmdEngine) next
to a single-chip reference over the SAME wire stream and emits ONE JSON
line on stdout:

  * parity gates — sharded store bytes vs per-shard substreams AND vs
    the v1 per-row router (the arena-path byte-identity oracle), fused
    query pages, metrics dict (rules on), merged rule-fire keys;
  * devicewatch gates — zero excess retraces, zero steady-state
    recompiles for the ``sharded.*`` families with ``scan_chunk = 2``;
  * arena-path gates — ``host_copies_per_batch == 0`` and arena ingest
    throughput >= the row-router contrast;
  * conservation — the flow ledger balances through the sharded lanes;
  * reported rates — N-chip ingest ev/s (arena and row-router), fused
    cross-shard query QPS, per-stage medians (decode / route / wal /
    dispatch_wait / device).

Env: BENCH_SPMD_SHARDS (default 2 smoke / all devices on hardware),
BENCH_SMOKE=1 for reduced sizes. Everything before the jax import is
stdlib-only so the import-hygiene sweep can load this module cheaply.
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    # standalone `python scripts/bench_spmd.py` finds the package too
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if smoke or os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import time

    import jax
    import numpy as np

    from sitewhere_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    from sitewhere_tpu.core.events import EpochBase
    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.parallel.placement import shard_for_token
    from sitewhere_tpu.parallel.sharded import SpmdEngine
    from sitewhere_tpu.rules import RulesManager
    from sitewhere_tpu.utils.conservation import (build_ledger,
                                                  check_conservation)
    from sitewhere_tpu.utils.devicewatch import WATCH

    n_devices = len(jax.devices())
    n_shards = int(os.environ.get(
        "BENCH_SPMD_SHARDS", 2 if smoke else max(2, n_devices)))
    n_shards = min(n_shards, n_devices)

    class FixedEpoch(EpochBase):
        def __init__(self, now_ms=500_000):
            super().__init__(0.0)
            self._now = now_ms

        def now_ms(self):
            return self._now

    DEVS = 32 if smoke else 256
    BATCH = 256 if smoke else 4096
    FRAMES = 24 if smoke else 64
    cfg = dict(device_capacity=max(64, DEVS * 2),
               token_capacity=max(128, DEVS * 2),
               assignment_capacity=max(128, DEVS * 2),
               store_capacity=1 << (14 if smoke else 18),
               batch_capacity=BATCH, channels=4,
               rule_groups=max(64, DEVS * 2), rollup_buckets=8,
               use_native=False)
    RULESET = {
        "name": "spmd-bench",
        "rules": [
            {"name": "hot", "kind": "threshold", "channel": "temp",
             "op": ">", "value": 90.0, "cooldownMs": 1000},
        ],
        "rollups": [],
    }

    def wire_frame(f):
        out = []
        for i in range(BATCH):
            d = (f * BATCH + i) % DEVS
            ts = 1_000 + (f * BATCH + i) * 3
            v = 96.5 if (f * BATCH + i) % 17 == 0 else 25.0 + (i % 50)
            out.append(json.dumps({
                "deviceToken": f"bs-{d}", "type": "DeviceMeasurement",
                "request": {"name": "temp", "value": v,
                            "eventDate": ts}}).encode())
        return out

    ref = Engine(EngineConfig(**cfg))
    # the headline engine runs the full arena path: packed 2-chunk scan
    # per dispatch, pipelined arena pool (ingest_arenas auto-depth > 1)
    spmd = SpmdEngine(EngineConfig(**cfg, scan_chunk=2),
                      n_shards=n_shards)
    for e in (ref, spmd):
        e.epoch = FixedEpoch()
    mref, mspmd = RulesManager(ref), RulesManager(spmd)
    mref.load(RULESET)
    mspmd.load(RULESET, precompile=False)

    frames = [wire_frame(f) for f in range(FRAMES)]
    # warm both engines (compile outside the timed window)
    for e in (ref, spmd):
        e.ingest_json_batch(frames[0])
        e.flush()
        e.query_events(device_token="bs-1", limit=64)

    pre_compiles = WATCH.compile_totals()
    pre_excess = WATCH.excess_total()
    copies_before = spmd.host_counters.get("staged_copy_rows", 0)

    # no per-frame flush: the arena packs scan_chunk device batches per
    # dispatch and auto-dispatches when its lanes fill
    t0 = time.perf_counter()
    for fr in frames[1:]:
        spmd.ingest_json_batch(fr)
    spmd.flush_async()
    spmd.barrier()
    spmd.drain()
    spmd_ingest_s = time.perf_counter() - t0
    host_copies_per_batch = (
        (spmd.host_counters.get("staged_copy_rows", 0) - copies_before)
        / max(1, len(frames) - 1))
    for fr in frames[1:]:
        ref.ingest_json_batch(fr)
        ref.flush_async()
    ref.barrier()
    ref.drain()

    n_events = (len(frames) - 1) * BATCH
    spmd_eps = n_events / max(spmd_ingest_s, 1e-9)

    # per-stage medians over the timed window's batch records (SPMD mark
    # order: decode -> wal_append -> route -> arena_fill -> commit ->
    # dispatch -> device_ready)
    def _stage_medians(recs):
        def deltas(lows, b):
            out = []
            for r in recs:
                st = r.get("stagesUs", {})
                hi = st.get(b)
                if hi is None:
                    continue
                # first present lower bound wins (WAL marks are absent
                # when no wal_dir is configured)
                lo = next((st[a] for a in lows if st.get(a) is not None),
                          0.0 if None in lows else None)
                if lo is not None:
                    out.append(max(0.0, (hi - lo) / 1000.0))
            return round(float(np.median(out)), 3) if out else None

        return {
            "decode_ms": deltas([None], "decode"),
            "wal_ms": deltas(["decode"], "wal_append"),
            "route_ms": deltas(["wal_append", "decode"], "route"),
            "dispatch_wait_ms": deltas(["commit"], "dispatch"),
            "device_ms": deltas(["dispatch"], "device_ready"),
        }

    stage_medians = _stage_medians(
        spmd.flight.recent(limit=len(frames), kind="ingest"))

    # fused cross-shard query rounds (steady-state: one compiled program)
    t0 = time.perf_counter()
    Q = 40 if smoke else 200
    for q in range(Q):
        spmd.query_events(device_token=f"bs-{q % DEVS}", limit=64)
    query_qps = Q / max(time.perf_counter() - t0, 1e-9)

    steady_recompiles = sum(
        (WATCH.compile_totals().get(k, 0) - v)
        for k, v in pre_compiles.items())
    excess_retraces = WATCH.excess_total() - pre_excess

    # --- v1 row-router contrast (same stream, per-row host routing) ------
    router = SpmdEngine(EngineConfig(**cfg), n_shards=n_shards,
                        arena=False)
    router.epoch = FixedEpoch()
    router.ingest_json_batch(frames[0])
    router.flush()
    t0 = time.perf_counter()
    for fr in frames[1:]:
        router.ingest_json_batch(fr)
        router.flush_async()
    router.barrier()
    router.drain()
    router_eps = n_events / max(time.perf_counter() - t0, 1e-9)

    # arena-path store bytes == row-router store bytes (the ISSUE 17
    # acceptance oracle), checked on the full stacked store
    arena_store_identical = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(spmd.state.store)),
            jax.tree_util.tree_leaves(jax.device_get(router.state.store))))

    # --- parity gates ----------------------------------------------------
    def page(eng, **kw):
        out = eng.query_events(**kw)
        return out["total"], [
            {k: v for k, v in ev.items() if k != "assignmentId"}
            for ev in out["events"]]

    query_parity = all(
        page(ref, **kw) == page(spmd, **kw) for kw in (
            dict(limit=200),
            dict(device_token="bs-3", limit=64),
            dict(device_token="bs-7", since_ms=2_000, limit=64),
        ))

    a, b = ref.metrics(), spmd.metrics()
    metric_keys = ("processed", "found", "missed", "registered",
                   "persisted", "reg_overflow", "channel_collisions",
                   "staged", "rule_fires", "rules_active")
    metrics_equal = all(a[k] == b[k] for k in metric_keys)

    rules_parity = ({x["alternateId"] for x in mref.poll()}
                    == {x["alternateId"] for x in mspmd.poll()})

    # store bytes: each shard vs a single-chip engine fed its substream
    all_events = []
    for f, fr in enumerate(frames):
        for payload in fr:
            env = json.loads(payload)
            all_events.append((env["deviceToken"], payload))
    store_parity = True
    for s in range(n_shards):
        sub = Engine(EngineConfig(**cfg))
        sub.epoch = FixedEpoch()
        lane = [p for tok, p in all_events
                if shard_for_token(tok, n_shards) == s]
        for lo in range(0, len(lane), BATCH):
            sub.ingest_json_batch(lane[lo:lo + BATCH])
            sub.flush()
        sub.barrier()
        sub.drain()
        ref_leaves = jax.tree_util.tree_leaves(
            jax.device_get(sub.state.store))
        spmd_leaves = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda x, _s=s: jax.device_get(x[_s]), spmd.state.store))
        for x, y in zip(ref_leaves, spmd_leaves):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                store_parity = False

    spmd.flush()
    violations = [v.to_dict() if hasattr(v, "to_dict") else str(v)
                  for v in check_conservation(build_ledger(spmd, mspmd))]

    # --- shard heat & skew hotspot leg (ISSUE 18) ------------------------
    # A seeded hotspot stream: a broad background tenant plus a "hot"
    # tenant whose abusive extra stream is pinned onto ONE device (the
    # loadgen hotspot knob), concentrating the burst on one placement
    # slot / shard lane. Gates: the heat plane's top-1 (shard, tenant)
    # cell and top-1 slot name the seeded target, the per-dispatch
    # accounting costs <= 3% (interleaved on/off contrast, min of 3
    # sessions — the placement-plane discipline), zero steady-state
    # recompiles with live harvests, and the per-shard conservation
    # breakdown balances.
    import statistics

    from sitewhere_tpu.loadgen import (OpenLoopSpec, TenantLoad,
                                       build_open_loop_schedule)
    from sitewhere_tpu.parallel.placement import slot_for_token
    from sitewhere_tpu.pipeline import TENANT_COUNTER_BUCKETS

    HOT_DEV = 0
    hot_spec = OpenLoopSpec(
        tenants=(
            TenantLoad("bg", rate_eps=(1500.0 if smoke else 12000.0),
                       n_devices=DEVS),
            TenantLoad("hot", rate_eps=(300.0 if smoke else 2400.0),
                       n_devices=8, abusive_mult=8.0,
                       abusive_device=HOT_DEV),
        ),
        duration_s=1.0 if smoke else 2.0,
        frame_size=max(64, BATCH // 2), seed=18)
    hot_frames = [(op.tenant, op.payloads)
                  for op in build_open_loop_schedule(hot_spec)
                  if op.kind == "ingest"]

    heng = SpmdEngine(EngineConfig(**cfg, scan_chunk=2),
                      n_shards=n_shards)
    heng.epoch = FixedEpoch()
    # warm: compile (and register both tenants' devices) outside the
    # timed window, with two harvests priming the EWMA baselines
    h_clock = 0.0
    for tenant, payloads in hot_frames[:4]:
        heng.ingest_json_batch(payloads, tenant)
    heng.flush()
    heng.drain()
    heng.harvest_shard_heat(now_s=h_clock)
    hot_pre_compiles = WATCH.compile_totals()

    # one continuous stream, per-batch plane toggle with alternating
    # phase per session; harvests run live (injected clock — the EWMA
    # maps are deterministic) so the recompile gate covers them
    overheads = []
    for sess in range(3):
        on: list[float] = []
        off: list[float] = []
        for k, (tenant, payloads) in enumerate(hot_frames):
            heng.shard_heat.enabled = bool((k + sess) % 2)
            t0 = time.perf_counter()
            heng.ingest_json_batch(payloads, tenant)
            dt = time.perf_counter() - t0
            (on if heng.shard_heat.enabled else off).append(dt)
            if k % 8 == 7:
                h_clock += 0.25
                heng.harvest_shard_heat(now_s=h_clock)
        heng.flush_async()
        heng.barrier()
        med_on = statistics.median(on)
        med_off = statistics.median(off)
        overheads.append(max(0.0, (med_on - med_off) / med_off * 100.0))
    heng.shard_heat.enabled = True
    heng.drain()
    heat_overhead_pct = round(min(overheads), 2)

    h_clock += 0.25
    tr = heng.harvest_shard_heat(now_s=h_clock)
    heat_recompiles = sum(
        (WATCH.compile_totals().get(k, 0) - v)
        for k, v in hot_pre_compiles.items())

    hot_bucket = None
    for tid in range(len(heng.tenants)):
        if heng.tenants.token(tid) == "hot":
            hot_bucket = tid % TENANT_COUNTER_BUCKETS
    hs, hb = np.unravel_index(int(np.argmax(tr.heat_grid)),
                              tr.heat_grid.shape)
    hot_slot = slot_for_token(f"hot-dev-{HOT_DEV}", n_shards)
    top = tr.top_slots(k=1)
    top1_tenant = hot_bucket is not None and int(hb) == hot_bucket
    top1_slot = bool(top) and top[0][0] == hot_slot

    heng.flush()
    hot_violations = check_conservation(build_ledger(heng))
    flow = heng.shard_flow()
    flow_balanced = (not hot_violations
                     and "spmd" in build_ledger(heng)["stages"]
                     and sum(r["accepted"] + r["invalid"]
                             for r in flow["perShard"])
                     == sum(r["processed"] for r in flow["perShard"]))

    print(json.dumps({
        "spmd_shards": n_shards,
        "spmd_store_parity": store_parity,
        "spmd_query_parity": query_parity,
        "spmd_metrics_equal": metrics_equal,
        "spmd_rules_parity": rules_parity,
        "spmd_steady_recompiles": steady_recompiles,
        "spmd_excess_retraces": excess_retraces,
        "conservation_spmd_violations": len(violations),
        "spmd_ingest_events_per_s": round(spmd_eps),
        "spmd_rowrouter_events_per_s": round(router_eps),
        "spmd_arena_store_identical": arena_store_identical,
        "spmd_arena_ge_rowrouter": bool(spmd_eps >= router_eps),
        "host_copies_per_batch": round(host_copies_per_batch, 3),
        "spmd_stage_medians": stage_medians,
        "spmd_query_qps": round(query_qps, 1),
        "spmd_events_total": n_events,
        "spmd_heat_top1_hot_tenant": bool(top1_tenant),
        "spmd_heat_top1_hot_slot": bool(top1_slot),
        "spmd_heat_overhead_pct": heat_overhead_pct,
        "spmd_heat_steady_recompiles": heat_recompiles,
        "spmd_shard_flow_balanced": bool(flow_balanced),
        "spmd_skew_index": round(float(tr.skew_index), 3),
        "spmd_hot_slot": int(hot_slot),
        "spmd_hot_shard": int(hs),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
