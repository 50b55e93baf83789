"""Shard heat & skew observability plane (ISSUE 18).

The contract pinned here, on the virtual 8-device CPU mesh:

  * **heat determinism** — the EWMA tracker never reads a clock; a
    seeded (grid, slot_rows, now_s) sequence replays to byte-identical
    heat maps, the first harvest primes baselines at zero heat, dt of
    one half-life halves a quiet cell EXACTLY, and counter regressions
    (restore) clamp to zero instead of going negative;
  * **skew discipline** — per-dispatch max/mean index, reset-on-scrape
    HWM (the PR-11 arena-HWM rule), and the two-consecutive-audit
    confirmation before a sustained-skew escalation (the PR-13
    conservation-auditor rule);
  * **per-shard conservation** — the ledger's new ``spmd-shard-flow``
    equation balances on a drained engine, per-shard lanes sum EXACTLY
    to the folded device stage, and perturbing one per-shard lane is a
    Violation (falsifiability);
  * **attribution** — a deliberately skewed two-tenant stream fingers
    the hot tenant in the (shard, tenant) heat map AND the hot token's
    placement slot as top-1;
  * **dispatch-shape pin** — exercising the whole plane leaves
    ``engine.metrics()`` dict-equal across ``scan_chunk`` retunes and
    free of heat/skew keys (the plane stays OUT, like every plane
    before it);
  * **surfaces** — scrape-time Prometheus export (lint-clean),
    ``spmd_heat_payload`` duck-typing ({"spmd": False} on single-chip),
    the debug bundle's "spmd" section, the ``decide_balance`` heat
    input (byte-identical policy when absent — the PR-15 pure-function
    pin), and the spmd.* flight spans surviving the offline
    trace2perfetto converter (smoke-invoked as a subprocess).
"""

import json
import subprocess
import sys

import numpy as np
import pytest

from sitewhere_tpu.engine import Engine, EngineConfig
from sitewhere_tpu.parallel.placement import (PlacementMap, decide_balance,
                                              slot_for_token)
from sitewhere_tpu.utils.conservation import build_ledger, check_conservation
from sitewhere_tpu.utils.metrics import MetricsRegistry, export_engine_metrics
from sitewhere_tpu.utils.shardobs import (ShardHeatTracker, heat_map_doc,
                                          spmd_heat_payload)
from sitewhere_tpu.utils.tracing import debug_bundle, timeline_events
from tests.test_spmd import CFG, FixedEpoch, _meas, _run, _spmd, _stream


def _grid(n_shards, n_buckets, accepted, invalid=None):
    """A synthetic cumulative tenant counter grid [S, T, 4] in
    TENANT_COUNTER_LANES order (accepted, dedup, geofence, invalid)."""
    g = np.zeros((n_shards, n_buckets, 4), np.int64)
    g[..., 0] = accepted
    if invalid is not None:
        g[..., 3] = invalid
    return g


# ===================================================================
# ShardHeatTracker unit pins
# ===================================================================

def test_first_harvest_primes_baselines_at_zero_heat():
    tr = ShardHeatTracker(2, 16)
    tr.harvest(_grid(2, 4, 100), np.zeros(16, np.int64), now_s=0.0)
    assert tr.harvests == 1
    assert tr.heat_grid is not None and not tr.heat_grid.any()
    assert not tr.slot_heat.any()
    assert tr.top_slots() == []


def test_heat_ewma_deterministic_and_halflife_exact():
    """Same (grid, slot_rows, now_s) sequence -> byte-identical maps;
    a quiet half-life halves heat EXACTLY (alpha = 1 - 0.5**(dt/hl))."""
    def replay():
        tr = ShardHeatTracker(2, 16, halflife_s=10.0)
        slots = np.zeros(16, np.int64)
        tr.harvest(_grid(2, 4, 0), slots, now_s=0.0)
        g = _grid(2, 4, 0)
        g[0, 1, 0] = 50                       # 50 ev in 1 s on (0, 1)
        s2 = slots.copy()
        s2[3] = 50
        tr.harvest(g, s2, now_s=1.0)
        tr.harvest(g, s2, now_s=3.5)          # quiet interval decays
        return tr

    a, b = replay(), replay()
    assert np.array_equal(a.heat_grid, b.heat_grid)
    assert np.array_equal(a.slot_heat, b.slot_heat)
    assert a.heat_grid[0, 1] > 0 and a.heat_grid[1, 1] == 0

    tr = ShardHeatTracker(1, 4, halflife_s=10.0)
    tr.harvest(_grid(1, 2, 0), np.zeros(4, np.int64), now_s=0.0)
    g = _grid(1, 2, 0)
    g[0, 0, 0] = 40
    tr.harvest(g, np.zeros(4, np.int64), now_s=10.0)
    warm = float(tr.heat_grid[0, 0])
    tr.harvest(g, np.zeros(4, np.int64), now_s=20.0)   # one quiet halflife
    assert float(tr.heat_grid[0, 0]) == warm * 0.5


def test_heat_counts_invalid_lane_and_clamps_regressions():
    """Heat is OFFERED load (accepted + invalid — garbage heats a shard
    like good rows do), and a counter regression (snapshot restore)
    clamps the delta to zero instead of producing negative heat."""
    tr = ShardHeatTracker(1, 4)
    tr.harvest(_grid(1, 2, 10, invalid=5), np.zeros(4, np.int64), 0.0)
    tr.harvest(_grid(1, 2, 14, invalid=11), np.zeros(4, np.int64), 1.0)
    assert float(tr.heat_grid[0, 0]) > 0
    tr2 = ShardHeatTracker(1, 4)
    tr2.harvest(_grid(1, 2, 100), np.zeros(4, np.int64), 0.0)
    tr2.harvest(_grid(1, 2, 7), np.zeros(4, np.int64), 1.0)  # went backwards
    assert float(tr2.heat_grid[0, 0]) == 0.0
    assert (tr2.heat_grid >= 0).all() and (tr2.slot_heat >= 0).all()


def test_dispatch_skew_index_and_hwm_reset_on_take():
    tr = ShardHeatTracker(4, 32)
    assert tr.note_dispatch([8, 0, 0, 0]) == 4.0
    assert tr.note_dispatch([2, 2, 2, 2]) == 1.0
    assert tr.note_dispatch([0, 0, 0, 0]) == 1.0     # empty = balanced
    assert tr.skew_hwm == 4.0                        # peek keeps the peak
    assert tr.take_skew_hwm() == 4.0                 # take resets...
    assert tr.take_skew_hwm() == 1.0                 # ...to the live index
    assert tr.dispatches == 3


def test_skew_escalation_needs_two_consecutive_audits():
    """One hot audit is a suspect, not a verdict; recovery between
    audits clears the suspicion (the PR-13 confirmation rule)."""
    tr = ShardHeatTracker(4, 32, skew_threshold=4.0)
    tr.note_dispatch([8, 0, 0, 0])                   # index 4.0: breach
    assert tr.audit_skew() is False                  # suspect only
    assert tr.audit_skew() is True                   # confirmed
    assert tr.sustained_total == 1
    # a PERSISTENT breach re-arms and escalates every other audit —
    # bounded noise, never a double-count within one confirmation
    assert tr.audit_skew() is False
    assert tr.audit_skew() is True
    assert tr.sustained_total == 2
    tr.note_dispatch([2, 2, 2, 2])                   # recovered
    assert tr.audit_skew() is False                  # suspicion cleared
    tr.note_dispatch([8, 0, 0, 0])
    assert tr.audit_skew() is False                  # must re-confirm
    assert tr.sustained_total == 2


def test_top_slots_hottest_first_quiet_omitted():
    tr = ShardHeatTracker(2, 16)
    tr.slot_heat[3] = 5.0
    tr.slot_heat[11] = 9.0
    tr.slot_heat[0] = 1.5
    assert tr.top_slots(2) == [(11, 9.0), (3, 5.0)]
    assert [s for s, _ in tr.top_slots()] == [11, 3, 0]


# ===================================================================
# Per-shard conservation (the spmd-shard-flow equation)
# ===================================================================

@pytest.mark.parametrize("n_shards", [2, 4])
def test_shard_flow_conservation_balances_and_is_falsifiable(n_shards):
    eng = _spmd(n_shards)
    _run([eng], _stream(n=96))
    eng.barrier()
    eng.drain()
    led = build_ledger(eng)
    assert not check_conservation(led)
    sp = led["stages"]["spmd"]
    assert sp["shards"] == n_shards and sp["counting"]
    dev = led["stages"]["device"]
    for lane in ("processed", "accepted", "invalid"):
        assert sum(r[lane] for r in sp["perShard"]) == dev[lane]
    # drained: routed == dispatched, zero backlog, work on every shard
    for row in sp["perShard"]:
        assert row["backlog_rows"] == 0
        assert row["routed_rows"] == row["dispatched_rows"] > 0
    # falsifiability (the PR-13 discipline): one per-shard lane off by
    # one breaks BOTH the partition and the fold-sum identity
    bad = json.loads(json.dumps(led))
    bad["stages"]["spmd"]["perShard"][0]["accepted"] += 1
    vs = check_conservation(bad)
    assert len(vs) == 2
    assert {v.equation for v in vs} == {"spmd-shard-flow"}
    bad2 = json.loads(json.dumps(led))
    bad2["stages"]["spmd"]["perShard"][-1]["dispatched_rows"] -= 1
    assert any(v.equation == "spmd-shard-flow"
               for v in check_conservation(bad2))


def test_shard_flow_mid_flight_backlog_is_the_legal_slack():
    eng = _spmd(2)
    wire = [_meas(f"sp-{i % 8}", 30.0, 1_000 + i * 10) for i in range(16)]
    eng.ingest_json_batch(wire)                       # staged, NOT flushed
    led = build_ledger(eng)
    assert not check_conservation(led)
    sp = led["stages"]["spmd"]
    assert sum(r["backlog_rows"] for r in sp["perShard"]) == 16
    assert all(r["dispatched_rows"] == 0 for r in sp["perShard"])
    eng.flush()
    eng.drain()
    assert not check_conservation(build_ledger(eng))


def test_single_chip_ledger_has_no_spmd_stage():
    eng = Engine(EngineConfig(**CFG))
    eng.epoch = FixedEpoch()
    _run([eng], _stream(n=32))
    led = build_ledger(eng)
    assert "spmd" not in led["stages"]
    assert not check_conservation(led)


# ===================================================================
# Heat attribution on the mesh engine
# ===================================================================

def test_heat_fingers_hot_tenant_and_hot_slot():
    """A stream where one tenant's one token carries 8x the rows: the
    (shard, tenant) heat map's hottest cell names THAT tenant and the
    top-1 slot is THAT token's placement slot (deterministic via the
    injected clock)."""
    eng = _spmd(2)
    eng.harvest_shard_heat(now_s=0.0)                 # prime baselines
    hot_tok, n_hot = "blaze-7", 64
    hot = [_meas(hot_tok, 21.0, 1_000 + i) for i in range(n_hot)]
    cold = [_meas(f"cold-{i}", 21.0, 1_000 + i) for i in range(8)]
    for lo in range(0, n_hot, 16):
        eng.ingest_json_batch(hot[lo:lo + 16], tenant="blaze")
        eng.flush()
    eng.ingest_json_batch(cold, tenant="quiet")
    eng.flush()
    eng.drain()
    tracker = eng.harvest_shard_heat(now_s=1.0)
    doc = heat_map_doc(tracker, eng.tenants)
    cells = [(eps, ten) for cells in doc.values()
             for ten, eps in cells.items()]
    assert max(cells)[1] == "blaze"
    by_tenant = {}
    for eps, ten in cells:
        by_tenant[ten] = by_tenant.get(ten, 0.0) + eps
    assert by_tenant["blaze"] > 4 * by_tenant["quiet"]
    top = tracker.top_slots()
    assert top and top[0][0] == slot_for_token(hot_tok, eng.n_shards)
    # the full document serves the same story
    payload = spmd_heat_payload(eng, now_s=2.0)
    assert payload["spmd"] is True
    assert payload["flow"]["perShard"] and payload["heat"]
    assert payload["slots"]["topK"][0]["slot"] == top[0][0]
    assert payload["skew"]["dispatches"] == tracker.dispatches > 0


def test_live_heat_harvests_compile_nothing():
    """Harvesting heat, flow and HWM between the batches of a packed-scan
    stream compiles no program and trips no retrace once the stream's
    shapes are warm: the plane reads counters the fused step already
    materialized."""
    from sitewhere_tpu.utils.devicewatch import WATCH

    eng = _spmd(2, scan_chunk=2)
    clock = iter(range(1000))

    def feed(events, tenant):
        for lo in range(0, len(events), 16):
            eng.ingest_json_batch([_meas(t, v, ts)
                                   for t, v, ts in events[lo:lo + 16]],
                                  tenant=tenant)
            eng.harvest_shard_heat(now_s=float(next(clock)))
            eng.shard_flow()
            eng.take_shard_staged_hwm()
        eng.flush()
        eng.drain()
        eng.spmd_heat()

    events = _stream(n=96)
    feed(events[:32], "bg")
    feed(events[:32], "hot")
    pre, pre_excess = WATCH.compile_totals(), WATCH.excess_total()
    feed(events[32:], "bg")
    feed([("blaze-1", 30.0, 5_000 + i) for i in range(48)], "hot")
    assert WATCH.compile_totals() == pre
    assert WATCH.excess_total() == pre_excess
    assert eng.harvest_shard_heat(now_s=float(next(clock))).dispatches > 0


def test_staged_hwm_reset_on_scrape_sees_drained_pileup():
    """The swtpu_shard_staged_rows blind-spot fix: a pileup that drained
    BEFORE the scrape still shows in the HWM take; the take resets."""
    eng = _spmd(2)
    wire = [_meas(f"sp-{i % 8}", 30.0, 1_000 + i * 10) for i in range(24)]
    eng.ingest_json_batch(wire)
    eng.flush()
    eng.drain()                                       # backlog is 0 now
    hwm = eng.take_shard_staged_hwm()
    assert sum(hwm) == 24 and all(h > 0 for h in hwm)
    assert eng.take_shard_staged_hwm() == [0, 0]      # reset on take


# ===================================================================
# Dispatch-shape pin: the plane stays OUT of engine.metrics()
# ===================================================================

@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_metrics_dict_unchanged_with_heat_plane_exercised(n_shards):
    """engine.metrics() is pinned dict-equal across scan_chunk retunes
    WITH the whole plane exercised between ingests — heat, skew, flow
    and HWM surfaces add zero keys and change zero values (the known
    limit: posture lives on shard_flow/spmd_heat, never metrics())."""
    a = _spmd(n_shards, scan_chunk=1)
    b = _spmd(n_shards, scan_chunk=2)
    events = _stream(n=64)
    clock = iter(range(100))
    for lo in range(0, len(events), 16):
        wire = [_meas(t, v, ts) for t, v, ts in events[lo:lo + 16]]
        for e in (a, b):
            e.ingest_json_batch(wire)
            e.flush()
            e.harvest_shard_heat(now_s=float(next(clock)))
            e.shard_flow()
            e.take_shard_staged_hwm()
            e.spmd_heat()
    for e in (a, b):
        e.barrier()
        e.drain()
    ma, mb = a.metrics(), b.metrics()
    assert ma == mb
    assert not any("heat" in k or "skew" in k or "slot" in k
                   for k in ma)


# ===================================================================
# Surfaces: exposition, payload duck-typing, bundle, placement input
# ===================================================================

def test_heat_series_export_at_scrape_and_lint():
    from tests.test_metrics_exposition import lint_prometheus

    eng = _spmd(2)
    wire = [_meas(f"sx-{i % 8}", float(i), 1_000 + i) for i in range(24)]
    eng.ingest_json_batch(wire)
    eng.flush()
    eng.drain()
    reg = MetricsRegistry()
    export_engine_metrics(eng, reg)                   # primes baselines
    eng.ingest_json_batch(wire)
    eng.flush()
    eng.drain()
    reg = MetricsRegistry()
    export_engine_metrics(eng, reg)
    text = reg.expose_text()
    lint_prometheus(text)
    lbl = eng.metrics_label
    for s in ("0", "1"):
        assert (f'swtpu_shard_staged_rows_hwm{{engine="{lbl}",shard="{s}"}}'
                in text)
        for lane in ("processed", "accepted", "routed_rows",
                     "dispatched_rows", "backlog_rows"):
            assert (f'swtpu_shard_flow_rows{{engine="{lbl}",'
                    f'lane="{lane}",shard="{s}"}}' in text)
    assert f'swtpu_shard_heat{{engine="{lbl}"' in text
    assert f'swtpu_slot_heat_topk{{engine="{lbl}"' in text
    assert f'swtpu_spmd_skew{{engine="{lbl}"}}' in text
    assert f'swtpu_spmd_skew_hwm{{engine="{lbl}"}}' in text
    # single-chip engines export NONE of the plane
    reg1 = MetricsRegistry()
    export_engine_metrics(Engine(EngineConfig(**CFG)), reg1)
    t1 = reg1.expose_text()
    assert "swtpu_shard_flow_rows" not in t1
    assert "swtpu_shard_heat" not in t1
    assert "swtpu_spmd_skew" not in t1


def test_spmd_heat_payload_duck_types_single_chip():
    assert spmd_heat_payload(Engine(EngineConfig(**CFG))) == {"spmd": False}
    assert spmd_heat_payload(object()) == {"spmd": False}


def test_decide_balance_heat_input_and_purity_pin():
    """slot_heat steers the peel toward the MEASURED hottest of the hot
    tenant's slots; None (and {}) keep the decision byte-identical to
    the PR-15 policy (slots[0]) — the pure-function pin."""
    m = PlacementMap.initial(2, slots_per_rank=2)      # slots 0..3
    pmap = m.with_moves({1: 0})       # rank 0 holds 3 slots, rank 1 one
    kw = dict(tenant_p99_ms={"hot": 900.0}, tenant_rank={"hot": 0},
              tenant_slots={"hot": [0, 2]}, pmap=pmap,
              p99_target_ms=250.0)
    base = decide_balance(**kw)
    assert base == [(0, 1)]
    assert decide_balance(**kw, slot_heat=None) == base
    assert decide_balance(**kw, slot_heat={}) == base
    assert decide_balance(**kw, slot_heat={2: 9.0, 0: 1.0}) == [(2, 1)]
    assert decide_balance(**kw, slot_heat={0: 9.0, 2: 1.0}) == base
    # unmeasured slots read heat 0.0; ties break to the lowest slot id
    assert decide_balance(**kw, slot_heat={99: 5.0}) == base


# ===================================================================
# SPMD flight spans + offline converter
# ===================================================================

def test_spmd_flight_spans_and_trace2perfetto_roundtrip(tmp_path):
    """SPMD ingest flights expose the route/scatter lifecycle as
    spmd.* child spans with the skew breadcrumbs on the root event;
    single-chip span derivation is untouched, and the offline
    trace2perfetto converter survives the new names (smoke-invoked as
    a subprocess, the ISSUE 11 discipline)."""
    eng = _spmd(2)
    wire = [_meas(f"fl-{i % 8}", 25.0, 1_000 + i) for i in range(16)]
    eng.ingest_json_batch(wire)
    eng.flush()
    eng.drain()
    rec = next(r for r in eng.flight.recent(kind="ingest")
               if "route" in (r.get("stagesUs") or {}))
    events = timeline_events(eng, rec["traceId"])
    names = {e["name"] for e in events}
    assert {"ingest.spmd.route", "ingest.spmd.scatter",
            "ingest.spmd.commit"} <= names
    root = next(e for e in events if e["name"] == "ingest")
    assert "shard_rows" in root["args"] and "skew" in root["args"]
    assert len(root["args"]["shard_rows"].split("/")) == 2

    bundle = debug_bundle(eng)
    assert bundle["spmd"]["spmd"] is True             # the new section
    assert bundle["spmd"]["flow"]["perShard"]
    path = tmp_path / "bundle.json"
    path.write_text(json.dumps(bundle))
    out = tmp_path / "trace.perfetto.json"
    r = subprocess.run(
        [sys.executable, "scripts/trace2perfetto.py", str(path),
         "--trace", rec["traceId"], "-o", str(out)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    xs = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "ingest.spmd.route" in xs and "ingest.spmd.scatter" in xs

    # a single-chip flight record derives NO spmd.* spans
    sc = Engine(EngineConfig(**CFG))
    sc.epoch = FixedEpoch()
    sc.ingest_json_batch(wire)
    sc.flush()
    screc = sc.flight.recent(kind="ingest")[0]
    scnames = {e["name"]
               for e in timeline_events(sc, screc["traceId"])}
    assert not any(n.startswith("ingest.spmd.") for n in scnames)
