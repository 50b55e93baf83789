"""Zero-downtime elastic tenant placement (ISSUE 15).

Pins the placement contract: the genesis map is byte-identical to the
legacy ``owner_rank`` partitioner (adopting the plane re-routes
nothing), every ownership surface resolves through ONE installed epoch,
the epoch-fenced handoff moves a tenant range with zero acked loss and
no dual-ownership window, mid-flight spilled frames re-route on
redirect, chaos kills mid-handoff abort to a consistent single-owner
state (conservation ledger balanced), and join/drain run the same
protocol end to end."""

import dataclasses
import json
import pathlib
import time
import types

import pytest

from sitewhere_tpu.parallel.cluster import (ClusterConfig, ClusterEngine,
                                            build_cluster_rpc, owner_rank)
from sitewhere_tpu.parallel.forward import ForwardQueue, SpillRegistry
from sitewhere_tpu.parallel.placement import (REDIRECT_CODE, PlacementManager,
                                              PlacementMap, decide_balance,
                                              drain_rank, join_rank,
                                              move_slots)
from sitewhere_tpu.rpc.protocol import RpcError
from sitewhere_tpu.utils import faults
from sitewhere_tpu.utils.conservation import build_ledger, check_conservation
from tests.test_cluster import (BASE_S, _engine_cfg, _free_ports,
                                _ServerHost, meas)


# ---------------------------------------------------------------- fixtures

def _mk_placement_cluster(tmp_path, n_ranks=2, initial_ranks=None,
                          wal=True, forwarding=True, slots_per_rank=4,
                          retry_interval_s=0.1):
    """n provisioned ranks with live RPC; optional WAL + durable
    forwarding (the handoff tests need both: catch-up replays the WAL,
    redirects re-route through the spill queue)."""
    ports = _free_ports(n_ranks)
    peers = [f"127.0.0.1:{p}" for p in ports]
    host = _ServerHost()
    clusters, queues = [], []
    for r in range(n_ranks):
        cc = ClusterConfig(
            rank=r, n_ranks=n_ranks, peers=peers, secret="pl-secret",
            epoch_base_unix_s=BASE_S,
            engine=_engine_cfg(tmp_path if wal else None, r),
            connect_timeout_s=5.0, slots_per_rank=slots_per_rank,
            initial_ranks=initial_ranks)
        c = ClusterEngine(cc)
        if forwarding:
            q = ForwardQueue(c, tmp_path / f"fwd-r{r}",
                             retry_interval_s=retry_interval_s)
            reg = SpillRegistry(tmp_path / f"fwd-r{r}" / "registry")
            c.attach_forwarding(q, reg)
            queues.append(q)
        host.start(build_cluster_rpc(c.local, "pl-secret"), ports[r])
        clusters.append(c)
    return clusters, queues, host


def _close(clusters, host):
    faults.clear()
    for c in clusters:
        c.close()
    host.close()


def _token_in_slot_of(cluster, rank, n=1, prefix="plt"):
    """Tokens owned by ``rank`` that all hash into the SAME slot (the
    moving range of the handoff tests)."""
    pm = cluster.placement
    first, out, i = None, [], 0
    while len(out) < n:
        t = f"{prefix}-{i}"
        i += 1
        if pm.owner(t) != rank:
            continue
        s = pm.slot_of(t)
        if first is None:
            first = s
        if s == first:
            out.append(t)
    return first, out


def _assert_balanced(cluster, what=""):
    led = build_ledger(cluster)
    violations = check_conservation(led)
    assert not violations, (what, [v.to_dict() for v in violations])


# ------------------------------------------------------------- pure layer

def test_initial_map_matches_legacy_partitioner():
    """The genesis contract: slot-space routing + the default map is
    BYTE-identical to owner_rank(token, n_ranks) — adopting the
    placement plane re-routes nothing on an existing cluster."""
    for n_ranks in (1, 2, 3, 5, 8):
        m = PlacementMap.initial(n_ranks)
        for i in range(256):
            t = f"dev-{i}-{n_ranks}"
            assert m.owner(t) == owner_rank(t, n_ranks), (t, n_ranks)


def test_map_moves_epoch_roundtrip_and_validation():
    m = PlacementMap.initial(2, slots_per_rank=4)
    assert m.epoch == 1 and m.n_slots == 8
    m2 = m.with_moves({0: 1, 5: 0})
    assert m2.epoch == 2
    assert m2.assignment[0] == 1 and m2.assignment[5] == 0
    assert m.assignment[0] == 0        # immutable
    rt = PlacementMap.from_dict(m2.to_dict())
    assert rt == m2
    with pytest.raises(ValueError):
        m.with_moves({99: 0})
    bad = m2.to_dict()
    bad["assignment"] = bad["assignment"][:-1]
    with pytest.raises(ValueError):
        PlacementMap.from_dict(bad)
    # a narrowed genesis (join-later ranks) covers only the active set
    m3 = PlacementMap.initial(3, slots_per_rank=2, active_ranks=[0, 1])
    assert m3.active_ranks() == [0, 1]
    with pytest.raises(ValueError):
        PlacementMap.initial(3, active_ranks=[0, 7])


def test_manager_epoch_fencing_and_persistence(tmp_path):
    """A manager never adopts a lower epoch, refuses a divergent
    same-epoch assignment (split-brain commit), persists installs, and
    reloads the highest persisted epoch at construction."""
    stub = types.SimpleNamespace(rank=0, n_ranks=2,
                                 local=types.SimpleNamespace())
    pm = PlacementManager(stub, PlacementMap.initial(2, 4),
                          directory=tmp_path / "pl")
    m2 = pm.map().with_moves({0: 1})
    assert pm.install(m2.to_dict())
    assert pm.epoch == 2 and pm.ever_moved
    # lower epoch refused, same-epoch idempotent, divergent loud
    assert not pm.install(PlacementMap.initial(2, 4).to_dict())
    assert pm.install(m2.to_dict())
    divergent = dataclasses.replace(
        pm.map(), assignment=tuple(
            1 - r for r in pm.map().assignment))
    assert not pm.install(divergent.to_dict())
    assert pm.epoch == 2
    # a fresh manager on the same dir resumes from the persisted epoch
    pm2 = PlacementManager(stub, PlacementMap.initial(2, 4),
                           directory=tmp_path / "pl")
    assert pm2.epoch == 2 and pm2.map() == pm.map()
    # the cached hot-path views reload with it (a stale routing table
    # would silently misroute every batch after a restart)
    assert pm2.slot_routing() == list(pm.map().assignment)
    # the slot space is fixed at genesis
    with pytest.raises(ValueError):
        pm.install(PlacementMap.initial(2, 8).with_moves({0: 1})
                   .with_moves({1: 1}).to_dict())


def test_fault_partition_and_delay_jitter_are_deterministic():
    """Satellite: the new fault kinds. ``partition`` severs BOTH
    directions of a rank pair (``drop`` stays one-way); ``delay_jitter``
    draws its jitter from the plan's seeded stream, so the same seed
    sleeps the same sequence."""
    inj = faults.FaultInjector(faults.FaultPlan(seed=3).partition(0, 2))
    with pytest.raises(ConnectionError):
        inj.before_call(0, 2, "Cluster.flush")
    with pytest.raises(ConnectionError):
        inj.before_call(2, 0, "Cluster.flush")
    inj.before_call(0, 1, "Cluster.flush")      # other links live
    inj.before_call(1, 2, "Cluster.flush")
    assert inj.counters["partitioned"] == 2

    def jitter_seq(seed, n=6):
        inj = faults.FaultInjector(faults.FaultPlan(seed=seed)
                                   .delay_jitter(0, 1, base_s=0.0,
                                                 jitter_s=0.002))
        out = []
        for _ in range(n):
            t0 = time.perf_counter()
            inj.before_call(0, 1, "Cluster.queryEvents")
            out.append(inj.counters["jitter_delayed"])
        return inj.counters["jitter_delayed"], out

    assert jitter_seq(11) == jitter_seq(11)
    # the draw sequence is the plan RNG's: two injectors with the same
    # seed burn identical streams (replayability)
    a = faults.FaultInjector(faults.FaultPlan(seed=5)
                             .delay_jitter(jitter_s=0.0))
    b = faults.FaultInjector(faults.FaultPlan(seed=5)
                             .delay_jitter(jitter_s=0.0))
    assert [a._draw() for _ in range(8)] == [b._draw() for _ in range(8)]


def test_decide_balance_policy():
    """The pure half of hot-tenant steering: breach -> peel the hot
    slot onto the lightest active rank; no breach, lightest-already, or
    last-slot cases propose nothing."""
    m = PlacementMap.initial(2, slots_per_rank=2)     # slots 0..3
    moves = decide_balance(
        tenant_p99_ms={"hot": 900.0, "cool": 20.0},
        tenant_rank={"hot": 0, "cool": 1},
        tenant_slots={"hot": [0, 2], "cool": [1]},
        pmap=m.with_moves({1: 0}),    # rank 0 holds 3 slots, rank 1 one
        p99_target_ms=250.0)
    assert moves == [(0, 1)]
    # nothing breaches -> no proposal
    assert decide_balance({"hot": 100.0}, {"hot": 0}, {"hot": [0]},
                          m, 250.0) == []
    # hot rank already lightest -> no proposal
    assert decide_balance({"hot": 900.0}, {"hot": 1}, {"hot": [1]},
                          m.with_moves({3: 0}), 250.0) == []


def test_conservation_placement_equation_is_falsifiable():
    """The new ledger equation: started == completed + aborted +
    in-flight, and a fence with no live move is a violation. Perturbing
    any term by one must produce a Violation (the PR-13 discipline)."""
    ledger = {"stages": {"placement": {
        "epoch": 3, "moves_started": 4, "moves_completed": 2,
        "moves_aborted": 1, "moves_in_flight": 1, "fenced_slots": 0,
        "fenced_write_redirects": 7, "stale_sender_redirects": 2}}}
    assert not check_conservation(ledger)
    bad = json.loads(json.dumps(ledger))
    bad["stages"]["placement"]["moves_started"] += 1
    vs = check_conservation(bad)
    assert [v.equation for v in vs] == ["placement-handoff"]
    bad2 = json.loads(json.dumps(ledger))
    bad2["stages"]["placement"]["fenced_slots"] = 2
    bad2["stages"]["placement"]["moves_in_flight"] = 0
    bad2["stages"]["placement"]["moves_completed"] = 3
    assert [v.equation for v in check_conservation(bad2)] == \
        ["placement-handoff"]


def test_no_runtime_surface_bypasses_the_placement_map():
    """Satellite pin: no ownership surface reads owner_rank(token,
    n_ranks) directly anymore — replication (fire-over), entity sync
    (schedule fire filter), and the cluster facade all resolve through
    the installed map. Source-level assert on the modules that used
    to."""
    root = pathlib.Path(__file__).resolve().parent.parent
    for mod in ("sitewhere_tpu/parallel/replication.py",
                "sitewhere_tpu/parallel/entity_sync.py",
                "sitewhere_tpu/parallel/forward.py"):
        src = (root / mod).read_text()
        assert "owner_rank(" not in src, f"{mod} bypasses the map"
    # cluster.py keeps the hash PRIMITIVE (owner_rank definition) but
    # its facade surface must resolve through the manager
    csrc = (root / "sitewhere_tpu/parallel/cluster.py").read_text()
    assert "return self.placement.owner(token)" in csrc
    assert "owner_rank(token, self.n_ranks)" not in csrc


# ----------------------------------------------------- one-epoch property

def test_every_surface_resolves_through_the_same_epoch(tmp_path):
    """THE versioning property (satellite): after a map with a moved
    slot installs, the facade owner(), the ingest partitioner, the
    scheduler fire filter, the data fan-out set, and the owner-side
    guard ALL answer from the same epoch — no surface left reading the
    static hash."""
    from sitewhere_tpu.parallel.replication import install_fireover

    clusters, _qs, host = _mk_placement_cluster(tmp_path, wal=False,
                                                forwarding=False)
    c0, c1 = clusters
    try:
        slot, (tok,) = _token_in_slot_of(c0, rank=0)
        assert c0.owner(tok) == 0 == c1.owner(tok)
        newmap = c0.placement.map().with_moves({slot: 1})
        for c in clusters:
            assert c.placement.install(newmap.to_dict())
        # 1) facade owner
        assert c0.owner(tok) == 1 == c1.owner(tok)
        # 2) ingest partitioner (native + fallback both resolve slots
        #    through the same installed assignment)
        by_rank = c0._partition_payloads([meas(tok, "t", 1.0, 10)],
                                         kind="json")
        assert list(by_rank) == [1]
        # 3) scheduler fire filter (fire-over wiring)
        sched0 = types.SimpleNamespace(fire_filter=None,
                                       catchup_filter=None)
        sched1 = types.SimpleNamespace(fire_filter=None,
                                       catchup_filter=None)
        install_fireover(sched0, c0)
        install_fireover(sched1, c1)
        assert not sched0.fire_filter(tok)
        assert sched1.fire_filter(tok)
        # 4) the data fan-out set tracks the assignment
        assert c0._data_ranks() == [0, 1]
        # 5) owner-side guard: the OLD owner redirects a stale direct
        #    send with a typed 473 carrying its (newer) map
        with pytest.raises(RpcError) as ei:
            c1._peer(0).call("Cluster.ingestJson",
                             lens=[len(meas(tok, "t", 2.0, 11))],
                             tenant="default",
                             _attachment=meas(tok, "t", 2.0, 11))
        assert ei.value.code == REDIRECT_CODE
        assert ei.value.data["map"]["epoch"] == newmap.epoch
        # 6) single-request process guard on the old owner
        with pytest.raises(RpcError) as ei2:
            c1._peer(0).call(
                "Cluster.processEnvelope",
                envelope={"deviceToken": tok,
                          "type": "DeviceMeasurements",
                          "request": {"measurements": {"t": 3.0}}},
                tenant="default")
        assert ei2.value.code == REDIRECT_CODE
        assert c0.placement.counters["stale_sender_redirects"] >= 2
        # 7) the posture surfaces (satellite): rank-labeled counters on
        #    the federated scrape + the debug-bundle placement section
        fed = c0.cluster_metrics()
        assert "swtpu_placement_epoch" in fed
        assert 'swtpu_placement_epoch{rank="1"}' in fed
        from sitewhere_tpu.utils.tracing import debug_bundle

        bundle = debug_bundle(c0.local)
        assert bundle["placement"]["map"]["epoch"] == newmap.epoch
        assert bundle["placement"]["counters"][
            "stale_sender_redirects"] >= 2
        # 8) the REST/RPC twin payload answers from the same epoch
        assert c0.placement.payload()["map"]["epoch"] == newmap.epoch
    finally:
        _close(clusters, host)


# ------------------------------------------------------------ live handoff

def test_live_handoff_moves_range_with_zero_acked_loss(tmp_path):
    """THE tentpole done-criterion at test scale: a tenant range (one
    slot) moves rank 0 -> rank 1 under the full protocol. Every acked
    event stays visible exactly once from BOTH facades, post-move
    ingest lands at the new owner, a stale spilled frame re-routes
    mid-flight, and the conservation ledger balances on every rank."""
    clusters, queues, host = _mk_placement_cluster(tmp_path)
    c0, c1 = clusters
    try:
        slot, toks = _token_in_slot_of(c0, rank=0, n=2)
        other = next(t for t in (f"oth-{i}" for i in range(64))
                     if c0.owner(t) == 0
                     and c0.placement.slot_of(t) != slot)
        sent = 0
        for rnd in range(3):
            c0.ingest_json_batch(
                [meas(t, "temp", rnd + i, 100 * rnd + i)
                 for i, t in enumerate(toks)]
                + [meas(other, "temp", rnd, 100 * rnd + 7)])
            sent += 1
        c0.flush()

        stats = move_slots(c0, [slot], 1)
        assert [m["state"] for m in stats["moves"]] == ["done"]
        assert stats["epoch_after"] == 2
        assert c0.placement.epoch == c1.placement.epoch == 2
        assert c0.owner(toks[0]) == 1
        # shipped history: every batch's fid recorded at the target
        assert stats["moves"][0]["shippedPayloads"] == sent * len(toks)

        # zero acked loss, exactly-once reads, from BOTH facades; the
        # un-moved token stays untouched at rank 0
        c0.flush()
        for c in clusters:
            for t in toks:
                assert c.query_events(device_token=t)["total"] == sent, \
                    (c.rank, t)
            assert c.query_events(device_token=other)["total"] == sent
        # the new owner's LOCAL engine serves the range now; the old
        # owner's local copy is dead (filtered) but its engine is not
        assert c1.local.query_events(device_token=toks[0])["total"] \
            == sent

        # post-move ingest routes to the new owner
        c0.ingest_json_batch([meas(toks[0], "temp", 99.0, 999)])
        c0.flush()
        assert c0.query_events(device_token=toks[0])["total"] == sent + 1
        assert c1.local.query_events(
            device_token=toks[0])["total"] == sent + 1

        # mid-flight re-route: a stale frame spilled toward the OLD
        # owner redirects (473 + map) and the pump re-spills it to the
        # new owner — delivered, never lost, never dual-applied
        stale = meas(toks[1], "temp", 123.0, 1234)
        queues[0].spill(0, "json", "default", "stale-fid-1",
                        payloads=[stale])
        for _ in range(8):
            queues[0].retry_once()
            if not queues[0].metrics()["forward_queue_depth"]:
                break
            time.sleep(0.05)
        m = queues[0].metrics()
        assert m["forward_queue_depth"] == 0
        assert m["forward_retry_redirects"] >= 1
        assert m["forward_rerouted_batches"] == 1
        c0.flush()
        assert c0.query_events(device_token=toks[1])["total"] == sent + 1

        # conservation: every rank's ledger balances across the
        # migration (the re-route slack term included), and the move
        # accounting closes
        for c in clusters:
            _assert_balanced(c, f"rank {c.rank}")
        st = c0.placement.ledger_stage()
        assert st["moves_started"] == st["moves_completed"] == 1
        assert st["moves_in_flight"] == 0 and st["fenced_slots"] == 0
    finally:
        _close(clusters, host)


def test_chaos_kill_mid_handoff_aborts_to_single_owner(tmp_path):
    """Chaos gate (test scale): the TARGET dies mid-catch-up -> the
    move aborts with ownership unchanged and the ledger balanced; after
    the revive the SAME slots move cleanly. Then the SOURCE dies
    mid-handoff coordinated from the other rank -> abort, unchanged,
    balanced."""
    clusters, _qs, host = _mk_placement_cluster(tmp_path)
    c0, c1 = clusters
    try:
        slot, toks = _token_in_slot_of(c0, rank=0, n=2)
        c0.ingest_json_batch([meas(t, "t", 1.0, i)
                              for i, t in enumerate(toks)])
        c0.flush()

        # ---- kill the TARGET mid-handoff -----------------------------
        faults.install(faults.FaultPlan(seed=7).kill(1))
        stats = move_slots(c0, [slot], 1)
        faults.clear()
        assert [m["state"] for m in stats["moves"]] == ["aborted"]
        assert c0.placement.epoch == 1          # commit never happened
        assert c0.owner(toks[0]) == 0           # single owner: source
        st = c0.placement.ledger_stage()
        assert st["moves_aborted"] == 1 and st["moves_in_flight"] == 0
        assert st["fenced_slots"] == 0          # nothing left fenced
        _assert_balanced(c0, "post-abort source")
        # writes still land at the source — no fence leaked
        c0.ingest_json_batch([meas(toks[0], "t", 2.0, 50)])
        c0.flush()
        assert c0.query_events(device_token=toks[0])["total"] == 2

        # ---- revive: the same range now moves cleanly ----------------
        stats2 = move_slots(c0, [slot], 1)
        assert [m["state"] for m in stats2["moves"]] == ["done"]
        assert c0.owner(toks[0]) == 1
        c0.flush()
        for c in clusters:
            assert c.query_events(device_token=toks[0])["total"] == 2
            _assert_balanced(c, f"post-move rank {c.rank}")

        # ---- kill the SOURCE mid-handoff (coordinator = rank 1) ------
        slot1, toks1 = _token_in_slot_of(c1, rank=0, n=1,
                                         prefix="src")
        faults.install(faults.FaultPlan(seed=9).kill(0))
        stats3 = move_slots(c1, [slot1], 1)
        faults.clear()
        assert [m["state"] for m in stats3["moves"]] == ["aborted"]
        assert c1.placement.epoch == 2          # unchanged by the abort
        assert c1.owner(toks1[0]) == 0
        _assert_balanced(c1, "post-abort coordinator")
    finally:
        _close(clusters, host)


def test_join_and_drain_under_the_same_protocol(tmp_path):
    """Elasticity end to end: a provisioned-but-inactive rank JOINS
    (bootstraps by handoff replay, takes over ranges at commit epochs)
    and an active rank DRAINS (hands off every slot, leaves the data
    fan-out set) — all acked events visible exactly once afterwards,
    ledgers balanced on every surviving rank."""
    clusters, _qs, host = _mk_placement_cluster(
        tmp_path, n_ranks=3, initial_ranks=[0, 1], slots_per_rank=2)
    c0, c1, c2 = clusters
    try:
        assert c0.placement.map().active_ranks() == [0, 1]
        assert c0._data_ranks() == [0, 1]
        toks = []
        for i in range(24):
            t = f"el-{i}"
            if len(toks) < 8:
                toks.append(t)
        c0.ingest_json_batch([meas(t, "t", float(i), i)
                              for i, t in enumerate(toks)])
        c0.flush()

        # ---- JOIN rank 2 ---------------------------------------------
        res = join_rank(c0, 2)
        assert res["joined"], res
        m = c0.placement.map()
        assert 2 in m.active_ranks()
        assert len(m.slots_of(2)) >= 1
        assert c0._data_ranks() == [0, 1, 2]
        # the joiner answers for its ranges; totals hold everywhere
        c0.flush()
        for c in clusters:
            for t in toks:
                assert c.query_events(device_token=t)["total"] == 1, \
                    (c.rank, t)

        # ---- DRAIN rank 1 --------------------------------------------
        res2 = drain_rank(c0, 1)
        assert res2["drained"], res2
        m2 = c0.placement.map()
        assert 1 not in m2.active_ranks()
        assert not m2.slots_of(1)
        assert c0._data_ranks() == [0, 2]
        c0.flush()
        for c in (c0, c2):
            for t in toks:
                assert c.query_events(device_token=t)["total"] == 1, \
                    (c.rank, t)
            _assert_balanced(c, f"post-drain rank {c.rank}")
        # ingest for a token the drained rank used to own lands at its
        # new owner without touching rank 1's engine
        moved = next(t for t in toks if owner_rank(t, 3) == 1
                     or c0.owner(t) != 1)
        before = c1.local.query_events(limit=1)["total"]
        c0.ingest_json_batch([meas(moved, "t", 9.0, 900)])
        c0.flush()
        assert c1.local.query_events(limit=1)["total"] == before
        # placement posture surfaces the journey
        pay = c0.placement.payload()
        assert pay["map"]["epoch"] == c2.placement.epoch
        assert str(1) not in pay["slots"]
    finally:
        _close(clusters, host)


def test_returning_range_never_dual_applies(tmp_path):
    """The ping-pong pin: a range moving
    A -> B -> A must NOT dual-count at A — A's dead rows from its first
    ownership era come back live with the slot, so the return handoff's
    replay must re-ingest ONLY what A does not already hold
    (handoff_prepare's content filter). Exact totals from both facades
    after every era, ledgers balanced."""
    clusters, _qs, host = _mk_placement_cluster(tmp_path)
    c0, c1 = clusters
    try:
        slot, toks = _token_in_slot_of(c0, rank=0, n=2)
        sent = 0
        c0.ingest_json_batch([meas(t, "t", 1.0 + i, i)
                              for i, t in enumerate(toks)])
        sent += 1
        c0.flush()

        # era 2: 0 -> 1, new traffic lands at rank 1
        assert [m["state"] for m in move_slots(c0, [slot], 1)["moves"]] \
            == ["done"]
        c0.ingest_json_batch([meas(t, "t", 2.0 + i, 100 + i)
                              for i, t in enumerate(toks)])
        sent += 1
        c0.flush()
        for c in clusters:
            for t in toks:
                assert c.query_events(device_token=t)["total"] == sent

        # era 3: 1 -> 0 (the RETURN): rank 0 already holds era 1
        assert [m["state"] for m in move_slots(c0, [slot], 0)["moves"]] \
            == ["done"]
        c0.ingest_json_batch([meas(t, "t", 3.0 + i, 200 + i)
                              for i, t in enumerate(toks)])
        sent += 1
        c0.flush()
        for c in clusters:
            for t in toks:
                assert c.query_events(device_token=t)["total"] == sent, \
                    (c.rank, t)
            _assert_balanced(c, f"rank {c.rank}")
        # and once more for good measure: 0 -> 1 again
        assert [m["state"] for m in move_slots(c0, [slot], 1)["moves"]] \
            == ["done"]
        c0.flush()
        for c in clusters:
            for t in toks:
                assert c.query_events(device_token=t)["total"] == sent
    finally:
        _close(clusters, host)


def test_commit_install_closes_move_and_finish_never_resurrects():
    """Review pins: (a) the commit INSTALL itself completes the source's
    move (a lost handoffFinish leaves no phantom in-flight handoff —
    install already dropped the fences, so no deadline would ever have
    fired); (b) handoffFinish after an ABORT must not resurrect the
    move — one move can never count in both completed and aborted."""
    stub = types.SimpleNamespace(rank=0, n_ranks=2,
                                 local=types.SimpleNamespace())
    pm = PlacementManager(stub, PlacementMap.initial(2, 4))
    from sitewhere_tpu.parallel.placement import _Move

    # (a) fenced move; the commit map lands; finish is then a no-op
    mv = _Move("m1", (0,), 1, state="fenced")
    with pm._lock:
        pm._moves["m1"] = mv
        pm._fences[0] = (1, "m1", time.monotonic() + 20)
        pm.has_fences = True
    assert pm.install(pm.map().with_moves({0: 1}).to_dict())
    assert mv.state == "done"
    assert pm.counters["moves_completed"] == 1
    assert not pm.fenced_slots() and not pm.has_fences
    pm.handoff_finish("m1")
    assert pm.counters["moves_completed"] == 1      # no double count
    st = pm.ledger_stage()
    assert st["moves_in_flight"] == 0
    assert not check_conservation({"stages": {"placement": st
                                              | {"moves_started": 1}}})

    # (b) an aborted move stays aborted through finish AND abort
    mv2 = _Move("m2", (1,), 1, state="aborted")
    with pm._lock:
        pm._moves["m2"] = mv2
        pm.counters["moves_started"] += 1
        pm.counters["moves_aborted"] += 1
    assert pm.handoff_finish("m2")["state"] == "aborted"
    assert pm.handoff_abort("m2")["state"] == "aborted"
    assert pm.counters["moves_completed"] == 1
    assert pm.counters["moves_aborted"] == 1


def test_fence_expiry_mid_ship_refuses_to_commit():
    """Review pin (the acked-loss hole): if the fences expire while the
    fence round is still shipping/verifying, handoff_fence must REFUSE
    (the coordinator aborts) — committing after writes may have resumed
    at the source would orphan them behind the read filter."""
    stub = types.SimpleNamespace(rank=0, n_ranks=2,
                                 local=types.SimpleNamespace(wal=None,
                                                             lock=None))
    pm = PlacementManager(stub, PlacementMap.initial(2, 4),
                          fence_timeout_s=20.0)
    from sitewhere_tpu.parallel.placement import _Move

    mv = _Move("mx", (0,), 1, state="fenced")
    with pm._lock:
        pm._moves["mx"] = mv
        # the fence ALREADY expired (ship outlasted the deadline) and a
        # concurrent scrape collected it
        pm._fences.pop(0, None)
        pm.has_fences = False
    with pm._lock:
        live = all(pm._fences.get(s, (None, None, 0.0))[1] == "mx"
                   for s in mv.slots)
    assert not live   # the condition handoff_fence's re-check enforces


def test_replay_wal_tails_accepts_generator_args(tmp_path):
    """Review pin: the up-front validation must not exhaust generator
    arguments (a silently-empty zip would drop every tail — the exact
    failure the validation exists to prevent)."""
    from sitewhere_tpu.engine import WAL_JSON
    from sitewhere_tpu.parallel.cluster_reshard import replay_wal_tails
    from sitewhere_tpu.utils.ingestlog import IngestLog

    snap = tmp_path / "snap"
    snap.mkdir()
    (snap / "host_distributed.json").write_text(
        json.dumps({"store_cursor": 0}))
    wal_dir = tmp_path / "wal"
    wal = IngestLog(wal_dir)
    for _ in range(3):
        wal.append(WAL_JSON + b"default\x00" + b'{"deviceToken":"g"}')
    wal.flush()
    wal.close()

    calls = []
    probe = types.SimpleNamespace(
        ingest_json_batch=lambda p, tenant="default":
            calls.append(len(p)) or {},
        ingest_binary_batch=lambda p, tenant="default": {},
        flush=lambda: {})
    n = replay_wal_tails(probe, (d for d in [snap]),
                         (d for d in [wal_dir]))
    assert n == 3 and sum(calls) == 3
