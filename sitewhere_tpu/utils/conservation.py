"""Event conservation ledger & continuous audit plane (ISSUE 14).

The platform's core promise is that no tenant event silently vanishes
between ingest, persistence, aggregation, and delivery. PRs 6/9/12
prove the zero-loss/zero-dup guarantees inside individual chaos tests;
this module makes loss continuously *measurable* in a live system:

  * :class:`FlowLedger` — host-side flow counters incremented at the
    two boundaries the engine itself controls (rows staged, valid rows
    dispatched to the device). Every other stage the ledger reports is
    sampled from counters that already exist (QoS admission, the
    device-side tenant counter grid, WAL sequence tickets, the replica
    feed, the forward spill queue, the archive spill cursors, the CEP
    harvest counters), so the ingest hot path pays only a dict add per
    batch plus one vectorized ``np.sum`` per dispatch.
  * :func:`build_ledger` — one mutually-consistent snapshot of every
    stage, taken under the engine lock: per-stage counts, monotone
    watermarks (WAL durable seq, dispatched rows, feed seq, standby
    applied seq, archive spill cursor, rollup window id), and the
    per-stage lag derived from them.
  * :func:`check_conservation` — a PURE function evaluating the
    conservation equations over one ledger snapshot. Slack terms are
    explicit (see ``EQUATIONS``): in-flight staged backlog, the WAL
    group-commit window, ring-wrap losses the archive already counted.
  * :class:`ConservationAuditor` — a background thread running the
    checker on a cadence. A violation must survive two consecutive
    audits before it escalates (a spill-file rename and its counter
    update are not atomic with a concurrent audit); escalated
    violations increment ``swtpu_conservation_violation_total`` and
    emit one loud structured log line.

Import hygiene: this module must import with jax blocked (offline
tooling reads ledger documents); jax is imported lazily inside
the snapshot helpers only.

Conservation equations (the contract future PRs must keep balanced):

  staging-balance       staged_rows == dispatched_rows + backlog_rows
                        (slack: the staged-but-undispatched backlog,
                        measured in the same critical section)
  device-processed      dispatched_rows == device ``processed`` delta
                        (exact at snapshot: reading the device counter
                        forces every dispatched program)
  device-disposition    accepted + invalid == processed (the tenant
                        counter grid partitions every valid row;
                        dedup_dropped / geofence_hit are annotations of
                        accepted rows, not extra dispositions)
  edge-admission        offered == admitted + edge sheds (offered is
                        counted independently at admit() entry; sheds
                        noted after admission — arena stalls — are
                        subtracted), and the per-tenant shed counts sum
                        to the total
  wal-durability        0 <= durable_seq <= appended_seq (the group
                        commit window is the only legal gap)
  forward-queue         spilled == redelivered + deadlettered +
                        rerouted + depth (dead-letter and placement
                        re-route are the ONLY legal sinks; a spilled
                        batch never just disappears. Re-route — ISSUE
                        15 — consumes the original and re-spills its
                        payloads toward the new owner, so the re-spills
                        count as fresh ``spilled`` while the consumed
                        original lands in ``rerouted``: the handoff
                        slack term that keeps the equation balanced
                        across a live migration)
  replication-feed      published == feed_seq and every follower's
                        acked <= feed_seq (slack: un-acked in-flight
                        publications; an un-resynced standby gap shows
                        as acked < seq, never as acked > seq)
  archive-spill         spilled(part) <= ring_head(part), and
                        ring_head - spilled <= arena_capacity +
                        lost_rows (rows wrapped before spooling are
                        only legal when the archive counted them)
  rules-harvest         harvested == emitted + suppressed + skipped,
                        and device missed <= fires, pending >= 0
  placement-handoff     moves_started == moves_completed +
                        moves_aborted + moves_in_flight (ISSUE 15: a
                        handoff always terminates in exactly one of
                        commit/abort; the in-flight term is the only
                        legal slack and is read in the same
                        lock-consistent snapshot)
  spmd-shard-flow       per shard s: accepted[s] + invalid[s] ==
                        processed[s], and routed_rows[s] ==
                        dispatched_rows[s] + backlog_rows[s]; every
                        per-shard lane sums EXACTLY to the folded
                        device-stage lane (ISSUE 18: the unfolded
                        counter grid is the same grid, read before the
                        fold — no new slack term anywhere)
  analytics-windows     windows_planned == windows_scored +
                        windows_skipped_underfilled + windows_cancelled
                        (ISSUE 19: every device window a scoring batch
                        plans lands in exactly one sink; the manager
                        commits planned ALONGSIDE its sinks in one lock
                        block per batch, so there is no in-flight slack
                        term — the equation is exact at every audit)
  wire-frames           frames_received == frames_admitted + frames_shed
                        + frames_invalid + frames_duplicate (ISSUE 20:
                        every frame a persistent connection delivers
                        gets exactly one edge disposition; received is
                        counted independently at frame arrival, so the
                        equation can actually fail)
  wire-rows             frames_admitted == rows_submitted +
                        frames_stalled + pending (admitted frames land
                        in the engine's batch-ingest facade — flowing
                        into staging-balance from there — or are
                        stall-shed with their acks withheld; the
                        arrival-window backlog is the only legal slack)
"""

from __future__ import annotations

import dataclasses
import json
import logging
import threading
import time

logger = logging.getLogger(__name__)

EQUATIONS = (
    "staging-balance", "device-processed", "device-disposition",
    "edge-admission", "wal-durability", "forward-queue",
    "replication-feed", "archive-spill", "rules-harvest",
    "placement-handoff", "spmd-shard-flow", "analytics-windows",
    "wire-frames", "wire-rows",
)


class FlowLedger:
    """Host-side flow counters for the boundaries nothing else counts.

    All mutation sites hold the engine lock, so no lock of its own;
    ``enabled`` toggles counting. ``rebase`` records the device counters a restored
    snapshot already carries, so a recovered engine's ledger balances
    over the rows IT staged (WAL replay), not the pre-crash history."""

    __slots__ = ("enabled", "counters", "baseline")

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.counters: dict[str, int] = {"staged_rows": 0,
                                         "dispatched_rows": 0}
        self.baseline: dict[str, int] = {}

    def add(self, key: str, n: int) -> None:
        if self.enabled and n:
            self.counters[key] = self.counters.get(key, 0) + int(n)

    def rebase(self, engine) -> None:
        """Snapshot the engine's device-side counters as the baseline —
        called after a snapshot restore, BEFORE any replay, so the
        ledger's device deltas cover exactly the rows this process
        staged."""
        m = engine.metrics()
        base = {"processed": int(m.get("processed", 0)),
                "persisted": int(m.get("persisted", 0))}
        grid = _grid_totals(engine)
        for lane, n in grid.items():
            base[f"grid_{lane}"] = n
        self.baseline = base


def _grid_totals(eng) -> dict[str, int]:
    """Lane totals of the device-side tenant counter grid (scrape-path
    readback; {} when the engine has no grid)."""
    tpc = getattr(eng, "tenant_pipeline_counters", None)
    if not callable(tpc):
        return {}
    totals: dict[str, int] = {}
    for lanes in tpc().values():
        for lane, n in lanes.items():
            totals[lane] = totals.get(lane, 0) + int(n)
    return totals


def _backlog_rows(eng) -> int:
    """Valid rows staged but not yet dispatched — measured field by
    field (``staged_count`` counts an arena's failed-decode padding
    rows too, which never dispatch as valid). Caller holds the lock."""
    import numpy as np

    n = 0
    buf = getattr(eng, "_buf", None)
    if buf is not None:
        total = getattr(buf, "total", None)
        n += int(total()) if callable(total) else len(buf)
    fq = getattr(eng, "_fair_queued", 0)
    n += int(fq.sum()) if hasattr(fq, "sum") else int(fq)
    fill = getattr(eng, "_arena_fill", None)
    if fill is not None:
        cursors = getattr(fill, "cursors", None)
        if cursors is not None:
            # SPMD stacked arena: [S, rows] lanes with per-shard cursors
            for s, cnt in enumerate(cursors):
                n += int(np.sum(fill.valid[s, :int(cnt)]))
        else:
            n += int(np.sum(fill.valid[:fill.cursor]))
    for b in getattr(eng, "_staged_batches", ()):
        n += int(np.sum(b.valid))
    # SPMD engine (ISSUE 16): per-shard staging buffers
    for b in getattr(eng, "_shard_bufs", ()):
        n += len(b)
    return n


def _rules_stage(eng, rules_manager) -> dict | None:
    """Device CEP counters + the manager's harvest accounting."""
    import jax
    import numpy as np

    rs = getattr(eng.state, "rules", None)
    if rs is None or (rs.rules is None and rs.rollups is None):
        return None
    out: dict = {}
    if rs.rules is not None:
        rb = rs.rules
        f, m, l, o, pw, ph, wid = jax.device_get(
            (rb.fires, rb.missed, rb.late, rb.oob, rb.pend_w, rb.pend_h,
             rb.acc_wid))
        # np.sum casts keep this correct for an SPMD engine's STACKED
        # rules block ([S, ...] leaves): totals sum over every shard
        out.update(fires=int(np.sum(f)), missed=int(np.sum(m)),
                   late=int(np.sum(l)), oob=int(np.sum(o)),
                   pending=int(np.sum(np.minimum(
                       np.asarray(pw) - np.asarray(ph),
                       rb.pend_key.shape[-1]))),
                   max_window_id=int(np.max(wid)))
    if rs.rollups is not None:
        wid = np.asarray(jax.device_get(rs.rollups.wid))
        live = wid[wid > np.iinfo(np.int32).min]
        out["rollup_window_id"] = int(live.max()) if live.size else None
        out["rollup_late"] = int(jax.device_get(rs.rollups.late))
    if rules_manager is not None:
        # one consistent read under the manager lock: poll() commits
        # its four counters in a single _mu block, so the harvest
        # equation is evaluated over pre- or post-poll totals only
        with rules_manager._mu:
            out.update(
                harvested=int(getattr(rules_manager,
                                      "fires_harvested", 0)),
                emitted=int(getattr(rules_manager, "alerts_emitted", 0)),
                suppressed=int(getattr(rules_manager,
                                       "alerts_suppressed", 0)),
                skipped=int(getattr(rules_manager, "harvest_skipped", 0)))
    return out


def build_ledger(engine, rules_manager=None) -> dict:
    """One mutually-consistent flow-accounting snapshot of ``engine``
    (a cluster facade snapshots its LOCAL rank — rank ledgers federate
    through the cluster fan-out, never through one merged snapshot).
    Reads the device counters (forcing in-flight dispatches), so this
    belongs on scrape/audit cadences, never the ingest hot loop."""
    eng = getattr(engine, "local", engine)
    led: FlowLedger | None = getattr(eng, "ledger", None)
    with eng.lock:
        base = dict(led.baseline) if led is not None else {}
        m = eng.metrics()
        grid = _grid_totals(eng)
        stages: dict = {}
        qos = getattr(eng, "qos", None)
        if qos is not None:
            with qos._lock:
                stages["edge"] = {
                    # offered is counted INDEPENDENTLY at admit() entry
                    # (never derived from admitted + shed), so the edge
                    # equation can actually fail on a real ledger
                    "offered": int(qos.offered_events),
                    "admitted": int(qos.admitted_events),
                    "shed": int(qos.shed_events),
                    # sheds noted AFTER admission (arena stall): those
                    # events were offered-and-admitted, the checker
                    # subtracts them from the edge shed total
                    "shed_noted": int(qos.shed_noted),
                    "shed_by_tenant": dict(qos.shed_by_tenant)}
        # persistent-connection wire edge (ISSUE 20): disposition
        # counters sampled from the attached edges' own snapshots. The
        # edge/batcher locks are distinct from the engine lock, so a
        # frame between its admission increment and its batcher append
        # can transiently skew wire-rows — exactly the non-atomic-update
        # race the auditor's two-consecutive-audit rule exists for; a
        # quiescent edge balances exactly.
        if getattr(eng, "wire_edges", None):
            from sitewhere_tpu.ingest.wire_edge import (
                aggregate_wire_snapshot)

            ws = aggregate_wire_snapshot(eng)
            if ws is not None:
                stages["wire"] = {k: ws[k] for k in (
                    "frames_received", "frames_admitted", "frames_shed",
                    "frames_invalid", "frames_duplicate",
                    "rows_submitted", "frames_stalled", "pending",
                    "backpressure_events", "connections_live",
                    "connections_peak")}
        ing = {"staged_rows": 0, "dispatched_rows": 0,
               "backlog_rows": _backlog_rows(eng), "counting": False}
        if led is not None:
            ing.update(staged_rows=led.counters.get("staged_rows", 0),
                       dispatched_rows=led.counters.get(
                           "dispatched_rows", 0),
                       counting=led.enabled)
        stages["ingest"] = ing
        stages["device"] = {
            "processed": int(m.get("processed", 0))
                          - base.get("processed", 0),
            "persisted": int(m.get("persisted", 0))
                          - base.get("persisted", 0),
            **{lane: n - base.get(f"grid_{lane}", 0)
               for lane, n in grid.items()},
        }
        # SPMD shard plane (ISSUE 18): the per-shard breakdown of the
        # device stage. Skipped when a restore baseline is active — the
        # device stage above is baseline-SUBTRACTED while the unfolded
        # grid is cumulative, and splitting the baseline per shard
        # would manufacture slack the equations don't have.
        sf = getattr(eng, "shard_flow", None)
        if callable(sf) and not base:
            stages["spmd"] = sf()
        wal = getattr(eng, "wal", None)
        if wal is not None:
            with wal._lock:
                appended, durable = int(wal._seq), int(wal._durable_seq)
            stages["wal"] = {"appended_seq": appended,
                             "durable_seq": durable,
                             "group_commit": bool(wal.group_commit)}
        fq = getattr(eng, "forward_queue", None)
        if fq is not None:
            fm = fq.metrics()
            stages["forward"] = {
                "spilled_batches": fm["forward_spilled_batches"],
                "redelivered_batches": fm["forward_redelivered_batches"],
                "deadlettered_batches":
                    fm["forward_deadlettered_batches"],
                "rerouted_batches":
                    fm.get("forward_rerouted_batches", 0),
                "queue_depth": fm["forward_queue_depth"],
                "open_circuits": fm["forward_open_circuits"],
            }
        pm = getattr(eng, "placement", None)
        if pm is not None:
            stages["placement"] = pm.ledger_stage()
        feed = getattr(eng, "replica_feed", None)
        applier = getattr(eng, "replica_applier", None)
        if feed is not None or applier is not None:
            rep: dict = {}
            if feed is not None:
                wm = feed.watermarks()
                rep.update(feed_seq=wm["seq"], published=wm["published"],
                           acked=wm["acked"], buffer=wm["buffer"])
            if applier is not None:
                rep["applied_by_leader"] = {
                    str(r): applier.applied(r)
                    for r in applier.leaders()}
            stages["replication"] = rep
        arch = getattr(eng, "archive", None)
        if arch is not None:
            # heads/capacity come from the engine's OWN spooler helpers
            # (engine.ring_heads / ring_arena_capacity) — one definition
            # for the spooler and its checker, no drift
            heads = eng.ring_heads()
            acap = eng.ring_arena_capacity()
            stages["archive"] = {
                "parts": {str(p): {"head": h,
                                   "spilled": arch.spilled(p),
                                   "capacity": acap}
                          for p, h in heads.items()},
                "rows": arch.total_rows(),
                "lost_rows": int(arch.lost_rows),
                "expired_rows": int(arch.expired_rows),
            }
        rules = _rules_stage(eng, rules_manager)
        if rules is not None:
            stages["rules"] = rules
        aj = getattr(eng, "analytics_jobs", None)
        if aj is not None:
            # one consistent read under the manager lock (the scoring
            # pass commits planned + sinks in a single _mu block, so
            # this only ever observes pre- or post-batch totals)
            stages["analytics"] = aj.ledger_stage()

    watermarks: dict = {"dispatched_rows": ing["dispatched_rows"]}
    lag: dict = {"staged_backlog_rows": ing["backlog_rows"]}
    if "wal" in stages:
        w = stages["wal"]
        watermarks["wal_appended"] = w["appended_seq"]
        watermarks["wal_durable"] = w["durable_seq"]
        lag["wal_durable_lag"] = w["appended_seq"] - w["durable_seq"]
    if "replication" in stages:
        r = stages["replication"]
        if "feed_seq" in r:
            watermarks["feed_seq"] = r["feed_seq"]
            acked = r.get("acked", {})
            lag["replication_lag_batches"] = (
                max(r["feed_seq"] - a for a in acked.values())
                if acked else 0)
        if r.get("applied_by_leader"):
            watermarks["standby_applied"] = r["applied_by_leader"]
    if "archive" in stages:
        parts = stages["archive"]["parts"]
        watermarks["archive_spill"] = {p: v["spilled"]
                                       for p, v in parts.items()}
        lag["archive_spill_lag_rows"] = (
            max((v["head"] - v["spilled"] for v in parts.values()),
                default=0))
    if "forward" in stages:
        lag["forward_queue_depth"] = stages["forward"]["queue_depth"]
    if "rules" in stages and "rollup_window_id" in stages["rules"]:
        watermarks["rollup_window_id"] = stages["rules"][
            "rollup_window_id"]
    if "placement" in stages:
        # the placement epoch is a monotone watermark like every other:
        # a rank observed at a LOWER epoch than its peers is lagging
        # the commit broadcast (redirects converge it)
        watermarks["placement_epoch"] = stages["placement"]["epoch"]

    return {
        "generatedMs": int(time.time() * 1000),
        "rank": getattr(engine, "rank", 0),
        "engine": getattr(eng, "metrics_label", "e?"),
        "stages": stages,
        "watermarks": watermarks,
        "lag": lag,
    }


@dataclasses.dataclass
class Violation:
    """One broken conservation equation: ``lhs`` and ``rhs`` are the
    evaluated sides, ``slack`` the tolerance the equation already
    granted when it still failed."""

    equation: str
    message: str
    lhs: float
    rhs: float
    slack: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def check_conservation(ledger: dict) -> list[Violation]:
    """Evaluate every conservation equation over one ledger snapshot.
    Pure: no engine access, no clock — the same ledger always yields
    the same verdict (the falsifiability tests perturb a ledger by one
    and must see a Violation)."""
    out: list[Violation] = []

    def bad(eq: str, msg: str, lhs, rhs, slack: float = 0.0) -> None:
        out.append(Violation(eq, msg, float(lhs), float(rhs),
                             float(slack)))

    st = ledger.get("stages", {})
    ing = st.get("ingest")
    dev = st.get("device", {})
    if ing and ing.get("counting"):
        staged = ing["staged_rows"]
        dispatched = ing["dispatched_rows"]
        backlog = ing["backlog_rows"]
        if staged != dispatched + backlog:
            bad("staging-balance",
                f"staged_rows {staged} != dispatched_rows {dispatched} "
                f"+ backlog {backlog}", staged, dispatched + backlog,
                slack=backlog)
        processed = dev.get("processed")
        if processed is not None and dispatched != processed:
            bad("device-processed",
                f"dispatched_rows {dispatched} != device processed "
                f"{processed}", dispatched, processed)
    if "accepted" in dev and "invalid" in dev and "processed" in dev:
        lhs = dev["accepted"] + dev["invalid"]
        if lhs != dev["processed"]:
            bad("device-disposition",
                f"accepted {dev['accepted']} + invalid {dev['invalid']}"
                f" != processed {dev['processed']}", lhs,
                dev["processed"])
    edge = st.get("edge")
    if edge:
        edge_shed = edge["shed"] - edge.get("shed_noted", 0)
        if edge["offered"] != edge["admitted"] + edge_shed:
            bad("edge-admission",
                f"offered {edge['offered']} != admitted "
                f"{edge['admitted']} + edge shed {edge_shed} "
                f"(shed total {edge['shed']} incl. "
                f"{edge.get('shed_noted', 0)} post-admission)",
                edge["offered"], edge["admitted"] + edge_shed,
                slack=edge.get("shed_noted", 0))
        by_tenant = sum(edge.get("shed_by_tenant", {}).values())
        if by_tenant != edge["shed"]:
            bad("edge-admission",
                f"per-tenant shed sum {by_tenant} != shed total "
                f"{edge['shed']}", by_tenant, edge["shed"])
    wal = st.get("wal")
    if wal and not (0 <= wal["durable_seq"] <= wal["appended_seq"]):
        bad("wal-durability",
            f"durable_seq {wal['durable_seq']} outside "
            f"[0, appended_seq {wal['appended_seq']}]",
            wal["durable_seq"], wal["appended_seq"])
    fwd = st.get("forward")
    if fwd:
        rerouted = fwd.get("rerouted_batches", 0)
        rhs = (fwd["redelivered_batches"] + fwd["deadlettered_batches"]
               + rerouted + fwd["queue_depth"])
        if fwd["spilled_batches"] != rhs:
            bad("forward-queue",
                f"spilled {fwd['spilled_batches']} != redelivered "
                f"{fwd['redelivered_batches']} + deadlettered "
                f"{fwd['deadlettered_batches']} + rerouted {rerouted} "
                f"+ depth {fwd['queue_depth']}",
                fwd["spilled_batches"], rhs,
                slack=fwd["queue_depth"])
    rep = st.get("replication")
    if rep and "feed_seq" in rep:
        if rep["published"] != rep["feed_seq"]:
            bad("replication-feed",
                f"published {rep['published']} != feed_seq "
                f"{rep['feed_seq']}", rep["published"], rep["feed_seq"])
        for f, acked in rep.get("acked", {}).items():
            if acked > rep["feed_seq"]:
                bad("replication-feed",
                    f"follower {f} acked {acked} > feed_seq "
                    f"{rep['feed_seq']}", acked, rep["feed_seq"])
    arch = st.get("archive")
    if arch:
        lost = arch.get("lost_rows", 0)
        for p, v in arch.get("parts", {}).items():
            if v["spilled"] > v["head"]:
                bad("archive-spill",
                    f"part {p} spill cursor {v['spilled']} ahead of "
                    f"ring head {v['head']}", v["spilled"], v["head"])
            elif v["head"] - v["spilled"] > v["capacity"] + lost:
                bad("archive-spill",
                    f"part {p} unspilled backlog "
                    f"{v['head'] - v['spilled']} exceeds capacity "
                    f"{v['capacity']} + counted losses {lost}",
                    v["head"] - v["spilled"], v["capacity"] + lost,
                    slack=v["capacity"] + lost)
    pl = st.get("placement")
    if pl:
        rhs = (pl["moves_completed"] + pl["moves_aborted"]
               + pl["moves_in_flight"])
        if pl["moves_started"] != rhs:
            bad("placement-handoff",
                f"moves_started {pl['moves_started']} != completed "
                f"{pl['moves_completed']} + aborted "
                f"{pl['moves_aborted']} + in_flight "
                f"{pl['moves_in_flight']}", pl["moves_started"], rhs,
                slack=pl["moves_in_flight"])
        if pl.get("fenced_slots", 0) and not pl["moves_in_flight"]:
            bad("placement-handoff",
                f"{pl['fenced_slots']} fenced slot(s) with no move in "
                "flight (a fence must belong to a live handoff)",
                pl["fenced_slots"], 0)
    sp = st.get("spmd")
    if sp:
        per = sp.get("perShard", [])
        for row in per:
            s = row["shard"]
            lhs = row["accepted"] + row["invalid"]
            if lhs != row["processed"]:
                bad("spmd-shard-flow",
                    f"shard {s}: accepted {row['accepted']} + invalid "
                    f"{row['invalid']} != processed {row['processed']}",
                    lhs, row["processed"])
            if sp.get("counting"):
                rhs = row["dispatched_rows"] + row["backlog_rows"]
                if row["routed_rows"] != rhs:
                    bad("spmd-shard-flow",
                        f"shard {s}: routed_rows {row['routed_rows']} "
                        f"!= dispatched_rows {row['dispatched_rows']} "
                        f"+ backlog {row['backlog_rows']}",
                        row["routed_rows"], rhs,
                        slack=row["backlog_rows"])
        # the unfolded grid is the SAME grid the device stage folds:
        # every per-shard lane must sum EXACTLY to the folded total
        for lane in ("processed", "accepted", "invalid",
                     "dedup_dropped", "geofence_hit"):
            if lane not in dev:
                continue
            total = sum(row.get(lane, 0) for row in per)
            if total != dev[lane]:
                bad("spmd-shard-flow",
                    f"per-shard {lane} sum {total} != device {lane} "
                    f"{dev[lane]}", total, dev[lane])
        if sp.get("counting") and ing and ing.get("counting"):
            routed = sum(row["routed_rows"] for row in per)
            if routed != ing["staged_rows"]:
                bad("spmd-shard-flow",
                    f"per-shard routed sum {routed} != staged_rows "
                    f"{ing['staged_rows']}", routed, ing["staged_rows"])
    rules = st.get("rules")
    if rules:
        if "harvested" in rules:
            rhs = (rules.get("emitted", 0) + rules.get("suppressed", 0)
                   + rules.get("skipped", 0))
            if rules["harvested"] != rhs:
                bad("rules-harvest",
                    f"harvested {rules['harvested']} != emitted "
                    f"{rules.get('emitted', 0)} + suppressed "
                    f"{rules.get('suppressed', 0)} + skipped "
                    f"{rules.get('skipped', 0)}", rules["harvested"],
                    rhs)
        if "fires" in rules and rules.get("missed", 0) > rules["fires"]:
            bad("rules-harvest",
                f"missed {rules['missed']} > fires {rules['fires']}",
                rules["missed"], rules["fires"])
        if rules.get("pending", 0) < 0:
            bad("rules-harvest",
                f"negative pending ring depth {rules['pending']}",
                rules["pending"], 0)
    an = st.get("analytics")
    if an and "planned" in an:
        rhs = (an.get("scored", 0) + an.get("skipped_underfilled", 0)
               + an.get("cancelled", 0))
        if an["planned"] != rhs:
            bad("analytics-windows",
                f"windows planned {an['planned']} != scored "
                f"{an.get('scored', 0)} + skipped_underfilled "
                f"{an.get('skipped_underfilled', 0)} + cancelled "
                f"{an.get('cancelled', 0)}", an["planned"], rhs)
    wire = st.get("wire")
    if wire:
        rhs = (wire.get("frames_admitted", 0) + wire.get("frames_shed", 0)
               + wire.get("frames_invalid", 0)
               + wire.get("frames_duplicate", 0))
        if wire.get("frames_received", 0) != rhs:
            bad("wire-frames",
                f"frames received {wire.get('frames_received', 0)} != "
                f"admitted {wire.get('frames_admitted', 0)} + shed "
                f"{wire.get('frames_shed', 0)} + invalid "
                f"{wire.get('frames_invalid', 0)} + duplicate "
                f"{wire.get('frames_duplicate', 0)}",
                wire.get("frames_received", 0), rhs)
        rhs = (wire.get("rows_submitted", 0)
               + wire.get("frames_stalled", 0) + wire.get("pending", 0))
        if wire.get("frames_admitted", 0) != rhs:
            bad("wire-rows",
                f"frames admitted {wire.get('frames_admitted', 0)} != "
                f"rows_submitted {wire.get('rows_submitted', 0)} + "
                f"stalled {wire.get('frames_stalled', 0)} + pending "
                f"{wire.get('pending', 0)}",
                wire.get("frames_admitted", 0), rhs,
                slack=wire.get("pending", 0))
    return out


def conservation_metrics(registry=None) -> dict:
    """The conservation plane's registry instruments. Kept OUT of
    ``engine.metrics()`` (dispatch-shape equality) like every plane
    before it:

      swtpu_conservation_violation_total  confirmed violations, per
                                          equation (auditor-escalated)
      swtpu_conservation_violations       current violation count of
                                          the latest audit (gauge)
      swtpu_conservation_audits_total     audit passes run (gauge,
                                          scrape-synced)
      swtpu_flow_rows                     ledger flow counters, labeled
                                          by stage (staged | dispatched
                                          | backlog), per engine
      swtpu_flow_lag                      per-stage lag derived from
                                          the watermarks at scrape
    """
    from sitewhere_tpu.utils.metrics import REGISTRY

    reg = registry or REGISTRY
    return {
        "violations_total": reg.counter(
            "swtpu_conservation_violation_total",
            "confirmed conservation-equation violations, per equation"),
        "violations": reg.gauge(
            "swtpu_conservation_violations",
            "violations in the most recent conservation audit"),
        "audits": reg.gauge(
            "swtpu_conservation_audits_total",
            "conservation audit passes run"),
        "flow": reg.gauge(
            "swtpu_flow_rows",
            "conservation ledger flow counters, per stage"),
        "lag": reg.gauge(
            "swtpu_flow_lag",
            "per-stage lag derived from the conservation watermarks"),
    }


def export_conservation_metrics(engine, registry=None) -> None:
    """Scrape-time export of the ledger's host-side counters and the
    auditor's posture. Deliberately does NOT build a full ledger (the
    device readbacks stay on the audit cadence); only the cheap host
    counters and the latest audit verdict land on the scrape."""
    eng = getattr(engine, "local", engine)
    led = getattr(eng, "ledger", None)
    if led is None:
        return
    inst = conservation_metrics(registry)
    lbl = getattr(eng, "metrics_label", "e?")
    flow = inst["flow"]
    flow.set(led.counters.get("staged_rows", 0), stage="staged",
             engine=lbl)
    flow.set(led.counters.get("dispatched_rows", 0), stage="dispatched",
             engine=lbl)
    aud = getattr(eng, "conservation_auditor", None)
    if aud is not None:
        inst["violations"].set(len(aud.last_violations), engine=lbl)
        inst["audits"].set(aud.audits, engine=lbl)
        for k, v in (aud.last_ledger or {}).get("lag", {}).items():
            inst["lag"].set(v, stage=k, engine=lbl)


class ConservationAuditor:
    """Background invariant checker: builds a ledger and evaluates the
    conservation equations every ``interval_s`` seconds. A violation
    escalates (counter + loud structured log) only when the SAME
    equation fails two consecutive audits — a spill file's rename and
    its counter update are not atomic with a concurrent audit, so a
    single-read imbalance is a suspect, not a verdict."""

    def __init__(self, engine, rules_manager=None,
                 interval_s: float = 5.0, registry=None):
        self.engine = engine
        self.rules_manager = rules_manager
        self.interval_s = float(interval_s)
        self._registry = registry
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._suspect: set[str] = set()
        self.audits = 0
        self.confirmed_total = 0
        self.last_ledger: dict | None = None
        self.last_violations: list[dict] = []
        # attach so the scrape exporter and REST payload can find us
        getattr(engine, "local", engine).conservation_auditor = self

    def audit(self) -> tuple[dict, list[Violation]]:
        """One audit pass (also the synchronous entry tests/bench use):
        returns (ledger, violations) and applies the two-read
        confirmation rule to the escalation side effects."""
        ledger = build_ledger(self.engine, self.rules_manager)
        violations = check_conservation(ledger)
        self.audits += 1
        self.last_ledger = ledger
        self.last_violations = [v.to_dict() for v in violations]
        now_suspect = {v.equation for v in violations}
        confirmed = [v for v in violations
                     if v.equation in self._suspect]
        self._suspect = now_suspect - {v.equation for v in confirmed}
        if confirmed:
            inst = conservation_metrics(self._registry)
            for v in confirmed:
                self.confirmed_total += 1
                inst["violations_total"].inc(equation=v.equation)
                logger.error(
                    "CONSERVATION VIOLATION %s",
                    json.dumps({"equation": v.equation,
                                "message": v.message, "lhs": v.lhs,
                                "rhs": v.rhs, "slack": v.slack,
                                "rank": ledger.get("rank"),
                                "engine": ledger.get("engine")}))
        return ledger, violations

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.audit()
            except Exception:
                logger.exception("conservation audit pass failed")

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> None:
        if self.running:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name="swtpu-conservation",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


def conservation_payload(engine, rules_manager=None) -> dict:
    """THE document behind ``GET /api/instance/conservation`` and the
    ``Instance.conservation`` RPC: a fresh ledger + verdict, plus the
    background auditor's posture when one is attached."""
    ledger = build_ledger(engine, rules_manager)
    violations = check_conservation(ledger)
    out = {"ledger": ledger,
           "violations": [v.to_dict() for v in violations],
           "balanced": not violations}
    aud = getattr(getattr(engine, "local", engine),
                  "conservation_auditor", None)
    if aud is not None:
        out["auditor"] = {"audits": aud.audits,
                          "confirmedViolations": aud.confirmed_total,
                          "intervalS": aud.interval_s,
                          "running": aud.running}
    return out
