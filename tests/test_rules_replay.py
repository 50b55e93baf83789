"""Replay/standby discipline of the streaming-rules tier (ISSUE 13) and
the admin-path standby-visibility satellite (the PR-6 documented limit).

Contract: alert events are dedup-keyed by rule+group+window, so
  * kill/recover re-evaluates rules over WAL replay and emits EXACTLY
    the fires the dead owner never shipped — zero lost, zero duplicate;
  * a standby running the same rule set over the same stream carries
    identical rule state; promotion emits only the un-shipped tail;
  * admin-path ``register_device`` (non-wire REST) is WAL-carried as its
    wire-form envelope, so it replays AND replica-feed publishes.
"""

import json

import pytest

from sitewhere_tpu.engine import WAL_BINARY, Engine, EngineConfig
from sitewhere_tpu.rules import RuleSet, RulesManager
from sitewhere_tpu.rules import oracle
from sitewhere_tpu.utils.checkpoint import (replay_wal_into,
                                            restore_engine, save_engine)

CFG = dict(device_capacity=256, token_capacity=512,
           assignment_capacity=512, store_capacity=4096,
           batch_capacity=32, channels=4, rule_groups=64,
           rollup_buckets=8)

RULESET = {
    "name": "rp",
    "rules": [
        {"name": "hot", "kind": "threshold", "channel": "temp",
         "op": ">", "value": 90.0, "cooldownMs": 1000},
        {"name": "burst", "kind": "window", "agg": "sum",
         "channel": "temp", "op": ">=", "value": 200.0,
         "windowMs": 2000},
        {"name": "silent", "kind": "absence", "channel": "temp",
         "deadlineMs": 3000},
    ],
    "rollups": [{"name": "temp-1s", "channel": "temp",
                 "windowMs": 1000, "scope": "device"}],
}


def _engine(tmp_path=None, name="wal", **kw):
    cfg = dict(CFG, **kw)
    if tmp_path is not None:
        cfg["wal_dir"] = str(tmp_path / name)
    return Engine(EngineConfig(**cfg))


def _meas(eng, tok, v, ts_rel):
    return json.dumps({
        "deviceToken": tok, "type": "DeviceMeasurement",
        "request": {"name": "temp", "value": v,
                    "eventDate": int(eng.epoch.base_unix_s * 1000)
                    + ts_rel}}).encode()


def _stream(n=72, devs=4, quiet_after=36):
    out = []
    for i in range(n):
        d = i % devs
        if d == 0 and i >= quiet_after:
            d = 1
        v = 96.5 if i % 9 == 0 else 30.0 + (i % 20) * 0.5
        out.append((d, v, i * 100))
    return out


def _oracle_keys(events, final_wm):
    ev = [{"ts": ts, "group": d, "value": v} for d, v, ts in events]
    exp = set()
    for g, w in oracle.threshold_fire_keys(ev, op=0, value=90.0,
                                           cooldown_ms=1000):
        exp.add(f"swr:hot:q-{g}:{w}")
    for g, w in oracle.window_fire_keys(ev, agg="sum", op=1, value=200.0,
                                        window_ms=2000):
        exp.add(f"swr:burst:q-{g}:{w}")
    for g, w in oracle.absence_fire_keys(ev, op=1, value=float("-inf"),
                                         deadline_ms=3000,
                                         final_watermark=final_wm):
        exp.add(f"swr:silent:q-{g}:{w}")
    return exp


def _feed(eng, events, lo, hi, chunk=24):
    for b in range(lo, hi, chunk):
        eng.ingest_json_batch([_meas(eng, f"q-{d}", v, ts)
                               for d, v, ts in events[b:min(b + chunk,
                                                            hi)]])
    eng.flush()


def test_kill_recover_reevaluation_zero_loss_zero_dup(tmp_path):
    """The chaos slice: half the stream emitted, half fired-but-unpolled,
    SIGKILL, recover from snapshot + WAL replay with the rule set
    reinstalled — the union of pre/post alert keys is exactly the
    oracle's, the intersection empty, and the recovered store holds each
    alert exactly once."""
    events = _stream()
    eng = _engine(tmp_path)
    mgr = RulesManager(eng)
    mgr.load(RuleSet.parse(RULESET), precompile=False)
    save_engine(eng, tmp_path / "snap")
    _feed(eng, events, 0, 36)
    pre = mgr.poll()                   # emitted + WAL-carried
    _feed(eng, events, 36, len(events))
    eng.wal.sync()
    eng.wal.close()                    # "SIGKILL" — pending fires lost?
    del eng

    r2 = restore_engine(tmp_path / "snap")
    m2 = RulesManager(r2)
    m2.load(RuleSet.parse(RULESET), precompile=False)
    replay_wal_into(r2, 0, tmp_path / "wal")
    post = m2.poll()
    pre_keys = {a["alternateId"] for a in pre}
    post_keys = {a["alternateId"] for a in post}
    assert pre_keys and post_keys
    assert not (pre_keys & post_keys), "duplicate alert after recovery"
    assert pre_keys | post_keys == _oracle_keys(events, events[-1][2])
    # store-level: every alert exactly once, queryable by its dedup key
    r2.flush()
    from sitewhere_tpu.core.types import EventType

    q = r2.query_events(etype=EventType.ALERT, limit=200)
    assert q["total"] == len(pre_keys | post_keys)
    # rollups rebuilt by replay match the oracle exactly
    ev = [{"ts": ts, "group": d, "value": v} for d, v, ts in events]
    want = oracle.rollup_oracle(ev, window_ms=1000, buckets=8)
    for g in range(4):
        got = m2.read_rollup("temp-1s", group=f"q-{g}")
        got_map = {b["windowStartMs"]: (b["count"], b["sum"], b["min"],
                                        b["max"])
                   for b in got["buckets"]}
        want_map = {st[0] * 1000: (st[1], st[2], st[3], st[4])
                    for (gg, s), st in want.items() if gg == g}
        assert got_map == want_map


def test_kill_recover_ledger_balances_with_rules(tmp_path):
    """The same kill/recover slice, audited: the recovered engine's
    ledger, rules stage included, balances over the WAL replay and the
    alerts emitted after recovery."""
    from sitewhere_tpu.utils.conservation import (build_ledger,
                                                  check_conservation)

    events = _stream()
    eng = _engine(tmp_path)
    mgr = RulesManager(eng)
    mgr.load(RuleSet.parse(RULESET), precompile=False)
    save_engine(eng, tmp_path / "snap")
    _feed(eng, events, 0, 36)
    assert mgr.poll()
    _feed(eng, events, 36, len(events))
    eng.wal.sync()
    eng.wal.close()
    del eng

    r2 = restore_engine(tmp_path / "snap")
    m2 = RulesManager(r2)
    m2.load(RuleSet.parse(RULESET), precompile=False)
    replay_wal_into(r2, 0, tmp_path / "wal")
    assert m2.poll()
    r2.flush()
    led = build_ledger(r2, m2)
    assert not check_conservation(led), \
        [v.to_dict() for v in check_conservation(led)]
    rules = led["stages"]["rules"]
    assert rules["harvested"] > 0 and rules["emitted"] > 0


def test_standby_runs_rules_and_promotion_emits_only_the_tail():
    """A standby applies the owner's stream (alert events included, as
    the replica feed ships them) with the same rule set but emission
    OFF: its carried rule state tracks the owner's, and promotion emits
    exactly the fires the dead owner never polled out — dedup-keyed
    against the replayed alerts, nothing twice."""
    events = _stream()
    owner = Engine(EngineConfig(**CFG))
    standby = Engine(EngineConfig(**CFG))
    standby.epoch = owner.epoch
    omgr = RulesManager(owner)
    smgr = RulesManager(standby, active=False)
    omgr.load(RuleSet.parse(RULESET), precompile=False)
    smgr.load(RuleSet.parse(RULESET), precompile=False)

    # "replica feed": every owner ingest batch (rule alerts included —
    # the manager emits through this very path) applies on the standby
    orig = owner.ingest_json_batch

    def forwarding(payloads, tenant="default", **kw):
        res = orig(payloads, tenant, **kw)
        standby.ingest_json_batch(list(payloads), tenant)
        return res

    owner.ingest_json_batch = forwarding
    _feed(owner, events, 0, 36)
    pre = omgr.poll()                  # shipped to the standby too
    _feed(owner, events, 36, len(events))
    standby.flush()
    # standby rule state == owner rule state (same stream, same kernel)
    import numpy as np

    ow, st = owner.state.rules.rules, standby.state.rules.rules
    assert np.array_equal(np.asarray(ow.fired_key),
                          np.asarray(st.fired_key))
    assert int(ow.fires) == int(st.fires)
    # a passive poll emits nothing and harvests nothing
    assert smgr.poll() == []
    # owner dies; standby promotes: resync registers the replayed alert
    # keys, the next poll emits only the unshipped tail
    suppressed0 = smgr.alerts_suppressed
    smgr.promote()
    post = smgr.poll()
    pre_keys = {a["alternateId"] for a in pre}
    post_keys = {a["alternateId"] for a in post}
    assert pre_keys and post_keys
    assert not (pre_keys & post_keys)
    assert pre_keys | post_keys == _oracle_keys(events, events[-1][2])
    assert smgr.alerts_suppressed > suppressed0   # dedup did real work


def test_admin_register_device_is_wal_replayed(tmp_path):
    """Satellite (PR-6 documented limit): a non-wire REST-path
    registration must survive WAL-only recovery — the admin mutation is
    logged as its wire-form envelope."""
    eng = _engine(tmp_path)
    eng.register_device("adm-1", device_type="sensor", tenant="t1",
                        area="zone-9")
    eng.ingest_json_batch([_meas(eng, "adm-1", 20.0, 100)], tenant="t1")
    eng.flush()
    eng.wal.sync()
    eng.wal.close()
    del eng

    r2 = _engine(tmp_path)             # same WAL dir, empty state
    replay_wal_into(r2, -1, tmp_path / "wal")
    info = r2.get_device("adm-1")
    assert info is not None
    assert info.device_type == "sensor"
    assert info.tenant == "t1" and info.area == "zone-9"
    assert r2.metrics()["persisted"] == 1


def test_admin_register_publishes_one_feed_record_and_wire_path_none():
    """The admin path publishes exactly ONE replica-feed record per
    registration; the wire path (process) keeps its single envelope —
    no double-publish from the nested admin call."""
    import tempfile

    eng = Engine(EngineConfig(**CFG, wal_dir=tempfile.mkdtemp(
        prefix="swtpu-admfeed-")))
    published = []

    class FeedStub:
        def publish(self, tag, payloads, tenant, ticket, now_ms):
            published.append((tag, len(payloads), tenant))

    eng.replica_feed = FeedStub()
    eng.register_device("fd-1", tenant="t2")
    assert published == [(WAL_BINARY, 1, "t2")]
    # idempotent get-or-create: no second record
    eng.register_device("fd-1", tenant="t2")
    assert len(published) == 1
    # wire-path registration envelope: exactly one record, logged by
    # process() itself (the nested admin call is suppressed)
    from sitewhere_tpu.ingest.requests import DecodedRequest, RequestType

    req = DecodedRequest(type=RequestType.REGISTER_DEVICE,
                         device_token="fd-2", tenant="t2",
                         extras={"deviceTypeToken": "sensor"})
    eng.process(req)
    assert len(published) == 2
    assert eng.get_device("fd-2").device_type == "sensor"


@pytest.mark.slow
def test_admin_register_standby_visible_through_real_replication(tmp_path):
    """End to end through the PR-6 machinery: an admin registration on
    the owner rank lands in the follower's standby engine registry."""
    from tests.test_replication import (_close, _mk_replicated_cluster,
                                        _wait)
    from tests.test_cluster import tokens_owned_by

    clusters, feeds, appliers, servers, host, ports = \
        _mk_replicated_cluster(tmp_path)
    c0 = clusters[0]
    try:
        tok = tokens_owned_by(0, 1, prefix="admrep")[0]
        did = c0.register_device(tok, tenant="default")
        assert did is not None
        _wait(feeds[0].drained, what="feed drain")
        st = appliers[1]._standby(0)
        assert st is not None
        st.engine.flush()
        tid = st.engine.tokens.lookup(tok)
        assert tid >= 0
        assert st.engine.token_device.get(tid) is not None
    finally:
        _close(clusters, feeds, host)
