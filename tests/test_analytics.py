"""Fleet-scale historical analytics (ISSUE 19): the archive->device
batched scoring pipeline.

Parity discipline: the job's streamed/planned/trimmed/batch-filled
windows must score IDENTICALLY (bit-for-bit, same jitted program) to a
host numpy oracle that rebuilds each device's window from the raw
archive rows with per-device Python loops — over compressed and
uncompressed segments, gap-registered partitions, underfilled windows
and time-range clips. Emission mirrors the PR-12 rule-alert replay
discipline: dedup keys are the durable registry, so kill/recover and
standby promotion emit exactly the score alerts the dead owner never
shipped. The analytics-windows conservation equation is falsifiable.
"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from sitewhere_tpu.core.types import EventType
from sitewhere_tpu.engine import Engine, EngineConfig
from sitewhere_tpu.models.analytics import (SCORE_KEY_PREFIX,
                                            AnalyticsJobSpec,
                                            AnalyticsManager)

# one jit-shape family for the whole module (W, C, M shared by every
# test -> the fill + score programs compile once per pytest process)
W, C, M = 8, 4, 8
MIN_FILL = 4

CFG = dict(device_capacity=64, token_capacity=256,
           assignment_capacity=128, store_capacity=64,
           batch_capacity=16, channels=C, archive_segment_rows=16)


def _engine(tmp_path, name="arch", **kw):
    cfg = dict(CFG, archive_dir=str(tmp_path / name), **kw)
    return Engine(EngineConfig(**cfg))


def _meas(eng, tok, ts_rel, vals):
    return json.dumps({
        "deviceToken": tok, "type": "DeviceMeasurements",
        "request": {"measurements": vals,
                    "eventDate": int(eng.epoch.base_unix_s * 1000)
                    + ts_rel}}).encode()


def _spy_ingest(eng):
    """Wrap ingest_json_batch, collecting every decoded envelope — the
    emission-capture idiom of tests/test_rules_replay.py's replica feed."""
    sent = []
    orig = eng.ingest_json_batch

    def spy(payloads, tenant="default", **kw):
        sent.extend(json.loads(p) for p in payloads)
        return orig(payloads, tenant, **kw)

    eng.ingest_json_batch = spy
    return sent


# --------------------------------------------------------------- oracle
def _fleet_rows(tid, tid_other, ids, oid):
    """Deterministic row set: 6 scoreable devices with overlapping time
    ranges, ids[4] underfilled below MIN_FILL, ids[5] underfilled but
    scoreable; plus decoy rows the job must drop (invalid, wrong etype,
    other-tenant device)."""
    rng = np.random.default_rng(11)
    rows = []
    counts = [16, 16, 12, 10, 3, 5]
    for d, n in enumerate(counts):
        for i in range(n):
            vmask = np.array([(i + d + k) % 4 != 0 for k in range(C)],
                             bool)
            if not vmask.any():
                vmask[0] = True
            rows.append(dict(
                etype=0, device=ids[d], tenant=tid,
                ts=1000 + i * 50 + d * 7,
                values=rng.standard_normal(C).astype(np.float32),
                vmask=vmask, valid=True))
    # decoys: invalid row, alert-typed row, other-tenant device
    rows.append(dict(etype=0, device=ids[0], tenant=tid, ts=5000,
                     values=np.ones(C, np.float32),
                     vmask=np.ones(C, bool), valid=False))
    rows.append(dict(etype=int(EventType.ALERT), device=ids[1],
                     tenant=tid, ts=5001, values=np.ones(C, np.float32),
                     vmask=np.ones(C, bool), valid=True))
    for i in range(6):
        rows.append(dict(etype=0, device=oid, tenant=tid_other,
                         ts=1000 + i * 50, values=np.ones(C, np.float32),
                         vmask=np.ones(C, bool), valid=True))
    rng.shuffle(rows)
    return rows, counts


def _append(arch, part, start, rows):
    """One handmade segment from row dicts (ring-slice shape)."""
    n = len(rows)
    sl = SimpleNamespace(
        etype=np.array([r["etype"] for r in rows], np.int64),
        device=np.array([r["device"] for r in rows], np.int64),
        assignment=np.full(n, part, np.int64),
        tenant=np.array([r["tenant"] for r in rows], np.int64),
        area=np.full(n, -1, np.int64),
        customer=np.full(n, -1, np.int64),
        asset=np.full(n, -1, np.int64),
        ts_ms=np.array([r["ts"] for r in rows], np.int64),
        received_ms=np.array([r["ts"] for r in rows], np.int64),
        values=np.stack([r["values"] for r in rows]),
        vmask=np.stack([r["vmask"] for r in rows]),
        aux=np.zeros((n, 2), np.int64),
        valid=np.array([r["valid"] for r in rows], bool))
    arch.append_segment(part, start, sl)


def _mk_handmade(tmp_path, compress):
    """Engine + handmade archive: part 0 starts at a REGISTERED GAP
    (migration padding, positions 0..16 never held data), part 1 at 0."""
    from sitewhere_tpu.utils.archive import EventArchive

    eng = Engine(EngineConfig(**CFG))
    ids = [eng.register_device(f"an-{d}") for d in range(6)]
    assert all(i is not None for i in ids)
    oid = eng.register_device("tz-0", tenant="t2")
    tid = eng.tenants.lookup("default")
    tid_other = eng.tenants.lookup("t2")
    assert tid >= 0 and tid_other >= 0 and tid != tid_other
    rows, counts = _fleet_rows(tid, tid_other, ids, oid)
    arch = EventArchive(tmp_path / ("c" if compress else "u"),
                        segment_rows=16, compress=compress)
    arch.register_gap(0, 0, 16)
    cuts = [0, 16, 32, 48, len(rows)]
    pos = {}
    starts = [16, 32, 48, 0]
    parts = [0, 0, 0, 1]
    for k in range(4):
        seg_rows = rows[cuts[k]:cuts[k + 1]]
        _append(arch, parts[k], starts[k], seg_rows)
        for j, r in enumerate(seg_rows):
            pos[id(r)] = (parts[k], starts[k] + j)
    eng.archive = arch
    for r in rows:
        r["pos"] = pos[id(r)]
    return eng, rows, counts, tid


def _oracle(mgr, eng, rows, tid, *, until_ms=None, threshold=None):
    """Per-device window rebuild with plain Python loops + the SAME
    jitted scorer, devices in id order padded to M — bit-identical input
    to the job's single batch, so scores must match exactly."""
    import jax.numpy as jnp

    by_dev = {}
    for r in rows:
        if not r["valid"] or r["etype"] != 0 or r["tenant"] != tid:
            continue
        if until_ms is not None and r["ts"] > until_ms:
            continue
        by_dev.setdefault(r["device"], []).append(r)
    devs = sorted(by_dev)
    data = np.zeros((M, W, C), np.float32)
    filled = np.zeros(M, np.int32)
    ends = {}
    for k, d in enumerate(devs):
        evs = sorted(by_dev[d], key=lambda r: (r["ts"], r["pos"]))
        ends[d] = evs[-1]["ts"]
        filled[k] = min(len(evs), W)
        for j, r in enumerate(evs[-W:]):
            data[k, W - min(len(evs), W) + j] = \
                np.where(r["vmask"], r["values"], 0.0)
    model, params, score_fn = mgr._model_bundle(W, C)
    scores, valid, _ = score_fn(model, params, jnp.asarray(data),
                                jnp.asarray(filled), jnp.int32(MIN_FILL))
    scores = np.asarray(scores)[:len(devs)]
    valid = np.asarray(valid)[:len(devs)]
    out = {}
    for k, d in enumerate(devs):
        tok = eng.devices[d].token
        out[d] = dict(token=tok, end=ends[d], score=float(scores[k]),
                      valid=bool(valid[k]))
    return out


@pytest.mark.parametrize("compress", [False, True])
def test_job_scores_match_host_oracle(tmp_path, compress):
    eng, rows, counts, tid = _mk_handmade(tmp_path, compress)
    mgr = AnalyticsManager(eng)
    oracle = _oracle(mgr, eng, rows, tid)
    valid_scores = sorted(o["score"] for o in oracle.values()
                          if o["valid"])
    thr = valid_scores[len(valid_scores) // 2]   # splits the fleet
    sent = _spy_ingest(eng)
    job = mgr.run_job(AnalyticsJobSpec(
        window=W, batch_devices=M, min_fill=MIN_FILL, threshold=thr,
        name="par"))
    assert job["state"] == "done" and job["error"] is None
    assert job["devices"] == 6
    assert job["planned"] == 6
    assert job["scored"] == sum(v["valid"] for v in oracle.values()) == 5
    assert job["skipped_underfilled"] == 1       # device 4: 3 < MIN_FILL
    # emitted alert set == oracle's strict threshold crossings, and the
    # .3f-formatted score in each message matches the oracle bit-for-bit
    want = {f"{SCORE_KEY_PREFIX}par:{o['token']}:{o['end']}":
            f"{o['score']:.3f}"
            for o in oracle.values() if o["valid"] and o["score"] > thr}
    got = {e["request"]["alternateId"]:
           e["request"]["message"].split()[3]
           for e in sent if e["type"] == "DeviceAlert"}
    assert want and got == want
    assert job["emitted"] == len(want) and job["suppressed"] == 0
    st = mgr.ledger_stage()
    assert st["planned"] == st["scored"] + st["skipped_underfilled"] \
        + st["cancelled"]


@pytest.mark.parametrize("compress", [False, True])
def test_time_range_clip_matches_oracle(tmp_path, compress):
    """until_ms clips each device's window mid-history: window ends,
    fill counts and the underfilled set all shift — and must match the
    oracle's clipped rebuild."""
    eng, rows, counts, tid = _mk_handmade(tmp_path, compress)
    mgr = AnalyticsManager(eng)
    cut = 1000 + 6 * 50                          # keeps ~7 rows/device
    oracle = _oracle(mgr, eng, rows, tid, until_ms=cut)
    sent = _spy_ingest(eng)
    job = mgr.run_job(AnalyticsJobSpec(
        window=W, batch_devices=M, min_fill=MIN_FILL, threshold=-1e9,
        until_ms=cut, name="rng"))
    assert job["state"] == "done"
    assert job["devices"] == len(oracle)
    want = {f"{SCORE_KEY_PREFIX}rng:{o['token']}:{o['end']}"
            for o in oracle.values() if o["valid"]}
    got = {e["request"]["alternateId"] for e in sent
           if e["type"] == "DeviceAlert"}
    assert got == want
    assert job["scored"] == sum(o["valid"] for o in oracle.values())
    assert job["skipped_underfilled"] == \
        sum(not o["valid"] for o in oracle.values())


def test_compressed_segments_byte_parity(tmp_path):
    """The codec round-trips bit-for-bit: a compressed archive's pushdown
    query equals the UNCOMPRESSED archive's frozen full-scan oracle
    (query_unpruned, untouched) field by field; compressed files hold
    packed members, cost less on disk, and decode into the cache at
    resident size."""
    from sitewhere_tpu.utils.archive import EventArchive

    eng = Engine(EngineConfig(**CFG))
    ids = [eng.register_device(f"an-{d}") for d in range(6)]
    oid = eng.register_device("tz-0", tenant="t2")
    tid = eng.tenants.lookup("default")
    rows, _ = _fleet_rows(tid, eng.tenants.lookup("t2"), ids, oid)
    archs = {}
    for compress in (False, True):
        a = EventArchive(tmp_path / ("bc" if compress else "bu"),
                         segment_rows=16, compress=compress)
        for k, lo in enumerate(range(0, len(rows), 16)):
            _append(a, 0, lo, rows[lo:lo + 16])
        archs[compress] = a
    total_u, rows_u = archs[False].query_unpruned(etype=0, tenant=tid,
                                                  limit=1000)
    total_c, rows_c = archs[True].query(etype=0, tenant=tid, limit=1000)
    assert total_c == total_u and len(rows_c) == len(rows_u) > 0
    for ru, rc in zip(rows_u, rows_c):
        assert ru.keys() == rc.keys()
        for k in ru:
            assert np.array_equal(np.asarray(ru[k]), np.asarray(rc[k])), k
    # on-disk members are packed and smaller; planner cost charges both
    for seg in archs[True].segments:
        with np.load(archs[True].dir / seg.path) as z:
            assert "valid__packed" in z.files and "valid" not in z.files
        assert 0 < seg.stats["enc_bytes"] < seg.stats["bytes"]
    # decoded columns land in the cache at RESIDENT (decoded) size
    arch = archs[True]
    seg = arch.segments[0]
    cols = arch._cols_or_drop(seg, ("valid", "values", "vmask"))
    decoded = sum(np.asarray(v).nbytes for v in cols.values())
    assert decoded > 0 and arch.cache.nbytes >= decoded


def test_engine_spool_job_rerun_suppresses_and_cancel_accounts(tmp_path):
    """End to end through the real ring->spool path (compressed): a
    re-run of the same job name emits nothing new, and a scope-limited
    run (max_batches) keeps the conservation equation exact."""
    eng = _engine(tmp_path, archive_compress=True)
    rng = np.random.default_rng(7)
    payloads = []
    for i in range(4 * CFG["store_capacity"]):
        payloads.append(_meas(eng, f"d-{i % 6}", 1000 + i,
                              {"c0": float(rng.standard_normal()),
                               "c1": float(rng.standard_normal())}))
    for lo in range(0, len(payloads), 16):
        eng.ingest_json_batch(payloads[lo:lo + 16])
    eng.flush()
    assert eng.archive.total_rows() > 0
    mgr = AnalyticsManager(eng)
    spec = AnalyticsJobSpec(window=W, batch_devices=M, min_fill=MIN_FILL,
                            threshold=-1e9, name="e2e")
    job = mgr.run_job(spec)
    assert job["state"] == "done" and job["devices"] == 6
    assert job["emitted"] == job["scored"] > 0
    eng.flush()
    q = eng.query_events(etype=EventType.ALERT, limit=200)
    assert q["total"] == job["emitted"]
    # recover sim: fresh manager on the same engine — interner resync
    # re-registers every shipped key, the re-run suppresses all of them
    mgr2 = AnalyticsManager(eng)
    job2 = mgr2.run_job(spec)
    assert job2["emitted"] == 0
    assert job2["suppressed"] == job["emitted"]
    # scope-limited run (max_batches): a completed partial job — only
    # the in-scope batch is planned, nothing lands in the cancelled sink
    job3 = mgr2.run_job(AnalyticsJobSpec(
        window=W, batch_devices=4, min_fill=MIN_FILL, threshold=-1e9,
        name="e2e-b", max_batches=1, emit=False))
    assert job3["state"] == "done"
    assert job3["planned"] == 4 and job3["cancelled"] == 0
    st = mgr2.ledger_stage()
    assert st["planned"] == st["scored"] + st["skipped_underfilled"] \
        + st["cancelled"]


def test_repeat_job_over_the_same_shapes_compiles_nothing(tmp_path):
    """A second scoring job with the same window, channels and batch
    width reuses the window-fill and scorer programs: no family
    compiles during it."""
    from sitewhere_tpu.utils.devicewatch import compile_totals

    eng = _engine(tmp_path)
    for lo in range(0, 4 * CFG["store_capacity"], 16):
        eng.ingest_json_batch([_meas(eng, f"rj-{i % 6}", 1000 + i,
                                     {"c0": float(i % 7), "c1": 0.5})
                               for i in range(lo, lo + 16)])
    eng.flush()
    mgr = AnalyticsManager(eng)

    def job(name):
        return mgr.run_job(AnalyticsJobSpec(
            window=W, batch_devices=M, min_fill=MIN_FILL, threshold=-1e9,
            name=name, emit=False))

    first = job("rj-warm")
    assert first["state"] == "done" and first["scored"] == 6
    before = compile_totals()
    again = job("rj-steady")
    assert (again["state"], again["scored"]) == ("done", 6)
    assert compile_totals() == before


def test_cancel_mid_run_lands_in_cancelled_sink(tmp_path):
    """A cancel landing between device batches routes every
    planned-but-unscored window into the cancelled sink — the equation
    stays exact for a job that died mid-pass (the killed-owner shape)."""
    eng = _engine(tmp_path, name="cx-arch")
    _prime_12_devices(eng)
    mgr = AnalyticsManager(eng)
    orig_emit = mgr._emit_batch

    def emit_then_cancel(job, *a, **kw):
        out = orig_emit(job, *a, **kw)
        job["cancel"].set()            # first harvest pulls the plug
        return out

    mgr._emit_batch = emit_then_cancel
    job = mgr.run_job(AnalyticsJobSpec(
        window=W, batch_devices=4, min_fill=MIN_FILL, threshold=-1e9,
        name="cx"))
    assert job["state"] == "cancelled"
    # 12 devices / m=4: batches 0+1 were in flight when the cancel hit,
    # batch 2 never ran — its 4 windows land in the cancelled sink
    assert job["planned"] == 12
    assert job["cancelled"] == 4
    assert job["scored"] + job["skipped_underfilled"] == 8
    st = mgr.ledger_stage()
    assert st["planned"] == st["scored"] + st["skipped_underfilled"] \
        + st["cancelled"]
    assert st["jobs_cancelled"] == 1


def test_conservation_equation_is_falsifiable(tmp_path):
    """The analytics-windows equation audits clean on a live engine and
    trips on a one-off perturbation of any term (the ISSUE 14
    falsifiability discipline)."""
    from sitewhere_tpu.utils.conservation import (build_ledger,
                                                  check_conservation)

    eng = _engine(tmp_path, name="fb-arch")
    _prime_12_devices(eng)
    mgr = AnalyticsManager(eng)
    mgr.run_job(AnalyticsJobSpec(window=W, batch_devices=M,
                                 min_fill=MIN_FILL, threshold=-1e9,
                                 name="fb", emit=False))
    eng.flush()
    base = build_ledger(eng)
    assert base["stages"]["analytics"]["planned"] == 12
    assert not check_conservation(base)

    def perturbed(key):
        led = json.loads(json.dumps(base))
        led["stages"]["analytics"][key] += 1
        return [v.equation for v in check_conservation(led)]

    for key in ("planned", "scored", "skipped_underfilled", "cancelled"):
        assert "analytics-windows" in perturbed(key), key


def _prime_12_devices(eng, n_each=10):
    rng = np.random.default_rng(3)
    for i in range(12 * n_each):
        eng.ingest_json_batch([_meas(
            eng, f"kr-{i % 12}", 1000 + i,
            {"c0": float(rng.standard_normal()),
             "c1": float(rng.standard_normal())})])
    eng.flush()


def test_kill_recover_emits_exactly_unshipped(tmp_path):
    """The chaos slice: the owner scores one device batch (8 of 12
    devices), ships those alerts, dies; snapshot + WAL replay rebuilds
    the engine over the SAME archive, a fresh manager re-runs the same
    job name — and emits exactly the 4 device windows the dead owner
    never shipped. Zero lost, zero duplicate, each alert in the store
    exactly once."""
    from sitewhere_tpu.utils.checkpoint import (replay_wal_into,
                                                restore_engine,
                                                save_engine)

    eng = _engine(tmp_path, name="kr-arch",
                  wal_dir=str(tmp_path / "wal"))
    save_engine(eng, tmp_path / "snap")
    _prime_12_devices(eng)
    mgr = AnalyticsManager(eng)
    # a BOUNDED range pins each device's window identity: the job's own
    # alert ingest advances the ring and spools more measurement rows,
    # so an open-ended re-run would legitimately see newer window ends
    spec = dict(window=W, batch_devices=M, min_fill=MIN_FILL,
                threshold=-1e9, until_ms=1103, name="kr")
    pre_sent = _spy_ingest(eng)
    job = mgr.run_job(AnalyticsJobSpec(**spec, max_batches=1))
    assert job["devices"] == 12 and job["planned"] == 8
    pre = {e["request"]["alternateId"] for e in pre_sent
           if e["type"] == "DeviceAlert"}
    assert len(pre) == job["emitted"] > 0
    eng.flush()
    eng.wal.sync()
    eng.wal.close()                    # "SIGKILL"
    del eng

    r2 = restore_engine(tmp_path / "snap")
    replay_wal_into(r2, 0, tmp_path / "wal")
    m2 = AnalyticsManager(r2)
    post_sent = _spy_ingest(r2)
    job2 = m2.run_job(AnalyticsJobSpec(**spec))
    post = {e["request"]["alternateId"] for e in post_sent
            if e["type"] == "DeviceAlert"}
    assert job2["state"] == "done" and job2["planned"] == 12
    assert post and not (pre & post), "duplicate score alert"
    assert job2["suppressed"] == len(pre)
    assert len(pre | post) == job2["scored"]
    r2.flush()
    q = r2.query_events(etype=EventType.ALERT, limit=200)
    assert q["total"] == len(pre | post)


def test_standby_promotion_emits_only_the_tail(tmp_path):
    """A standby receives the owner's full stream (score alerts
    included, replica-feed style) with emission OFF; promotion resyncs
    the shipped keys and the next run emits exactly the unshipped
    complement."""
    owner = _engine(tmp_path, name="own-arch")
    standby = _engine(tmp_path, name="sby-arch")
    standby.epoch = owner.epoch
    omgr = AnalyticsManager(owner)
    smgr = AnalyticsManager(standby, active=False)
    orig = owner.ingest_json_batch

    def forwarding(payloads, tenant="default", **kw):
        res = orig(payloads, tenant, **kw)
        standby.ingest_json_batch(list(payloads), tenant)
        return res

    owner.ingest_json_batch = forwarding
    _prime_12_devices(owner)
    standby.flush()
    spec = dict(window=W, batch_devices=M, min_fill=MIN_FILL,
                threshold=-1e9, until_ms=1103, name="sp")
    pre_sent = _spy_ingest(owner)
    job = omgr.run_job(AnalyticsJobSpec(**spec, max_batches=1))
    pre = {e["request"]["alternateId"] for e in pre_sent
           if e["type"] == "DeviceAlert"}
    assert len(pre) == job["emitted"] > 0
    standby.flush()
    # a passive (standby) run scores but ships nothing
    passive = smgr.run_job(AnalyticsJobSpec(
        window=W, batch_devices=M, min_fill=MIN_FILL, threshold=-1e9,
        name="sp-passive"))
    assert passive["scored"] > 0 and passive["emitted"] == 0
    # owner dies; promotion enables emission (the passive run's own
    # resync already registered the replayed keys — promote's
    # incremental rescan finds nothing new) and the next run emits only
    # the unshipped tail
    assert smgr.promote() == 0 and smgr.active
    post_sent = _spy_ingest(standby)
    job2 = smgr.run_job(AnalyticsJobSpec(**spec))
    post = {e["request"]["alternateId"] for e in post_sent
            if e["type"] == "DeviceAlert"}
    assert post and not (pre & post)
    assert job2["suppressed"] == len(pre)
    assert len(pre | post) == job2["scored"] == 12


# ----------------------------------------------------- rollup spill tier
def _rollup_engine(tmp_path, compress=True):
    from sitewhere_tpu.rules import RulesManager

    cfg = dict(device_capacity=256, token_capacity=512,
               assignment_capacity=512, store_capacity=4096,
               batch_capacity=32, channels=4, rule_groups=64,
               rollup_buckets=8, archive_dir=str(tmp_path / "ra"),
               archive_segment_rows=16, archive_compress=compress)
    eng = Engine(EngineConfig(**cfg))
    mgr = RulesManager(eng)
    mgr.load({"name": "t", "rules": [],
              "rollups": [{"name": "temp-1s", "channel": "temp",
                           "windowMs": 1000, "scope": "device"}]})
    base = int(eng.epoch.base_unix_s * 1000)
    payloads = [json.dumps({
        "deviceToken": f"r-{i % 4}", "type": "DeviceMeasurement",
        "request": {"name": "temp", "value": 10.0 + (i % 7) * 0.5,
                    "eventDate": base + i * 250}}).encode()
        for i in range(96)]
    for lo in range(0, 96, 32):
        eng.ingest_json_batch(payloads[lo:lo + 32])
        eng.flush()
    return eng, mgr


def test_rollup_spill_history_parity_and_idempotence(tmp_path):
    """Closed rollup windows spill through the archive (compressed
    segments under the rollups/ subdir): the spilled history reads back
    exactly the closed live windows, a respill is a no-op, and a FRESH
    manager over the same archive recovers the watermark from the
    segment zone maps — restart-safe, no double spill."""
    eng, mgr = _rollup_engine(tmp_path)
    live = mgr.read_rollup("temp-1s", limit=1000)
    live_map = {(b["group"], b["windowStartMs"]):
                (b["count"], b["sum"], b["min"], b["max"])
                for b in live["buckets"]}
    newest = max(ws for _, ws in live_map)
    out = mgr.spill_rollups(lag=1)
    assert out["spilled"] > 0 and out["rollups"] == 1
    assert mgr.spill_rollups(lag=1)["spilled"] == 0   # idempotent
    hist = mgr.read_rollup_history("temp-1s", limit=1000)
    hist_map = {(b["group"], b["windowStartMs"]):
                (b["count"], b["sum"], b["min"], b["max"])
                for b in hist["buckets"]}
    closed = {k: v for k, v in live_map.items() if k[1] <= newest - 1000}
    assert hist_map == closed and closed
    one = mgr.read_rollup_history("temp-1s", group="r-1", limit=1000)
    assert one["buckets"] and all(b["group"] == "r-1"
                                  for b in one["buckets"])
    # rollup segments live under rollups/ and inherit compression —
    # invisible to the MAIN archive's non-recursive recovery glob
    ra = mgr.rollup_archive()
    assert ra.dir.name == "rollups" and ra.total_rows() == out["spilled"]
    for seg in ra.segments:
        assert seg.stats["enc_bytes"] < seg.stats["bytes"]
    assert eng.archive.total_rows() >= 0
    assert not any("rollups" in s.path for s in eng.archive.segments)
    # restart: a fresh manager recovers the spill watermark from disk
    from sitewhere_tpu.rules import RulesManager

    m2 = RulesManager(eng)
    m2.load({"name": "t", "rules": [],
             "rollups": [{"name": "temp-1s", "channel": "temp",
                          "windowMs": 1000, "scope": "device"}]})
    assert m2.spill_rollups(lag=1)["spilled"] == 0


# ------------------------------------------------------ loadgen markers
def test_loadgen_analytics_markers_deterministic_and_resolved(tmp_path):
    from sitewhere_tpu.loadgen import (OpenLoopSpec, TenantLoad,
                                       build_open_loop_schedule,
                                       run_open_loop,
                                       schedule_fingerprint)

    tl = TenantLoad(tenant="default", rate_eps=400, n_devices=4,
                    analytics_every=4)
    spec = OpenLoopSpec(duration_s=0.4, tenants=(tl,), seed=7,
                        frame_size=16)
    s1, s2 = (build_open_loop_schedule(spec) for _ in range(2))
    assert schedule_fingerprint(s1) == schedule_fingerprint(s2)
    marks = [op for op in s1 if op.kind == "analytics"]
    assert marks and all(op.analytics["emit"] is False for op in marks)
    # knob off -> no markers (pre-knob schedules replay unchanged)
    s0 = build_open_loop_schedule(OpenLoopSpec(
        duration_s=0.4, tenants=(TenantLoad(tenant="default",
                                            rate_eps=400, n_devices=4),),
        seed=7, frame_size=16))
    assert all(op.kind != "analytics" for op in s0)
    # the driver resolves markers against engine.analytics_jobs; a plain
    # engine (no archive) skips them silently
    eng = _engine(tmp_path)
    AnalyticsManager(eng)
    res = run_open_loop(eng, s1, time_scale=0.01)
    assert res.scoring_jobs == len(marks)
    assert res.scoring_p50_ms is not None
    assert res.to_dict()["scoring_p99_ms"] is not None
    plain = Engine(EngineConfig(**CFG))
    res0 = run_open_loop(plain, s1, time_scale=0.01)
    assert res0.scoring_jobs == 0 and res0.scoring_p50_ms is None


def test_manager_status_and_cancel_surface(tmp_path):
    eng, rows, counts, tid = _mk_handmade(tmp_path, False)
    mgr = AnalyticsManager(eng)
    job = mgr.run_job(AnalyticsJobSpec(window=W, batch_devices=M,
                                       min_fill=MIN_FILL, emit=False,
                                       name="st"))
    st = mgr.status()
    assert st["active"] and st["jobs_started"] == 1
    row = mgr.status(job["id"])
    assert row["state"] == "done" and row["spec"]["name"] == "st"
    assert not mgr.cancel(job["id"])          # finished: not cancellable
    with pytest.raises(KeyError):
        mgr.status("aj-404")
    # unknown tenant -> empty done job, nothing planned
    empty = mgr.run_job(AnalyticsJobSpec(tenant="ghost", window=W,
                                         batch_devices=M, name="g"))
    assert empty["state"] == "done" and empty["devices"] == 0
