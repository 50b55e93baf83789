"""Sharded multi-core arena decode (ISSUE 4 tentpole, pillar 1).

One wire batch splits across N decode workers by payload BYTES, each
worker filling a disjoint row range of the same staging arena through
per-shard overlay interners; a serial merge interns first-seen strings
in shard order (== first-occurrence row order). The contract these
tests pin: arena contents — every column, including interner id
assignment — are BYTE-IDENTICAL to the single-threaded decode, for JSON
and binary wire batches, under an odd payload-size mix, with first-seen
tokens / measurement names / alert types / alternate ids appearing
mid-batch.
"""

import dataclasses
import json

import numpy as np
import pytest

from sitewhere_tpu.engine import Engine, EngineConfig
from sitewhere_tpu.ingest.arena import StagingArena
from sitewhere_tpu.ingest.decoders import encode_binary_request
from sitewhere_tpu.ingest.requests import DecodedRequest, RequestType
from sitewhere_tpu.loadgen import generate_measurements_message

SMALL = dict(device_capacity=1 << 10, token_capacity=1 << 11,
             assignment_capacity=1 << 11, store_capacity=1 << 12,
             batch_capacity=128)


def _require_shard(eng):
    if eng._arena_pool is None:
        pytest.skip("native arena path unavailable")
    if eng._sharder is None:
        pytest.skip("sharded decode entry points unavailable")


def _odd_mix_json(n=420):
    """Payload-size spread from tiny to multi-KB so byte-based cuts land
    at uneven payload indexes; new strings of every kind appear at odd
    positions (including inside what becomes a later shard)."""
    pay = []
    for i in range(n):
        if i % 11 == 0:
            # fat multi-measurement envelope with fresh names + alt ids
            pay.append(json.dumps({
                "deviceToken": f"fat-{i % 13}", "type": "DeviceMeasurements",
                "request": {
                    "measurements": {f"lane.{i % 29}": float(i),
                                     "engine.temperature": float(i % 80),
                                     f"pad.{'x' * (i % 200)}": 1.0},
                    "alternateId": f"alt-{i % 37}",
                    "eventDate": 1700000000000 + i}}).encode())
        elif i % 7 == 0:
            pay.append(json.dumps({
                "deviceToken": f"al-{i % 9}", "type": "DeviceAlert",
                "request": {"type": f"alert.kind{i % 17}",
                            "level": "Critical",
                            "alternateId": f"alt-{i % 23}",
                            "eventDate": None}}).encode())
        elif i % 5 == 0:
            pay.append(json.dumps({
                "deviceToken": f"lo-{i % 8}", "type": "DeviceLocation",
                "request": {"latitude": 33.75 + i * 0.01,
                            "longitude": -84.39,
                            "elevation": 300.0}}).encode())
        else:
            pay.append(generate_measurements_message(
                f"sd-{i % 40}", i, value=float(i % 90)))
    return pay


def _bin_mix(n=260):
    return [encode_binary_request(DecodedRequest(
        type=RequestType.DEVICE_MEASUREMENT,
        device_token=f"bn-{i % 31}",
        measurements={f"bin.lane{i % 19}": float(i % 100)},
        event_ts_ms=1700000000000 + i)) for i in range(n)]


def _engine(workers, min_shard=16, **kw):
    eng = Engine(EngineConfig(**{**SMALL, **kw}, ingest_workers=workers))
    if eng._arena_pool is None:
        pytest.skip("native arena path unavailable")
    if workers > 1:
        _require_shard(eng)
        eng._sharder.min_shard_payloads = min_shard
    eng.epoch.base_unix_s = 1700000000.0 - 1000.0
    eng.epoch.now_ms = lambda: 12345
    return eng


def _run(workers, min_shard=16):
    eng = _engine(workers, min_shard)
    eng.ingest_json_batch(_odd_mix_json())
    eng.ingest_binary_batch(_bin_mix())
    eng.flush()
    return eng


def _assert_engines_identical(a, b):
    import jax

    sa, sb = jax.device_get(a.state.store), jax.device_get(b.state.store)
    for f in dataclasses.fields(sa):
        assert np.array_equal(np.asarray(getattr(sa, f.name)),
                              np.asarray(getattr(sb, f.name))), \
            f"store.{f.name} diverges"
    da, db = (jax.device_get(a.state.device_state),
              jax.device_get(b.state.device_state))
    for f in dataclasses.fields(da):
        assert np.array_equal(np.asarray(getattr(da, f.name)),
                              np.asarray(getattr(db, f.name))), \
            f"device_state.{f.name} diverges"
    # interner ID ASSIGNMENT parity — the merge-order invariant
    assert list(a.tokens.items()) == list(b.tokens.items())
    assert list(a.channel_map.names.items()) == \
        list(b.channel_map.names.items())
    assert list(a.alert_types.items()) == list(b.alert_types.items())
    assert list(a.event_ids.items()) == list(b.event_ids.items())
    ma, mb = a.metrics(), b.metrics()
    for k in ("processed", "found", "missed", "registered", "persisted",
              "channel_collisions"):
        assert ma[k] == mb[k], k


def test_sharded_decode_byte_identical_two_workers():
    single = _run(1)
    sharded = _run(2)
    assert sharded._sharder.sharded_batches > 0, \
        "sharded path never engaged — the test proved nothing"
    _assert_engines_identical(single, sharded)


def test_sharded_decode_byte_identical_three_workers():
    """More shards than cores is legal (threads, not processes) and must
    still merge deterministically."""
    single = _run(1)
    sharded = _run(3)
    assert sharded._sharder.sharded_batches > 0
    _assert_engines_identical(single, sharded)


# --- one behaviour per case, each batch cut into 2-3 shards of the arena ---
def _env(token, typ, request):
    return json.dumps({"deviceToken": token, "type": typ,
                       "request": request}).encode()


def _meas(token, i, **extra):
    return _env(token, "DeviceMeasurement",
                {"name": "temp", "value": float(i % 90), **extra,
                 "eventDate": 1700000000000 + i})


def _case_register_mid_batch(make, tmp):
    """RegisterDevice envelopes inside sharded chunks re-route through
    process() during the arena commit; the measurements around them, the
    registered devices' own later rows included, stage unchanged."""
    eng = make()
    pay = [_meas(f"rg-{i % 12}", i) for i in range(300)]
    for pos, tok in ((40, "rg-admin-0"), (97, "rg-admin-1"),
                     (230, "rg-admin-2")):
        pay.insert(pos, _env(tok, "RegisterDevice",
                             {"deviceTypeToken": f"type-{tok[-1]}"}))
        pay.insert(pos + 9, _meas(tok, pos))
    s = eng.ingest_json_batch(pay)
    assert s["failed"] == 0 and s["staged"] == len(pay) - 3
    eng.flush()
    for n in range(3):
        info = eng.get_device(f"rg-admin-{n}")
        assert (info.device_type, info.auto_registered) == \
            (f"type-{n}", False)
    return eng


def _case_locations(make, tmp):
    """Full location rows and rows with null or missing coordinates."""
    eng = make()
    pay = []
    for i in range(300):
        req = {"latitude": 33.75 + i * 0.01, "longitude": -84.39,
               "elevation": 300.0 + i, "eventDate": 1700000000000 + i}
        if i % 4 == 1:
            req["latitude"] = None
        elif i % 4 == 2:
            req.update(longitude=None, elevation=None)
        elif i % 4 == 3:
            del req["elevation"]
        pay.append(_env(f"lo-{i % 24}", "DeviceLocation", req)
                   if i % 3 else _meas(f"lo-{i % 24}", i))
    eng.ingest_json_batch(pay)
    eng.flush()
    # lo-4 sends only full rows (i % 4 == 0); its newest is i = 292
    (newest, *_) = eng.get_device_state("lo-4")["recent_locations"]
    assert (newest["latitude"], newest["longitude"],
            newest["elevation"]) == (np.float32(33.75 + 2.92),
                                     np.float32(-84.39), 592.0)
    return eng


_LEVELS = ("Info", "Warning", "Error", "Critical", "Unknown", 2, None)


def _case_alert_levels(make, tmp):
    """Alert levels, by name, by number and absent, fold into lane 0 of
    the row; alert types are first seen in every shard."""
    eng = make()
    pay = []
    for i in range(300):
        req = {"type": f"kind-{i % 41}", "message": "m",
               "eventDate": 1700000000000 + i}
        if _LEVELS[i % 7] is not None:
            req["level"] = _LEVELS[i % 7]
        pay.append(_env(f"al-{i % 7}", "DeviceAlert", req)
                   if i % 2 else _meas(f"al-{i % 7}", i))
    eng.ingest_json_batch(pay)
    eng.flush()
    from sitewhere_tpu.core.types import EventType

    want = {"Info": 0, "Warning": 1, "Error": 2, "Critical": 3,
            "Unknown": 0, 2: 2, None: 0}
    for d in range(7):
        page = eng.query_events(device_token=f"al-{d}",
                                etype=EventType.ALERT, limit=300)
        assert page["total"] > 0
        assert {e["level"] for e in page["events"]} == \
            {want[_LEVELS[d]]}
    return eng


def _case_alt_ids_late(make, tmp):
    """Alternate ids first seen past the first shard (and repeated
    across shards) resolve to the same rows."""
    eng = make()
    pay = [_meas(f"ai-{i % 16}", i, **({"alternateId": f"alt-{i % 70}"}
                                        if i >= 64 else {}))
           for i in range(300)]
    eng.ingest_json_batch(pay)
    eng.flush()
    for k in (0, 33, 69):
        page = eng.query_events(alternate_id=f"alt-{k}", limit=16)
        assert page["total"] >= 1
        assert {e["deviceToken"] for e in page["events"]} == \
            {f"ai-{i % 16}" for i in range(64, 300) if i % 70 == k}
    return eng


def _case_wal_replay(make, tmp):
    """The WAL holds the raw batches a sharded decode staged: replaying
    it into a fresh engine through utils/checkpoint rebuilds the same
    state."""
    from sitewhere_tpu.utils.checkpoint import replay_wal_into

    wal = tmp / "wal"
    live = make(wal_dir=str(wal))
    live.ingest_json_batch(_odd_mix_json(300))
    live.ingest_binary_batch(_bin_mix(200))
    live.flush()
    live.wal.close()
    rep = make()
    replay_wal_into(rep, -1, wal)
    rep.flush()
    _assert_engines_identical(live, rep)
    return rep


def _case_strict_rollback(make, tmp):
    """A strict_channels engine accepts a batch within its lanes, then
    rejects a colliding batch whole and rolls back the names it
    interned."""
    from sitewhere_tpu.engine import ChannelCapacityError

    eng = make(channels=3, strict_channels=True)
    eng.ingest_json_batch([_env(f"sc-{i % 8}", "DeviceMeasurement",
                                {"measurements": {"a": float(i),
                                                  "b": float(i + 1)},
                                 "eventDate": 1700000000000 + i})
                           for i in range(200)])
    with pytest.raises(ChannelCapacityError):
        eng.ingest_json_batch([_env(f"sc-{i % 8}", "DeviceMeasurement",
                                    {"measurements": {f"n{i % 5}": 1.0},
                                     "eventDate": 1700000000000 + i})
                               for i in range(200)])
    eng.flush()
    assert len(eng.channel_map.names) == 2
    assert eng.metrics()["persisted"] == 200
    return eng


_CASES = {
    "register_mid_batch": (_case_register_mid_batch, True),
    "locations": (_case_locations, True),
    "alert_levels": (_case_alert_levels, True),
    "alt_ids_late": (_case_alt_ids_late, True),
    "wal_replay": (_case_wal_replay, True),
    # strict engines decode under the lock with the single scanner so a
    # rejected batch can roll back; ingest_workers must not change that
    "strict_rollback": (_case_strict_rollback, False),
}


@pytest.mark.parametrize("workers", [2, 3])
@pytest.mark.parametrize("case", list(_CASES))
def test_sharded_decode_byte_identical_cases(case, workers, tmp_path):
    fn, shards = _CASES[case]

    def run(w):
        def make(**kw):
            return _engine(w, **kw)
        return fn(make, tmp_path / f"w{w}")

    single = run(1)
    sharded = run(workers)
    if shards:
        assert sharded._sharder.sharded_batches > 0, \
            "sharded path never engaged — the case proved nothing"
    _assert_engines_identical(single, sharded)


def test_sharded_decoder_raw_arena_columns():
    """Column-level check without the engine: the shard merge writes the
    same bytes into every arena column the direct decoder writes —
    including the strided aux0/aux1 lanes."""
    from sitewhere_tpu.ingest.fast_decode import (NativeBatchDecoder,
                                                  native_available)
    from sitewhere_tpu.ingest.workers import ShardedArenaDecoder
    from sitewhere_tpu.native.binding import NativeInterner

    if not native_available():
        pytest.skip("native library unavailable")
    pay = _odd_mix_json(300)

    def decode(sharded):
        dec = NativeBatchDecoder(NativeInterner(1 << 11), 8)
        if not dec.has_arena:
            pytest.skip("arena entry points unavailable")
        arena = StagingArena(512, 8)
        if sharded:
            if not dec.has_shard:
                pytest.skip("shard entry points unavailable")
            sh = ShardedArenaDecoder(dec, 3)
            sh.min_shard_payloads = 16
            out = sh.decode_into(pay, arena, 0)
            assert sh.last_workers > 1
        else:
            out = dec.decode_into(pay, arena, 0)
        return out, arena, dec

    (ok1, coll1), a1, d1 = decode(False)
    (ok2, coll2), a2, d2 = decode(True)
    assert (ok1, coll1) == (ok2, coll2)
    n = len(pay)
    for col in ("rtype", "token_id", "ts64", "values", "vmask", "aux",
                "level"):
        assert np.array_equal(getattr(a1, col)[:n], getattr(a2, col)[:n]), \
            f"arena.{col} diverges"
    assert list(d1.tokens.items()) == list(d2.tokens.items())
    assert list(d1.names.items()) == list(d2.names.items())
    assert list(d1.event_ids.items()) == list(d2.event_ids.items())


def test_sharded_decoder_nonlist_falls_back():
    """A non-list payload iterable can't take the pylist shard path; the
    sharder must degrade to the single decoder, not fail."""
    from sitewhere_tpu.ingest.fast_decode import (NativeBatchDecoder,
                                                  native_available)
    from sitewhere_tpu.ingest.workers import ShardedArenaDecoder
    from sitewhere_tpu.native.binding import NativeInterner

    if not native_available():
        pytest.skip("native library unavailable")
    dec = NativeBatchDecoder(NativeInterner(1 << 11), 8)
    if not (dec.has_arena and dec.has_shard):
        pytest.skip("arena/shard entry points unavailable")
    sh = ShardedArenaDecoder(dec, 2)
    sh.min_shard_payloads = 4
    pay = tuple(generate_measurements_message(f"t-{i}", i)
                for i in range(64))
    arena = StagingArena(128, 8)
    n_ok, _ = sh.decode_into(pay, arena, 0)
    assert n_ok == 64
    assert sh.last_workers == 1


def test_sharded_small_batch_stays_single():
    """Below the per-shard minimum the batch must not pay thread+merge
    overhead."""
    eng = Engine(EngineConfig(**SMALL, ingest_workers=2))
    _require_shard(eng)
    eng.ingest_json_batch([generate_measurements_message(f"s-{i}", i)
                           for i in range(16)])
    eng.flush()
    assert eng._sharder.sharded_batches == 0
    assert eng.metrics()["persisted"] == 16


def test_set_active_workers_clamps():
    eng = Engine(EngineConfig(**SMALL, ingest_workers=2))
    _require_shard(eng)
    assert eng._sharder.set_active_workers(99) == 2
    assert eng._sharder.set_active_workers(0) == 1
    assert eng.set_ingest_tuning(ingest_workers=2)["ingest_workers"] == 2


@pytest.mark.slow
def test_sharded_decode_stress_random_batches():
    """Hundreds of random-size batches with churning new strings stay
    byte-identical between one and three workers."""
    rng = np.random.default_rng(7)
    sizes = [int(rng.integers(1, 400)) for _ in range(60)]

    def run(workers):
        eng = Engine(EngineConfig(**SMALL, ingest_workers=workers))
        if eng._arena_pool is None:
            pytest.skip("native arena path unavailable")
        if workers > 1:
            _require_shard(eng)
            eng._sharder.min_shard_payloads = 8
        eng.epoch.base_unix_s = 1700000000.0 - 1000.0
        eng.epoch.now_ms = lambda: 777
        base = 0
        for n in sizes:
            eng.ingest_json_batch([
                generate_measurements_message(
                    f"st-{(base + i) % 257}", base + i,
                    value=float(i % 90))
                for i in range(n)])
            base += n
        eng.flush()
        return eng

    _assert_engines_identical(run(1), run(3))
