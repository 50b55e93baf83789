"""The plain reference on hand-made cases, and the log reader on a log
the program wrote."""

import numpy as np

from benchmark import traffic_gen as tg
from benchmark import wal_reader
from benchmark.reference import Reference

CFG = {"event_mix": {"measurement": 1, "location": 0, "alert": 0},
       "measurement_names": ["a", "b"], "alert_types": ["x"]}
T0 = 1_760_000_000_000


def table(kinds, devs, ts):
    rng = np.random.default_rng(0)
    return tg.make_events(rng, np.array(devs), np.zeros(len(devs)),
                          T0 + np.array(ts, np.int64), CFG, ["d0", "d1"],
                          kinds=np.array(kinds, np.int8))


def ref(t, rows, ts=None, cap=8):
    rows = np.asarray(rows)
    ts = t.ts_abs[rows] if ts is None else T0 + np.asarray(ts)
    return Reference(t, rows, ts, ["d0", "d1"], ["a", "b"], ["x"], T0,
                     store_capacity=cap)


def test_newest_by_event_date_then_arrival():
    t = table([1, 1, 1, 1, 0], [0, 0, 0, 0, 0], [5, 9, 7, 9, 1])
    st = ref(t, range(5)).final_state(0)
    # locations newest first; the tie at 9 goes to the later arrival
    assert [x["ts_ms"] for x in st["recent_locations"]] == [9, 9, 7]
    assert st["recent_locations"][0]["latitude"] == float(
        np.float32(t.loc[3, 0]))
    assert st["event_counts"]["LOCATION"] == 4
    assert st["last_interaction_ms"] == 9


def test_passes_restamped_and_repeated():
    t = table([1, 0], [0, 0], [5, 1])
    # the pool sent 4 times: twice stamped 10 ms later, then repeated
    r = ref(t, [0, 1] * 4, [5, 1, 15, 11, 5, 1, 15, 11], cap=5)
    st = r.final_state(0)
    assert [x["ts_ms"] for x in st["recent_locations"]] == [15, 15, 5]
    assert st["event_counts"]["LOCATION"] == 4
    assert st["last_interaction_ms"] == 15
    # the ring keeps the newest 5 of 8 arrivals, newest eventDate first,
    # ties in ring order
    page = r.query(10, device=0)
    assert page["total"] == 5
    assert [e["eventDateMs"] for e in page["events"]] == [15, 11, 11, 5, 1]


def test_measurement_carries_one_name():
    t = table([0] * 50, [0] * 50, range(50))
    assert np.all(np.sum(~np.isnan(t.meas), axis=1) == 1)
    for p, row in zip(t.payloads, t.meas):
        c = int(np.nonzero(~np.isnan(row))[0][0])
        assert b'"name":"%s"' % CFG["measurement_names"][c].encode() in p


def test_stamp_keeps_length_and_moves_the_date():
    t = table([0, 1], [0, 1], [3, 4])
    out = tg.stamp(t.payloads, t.ts_abs + 123_456)
    for a, b, ts in zip(t.payloads, out, t.ts_abs):
        assert len(a) == len(b)
        assert b.endswith(b'"eventDate":%d}}' % (ts + 123_456))
        assert a[:-tg.TAIL] == b[:-tg.TAIL]
    assert tg.stamp(t.payloads, t.ts_abs) == t.payloads


def test_arrivals_rise_with_the_pass():
    from benchmark import harness

    cfg = dict(CFG, tenants=2, token_prefix="p", registered_devices=40)
    mix = {"frame_events": 8, "pool_events": 32, "stamps": 3}
    dep = harness.build_deployment(cfg, mix, 2**31 + 5)
    rows, ts = harness.arrivals(dep, 4 * 3 + 2)
    assert len(rows) == dep.n_onboard + 14 * 8
    assert np.all(np.diff(ts[:dep.n_onboard + 12 * 8]) > 0)
    # after three passes the stamps repeat
    assert np.array_equal(ts[-16:], ts[dep.n_onboard:dep.n_onboard + 16])
    # and each frame sent is the pool frame stamped for its pass
    for k in (0, 5, 9):
        s, f = divmod(k, 4)
        lo = dep.n_onboard + k * 8
        want = tg.stamp([dep.table.payloads[r] for r in rows[lo:lo + 8]],
                        ts[lo:lo + 8])
        assert dep.passes[s][f] == want


def test_wal_reader_reads_the_programs_log(tmp_path):
    from sitewhere_tpu.utils.ingestlog import IngestLog

    log = IngestLog(tmp_path, group_commit=True, segment_bytes=200)
    payloads = [b'{"n":%d}' % i for i in range(40)]
    for i in range(0, 40, 8):
        log.wait_durable(log.append_many(
            payloads[i:i + 8], tg.WAL_JSON_TAG + b"tenant-01\x00"))
    log.close()
    segs = wal_reader.segments(str(tmp_path))
    assert len(segs) > 1
    rec = wal_reader.read_records(segs)
    want = [tg.payload_crc("tenant-01", p) for p in payloads]
    assert rec["crc"].tolist() == want
    # a fresh segment after the last rotation holds only its header
    assert 0 <= wal_reader.total_bytes(str(tmp_path)) - rec["end"][-1] <= 6
    assert np.all(np.diff(rec["end"]) > 0)
