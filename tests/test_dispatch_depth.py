"""Double-buffered dispatch (``EngineConfig.dispatch_depth``).

At the default depth 2 a dispatch waits on the program before it, so the
host stages the next batch while the device runs this one; depth 1 waits
on the program just dispatched. The depth moves only where the host
waits: on every staging path (the arena, copy staging, the packed scan
of ``scan_chunk`` 2, the four-shard ``SpmdEngine``) each event persists
exactly once, its WAL record is written before the program that applies
it is dispatched, ``barrier`` leaves no program running, and the
engine's metrics, device states and event pages do not depend on the
depth.
"""

import functools
import json

import numpy as np
import pytest

from sitewhere_tpu.engine import Engine, EngineConfig
from sitewhere_tpu.loadgen import generate_measurements_message
from sitewhere_tpu.utils.ingestlog import IngestLog

CFG = dict(device_capacity=256, token_capacity=512, assignment_capacity=512,
           store_capacity=4096, batch_capacity=64, channels=4)

PATHS = {"arena": {}, "copy": {"ingest_arenas": -1},
         "scan2": {"scan_chunk": 2}, "spmd": {"shards": 4}}

TOKENS = [f"dd-{i}" for i in range(40)]
FRAME = 96          # 1.5 batches: arenas fill and dispatch mid-frame
N_FRAMES = 5


def _frames():
    """Measurements, alerts and locations over 40 devices, frame by frame
    (every fifth event an alert or a location)."""
    out = []
    for f in range(N_FRAMES):
        frame = []
        for i in range(FRAME):
            n = f * FRAME + i
            tok = TOKENS[n % len(TOKENS)]
            if n % 10 == 3:
                frame.append(json.dumps({
                    "deviceToken": tok, "type": "DeviceAlert",
                    "request": {"type": "engine.overheat",
                                "level": "Critical"}}).encode())
            elif n % 10 == 7:
                frame.append(json.dumps({
                    "deviceToken": tok, "type": "DeviceLocation",
                    "request": {"latitude": 33.0 + n / 100,
                                "longitude": -84.39,
                                "elevation": 300.0}}).encode())
            else:
                frame.append(generate_measurements_message(
                    tok, n, value=float(n % 70)))
        out.append(frame)
    return out


@functools.cache
def _run(path, depth, wal_dir):
    """Ingest the frames at ``depth``, checking the WAL before every
    dispatch; returns what the test compares."""
    eng = Engine(EngineConfig(**CFG, **PATHS[path], dispatch_depth=depth,
                              wal_dir=wal_dir))
    eng.epoch.base_unix_s = 1700000000.0 - 1000.0
    eng.epoch.now_ms = lambda: 12345           # one staging clock
    dispatched = []

    def checked(step):
        def run(state, batch):
            rows = int(np.sum(np.asarray(batch.valid)))
            logged = sum(1 for _ in IngestLog(wal_dir,
                                              readonly=True).replay())
            assert logged >= sum(dispatched) + rows, \
                "dispatch ran ahead of the WAL"
            dispatched.append(rows)
            return step(state, batch)
        return run

    for name in ("_step", "_arena_step", "_scan_step"):
        if getattr(eng, name, None) is not None:
            setattr(eng, name, checked(getattr(eng, name)))
    for fr in _frames():
        eng.ingest_json_batch(fr)
    eng.barrier()
    outstanding = sum(not o.n_persisted.is_ready()
                      for o in eng._pending_outs)
    eng.flush()
    got = {"path_ok": path != "arena" or eng._arena_pool is not None,
           "outstanding": outstanding,
           "dispatched": sum(dispatched),
           "logged": sum(1 for _ in IngestLog(wal_dir,
                                              readonly=True).replay()),
           "metrics": eng.metrics(),
           "states": [eng.get_device_state(t) for t in TOKENS],
           "page": eng.query_events(limit=1024),
           "device_pages": [eng.query_events(device_token=t, limit=16)
                            for t in TOKENS[:8]]}
    eng.wal.close()
    return got


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("depth", [1, 2])
def test_depth_changes_no_result(tmp_path_factory, path, depth):
    base = tmp_path_factory.getbasetemp() / "dispatch_depth"
    runs = {d: _run(path, d, str(base / f"{path}-{d}")) for d in (1, 2)}
    got = runs[depth]
    if not got["path_ok"]:
        pytest.skip("native arena path unavailable")
    total = N_FRAMES * FRAME
    # exactly once: one WAL record, one dispatched row, one store row
    assert got["logged"] == got["dispatched"] == total
    assert got["metrics"]["persisted"] == total
    assert got["page"]["total"] == total
    assert got["outstanding"] == 0
    # the one metric the depth sets: the arena pool holds depth + 2; the
    # group commit's fsync count follows its thread's timing, not the depth
    other = runs[3 - depth]
    pool = {"arena_pool_size": depth + 2} if path != "copy" else {}
    assert got["metrics"]["wal_fsyncs"] >= 1
    assert got["metrics"] == {**other["metrics"], **pool,
                              "wal_fsyncs": got["metrics"]["wal_fsyncs"]}
    assert got["states"] == other["states"]
    assert got["page"] == other["page"]
    assert got["device_pages"] == other["device_pages"]
