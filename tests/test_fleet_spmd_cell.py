"""The four-chip fleet deployment (``fleet_156k``) at test size on four
virtual CPU devices, through the benchmark's own harness and the plain
reference (``benchmark/reference.py``): the sharded engine that
``Engine(EngineConfig(shards=4))`` builds answers as the reference does,
a planted fault in one shard lane is caught, and one and four shards give
the same answers.

The test deployment keeps the cell's sizing rule: every ring holds every
arrival of the window, so the reference's one global ring agrees with the
per-shard rings. Equal eventDates across shards cannot arise: each pool
row has its own date, and a stamp that repeats repeats the same row, whose
device lives on one shard.
"""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import page_view

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "tiny4.tiny_backlog32"
LIKE = "fleet_156k.backlog32"   # the chip cell whose metrics this reports
SEED = 2**31 + 2606

MIX = {"kind": "backlog", "why": "test size", "frame_events": 256,
       "pool_events": 4096, "stamps": 16, "trace_s": 1,
       "wal_full_check_bytes": 268435456}


def tiny4_config(shards: int = 4) -> dict:
    """``fleet_156k.json`` at test size: 400 devices over ``shards``
    chips, a few frames a pass, rings that hold the whole window."""
    with open(os.path.join(BENCH, "configs", "fleet_156k.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny4", tenants=4, token_prefix="tiny4",
               registered_devices=400)
    per = 4 // shards     # the same capacity in all, per shard
    cfg["engine"].update(shards=shards, device_capacity=256 * per,
                         token_capacity=512 * per,
                         assignment_capacity=512 * per,
                         store_capacity=1 << 19, batch_capacity=256)
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-shaped directory: ``benchmark/`` with the test-size
    deployment and mix added as new files, and a BENCHMARK.json whose
    four-chip cell's metrics also name the test cell."""
    root = tmp_path_factory.mktemp("spmd_cell") / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(root / "benchmark" / "configs" / "tiny4.json", "w") as f:
        json.dump(tiny4_config(), f)
    with open(root / "benchmark" / "traffic" / "tiny_backlog32.json",
              "w") as f:
        json.dump(MIX, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny4", "source": "test",
                             "why": "test", "reduced": [],
                             "file": "benchmark/configs/tiny4.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny4",
                               "traffic": "tiny_backlog32", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root


def run(root, **kw):
    return harness.run_cell(str(root), CELL, SEED, 1.0, False,
                            require_chip=False, **kw)


def test_the_cell_builds_the_four_chip_engine():
    from sitewhere_tpu.parallel.sharded import SpmdEngine

    with open(os.path.join(BENCH, "configs", "fleet_156k.json")) as f:
        cfg = json.load(f)
    assert cfg["engine"]["shards"] == cfg["chips"] == 4
    eng = harness.make_engine(tiny4_config(), None)
    assert isinstance(eng, SpmdEngine)
    assert eng.n_shards == eng.config.shards == 4


@pytest.mark.parametrize("trace", [False, True])
def test_four_shard_cell_agrees_with_the_reference(root, trace):
    res = harness.run_cell(str(root), CELL, SEED, 1.0, trace,
                           require_chip=False)
    assert res["correct"], res["checks"]
    assert all(c["value"] == 0 for c in res["checks"].values())
    # sized as the chip cell is: no ring wraps
    assert 0 < res["attempted"] + 400 <= 1 << 19
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in harness.metrics_for(bench, section, CELL)}
    assert set(res["metrics"]) <= want
    if trace:
        # the program's spans and counts read on any backend; the device
        # readers need a chip's trace
        for name in ("route_ms", "lane_fill_pct", "dispatch_ms.fleet156k",
                     "step_wait_ms.fleet156k", "host_batch_ms.fleet156k"):
            assert res["metrics"][name]["value"] > 0, name
        assert 0 < res["metrics"]["lane_fill_pct"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {"ingest_eps.fleet", "setup_s"}


def _drop_lane(shard):
    """A fault: every dispatch loses one shard lane's rows."""
    def hook(eng):
        real = eng._dispatch_arena

        def dispatch():
            if eng._arena_fill is not None:
                eng._arena_fill.valid[shard] = False
            real()

        eng._dispatch_arena = dispatch
    return hook


def test_a_dropped_shard_lane_is_not_correct(root):
    res = run(root, engine_hook=_drop_lane(2))
    assert not res["correct"], res["checks"]
    assert res["checks"]["count_mismatch"]["value"] > 0


def _answers(cfg, dep, frames, tmp_path, epoch=None):
    """What the engine answers after onboarding and ``frames`` backlog
    frames, as the harness's check collects it."""
    eng = harness.make_engine(cfg, str(tmp_path))
    if epoch is not None:
        eng.epoch = epoch
    harness.setup(eng, dep)
    n = len(dep.frame_ten)
    for k in range(frames):
        s, f = divmod(k, n)
        eng.ingest_json_batch(dep.passes[s][f],
                              dep.tenants[int(dep.frame_ten[f])])
    eng.barrier()
    rows, _ = harness.arrivals(dep, frames)
    tail = sorted(set(dep.table.dev[rows[-harness.TAIL_DEVICES:]].tolist()))
    ans = harness.collect_answers(eng, dep, SEED, tail)
    eng.wal.close()
    return ans, eng.epoch


def test_one_and_four_shards_answer_alike(tmp_path):
    """Same seed, same frames (two passes over the pool): counts, device
    state, registrations and pages (less the engine-assigned ids) are
    equal."""
    dep = harness.build_deployment(tiny4_config(),
                                   dict(MIX, pool_events=1024), SEED)
    one, epoch = _answers(tiny4_config(1), dep, 8, tmp_path / "one")
    four, _ = _answers(tiny4_config(4), dep, 8, tmp_path / "four", epoch)
    assert one["persisted"] == four["persisted"] == 400 + 8 * 256
    assert one["registered"] == four["registered"] == 400
    assert one["plan"] == four["plan"]
    assert one["tenant_of"] == four["tenant_of"]
    assert one["states"] == four["states"]
    assert all(v is not None for v in four["states"].values())
    for d in one["dev_pages"]:
        assert page_view(one["dev_pages"][d]) == \
            page_view(four["dev_pages"][d])
    assert [page_view(p) for p in one["ten_pages"]] == \
        [page_view(p) for p in four["ten_pages"]]
    assert sum(p["total"] for p in four["ten_pages"]) == four["persisted"]
    assert np.all([len(p["events"]) == 100 for p in four["ten_pages"]])
