"""Stage-time autotuner (ISSUE 4 tentpole, pillar 3).

The decision policy is pure (utils/autotune.decide) so it pins cheaply;
the engine-level tests check the control loop actually reads the flight
recorder, applies ONE knob per evaluation through set_ingest_tuning, and
exports its beliefs as gauges. scan_chunk changes rebuild the arena pool
— the rebuilt pipeline must keep producing identical results.
"""

import dataclasses

import numpy as np
import pytest

from sitewhere_tpu.engine import Engine, EngineConfig
from sitewhere_tpu.loadgen import generate_measurements_message
from sitewhere_tpu.utils.autotune import StageTimeAutotuner, decide

SMALL = dict(device_capacity=1 << 10, token_capacity=1 << 11,
             assignment_capacity=1 << 11, store_capacity=1 << 12,
             batch_capacity=128)

BOUNDS = {"max_workers": 4, "max_depth": 4, "max_chunk": 8}
CUR = {"ingest_workers": 1, "dispatch_depth": 1, "scan_chunk": 1}


# -------------------------------------------------------------- the policy
def test_decide_decode_bound_widens_fanout():
    out = decide({"decode_ms": 5.0, "wal_ms": 0.5, "dispatch_wait_ms": 0.2,
                  "device_ms": 1.0}, CUR, BOUNDS)
    assert out[0][0] == "ingest_workers" and out[0][1] == 2


def test_decide_device_bound_deepens_dispatch():
    out = decide({"decode_ms": 0.3, "wal_ms": 0.1, "dispatch_wait_ms": 0.2,
                  "device_ms": 5.0}, CUR, BOUNDS)
    assert ("dispatch_depth", 2) in [(k, v) for k, v, _ in out]


def test_decide_dispatch_overhead_raises_chunk():
    out = decide({"decode_ms": 0.5, "wal_ms": 0.1, "dispatch_wait_ms": 9.0,
                  "device_ms": 1.0}, CUR, BOUNDS)
    assert ("scan_chunk", 2) in [(k, v) for k, v, _ in out]


def test_decide_sheds_overprovisioned_knobs():
    out = decide({"decode_ms": 0.2, "wal_ms": 0.1, "dispatch_wait_ms": 0.1,
                  "device_ms": 5.0},
                 {"ingest_workers": 3, "dispatch_depth": 1, "scan_chunk": 4},
                 BOUNDS)
    knobs = {(k, v) for k, v, _ in out}
    assert ("ingest_workers", 2) in knobs
    assert ("scan_chunk", 2) in knobs


def test_decide_respects_bounds():
    out = decide({"decode_ms": 9.0, "wal_ms": 0.1, "dispatch_wait_ms": 9.0,
                  "device_ms": 0.1},
                 {"ingest_workers": 4, "dispatch_depth": 4, "scan_chunk": 8},
                 BOUNDS)
    for knob, value, _ in out:
        assert value <= BOUNDS[{"ingest_workers": "max_workers",
                                "dispatch_depth": "max_depth",
                                "scan_chunk": "max_chunk"}[knob]]


def test_decide_hysteresis_dead_zone():
    """Between the raise and shed thresholds nothing moves — a noisy
    window must not ping-pong a knob."""
    out = decide({"decode_ms": 1.0, "wal_ms": 0.2, "dispatch_wait_ms": 1.0,
                  "device_ms": 1.5},
                 {"ingest_workers": 2, "dispatch_depth": 2, "scan_chunk": 2},
                 BOUNDS)
    assert out == []


# ---------------------------------------------------------- engine control
def test_autotuner_adapts_from_flight_records():
    # one decode worker, whatever the host's core count: with auto
    # (os.cpu_count()) workers the tuner first sheds workers instead;
    # depth 1, below the default, so that deepening shows
    eng = Engine(EngineConfig(**SMALL, autotune=True, autotune_interval=4,
                              ingest_workers=1, dispatch_depth=1))
    assert eng._autotuner is not None
    for b in range(16):
        eng.ingest_json_batch([
            generate_measurements_message(f"at-{i % 20}", b * 128 + i)
            for i in range(128)])
    eng.flush()
    t = eng._autotuner
    assert t.evaluations >= 2
    # on the CPU backend the device step dominates by orders of
    # magnitude: the tuner must have deepened dispatch_depth
    assert eng.config.dispatch_depth > 1
    assert t.decisions, "no decision recorded"
    d = t.decisions[0]
    assert {"knob", "from", "to", "reason", "stats"} <= set(d)


def test_autotuner_gauges_exported():
    from sitewhere_tpu.utils.metrics import REGISTRY

    eng = Engine(EngineConfig(**SMALL, autotune=True, autotune_interval=2))
    for b in range(8):
        eng.ingest_json_batch([
            generate_measurements_message(f"ag-{i % 10}", b * 128 + i)
            for i in range(128)])
    eng.flush()
    text = REGISTRY.expose_text()
    assert "swtpu_autotune_dispatch_depth" in text
    assert "swtpu_autotune_ingest_workers" in text


def test_autotuner_needs_min_samples():
    eng = Engine(EngineConfig(**SMALL, autotune=True))
    t = eng._autotuner
    assert t.window_stats() is None       # empty recorder
    assert t.evaluate() is None           # and evaluate() tolerates it


def test_scan_chunk_retune_rebuilds_and_stays_correct():
    """set_ingest_tuning(scan_chunk=...) mid-run: the pool + scan step
    rebuild, in-flight work drains, and subsequent ingest persists
    exactly — results identical to a never-retuned engine."""
    def run(retune):
        eng = Engine(EngineConfig(**SMALL))
        if eng._arena_pool is None:
            pytest.skip("native arena path unavailable")
        eng.epoch.base_unix_s = 1700000000.0 - 1000.0
        eng.epoch.now_ms = lambda: 999
        pay = [generate_measurements_message(f"rc-{i % 30}", i)
               for i in range(600)]
        eng.ingest_json_batch(pay[:300])
        if retune:
            applied = eng.set_ingest_tuning(scan_chunk=2)
            assert applied["scan_chunk"] == 2
            assert eng._arena_step is not None
        eng.ingest_json_batch(pay[300:])
        eng.flush()
        if retune:   # and back down: rebuild to single-step shape
            eng.set_ingest_tuning(scan_chunk=1)
            assert eng._arena_step is None
        return eng

    import jax

    a, b = run(False), run(True)
    assert a.metrics()["persisted"] == b.metrics()["persisted"] == 600
    sa, sb = jax.device_get(a.state.store), jax.device_get(b.state.store)
    for f in dataclasses.fields(sa):
        assert np.array_equal(np.asarray(getattr(sa, f.name)),
                              np.asarray(getattr(sb, f.name))), \
            f"store.{f.name} diverges"


def test_autotuner_scan_chunk_gated_by_opt_in():
    eng = Engine(EngineConfig(**SMALL, autotune=True))
    t = eng._autotuner
    assert not t.adapt_scan_chunk
    eng2 = Engine(EngineConfig(**SMALL, autotune=True,
                               autotune_scan_chunk=True))
    assert eng2._autotuner.adapt_scan_chunk
