"""Tests for telemetry windows + anomaly models (the tpu-analytics service)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sitewhere_tpu.models.anomaly import (
    AnomalyConfig,
    AnomalyModel,
    make_train_step,
    param_shardings,
)
from sitewhere_tpu.models.windows import (
    TelemetryWindows,
    append_measurements,
    snapshot_windows,
)

CFG = AnomalyConfig(sensors=8, window=16, hidden=128, lstm_hidden=128, latent=16)


def test_window_ring_append_and_snapshot(rng):
    m, w, c = 4, 8, 3
    wins = TelemetryWindows.zeros(m, w, c)
    # two batches: device 1 gets 5 then 6 rows -> ring wraps, order preserved
    vals1 = rng.random((5, c)).astype(np.float32)
    vals2 = rng.random((6, c)).astype(np.float32)

    def push(wins, vals, ts0):
        b = vals.shape[0]
        return append_measurements(
            wins,
            dev=jnp.full((b,), 1, jnp.int32),
            found=jnp.ones((b,), bool),
            etype=jnp.zeros((b,), jnp.int32),
            ts_ms=jnp.arange(ts0, ts0 + b, dtype=jnp.int32),
            seq=jnp.arange(b, dtype=jnp.int32),
            values=jnp.asarray(vals),
        )

    wins = push(wins, vals1, 0)
    wins = push(wins, vals2, 100)
    assert int(wins.filled[1]) == 11
    snap = np.asarray(snapshot_windows(wins))[1]  # [W, C] oldest..newest
    # last 8 of the 11 appended rows, in order
    expect = np.concatenate([vals1, vals2])[-w:]
    np.testing.assert_allclose(snap, expect, rtol=1e-6)


def test_window_interleaved_devices(rng):
    m, w, c = 3, 4, 2
    wins = TelemetryWindows.zeros(m, w, c)
    devs = np.array([0, 1, 0, 2, 1, 0], np.int32)
    vals = rng.random((6, c)).astype(np.float32)
    wins = append_measurements(
        wins,
        dev=jnp.asarray(devs),
        found=jnp.ones(6, bool),
        etype=jnp.zeros(6, jnp.int32),
        ts_ms=jnp.arange(6, dtype=jnp.int32),
        seq=jnp.arange(6, dtype=jnp.int32),
        values=jnp.asarray(vals),
    )
    for d in range(3):
        mine = vals[devs == d]
        assert int(wins.filled[d]) == len(mine)
        got = np.asarray(wins.data[d, : len(mine)])
        np.testing.assert_allclose(got, mine, rtol=1e-6)


def test_anomaly_model_forward_and_train(rng):
    model = AnomalyModel(CFG)
    x = jnp.asarray(rng.random((4, CFG.window, CFG.sensors)), jnp.float32)
    params = model.init(jax.random.key(0), x)
    scores = model.apply(params, x)
    assert scores.shape == (4,)
    assert np.all(np.isfinite(np.asarray(scores)))

    tx = optax.adamw(1e-3)
    step = jax.jit(make_train_step(model, tx))
    opt_state = tx.init(params)
    l0 = None
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, x)
        l0 = l0 if l0 is not None else float(loss)
    assert float(loss) < l0  # training reduces reconstruction+forecast error


def test_anomaly_model_dp_tp_sharded(rng):
    """Train step under a real (dp, tp) mesh: batch on dp, hidden on tp."""
    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    model = AnomalyModel(CFG)
    x = jnp.asarray(rng.random((8, CFG.window, CFG.sensors)), jnp.float32)
    params = model.init(jax.random.key(0), x)
    params = jax.device_put(params, param_shardings(params, mesh, "tp"))
    x = jax.device_put(x, NamedSharding(mesh, P("dp")))
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    step = jax.jit(make_train_step(model, tx))
    params, opt_state, loss = step(params, opt_state, x)
    assert np.isfinite(float(loss))
    # params keep their tp sharding after the update
    flat = jax.tree_util.tree_leaves(params)
    assert any(
        "tp" in str(getattr(leaf, "sharding", "")) for leaf in flat
    )


def test_window_features_pallas_matches_reference(rng):
    from sitewhere_tpu.ops.window_features import (
        normalize_windows,
        window_features,
        window_features_reference,
    )

    x = jnp.asarray(rng.standard_normal((100, 16, 8)), jnp.float32)
    ref = window_features_reference(x)
    pal = window_features(x, tile_m=32, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    normed = normalize_windows(x, ref)
    np.testing.assert_allclose(np.asarray(normed.mean(axis=1)), 0.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(normed.std(axis=1)), 1.0, atol=1e-2)


def test_analytics_service_end_to_end(rng):
    """Windows fill from live events through the pipeline; the analytics
    service trains, scores, and injects anomaly alerts back as events."""
    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.ingest.requests import DecodedRequest, RequestType
    from sitewhere_tpu.models.anomaly import AnomalyConfig
    from sitewhere_tpu.models.service import AnalyticsService

    W = 8
    engine = Engine(EngineConfig(
        device_capacity=32, token_capacity=64, assignment_capacity=64,
        store_capacity=4096, batch_capacity=32, channels=4,
        analytics_devices=16, analytics_window=W,
    ))
    svc = AnalyticsService(
        engine,
        AnomalyConfig(sensors=4, window=W, hidden=64, lstm_hidden=64, latent=8),
        threshold=2.5, min_fill=W,
    )
    # 8 devices emit W sinusoid-ish samples; device an-7 is wildly different
    for t in range(W):
        for d in range(8):
            val = float(np.sin(t / 3) + 0.01 * d) if d != 7 else float(1e3 * (t + 1))
            engine.process(DecodedRequest(
                type=RequestType.DEVICE_MEASUREMENT, device_token=f"an-{d}",
                measurements={"x": val},
            ))
    engine.flush()
    wins = engine.state.windows
    assert int(wins.filled[0]) == W  # windows actually filled by the pipeline
    loss = svc.train_on_live(batch_size=8, steps=3)
    assert np.isfinite(loss)
    result = svc.score_all()
    assert result["valid"][:8].all()
    assert not result["valid"][8:].any()
    n = svc.emit_anomaly_alerts(result)
    if n:  # alerts landed in device state as system alerts
        st = engine.get_device_state(result["anomalous_tokens"][0])
        assert st["recent_alerts"][0]["type"] == "analytics.anomaly"


def test_analytics_checkpoint_roundtrip(tmp_path):
    """Trained model params + score stats survive save/restore (orbax)."""
    import numpy as np

    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.ingest.requests import DecodedRequest, RequestType
    from sitewhere_tpu.models.service import AnalyticsService

    eng = Engine(EngineConfig(
        device_capacity=32, token_capacity=64, assignment_capacity=64,
        store_capacity=1024, batch_capacity=16, channels=4,
        analytics_devices=8, analytics_window=8))
    rng = np.random.default_rng(0)
    for step in range(10):
        for d in range(4):
            eng.process(DecodedRequest(
                type=RequestType.DEVICE_MEASUREMENT, device_token=f"an-{d}",
                measurements={"v": float(rng.standard_normal())},
                event_ts_ms=None))
        eng.flush()
    from sitewhere_tpu.models.anomaly import AnomalyConfig

    # tiny model: the roundtrip property is size-independent and the
    # default 256-hidden LSTM costs ~25s of CPU-mesh compile alone
    tiny = AnomalyConfig(sensors=4, window=8, hidden=32, lstm_hidden=32,
                         latent=8)
    svc = AnalyticsService(eng, cfg=tiny, min_fill=8, learning_rate=1e-3)
    loss = svc.train_on_live(batch_size=4, steps=2)
    assert loss == loss  # trained (not NaN)
    before = svc.score_all()

    svc.save_model(tmp_path / "ckpt")
    svc2 = AnalyticsService(eng, cfg=tiny, min_fill=8)
    svc2.restore_model(tmp_path / "ckpt")
    after = svc2.score_all()
    np.testing.assert_allclose(np.asarray(after["scores"]),
                               np.asarray(before["scores"]), rtol=1e-5)
    assert svc2.threshold == svc.threshold


def test_analytics_rest_surface():
    """Scores/train/detect endpoints over a live instance."""
    import asyncio
    import base64

    import numpy as np

    from sitewhere_tpu.engine import EngineConfig
    from sitewhere_tpu.ingest.requests import DecodedRequest, RequestType
    from sitewhere_tpu.instance.instance import InstanceConfig, SiteWhereTpuInstance
    from sitewhere_tpu.web.rest import start_server

    async def go():
        import aiohttp

        inst = SiteWhereTpuInstance(InstanceConfig(engine=EngineConfig(
            device_capacity=32, token_capacity=64, assignment_capacity=64,
            store_capacity=1024, batch_capacity=16, channels=4,
            analytics_devices=8, analytics_window=16)))
        assert inst.analytics is not None
        rng = np.random.default_rng(0)
        for step in range(16):
            for d in range(3):
                inst.engine.process(DecodedRequest(
                    type=RequestType.DEVICE_MEASUREMENT,
                    device_token=f"ar-{d}",
                    measurements={"v": float(rng.standard_normal())}))
            inst.engine.flush()
        server = await start_server(inst)
        base = f"http://127.0.0.1:{server.port}"
        try:
            async with aiohttp.ClientSession() as s:
                basic = base64.b64encode(b"admin:password").decode()
                async with s.get(f"{base}/api/authapi/jwt",
                                 headers={"Authorization": f"Basic {basic}"}) as r:
                    jwt = (await r.json())["token"]
                h = {"Authorization": f"Bearer {jwt}"}
                async with s.post(f"{base}/api/analytics/train",
                                  json={"batchSize": 4, "steps": 1},
                                  headers=h) as r:
                    assert r.status == 200
                    assert (await r.json())["loss"] is not None
                async with s.get(f"{base}/api/analytics/scores", headers=h) as r:
                    body = await r.json()
                    assert body["numResults"] == 3
                async with s.post(f"{base}/api/analytics/detect", headers=h) as r:
                    assert r.status == 200
        finally:
            await server.cleanup()

    asyncio.new_event_loop().run_until_complete(go())
