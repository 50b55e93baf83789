#!/usr/bin/env python3
"""Chip smoke: the served ingest path, end to end, on a TPU.

One process, phases in order; any failed check exits non-zero.

  (a) ingest  -- ``Engine`` at one chip's share of a 10M-device fleet
      (~0.75 GiB of ``PipelineState``) takes >= 2^19 canonical
      DeviceMeasurement payloads over 2^17 auto-registering tokens
      through ``ingest_json_batch``; counts, device state and event
      queries are checked against a plain host-side count.
  (b) analytics -- ``AnalyticsService.score_all`` over the live windows
      (the ``window_features`` Pallas kernel), the kernel's features
      against the jnp reference, and the kernel at 100 channels.
  (c) rest -- ``SiteWhereTpuInstance`` + ``start_server``: a batch POST
      and a device-state read back over HTTP.
  (d) ``--chips 4`` runs only the SPMD phase: ``SpmdEngine`` over four
      chips against single-chip engines fed the same stream.

Earlier stdout lines report each phase. The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script fails before any phase runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# One chip's share of BASELINE config 5 (10M-device multi-tenant fleet).
PER_CHIP = dict(device_capacity=1 << 20, token_capacity=1 << 21,
                assignment_capacity=1 << 21, store_capacity=1 << 22,
                batch_capacity=16384)
ANALYTICS = dict(analytics_devices=4096, analytics_window=128)
N_EVENTS = 1 << 19
N_TOKENS = 1 << 17
N_SAMPLE = 256
# --chips 4: half the stream of (a), so four chips' minutes stay affordable;
# the state is still four chips' worth of PER_CHIP
N_EVENTS_SPMD = 1 << 18
N_TOKENS_SPMD = 1 << 16
N_SAMPLE_SPMD = 64
KERNEL_SHAPE = (4096, 128, 100)    # BASELINE config 4's 100-sensor windows
MEAS = "engine.temperature"


class SmokeFailure(Exception):
    pass


def log(*a) -> None:
    print(*a, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  check ok: {what}")


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.compile_s, self.hits, self.misses


def peak_bytes(devices) -> dict:
    return {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices}


def run_phase(name: str, meter: CompileMeter, fn, *args) -> object:
    import jax

    log(f"phase {name}: start")
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    log(f"phase {name}: ok wall_s={wall:.3f} backend_compile_s={c1 - c0:.3f} "
        f"cache_hits={h1 - h0} cache_misses={m1 - m0} "
        f"peak_bytes_in_use={peak_bytes(jax.local_devices())}")
    return out


# --------------------------------------------------------------- the stream
def make_stream(seed: int, n_events: int, n_tokens: int, with_ts: bool):
    """Seeded canonical DeviceMeasurement payloads; every token appears.
    Values are multiples of 1/64 in [20, 84): exact in float32.
    ``with_ts`` stamps unique event dates (1000 + i ms)."""
    import numpy as np

    from sitewhere_tpu.loadgen import generate_measurements_message

    rng = np.random.default_rng(seed)
    tok = rng.permutation(np.arange(n_events) % n_tokens)
    vals = 20.0 + rng.integers(0, 1 << 12, n_events) / 64.0
    names = [f"smoke-{seed}-{i:07d}" for i in range(n_tokens)]
    if with_ts:
        payloads = [json.dumps({
            "deviceToken": names[t], "type": "DeviceMeasurement",
            "request": {"name": MEAS, "value": float(v),
                        "eventDate": 1000 + i, "updateState": True}}).encode()
            for i, (t, v) in enumerate(zip(tok.tolist(), vals.tolist()))]
    else:
        payloads = [generate_measurements_message(names[t], i, value=float(v))
                    for i, (t, v) in enumerate(zip(tok.tolist(),
                                                   vals.tolist()))]
    return payloads, tok, vals, names


def expected_by_token(tok, vals) -> dict[int, list[float]]:
    out: dict[int, list[float]] = {}
    for t, v in zip(tok.tolist(), vals.tolist()):
        out.setdefault(t, []).append(v)
    return out


def stage_medians(eng) -> dict:
    """Median flight-recorder stage offsets (us from batch start) over the
    recent ingest batches: where a batch's time goes."""
    import statistics

    stages: dict[str, list[float]] = {}
    for rec in eng.recent_traces(1024):
        if rec.get("kind") == "ingest":
            for k, v in rec.get("stagesUs", {}).items():
                stages.setdefault(k, []).append(v)
    return {k: statistics.median(v) for k, v in stages.items()}


def feed(eng, payloads, batch: int) -> int:
    """Ingest in wire batches; returns decode failures."""
    failed = 0
    for lo in range(0, len(payloads), batch):
        failed += eng.ingest_json_batch(payloads[lo:lo + batch])["failed"]
    return failed


# ------------------------------------------------------------ (a) ingest
def phase_ingest(cfg_kw: dict, n_events: int, n_tokens: int, n_sample: int,
                 seed: int):
    import jax
    import numpy as np

    from sitewhere_tpu.engine import Engine, EngineConfig

    payloads, tok, vals, names = make_stream(seed, n_events, n_tokens,
                                             with_ts=False)
    exp = expected_by_token(tok, vals)
    eng = Engine(EngineConfig(**cfg_kw))
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.state))
    log(f"  PipelineState bytes={state_bytes} "
        f"native_decoder={eng._native_decoder is not None} "
        f"arena_pool={eng._arena_pool is not None}")
    check(eng._native_decoder is not None and eng._arena_pool is not None,
          "native decoder and staging arenas loaded")
    batch = cfg_kw["batch_capacity"]
    t0 = time.perf_counter()
    failed = feed(eng, payloads[:batch], batch)
    eng.flush()
    t1 = time.perf_counter()
    failed += feed(eng, payloads[batch:], batch)
    eng.flush()
    t2 = time.perf_counter()
    log(f"  first batch (incl. compile) wall_s={t1 - t0:.3f}; "
        f"remaining {n_events - batch} events wall_s={t2 - t1:.3f}")
    log(f"  median stage offsets us: {stage_medians(eng)}")

    m = eng.metrics()
    check(failed == 0, "no decode failures")
    check(m["persisted"] == n_events,
          f"persisted {m['persisted']} == sent {n_events}")
    check(m["registered"] == n_tokens,
          f"registered {m['registered']} == distinct tokens {n_tokens}")
    hc = eng.host_counters
    check(hc.get("arena_rows") == n_events
          and hc.get("staged_copy_rows", 0) == 0,
          f"host_counters arena_rows={hc.get('arena_rows')} "
          f"staged_copy_rows={hc.get('staged_copy_rows', 0)}")

    rng = np.random.default_rng(seed + 1)
    sample = rng.choice(n_tokens, size=min(n_sample, n_tokens),
                        replace=False).tolist()
    for t in sample:
        want = exp[t]
        st = eng.get_device_state(names[t])
        got = st["measurements"][MEAS]["value"]
        if got != np.float32(want[-1]):
            raise SmokeFailure(f"{names[t]}: last value {got} != {want[-1]}")
        page = eng.query_events(device_token=names[t], limit=64)
        evs = page["events"]
        if (page["total"] != len(want)
                or any(e["deviceToken"] != names[t] for e in evs)
                or sorted(e["measurements"][MEAS] for e in evs)
                != sorted(np.float32(want).tolist())):
            raise SmokeFailure(f"{names[t]}: query page {page} != {want}")
    check(True, f"device state and query pages of {len(sample)} sampled "
                f"tokens match the host count")
    return eng


# --------------------------------------------------------- (b) analytics
def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def phase_analytics(eng, kernel_shape: tuple, seed: int,
                    interpret: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sitewhere_tpu.models.service import AnalyticsService, _score_windows
    from sitewhere_tpu.models.windows import snapshot_windows
    from sitewhere_tpu.ops.window_features import (
        window_features,
        window_features_reference,
    )

    svc = AnalyticsService(eng)
    res = svc.score_all()
    m = eng.config.analytics_devices
    check(res["scores"].shape == (m,) and np.isfinite(res["scores"]).all(),
          f"score_all: {m} finite scores")

    wins = eng.state.windows
    data = snapshot_windows(wins)
    for name, x in (("live windows", data),
                    (f"random {kernel_shape}",
                     jax.random.normal(jax.random.key(seed), kernel_shape,
                                       jnp.float32))):
        got = np.asarray(window_features(x, interpret=interpret))
        ref = np.asarray(window_features_reference(x))
        err = float(np.max(np.abs(got - ref)))
        check(np.allclose(got, ref, rtol=1e-4, atol=1e-3),
              f"window_features vs reference on {name} {tuple(x.shape)}: "
              f"max abs err {err}")
        if not interpret:
            check(_has_kernel(window_features.lower(x).compile()),
                  f"Pallas kernel (tpu_custom_call) in the compiled "
                  f"window_features program for {tuple(x.shape)}")
    if not interpret:
        check(_has_kernel(_score_windows.lower(
            svc.model, svc.params, data, wins.filled,
            jnp.int32(svc.min_fill)).compile()),
            "Pallas kernel (tpu_custom_call) in the compiled score_all "
            "program")


# ---------------------------------------------------------------- (c) rest
def phase_rest(n_rows: int, seed: int):
    import asyncio
    import base64

    import aiohttp
    import numpy as np

    from sitewhere_tpu.instance.instance import (
        InstanceConfig,
        SiteWhereTpuInstance,
    )
    from sitewhere_tpu.web.rest import start_server

    rng = np.random.default_rng(seed + 2)
    toks = [f"rest-{seed}-{i}" for i in range(8)]
    rows = [{"deviceToken": toks[i % len(toks)], "type": "DeviceMeasurement",
             "request": {"name": MEAS,
                         "value": float(20 + rng.integers(0, 4096) / 64)}}
            for i in range(n_rows)]
    last = rows[-1]

    async def go():
        inst = SiteWhereTpuInstance(InstanceConfig())
        server = await start_server(inst, port=0)
        base = f"http://127.0.0.1:{server.port}"
        try:
            async with aiohttp.ClientSession() as s:
                basic = base64.b64encode(b"admin:password").decode()
                async with s.get(f"{base}/api/authapi/jwt", headers={
                        "Authorization": f"Basic {basic}"}) as r:
                    check(r.status == 200, "REST login")
                    hdr = {"Authorization":
                           f"Bearer {(await r.json())['token']}"}
                async with s.post(f"{base}/api/events/batch", json=rows,
                                  headers=hdr) as r:
                    body = await r.json()
                    check(r.status == 201,
                          f"POST /api/events/batch of {n_rows} -> "
                          f"{r.status} {body}")
                tok = last["deviceToken"]
                async with s.get(f"{base}/api/devices/{tok}/state",
                                 headers=hdr) as r:
                    st = await r.json()
                    check(r.status == 200, f"GET device state -> {r.status}")
                got = st["measurements"][MEAS]["value"]
                want = float(np.float32(last["request"]["value"]))
                check(got == want, f"REST device state {tok}: last value "
                                   f"{got} == {want}")
        finally:
            await server.cleanup()

    asyncio.run(go())


# ---------------------------------------------------------------- (d) spmd
def fixed_epoch(now_ms: int):
    """Deterministic received time, so the SPMD and single-chip engines
    stamp identical rows (tests/test_spmd.py's FixedEpoch)."""
    from sitewhere_tpu.core.events import EpochBase

    epoch = EpochBase(0.0)
    epoch.now_ms = lambda: now_ms
    return epoch


def _page(eng, **kw):
    out = eng.query_events(**kw)
    return out["total"], [{k: v for k, v in ev.items()
                           if k != "assignmentId"} for ev in out["events"]]


def phase_spmd(cfg_kw: dict, n_shards: int, n_events: int, n_tokens: int,
               n_sample: int, seed: int):
    import gc

    import jax
    import numpy as np

    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.parallel.placement import shard_for_token
    from sitewhere_tpu.parallel.sharded import SpmdEngine

    payloads, tok, vals, names = make_stream(seed, n_events, n_tokens,
                                             with_ts=True)
    now = 1000 + n_events + 1000
    batch = cfg_kw["batch_capacity"]

    spmd = SpmdEngine(EngineConfig(**cfg_kw), n_shards=n_shards)
    spmd.epoch = fixed_epoch(now)
    t0 = time.perf_counter()
    check(feed(spmd, payloads, batch) == 0, "SPMD: no decode failures")
    spmd.flush()
    log(f"  SPMD ingest of {n_events} events wall_s="
        f"{time.perf_counter() - t0:.3f}")
    check(spmd.metrics()["persisted"] == n_events,
          f"SPMD persisted == sent {n_events}")

    devs = set()
    for leaf in jax.tree_util.tree_leaves(spmd.state.store):
        shards = leaf.addressable_shards
        if (len({s.device for s in shards}) != n_shards
                or any(s.data.shape[0] != leaf.shape[0] // n_shards
                       for s in shards)):
            raise SmokeFailure(f"store leaf {leaf.shape} not split over "
                               f"{n_shards} devices: {leaf.sharding}")
        devs |= {s.device for s in shards}
    check(len(devs) == n_shards,
          f"SPMD store leaves split over {n_shards} distinct devices "
          f"{sorted(d.id for d in devs)}")

    # store byte-identity: each shard vs a single-chip engine fed its
    # substream (tests/test_spmd.py::test_store_byte_identical_...)
    shard_of = np.array([shard_for_token(n, n_shards) for n in names])
    spmd_store = jax.device_get(spmd.state.store)
    for s in range(n_shards):
        sub = [p for p, t in zip(payloads, tok.tolist()) if shard_of[t] == s]
        ref = Engine(EngineConfig(**cfg_kw))
        ref.epoch = fixed_epoch(now)
        feed(ref, sub, batch)
        ref.flush()
        ref_store = jax.device_get(ref.state.store)
        for a, b in zip(jax.tree_util.tree_leaves(ref_store),
                        jax.tree_util.tree_leaves(spmd_store)):
            if not np.array_equal(np.asarray(a), np.asarray(b)[s]):
                raise SmokeFailure(f"shard {s} store differs from its "
                                   f"substream engine")
        check(True, f"shard {s} store byte-identical to a single-chip "
                    f"engine fed its {len(sub)}-event substream")
        del ref, ref_store
        gc.collect()

    # query pages, device state and tenant metrics vs a single-chip engine
    # fed the whole stream (test_query_pages_match_single_chip,
    # test_device_state_and_tenant_metrics_match)
    ref = Engine(EngineConfig(**cfg_kw))
    ref.epoch = fixed_epoch(now)
    feed(ref, payloads, batch)
    ref.flush()
    rng = np.random.default_rng(seed + 3)
    sample = [names[t] for t in rng.choice(n_tokens, size=min(n_sample,
                                                              n_tokens),
                                           replace=False).tolist()]
    mid = 1000 + n_events // 2
    kws = [dict(limit=200), dict(limit=7), dict(limit=20, since_ms=mid),
           dict(device_token=sample[0], limit=20),
           dict(device_token=sample[1], since_ms=1000, until_ms=mid,
                limit=20)]
    for kw in kws:
        if _page(ref, **kw) != _page(spmd, **kw):
            raise SmokeFailure(f"query page differs for {kw}")
    check(True, f"{len(kws)} query pages equal the single-chip engine's")
    for t in sample:
        if ref.get_device_state(t) != spmd.get_device_state(t):
            raise SmokeFailure(f"device state differs for {t}")
    check(True, f"device state of {len(sample)} sampled tokens equal")
    check(ref.tenant_metrics() == spmd.tenant_metrics()
          and ref.tenant_pipeline_counters()
          == spmd.tenant_pipeline_counters(),
          "tenant metrics and pipeline counters equal")


# -------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the SPMD phase over four chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"no TPU: jax.devices()[0].platform == {dev.platform!r}")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}")
        return 2

    from sitewhere_tpu.utils.compile_cache import configure_compile_cache

    log(f"device kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={configure_compile_cache()}")
    meter = CompileMeter()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_phase("spmd", meter, phase_spmd, PER_CHIP, 4, N_EVENTS_SPMD,
                      N_TOKENS_SPMD, N_SAMPLE_SPMD, args.seed)
        else:
            eng = run_phase("ingest", meter, phase_ingest,
                            {**PER_CHIP, **ANALYTICS}, N_EVENTS, N_TOKENS,
                            N_SAMPLE, args.seed)
            run_phase("analytics", meter, phase_analytics, eng,
                      KERNEL_SHAPE, args.seed)
            del eng
            run_phase("rest", meter, phase_rest, 300, args.seed)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    c, h, m = meter.snapshot()
    log(f"total wall_s={time.perf_counter() - t0:.3f} "
        f"backend_compile_s={c:.3f} cache_hits={h} cache_misses={m}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
