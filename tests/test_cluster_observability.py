"""Cluster-scale observability (ISSUE 7): the federated metrics/SLO
plane and per-peer replication staleness.

Covers the acceptance surface end to end: ``ClusterEngine.cluster_metrics``
returns ONE lint-clean rank-labeled exposition covering every live rank
(HELP/TYPE deduped), the ``GET /api/instance/cluster/metrics`` REST
endpoint serves it, SLO histogram exemplars resolve back through
``/api/instance/trace/<id>``, and a follower's staleness watermark is
visible per LEADER both on the Prometheus plane
(``swtpu_replication_stale_ms{leader=...}``) and in the
``cluster_status`` health block.

Topology note: both ranks live in one process here, so they share the
process-global metrics REGISTRY — each rank's exposition text is
captured by that rank's own export call, which is exactly the per-rank
snapshot a real (per-process) deployment federates.
"""

import json
import time

import pytest

from sitewhere_tpu.parallel.cluster import ClusterEngine
from sitewhere_tpu.parallel.replication import (ReplicaApplier, ReplicaFeed,
                                                register_replication_rpc)
from tests.test_cluster import (_close, _free_ports, _mk_cluster, meas,
                                tokens_owned_by)
from tests.test_metrics_exposition import lint_prometheus


def _ingest_both_ranks(c0, n=8, prefix="fm", tenant="default"):
    toks = tokens_owned_by(0, n // 2, prefix=prefix) + \
        tokens_owned_by(1, n // 2, prefix=prefix)
    c0.ingest_json_batch([meas(t, "t", float(i), 50 + i)
                          for i, t in enumerate(toks)], tenant)
    c0.flush()
    return toks


def test_cluster_metrics_is_one_lint_clean_rank_labeled_exposition(tmp_path):
    clusters, host, _ = _mk_cluster(tmp_path)
    c0, _c1 = clusters
    try:
        _ingest_both_ranks(c0)
        text = c0.cluster_metrics()
        lint_prometheus(text)
        # every live rank present, under a rank label
        assert 'rank="0"' in text and 'rank="1"' in text
        assert 'swtpu_cluster_rank_up{rank="0"} 1' in text
        assert 'swtpu_cluster_rank_up{rank="1"} 1' in text
        # HELP/TYPE deduped across ranks even though both expose the
        # same families
        assert text.count("# HELP swtpu_engine_persisted") == 1
        assert text.count("# TYPE swtpu_ingest_e2e_seconds histogram") == 1
        # the per-tenant SLO histogram harvested from flight records
        assert 'swtpu_ingest_e2e_seconds_bucket{' in text
        # device plane (ISSUE 11): every rank's scrape carries the XLA
        # watchdog counters and the memory-ledger gauges — the federated
        # payload is the single pane the ROADMAP-2 sharded-store work
        # reads "does tenants x devices still fit one chip's HBM" from
        for rank in ("0", "1"):
            assert (f'swtpu_xla_compiles_total{{rank="{rank}",'
                    f'family="sharded.step"}}') in text
        import re as _re

        for rank in ("0", "1"):
            assert _re.search(
                rf'swtpu_device_mem_bytes\{{rank="{rank}",'
                r'component="ring_store",engine="e\d+"\}', text), (
                f"rank {rank} exports no memory ledger")
    finally:
        _close(clusters, host)


def test_cluster_metrics_exemplar_links_to_a_resolvable_trace(tmp_path):
    """A slowest-decile SLO observation carries a trace-id exemplar, and
    that id resolves through the cluster trace fan-out — the p99-spike →
    flight-record drill-down path."""
    import re

    clusters, host, _ = _mk_cluster(tmp_path)
    c0, _c1 = clusters
    try:
        # a FRESH tenant: the process-global registry accumulates SLO
        # series (and exemplars) across tests in this process, and an
        # old exemplar's records live in recorders long since closed
        _ingest_both_ranks(c0, prefix="ex", tenant="ex-tenant")
        text = c0.cluster_metrics()
        m = re.search(r'swtpu_ingest_e2e_seconds_bucket\{[^{}]*'
                      r'tenant="ex-tenant"[^{}]*\} \d+ '
                      r'# \{trace_id="([^"]+)"\}', text)
        assert m, "no exemplar on the SLO histogram buckets"
        trace = c0.get_trace(m.group(1))
        assert trace["records"], "exemplar trace id did not resolve"
    finally:
        _close(clusters, host)


def test_cluster_steady_traffic_compiles_nothing(tmp_path):
    """Once local and forwarded ingest and the per-token, fan-out and
    time-window queries have each run on the two ranks, the same mix
    again compiles no program on either rank: a compile in steady
    traffic is a latency cliff the SLO histograms would record as one
    slow frame."""
    from sitewhere_tpu.utils.devicewatch import WATCH

    clusters, host, _ = _mk_cluster(tmp_path)
    c0, _c1 = clusters

    def mix(tag):
        toks = _ingest_both_ranks(c0, prefix=f"st{tag}")
        for tok in (toks[0], toks[-1]):        # owned by rank 0, rank 1
            assert c0.query_events(device_token=tok, limit=20)["total"]
        c0.query_events(limit=20)
        c0.query_events(since_ms=0, limit=20)

    try:
        mix(0)
        before, excess = WATCH.compile_totals(), WATCH.excess_total()
        mix(1)
        mix(2)
        assert WATCH.compile_totals() == before
        assert WATCH.excess_total() == excess
    finally:
        _close(clusters, host)


def test_slo_harvest_consumes_each_record_once(tmp_path):
    """Two consecutive scrapes must not double-count: the flight-record
    harvest marks records consumed, so the histogram's event count equals
    ingested events no matter how many scrape surfaces race."""
    from sitewhere_tpu.utils.metrics import slo_metrics

    clusters, host, _ = _mk_cluster(tmp_path)
    c0, _c1 = clusters
    try:
        _ingest_both_ranks(c0, n=8, prefix="hv")
        hist = slo_metrics()["ingest_e2e"]
        before = hist.count_where(tenant="default")
        c0.cluster_metrics()
        mid = hist.count_where(tenant="default")
        c0.cluster_metrics()          # second scrape: nothing new
        after = hist.count_where(tenant="default")
        assert mid - before >= 8      # every ingested event observed once
        assert after == mid
    finally:
        _close(clusters, host)


def test_cluster_metrics_down_rank_degrades_not_fails(tmp_path):
    clusters, host, _ = _mk_cluster(tmp_path)
    # short timeout so the tolerant fan-out does not stall the test
    for c in clusters:
        c.cluster_config.connect_timeout_s = 1.0
    c0, _c1 = clusters
    try:
        _ingest_both_ranks(c0, prefix="dn")
        host.stop(host.servers[1])
        text = c0.cluster_metrics()
        lint_prometheus(text)
        assert 'swtpu_cluster_rank_up{rank="0"} 1' in text
        assert 'swtpu_cluster_rank_up{rank="1"} 0' in text
    finally:
        _close(clusters, host)


def test_rest_cluster_metrics_endpoint(tmp_path):
    """GET /api/instance/cluster/metrics serves the federated payload;
    on a SINGLE-NODE instance it degrades to the local registry under
    rank=\"0\" — the scrape contract is topology-independent."""
    import asyncio
    import base64

    from sitewhere_tpu.engine import EngineConfig
    from sitewhere_tpu.instance.instance import (InstanceConfig,
                                                 SiteWhereTpuInstance)
    from sitewhere_tpu.web.rest import start_server

    async def go():
        import aiohttp

        inst = SiteWhereTpuInstance(InstanceConfig(engine=EngineConfig(
            device_capacity=64, token_capacity=128, assignment_capacity=128,
            store_capacity=4096, batch_capacity=16, channels=4)))
        inst.engine.ingest_json_batch([json.dumps(
            {"deviceToken": f"rm-{i}", "type": "DeviceMeasurements",
             "request": {"measurements": {"t": float(i)}}}).encode()
            for i in range(6)])
        inst.engine.flush()
        server = await start_server(inst)
        base = f"http://127.0.0.1:{server.port}"
        try:
            async with aiohttp.ClientSession() as s:
                basic = base64.b64encode(b"admin:password").decode()
                async with s.get(
                    f"{base}/api/authapi/jwt",
                    headers={"Authorization": f"Basic {basic}"},
                ) as r:
                    jwt = (await r.json())["token"]
                H = {"Authorization": f"Bearer {jwt}"}
                async with s.get(
                    f"{base}/api/instance/cluster/metrics", headers=H,
                ) as r:
                    assert r.status == 200
                    assert r.content_type == "text/plain"
                    plain = await r.text()
                async with s.get(
                    f"{base}/api/instance/cluster/metrics",
                    headers={**H,
                             "Accept": "application/openmetrics-text"},
                ) as r:
                    assert r.status == 200
                    assert r.content_type == "application/openmetrics-text"
                    om = await r.text()
                return plain, om
        finally:
            await server.cleanup()

    plain, om = asyncio.new_event_loop().run_until_complete(go())
    # default: strict text-format 0.0.4 — no exemplar syntax at all
    lint_prometheus(plain)
    assert 'rank="0"' in plain
    assert "swtpu_ingest_e2e_seconds" in plain
    assert "# {" not in plain
    # the same-contract availability series exists on a single node too
    assert 'swtpu_cluster_rank_up{rank="0"} 1' in plain
    # negotiated OpenMetrics: exemplars allowed, mandatory EOF terminator
    assert om.endswith("# EOF\n")
    lint_prometheus(om.rsplit("# EOF\n", 1)[0])


def _mk_replicated_cluster(tmp_path):
    """Two ranks with RF=2 replication attached (feed + applier + the
    replication RPC surface), feeds running."""
    from sitewhere_tpu.parallel.cluster import (ClusterConfig,
                                                build_cluster_rpc)
    from tests.test_cluster import BASE_S, _ServerHost, _engine_cfg

    ports = _free_ports(2)
    peers = [f"127.0.0.1:{p}" for p in ports]
    host = _ServerHost()
    clusters, feeds = [], []
    for r in range(2):
        cc = ClusterConfig(rank=r, n_ranks=2, peers=peers,
                           secret="obs-secret", epoch_base_unix_s=BASE_S,
                           engine=_engine_cfg(tmp_path, r),
                           connect_timeout_s=10.0)
        c = ClusterEngine(cc)
        feed = ReplicaFeed(c, str(tmp_path / f"rep-r{r}"), rf=2,
                           heartbeat_s=0.2)
        applier = ReplicaApplier(c, rf=2, detect_s=5.0)
        c.attach_replication(feed, applier)
        srv = build_cluster_rpc(c.local, "obs-secret")
        register_replication_rpc(srv, applier)
        host.start(srv, ports[r])
        clusters.append(c)
        feeds.append(feed)
    for f in feeds:
        f.start()
    return clusters, feeds, host


def test_per_peer_stale_in_health_block_and_exposition(tmp_path):
    """The staleness watermark is per LEADER rank (labels, not one
    global gauge), surfaced in the cluster_status health block AND as
    swtpu_replication_stale_ms{leader=...} — a single lagging follower
    is visible before any failover read hits it."""
    from sitewhere_tpu.utils.metrics import MetricsRegistry
    from sitewhere_tpu.utils.metrics import export_engine_metrics

    clusters, feeds, host = _mk_replicated_cluster(tmp_path)
    c0, c1 = clusters
    try:
        toks = tokens_owned_by(0, 4, prefix="st")
        c0.ingest_json_batch([meas(t, "t", 1.0, 60 + i)
                              for i, t in enumerate(toks)])
        c0.flush()
        deadline = time.monotonic() + 20
        while not feeds[0].drained() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert feeds[0].drained()
        # rank 1 follows rank 0: its applier tracks leader 0 per-peer
        stale = c1.replica_applier.stale_by_leader()
        assert 0 in stale and stale[0] >= 0.0
        # cluster_status health block carries it, keyed by leader rank
        s = c1.cluster_status()
        assert s["health"]["peers"]                      # peer FSM states
        assert "0" in s["health"]["replicationStaleMs"]
        assert s["health"]["replicationStaleMs"]["0"] >= 0.0
        # and the Prometheus plane exports one labeled series per leader
        reg = MetricsRegistry()
        export_engine_metrics(c1.local, reg)
        text = reg.expose_text()
        lint_prometheus(text)
        assert 'swtpu_replication_stale_ms{leader="0"}' in text
    finally:
        for f in feeds:
            f.stop()
        _close(clusters, host)


def test_forward_hop_histogram_observes_forwards(tmp_path):
    """Every cross-rank forward lands in swtpu_forward_hop_seconds under
    its destination-rank label, and the forwarded-hop p99 comes straight
    off this series via Histogram.quantile."""
    from sitewhere_tpu.utils.metrics import cluster_metrics_instruments

    clusters, host, _ = _mk_cluster(tmp_path)
    c0, _c1 = clusters
    try:
        hop = cluster_metrics_instruments()["forward_hop"]
        before = hop.count(dst="1")
        remote = tokens_owned_by(1, 3, prefix="fh")
        c0.ingest_json_batch([meas(t, "t", 1.0, 70 + i)
                              for i, t in enumerate(remote)])
        c0.flush()
        assert hop.count(dst="1") > before
        assert hop.quantile(0.99, dst="1") > 0.0
    finally:
        _close(clusters, host)


@pytest.mark.slow
def test_open_loop_cluster_load_stress(tmp_path):
    """Heavy cluster-load leg in miniature (slow): open-loop mixed traffic over a
    replicated 2-rank cluster with a federated scrape mid-load, then
    no-loss + SLO-plane accounting at the end."""
    from sitewhere_tpu.loadgen import (OpenLoopSpec, TenantLoad,
                                       build_open_loop_schedule,
                                       run_open_loop)
    from sitewhere_tpu.utils.metrics import slo_metrics

    clusters, feeds, host = _mk_replicated_cluster(tmp_path)
    c0, _c1 = clusters
    try:
        # warm: compile both ranks before the measured run
        warm = tokens_owned_by(0, 4, prefix="wl") + \
            tokens_owned_by(1, 4, prefix="wl")
        c0.ingest_json_batch([meas(t, "t", 1.0, 10 + i)
                              for i, t in enumerate(warm)])
        c0.flush()
        spec = OpenLoopSpec(
            tenants=(TenantLoad("load-a", 2500.0, n_devices=32,
                                query_every=4, mutate_every=8),
                     TenantLoad("load-b", 1500.0, n_devices=32)),
            duration_s=2.5, frame_size=128, seed=21)
        sched = build_open_loop_schedule(spec)
        expected = sum(len(op.payloads) for op in sched
                       if op.kind == "ingest")
        res = run_open_loop(c0, sched, checkpoint_frames=4)
        assert res.events == expected
        # federated scrape under/after load covers both ranks and the
        # per-tenant SLO series exists for every tenant that ingested
        text = c0.cluster_metrics()
        lint_prometheus(text)
        assert 'rank="0"' in text and 'rank="1"' in text
        hist = slo_metrics()["ingest_e2e"]
        assert hist.count_where(tenant="load-a") == \
            res.per_tenant["load-a"]["events"]
        assert hist.count_where(tenant="load-b") == \
            res.per_tenant["load-b"]["events"]
        # no loss: the cluster-merged persisted counter accounts every
        # event (the RING query would undercount here by design — this
        # load wraps the small test store several times over)
        m = c0.metrics()
        assert m["persisted"] >= res.events
        # replication kept pace (feeds drain within the test budget)
        deadline = time.monotonic() + 30
        while (not all(f.drained() for f in feeds)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert all(f.drained() for f in feeds)
    finally:
        for f in feeds:
            f.stop()
        _close(clusters, host)
