"""Prometheus exposition lint + Counter/Gauge API split (PR 3 satellites).

A promtool-style checker over the text format: HELP/TYPE ordering, family
contiguity, cumulative histogram buckets ending in ``+Inf`` with
count == +Inf, label escaping, and no duplicate series — run against both
a synthetic registry and the full engine export that
``/api/instance/metrics/prometheus`` serves.
"""

import re

import pytest

from sitewhere_tpu.utils.metrics import (Counter, Gauge, MetricsRegistry,
                                         export_engine_metrics)

_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?P<labels>\{[^{}]*\})? (?P<value>[^ ]+)'
    r'(?P<exemplar> # \{[^{}]*\} [^ ]+( [^ ]+)?)?$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_EXEMPLAR_RE = re.compile(r'^ # (?P<labels>\{[^{}]*\}) (?P<value>[^ ]+)'
                          r'( (?P<ts>[^ ]+))?$')


def _parse_labels(text):
    if not text:
        return ()
    body = text[1:-1]
    labels = _LABEL_RE.findall(body)
    # the full body must be consumed by well-formed pairs — an unescaped
    # quote or raw newline would leave residue
    rebuilt = ",".join(f'{k}="{v}"' for k, v in labels)
    assert rebuilt == body, f"malformed label set: {text!r}"
    return tuple(sorted(labels))


def lint_prometheus(text: str) -> None:
    """Promtool-style structural lint of one exposition payload."""
    families: dict[str, dict] = {}
    current = None
    seen_series: set = set()
    family_done: set = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in families, f"duplicate HELP for {name}"
            families[name] = {"help": True, "type": None, "samples": []}
            current = name
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            name, kind = parts[2], parts[3]
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), line
            assert current == name, f"TYPE {name} not preceded by its HELP"
            assert families[name]["type"] is None, f"duplicate TYPE {name}"
            families[name]["type"] = kind
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name = m.group("name")
        if m.group("exemplar"):
            # OpenMetrics exemplars are legal only on histogram bucket
            # lines; labels must be well-formed and the value must parse
            assert name.endswith("_bucket"), (
                f"exemplar on non-bucket line: {line!r}")
            em = _EXEMPLAR_RE.match(m.group("exemplar"))
            assert em, f"malformed exemplar: {line!r}"
            _parse_labels(em.group("labels"))
            float(em.group("value"))
        base = re.sub(r"_(bucket|sum|count|total)$", "", name)
        fam = name if name in families else base
        assert fam in families, f"sample {name} has no HELP/TYPE"
        assert fam == current, (
            f"family {fam} not contiguous: sample after {current}")
        assert fam not in family_done, f"family {fam} reopened"
        float(m.group("value"))       # value parses
        labels = _parse_labels(m.group("labels"))
        key = (name, labels)
        assert key not in seen_series, f"duplicate series {key}"
        seen_series.add(key)
        families[fam]["samples"].append((name, dict(labels),
                                         float(m.group("value"))))
    # histogram invariants. A family with HELP/TYPE and no samples yet is
    # LEGAL exposition (e.g. a registered histogram that never observed —
    # the WAL fsync histogram on an engine without a WAL); the invariants
    # apply per label set that does expose.
    for fam, info in families.items():
        if info["type"] != "histogram":
            continue
        by_labelset: dict = {}
        for name, labels, value in info["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            slot = by_labelset.setdefault(
                key, {"buckets": [], "sum": None, "count": None})
            if name == f"{fam}_bucket":
                slot["buckets"].append((labels["le"], value))
            elif name == f"{fam}_sum":
                slot["sum"] = value
            elif name == f"{fam}_count":
                slot["count"] = value
        for key, slot in by_labelset.items():
            assert slot["buckets"], f"{fam}{key}: no buckets"
            assert slot["buckets"][-1][0] == "+Inf", (
                f"{fam}{key}: buckets must end with +Inf")
            counts = [v for _, v in slot["buckets"]]
            assert counts == sorted(counts), (
                f"{fam}{key}: buckets not cumulative: {counts}")
            assert slot["count"] is not None and slot["sum"] is not None
            assert slot["count"] == counts[-1], (
                f"{fam}{key}: count != +Inf bucket")


# ------------------------------------------------------------------- lint
def test_lint_synthetic_registry():
    reg = MetricsRegistry()
    c = reg.counter("swtpu_lint_total", "events")
    c.inc(tenant="a")
    c.inc(2, tenant="b")
    g = reg.gauge("swtpu_lint_depth", "queue depth")
    g.set(3, queue="q1")
    h = reg.histogram("swtpu_lint_seconds", "latency")
    h.observe(0.001, stage="x")
    h.observe(9.0, stage="x")
    h.observe(99.0, stage="x")       # beyond the last finite bucket
    lint_prometheus(reg.expose_text())


def test_sampleless_histogram_family_lints():
    """A registered-but-never-observed histogram (the WAL fsync histogram
    on a WAL-less engine) exposes HELP/TYPE with no samples — legal."""
    reg = MetricsRegistry()
    reg.histogram("swtpu_empty_seconds", "never observed")
    lint_prometheus(reg.expose_text())


def test_label_values_escaped():
    reg = MetricsRegistry()
    g = reg.gauge("swtpu_esc", "escaping")
    hostile = 'a"b\\c\nd'
    g.set(1, tenant=hostile)
    text = reg.expose_text()
    assert '\\"b' in text and "\\\\c" in text and "\\nd" in text
    # the hostile value must not break line structure: every line lints
    lint_prometheus(text)


def test_full_engine_exposition_lints():
    """The payload /api/instance/metrics/prometheus actually serves:
    engine export + stage histogram, linted end to end."""
    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.utils.tracing import stage

    reg = MetricsRegistry()
    eng = Engine(EngineConfig(
        device_capacity=64, token_capacity=128, assignment_capacity=128,
        store_capacity=1024, batch_capacity=16, channels=4))
    import json as _json

    eng.ingest_json_batch([_json.dumps(
        {"deviceToken": f"mx-{i}", "type": "DeviceMeasurements",
         "request": {"measurements": {"t": float(i)}}}).encode()
        for i in range(6)])
    eng.flush()
    export_engine_metrics(eng, reg)
    h = reg.histogram("swtpu_stage_seconds", "host pipeline stage latency")
    with h.time(stage="unit"):
        pass
    text = reg.expose_text()
    lint_prometheus(text)
    assert 'swtpu_engine_processed{tenant="all"} 6' in text
    assert 'swtpu_pipeline_accepted{tenant="default"} 6' in text
    assert "swtpu_dispatch_inflight" in text
    # device plane (ISSUE 11): the scrape-time exports land in the SAME
    # registry and must lint with everything else (the live watchdog
    # counters go to the process-global REGISTRY, checked in
    # tests/test_devicewatch.py)
    assert 'swtpu_device_mem_bytes{component="ring_store"' in text
    assert "swtpu_xla_programs_live" in text
    assert "swtpu_staged_backlog_hwm_rows" in text
    # conservation plane (ISSUE 14): the flow ledger's scrape-time
    # gauges ride the same exposition and stay 0.0.4-clean
    lbl = eng.metrics_label
    assert (f'swtpu_flow_rows{{engine="{lbl}",stage="staged"}} 6'
            in text)
    assert (f'swtpu_flow_rows{{engine="{lbl}",stage="dispatched"}} 6'
            in text)


def test_rules_counters_export_at_scrape():
    """ISSUE 14 satellite: the cadence-dependent CEP counters
    (missed/late/oob fires) export as swtpu_rules_* at SCRAPE time —
    kept OUT of engine.metrics() (the dispatch-shape pin is asserted by
    tests/test_rules.py) but no longer invisible without the
    Python API. An engine with no rule set exports none of them."""
    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.rules import RuleSet, RulesManager

    reg = MetricsRegistry()
    plain = Engine(EngineConfig(
        device_capacity=64, token_capacity=128, assignment_capacity=128,
        store_capacity=1024, batch_capacity=16, channels=4))
    export_engine_metrics(plain, reg)
    assert "swtpu_rules_missed_total" not in reg.expose_text()

    eng = Engine(EngineConfig(
        device_capacity=64, token_capacity=128, assignment_capacity=128,
        store_capacity=1024, batch_capacity=16, channels=8,
        rule_groups=32, rollup_buckets=8))
    RulesManager(eng).load(RuleSet.parse({
        "name": "x",
        "rules": [{"name": "hot", "kind": "threshold",
                   "channel": "temp", "op": ">", "value": 90.0,
                   "cooldownMs": 1000}]}), precompile=False)
    reg = MetricsRegistry()
    export_engine_metrics(eng, reg)
    text = reg.expose_text()
    lint_prometheus(text)
    for name in ("swtpu_rules_missed_total", "swtpu_rules_late_total",
                 "swtpu_rules_oob_groups_total",
                 "swtpu_rules_fires_total"):
        assert f"{name} 0" in text, name
    assert "swtpu_rules_active 1" in text
    # the dispatch-shape pin's premise: none of these leak into
    # engine.metrics() (missed/late are harvest-cadence dependent)
    assert "rule_missed" not in eng.metrics()
    assert "ruleMissedFires" not in eng.metrics()


def test_spmd_series_export_at_scrape_and_lint():
    """ISSUE 16 satellite: the mesh-sharded engine exports its per-shard
    posture (swtpu_spmd_* / swtpu_shard_* gauges) at SCRAPE time — kept
    OUT of engine.metrics(), whose dict is pinned equal to single-chip.
    A single-chip engine exports none of them."""
    import json as _json

    from sitewhere_tpu.engine import Engine, EngineConfig
    from sitewhere_tpu.parallel.sharded import SpmdEngine

    cfg = EngineConfig(
        device_capacity=64, token_capacity=128, assignment_capacity=128,
        store_capacity=1024, batch_capacity=16, channels=4,
        use_native=False)
    reg = MetricsRegistry()
    export_engine_metrics(Engine(cfg), reg)
    assert "swtpu_spmd_shards" not in reg.expose_text()

    eng = SpmdEngine(cfg, n_shards=2)
    eng.ingest_json_batch([_json.dumps(
        {"deviceToken": f"sx-{i}", "type": "DeviceMeasurement",
         "request": {"name": "t", "value": float(i), "eventDate": 1000}}
        ).encode() for i in range(6)])
    eng.flush()
    reg = MetricsRegistry()
    export_engine_metrics(eng, reg)
    text = reg.expose_text()
    lint_prometheus(text)
    lbl = eng.metrics_label
    assert f'swtpu_spmd_shards{{engine="{lbl}"}} 2' in text
    for s in ("0", "1"):
        assert (f'swtpu_shard_staged_rows{{engine="{lbl}",shard="{s}"}}'
                in text)
        assert (f'swtpu_shard_devices{{engine="{lbl}",shard="{s}"}}'
                in text)
        assert (f'swtpu_shard_assignments{{engine="{lbl}",shard="{s}"}}'
                in text)
    # devices landed on BOTH shards and the per-shard counts sum to the
    # registered total
    devs = {s: eng._next_local_device[s] for s in range(2)}
    assert sum(devs.values()) == 6 and all(v > 0 for v in devs.values())


# --------------------------------------------------------- API separation
def test_counter_has_no_set_and_rejects_decrease():
    c = Counter("c_total", "")
    assert not hasattr(c, "set")
    c.inc(2, tenant="a")
    with pytest.raises(ValueError):
        c.inc(-1, tenant="a")
    assert c.value(tenant="a") == 2


def test_gauge_moves_freely():
    g = Gauge("g", "")
    g.set(5, q="x")
    g.inc(q="x")
    g.dec(2, q="x")
    assert g.value(q="x") == 4
    g.retain(set())
    assert g.value(q="x") == 0.0     # retained away


def test_registry_kind_mismatch_both_directions():
    reg = MetricsRegistry()
    reg.counter("swtpu_kind_a", "")
    with pytest.raises(TypeError):
        reg.gauge("swtpu_kind_a")
    reg.gauge("swtpu_kind_b", "")
    with pytest.raises(TypeError):
        reg.counter("swtpu_kind_b")
    with pytest.raises(TypeError):
        reg.histogram("swtpu_kind_a")


# -------------------------------------------- quantile estimator (ISSUE 7)
def test_quantile_interpolates_within_bounding_bucket():
    from sitewhere_tpu.utils.metrics import Histogram

    h = Histogram("swtpu_q_seconds", "", buckets=(1.0, 2.0, 4.0))
    for _ in range(100):
        h.observe(1.5)                 # every sample in the (1, 2] bucket
    # uniform-within-bucket rule: p50 = lo + 0.5 * width
    assert abs(h.quantile(0.5) - 1.5) < 1e-9
    assert h.quantile(1.0) == 2.0      # upper edge of the bounding bucket
    # first bucket interpolates down from 0
    h2 = Histogram("swtpu_q2_seconds", "", buckets=(1.0, 2.0))
    for _ in range(10):
        h2.observe(0.2)
    assert abs(h2.quantile(0.5) - 0.5) < 1e-9


def test_quantile_matches_numpy_percentiles_within_bucket_width():
    """The satellite's contract: bucket-quantile vs exact numpy
    percentiles on known distributions, within one bucket width."""
    import bisect

    import numpy as np

    from sitewhere_tpu.utils.metrics import Histogram

    rng = np.random.default_rng(0)
    for dist in (rng.uniform(0.0, 1.0, 5000),
                 rng.exponential(0.05, 5000),
                 rng.lognormal(-4.0, 1.0, 5000)):
        h = Histogram("swtpu_qn_seconds", "")
        for v in dist:
            h.observe(float(v))
        for q in (0.5, 0.9, 0.99):
            exact = float(np.percentile(dist, q * 100))
            est = h.quantile(q)
            i = bisect.bisect_left(h.buckets, exact)
            if i >= len(h.buckets):      # beyond the last finite bucket
                assert est == h.buckets[-1]
                continue
            lo = h.buckets[i - 1] if i else 0.0
            assert abs(est - exact) <= (h.buckets[i] - lo) + 1e-12, \
                (q, est, exact)


def test_quantile_overflow_clamps_to_last_finite_bound():
    from sitewhere_tpu.utils.metrics import Histogram

    h = Histogram("swtpu_qo_seconds", "", buckets=(0.1, 1.0))
    h.observe(50.0)
    h.observe(60.0)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 1.0
    assert Histogram("swtpu_qe_seconds", "").quantile(0.5) is None


# ------------------------------------------------- exemplars (ISSUE 7)
def test_histogram_exemplars_only_on_request():
    """Exemplars ride ONLY exemplar-aware expositions: the plain
    text-format payload stays strictly Prometheus-0.0.4 parseable."""
    from sitewhere_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("swtpu_ex_seconds", "exemplars")
    h.observe_n(0.2, 3, exemplar="00-abcdef-01", tenant="t")
    plain = reg.expose_text()
    assert "# {" not in plain
    lint_prometheus(plain)
    rich = reg.expose_text(exemplars=True)
    assert '# {trace_id="00-abcdef-01"} 0.2' in rich
    lint_prometheus(rich)


def test_observe_n_weights_event_counts():
    from sitewhere_tpu.utils.metrics import Histogram

    h = Histogram("swtpu_w_seconds", "")
    h.observe_n(0.003, 10, tenant="a")
    h.observe_n(0.03, 90, tenant="a")
    assert h.count(tenant="a") == 100
    q = h.quantile(0.5, tenant="a")    # p50 weighted by EVENTS
    assert 0.025 <= q <= 0.05


# ------------------------------------- federated exposition (ISSUE 7)
def _mk_rank_text(val: float) -> str:
    from sitewhere_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("swtpu_fed_total", "events")
    c.inc(val, tenant="a")
    g = reg.gauge("swtpu_fed_depth", "queue depth")
    g.set(val)
    h = reg.histogram("swtpu_fed_seconds", "latency")
    h.observe(0.01 * val)
    return reg.expose_text()


def test_federate_dedups_help_type_and_labels_every_sample():
    from sitewhere_tpu.utils.metrics import federate_expositions

    fed = federate_expositions({0: _mk_rank_text(1), 1: _mk_rank_text(2)})
    lint_prometheus(fed)
    # ONE HELP/TYPE per family across ranks
    assert fed.count("# HELP swtpu_fed_total") == 1
    assert fed.count("# TYPE swtpu_fed_seconds histogram") == 1
    # every sample rank-labeled, existing labels preserved
    assert 'swtpu_fed_total{rank="0",tenant="a"} 1.0' in fed
    assert 'swtpu_fed_total{rank="1",tenant="a"} 2.0' in fed
    assert 'swtpu_fed_depth{rank="0"} 1' in fed
    assert 'swtpu_fed_depth{rank="1"} 2' in fed


def test_federate_escapes_rank_and_survives_hostile_label_values():
    from sitewhere_tpu.utils.metrics import (MetricsRegistry,
                                             federate_expositions)

    reg = MetricsRegistry()
    g = reg.gauge("swtpu_fed_esc", "escaping")
    g.set(1, tenant='a"b\\c\nd')        # hostile VALUE inside the rank text
    fed = federate_expositions({'r"0\\x': reg.expose_text()})
    lint_prometheus(fed)
    assert 'rank="r\\"0\\\\x"' in fed   # hostile RANK key escaped
    assert '\\"b' in fed and "\\\\c" in fed and "\\nd" in fed


def test_federate_preserves_exemplars():
    from sitewhere_tpu.utils.metrics import (MetricsRegistry,
                                             federate_expositions)

    reg = MetricsRegistry()
    h = reg.histogram("swtpu_fed_ex_seconds", "latency")
    h.observe_n(0.02, 1, exemplar="tid-1")
    fed = federate_expositions({3: reg.expose_text(exemplars=True)})
    lint_prometheus(fed)
    assert '# {trace_id="tid-1"}' in fed
    assert 'rank="3"' in fed


def test_federate_cross_rank_type_conflict_is_loud():
    from sitewhere_tpu.utils.metrics import (MetricsRegistry,
                                             federate_expositions)

    ra = MetricsRegistry()
    ra.counter("swtpu_fed_kind", "k").inc()
    rb = MetricsRegistry()
    rb.gauge("swtpu_fed_kind", "k").set(1)
    with pytest.raises(ValueError):
        federate_expositions({0: ra.expose_text(), 1: rb.expose_text()})
