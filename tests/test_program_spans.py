"""Program spans on the profiler's clock (``utils/tracing.stage``) and the
named scopes of the fused step.

Each boundary of the ingest -> WAL -> dispatch -> device-wait path writes
a ``swtpu.<stage>`` span with its counts into the profiler's own trace,
and stamps the flight stage of that boundary from the same call site; a
flight record's start is placed on the same wall clock, so its stages
line up with the trace. With no profiler running nothing the engine
reports changes.
"""

import glob

import jax
import numpy as np
import pytest

from sitewhere_tpu.core.events import EventBatch
from sitewhere_tpu.engine import Engine, EngineConfig
from sitewhere_tpu.loadgen import generate_measurements_message
from sitewhere_tpu.pipeline import (PipelineConfig, PipelineState,
                                    make_pipeline_step)
from sitewhere_tpu.utils import tracing
from sitewhere_tpu.utils.flight import FlightRecorder

FRAME = 64     # one frame fills one staging arena: it dispatches in ingest

CFG = dict(device_capacity=256, token_capacity=512, assignment_capacity=512,
           store_capacity=2048, batch_capacity=FRAME, channels=4,
           flush_interval_s=0.0)

# span -> the span it runs inside on the arena ingest path
NESTED = {"swtpu.ingest.decode": "swtpu.ingest",
          "swtpu.wal.append": "swtpu.ingest",
          "swtpu.ingest.commit": "swtpu.ingest",
          "swtpu.wal.gate": "swtpu.ingest",
          "swtpu.step.dispatch": "swtpu.ingest",
          "swtpu.step.wait": "swtpu.ingest"}


def _frames(n):
    return [[generate_measurements_message(f"ps-{(f * FRAME + i) % 90}",
                                           f * FRAME + i,
                                           value=float(i % 50))
             for i in range(FRAME)] for f in range(n)]


def _engine(tmp_path, name):
    eng = Engine(EngineConfig(**CFG, wal_dir=str(tmp_path / name)))
    eng.epoch.now_ms = lambda: 4242        # one staging clock for both
    return eng


def _drive(eng, frames):
    for fr in frames:
        eng.ingest_json_batch(fr)
    eng.maybe_flush()       # flush_interval_s 0: drains the outputs


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two frames ingested under the profiler: (engine, spans, flight
    records), span times on the wall clock in ns."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("spans")
    eng = _engine(tmp, "wal")
    frames = _frames(3)
    # compile outside the trace and leave that program outstanding, as
    # in a steady stream: each traced dispatch then waits on the one
    # before it (the default dispatch depth 2)
    eng.ingest_json_batch(frames[0])
    with jax.profiler.trace(str(tmp / "trace")):
        _drive(eng, frames[1:])
    path, = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    base = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                if "profile_start_time" in dict(p.stats))
    spans = [(ev.name, base + ev.start_ns, base + ev.end_ns,
              dict(ev.stats))
             for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events
             if ev.name.startswith("swtpu.")]
    recs = [r for r in eng.recent_traces(16)
            if r["kind"] == "ingest"][:2]
    yield eng, spans, recs
    eng.wal.close()


def _inside(inner, outer, slack_ns=0):
    return (outer[1] - slack_ns <= inner[1]
            and inner[2] <= outer[2] + slack_ns)


def test_every_span_is_written_and_nested(traced):
    _, spans, recs = traced
    names = {n for n, *_ in spans}
    assert set(NESTED) | {"swtpu.ingest", "swtpu.flush"} <= names
    roots = sorted((s for s in spans if s[0] == "swtpu.ingest"),
                   key=lambda s: s[1])
    assert len(roots) == 2
    for child, parent in NESTED.items():
        kids = [s for s in spans if s[0] == child
                and s[3].get("depth", 1) > 0]
        assert len(kids) >= 2, child
        for k in kids:
            assert any(_inside(k, p) for p in spans if p[0] == parent), \
                (child, parent)
    # the root carries the batch's trace id; the counts ride as args
    assert {r[3]["trace_id"] for r in roots} == {r["traceId"] for r in recs}
    assert all(r[3]["payloads"] == FRAME for r in roots)
    dec = [s[3] for s in spans if s[0] == "swtpu.ingest.decode"]
    assert all(d["rows"] == FRAME and d["failed"] == 0 for d in dec)
    wal = [s[3] for s in spans if s[0] == "swtpu.wal.append"]
    assert all(w["records"] == FRAME and w["bytes"] > FRAME for w in wal)
    assert all(s[3]["staged"] == FRAME for s in spans
               if s[0] == "swtpu.ingest.commit")
    assert all(s[3]["rows"] == FRAME for s in spans
               if s[0] == "swtpu.step.dispatch")
    assert all(s[3]["batches"] == 1 for s in spans
               if s[0] == "swtpu.wal.gate")
    # every wait says whether its program had finished when it began;
    # an ingest waits on the program before its own (depth 2)
    waits = [s[3] for s in spans if s[0] == "swtpu.step.wait"]
    assert waits and all(w["ready"] in (0, 1) for w in waits)
    assert all(w["depth"] in (0, 2) for w in waits)


def test_flight_stages_fall_inside_their_spans(traced):
    """A record's ``decode`` and ``dispatch`` marks, placed on the wall
    clock by ``startedNs``, lie inside that batch's spans (1 ms)."""
    _, spans, recs = traced
    ms = 1_000_000
    for rec in recs:
        root, = [s for s in spans if s[0] == "swtpu.ingest"
                 and s[3]["trace_id"] == rec["traceId"]]
        at = {k: rec["startedNs"] + v * 1000
              for k, v in rec["stagesUs"].items()}
        assert abs(rec["startedNs"] // ms - rec["startedMs"]) <= 1
        assert root[1] - ms <= rec["startedNs"] <= root[2]
        decode = [s for s in spans if s[0] == "swtpu.ingest.decode"
                  and _inside(s, root)]
        assert any(s[1] - ms <= at["decode"] <= s[2] + ms for s in decode)
        dispatch = [s for s in spans if s[0] == "swtpu.step.dispatch"
                    and _inside(s, root)]
        assert any(s[1] - ms <= at["dispatch"] <= s[2] + ms
                   for s in dispatch)


def test_no_profiler_changes_nothing_reported(tmp_path, traced):
    """The same frames with no profiler running: the engine's metrics
    and the flight records' stages and fields are those of the traced
    engine, and the records carry no per-stage duration copies."""
    eng_t, _, recs_t = traced
    eng = _engine(tmp_path, "wal")
    _drive(eng, _frames(3))
    assert eng.metrics() == eng_t.metrics()
    recs = [r for r in eng.recent_traces(16) if r["kind"] == "ingest"][:2]
    assert [sorted(r["stagesUs"]) for r in recs] == \
        [sorted(r["stagesUs"]) for r in recs_t]
    assert [sorted(r) for r in recs] == [sorted(r) for r in recs_t]
    for r in recs:
        assert not {"wal_flush_ms", "wal_gate_ms"} & set(r)
        assert set(r["stagesUs"]) == {"decode", "arena_fill", "wal_append",
                                      "commit", "wal_durable", "dispatch",
                                      "device_ready", "readback"}
    eng.wal.close()


class _Ticket:
    """A step output that reports not ready until it is waited on or
    ``ready`` is set (the arena tests' stub ticket), holding the real
    array for the readback."""

    def __init__(self, arr):
        self.arr, self.ready, self.waited = arr, False, False

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.ready = self.waited = True
        self.arr.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.arr, dtype)


def test_default_depth_waits_on_the_program_before(tmp_path):
    """At the default depth an ingest returns with the program it just
    dispatched still outstanding, and its dispatch waited on the program
    before it; each ``swtpu.step.wait`` says whether that program had
    finished when the wait began (``ready``)."""
    from jax.profiler import ProfileData

    eng = Engine(EngineConfig(**CFG))
    assert eng.config.dispatch_depth == 2
    real_step, tickets = eng._step, []

    def step(state, batch):
        state, out = real_step(state, batch)
        tickets.append(_Ticket(out.n_persisted))
        return state, out._replace(n_persisted=tickets[-1])

    eng._step = step
    frames = _frames(3)
    eng.ingest_json_batch(frames[0])         # compiles outside the trace
    assert len(tickets) == 1 and not tickets[0].waited
    with jax.profiler.trace(str(tmp_path / "trace")):
        eng.ingest_json_batch(frames[1])
        # waited on the program before, returned with its own running
        assert tickets[0].waited and not tickets[1].waited
        tickets[1].ready = True              # the chip finished it
        eng.ingest_json_batch(frames[2])
        assert not tickets[2].waited
        eng.barrier()                        # waits on the newest
    assert all(t.waited for t in tickets)
    assert len(eng._pending_outs) == 3
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    spans = sorted(((ev.start_ns, ev.name, dict(ev.stats))
                    for p in ProfileData.from_file(path).planes
                    if p.name.startswith("/host:")
                    for ln in p.lines for ev in ln.events
                    if ev.name == "swtpu.step.wait"), key=lambda s: s[0])
    assert [(s[2]["depth"], s[2]["ready"]) for s in spans] == \
        [(2, 0), (2, 1), (0, 0)]
    # the readback drains all three, through the stub outputs
    assert eng.flush()["persisted"] == 3 * FRAME
    assert not eng._pending_outs and eng.metrics()["persisted"] == 3 * FRAME


def test_stage_marks_the_bound_record_and_keeps_the_histogram():
    from sitewhere_tpu.utils.metrics import REGISTRY

    fr = FlightRecorder(capacity=4)
    rec = fr.begin("ingest")
    with fr.bind(rec):
        assert fr.current() is rec and tracing.bound_record() is rec
        with tracing.stage("unit.bound", mark="decode", rows=3) as sp:
            sp.set_metadata(failed=0)
    assert "decode" in rec.stages
    assert tracing.bound_record() is None and fr.current() is not rec
    with tracing.stage("unit.unbound", mark="commit"):
        pass                 # no bound record: nothing to mark
    assert "commit" not in rec.stages
    assert 'stage="unit.bound"' in REGISTRY.expose_text()


def test_step_hlo_carries_the_stage_scopes():
    state = PipelineState.create(device_capacity=64, token_capacity=128,
                                 assignment_capacity=128, store_capacity=256)
    batch = EventBatch.zeros(16)
    text = make_pipeline_step(PipelineConfig(auto_register=True)).lower(
        state, batch).as_text(debug_info=True)
    for scope in ("lookup", "register", "expand", "append", "merge"):
        assert f"/{scope}/" in text, scope
    # the scopes name ops; the program keeps the name it had
    assert "jit(<unknown>)/append/" in text


# ---------------------------------------------------------- the SPMD engine
SPMD_CFG = dict(CFG, device_capacity=64, token_capacity=128,
                assignment_capacity=128)


def test_engine_entry_point_spans_one_or_more_chips():
    from sitewhere_tpu.parallel.sharded import SpmdEngine

    one = Engine(EngineConfig(**CFG))
    assert type(one) is Engine and one.config.shards == 1
    eng = Engine(EngineConfig(**SPMD_CFG, shards=4))
    assert type(eng) is SpmdEngine
    assert eng.n_shards == eng.config.shards == eng.mesh.devices.size == 4
    # the explicit count overrides the config's; the config records it
    assert SpmdEngine(EngineConfig(**SPMD_CFG, shards=4),
                      n_shards=2).config.shards == 2
    assert SpmdEngine(EngineConfig(**SPMD_CFG),
                      n_shards=2).config.shards == 2
    assert SpmdEngine(EngineConfig(**SPMD_CFG, shards=2)).n_shards == 2
    with pytest.raises(ValueError, match="devices"):
        Engine(EngineConfig(**SPMD_CFG, shards=len(jax.devices()) + 1))


@pytest.fixture(scope="module")
def traced_spmd(tmp_path_factory):
    """Frames of twice a lane ingested by a four-shard engine under the
    profiler, lanes overflowing mid-batch: (spans, flight records)."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("spmd_spans")
    eng = Engine(EngineConfig(**SPMD_CFG, shards=4,
                              wal_dir=str(tmp / "wal")))
    eng.epoch.now_ms = lambda: 4242
    frames = [sum(fr, []) for fr in zip(*[iter(_frames(8))] * 2)]
    # compile outside the trace, leaving the program outstanding
    eng.ingest_json_batch(frames[0])
    eng.flush_async()
    with jax.profiler.trace(str(tmp / "trace")):
        for fr in frames[1:]:
            eng.ingest_json_batch(fr)
        eng.barrier()
        eng.maybe_flush()
    path, = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    spans = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
             for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events
             if ev.name.startswith("swtpu.")]
    recs = [r for r in eng.recent_traces(16) if r["kind"] == "ingest"][:3]
    yield spans, recs
    eng.wal.close()


def test_spmd_path_writes_the_spans(traced_spmd):
    spans, recs = traced_spmd
    names = {n for n, *_ in spans}
    assert set(NESTED) | {"swtpu.ingest", "swtpu.ingest.route",
                          "swtpu.flush"} <= names
    roots = [s for s in spans if s[0] == "swtpu.ingest"]
    assert len(roots) == 3 and all(r[3]["payloads"] == 2 * FRAME
                                   for r in roots)
    for child, parent in NESTED.items():
        assert any(_inside(k, p) for k in spans if k[0] == child
                   and k[3].get("depth", 1) > 0
                   for p in spans if p[0] == parent), child
    assert any(s[0] == "swtpu.step.wait" and s[3]["depth"] == 0
               for s in spans)
    assert all(s[3]["ready"] in (0, 1) for s in spans
               if s[0] == "swtpu.step.wait")
    # a batch's route passes stage all its rows, between commit's bounds
    for root in roots:
        commit, = [s for s in spans if s[0] == "swtpu.ingest.commit"
                   and _inside(s, root)]
        route = [s[3] for s in spans if s[0] == "swtpu.ingest.route"
                 and _inside(s, commit)]
        assert route and sum(r["rows"] for r in route) == \
            commit[3]["staged"] == 2 * FRAME
        assert all(r["lane_min"] <= r["lane_max"] <= r["rows"]
                   for r in route)
    # a lane of FRAME rows overflows within a batch: it dispatches
    # between two route passes
    assert any(len([s for s in spans if s[0] == "swtpu.ingest.route"
                    and _inside(s, root)]) > 1 for root in roots)
    dispatch = [s[3] for s in spans if s[0] == "swtpu.step.dispatch"]
    assert dispatch and all(d["shards"] == 4 for d in dispatch)
    assert all(d["rows"] <= 4 * d["lane_max"] and d["lane_max"] <= FRAME
               for d in dispatch)
    for r in recs:
        assert {"decode", "wal_append", "route", "commit", "wal_durable",
                "dispatch"} <= set(r["stagesUs"])


def test_spmd_step_has_a_stable_program_name():
    """The sharded step's program is named for the trace's readers
    (``benchmark/metrics/spmd_step_ms.py``)."""
    from sitewhere_tpu.parallel.mesh import make_mesh
    from sitewhere_tpu.parallel.sharded import (_make_spmd_scan_step,
                                                _make_spmd_step,
                                                create_stacked_state)

    mesh = make_mesh(2)
    cfg = PipelineConfig(auto_register=True)
    state = create_stacked_state(mesh, 16, 32, 32, 64)
    batch = jax.tree_util.tree_map(lambda x: jax.numpy.stack([x, x]),
                                   EventBatch.zeros(8))
    text = _make_spmd_step(mesh, cfg).lower(state, batch).as_text()
    assert "module @jit_spmd_pipeline_step" in text
    text = _make_spmd_scan_step(mesh, cfg, 4, 2).lower(state,
                                                       batch).as_text()
    assert "module @jit_spmd_scan_step" in text
