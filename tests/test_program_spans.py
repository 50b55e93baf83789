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
    _drive(eng, frames[:1])      # compile outside the trace
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
