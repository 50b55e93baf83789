"""The program's marks in a run's trace (``benchmark/program_trace.py``):
the idle sweep by span on synthetic views, the step's scope time per run,
the ops' scope paths from the kept CPU trace's HLO modules, and the
readers in a traced run of the test-size cell."""

import gzip
import os

import pytest

from benchmark import harness
from benchmark import program_trace as pt
from benchmark import trace_reduce as tr
from benchmark.tests.conftest import CELL

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "cpu_trace.xplane.pb.gz")


def synthetic(spans, host=(), ops=((100, 200), (900, 1000))):
    view = tr.TraceView({0: tr.ChipView([("op", s, e) for s, e in ops], [])},
                        list(host))
    return view, [(n, s, e, {}) for n, s, e in spans]


def test_gaps_by_span_names_the_innermost_span():
    # idle: [0, 100) and [200, 900); the root spans the whole window,
    # the decode nests inside it, the dispatch nests in the root after it
    view, spans = synthetic([("swtpu.ingest", 0, 1000),
                             ("swtpu.ingest.decode", 250, 600),
                             ("swtpu.step.dispatch", 700, 950)])
    tot = pt.idle_by_span(view, spans, 0, 0, 1000)
    assert tot == {"swtpu.ingest": 100 + 50 + 100,
                   "swtpu.ingest.decode": 350,
                   "swtpu.step.dispatch": 200}
    assert sum(tot.values()) == sum(e - s for s, e in
                                    tr.idle_gaps(view.chips[0], 0, 1000))
    assert pt.gaps_by_span(view, spans, 0, 0, 1000, k=1) == [
        ["swtpu.ingest.decode", 350 / 1e9]]


def test_gaps_by_span_splits_a_gap_across_two_spans():
    # one gap [200, 900) covered by two spans in turn (two threads, or
    # one after the other): each takes its own part
    view, spans = synthetic([("swtpu.wal.append", 150, 500),
                             ("swtpu.wal.gate", 500, 950)])
    assert pt.idle_by_span(view, spans, 0, 0, 1000) == {
        "bench.none": 100, "swtpu.wal.append": 300, "swtpu.wal.gate": 400}
    # overlapping spans on two threads: the one opened last is innermost
    view, spans = synthetic([("swtpu.flush", 150, 950),
                             ("swtpu.ingest.decode", 400, 600)])
    assert pt.idle_by_span(view, spans, 0, 0, 1000) == {
        "bench.none": 100, "swtpu.flush": 500, "swtpu.ingest.decode": 200}


def test_gaps_by_span_falls_back_to_the_harness():
    # idle under no program span goes to the harness annotation open
    # then (never the window itself), else to bench.none
    view, spans = synthetic([("swtpu.ingest", 300, 600)],
                            host=[("bench.window", 0, 1000),
                                  ("bench.ingest", 250, 800)])
    assert pt.idle_by_span(view, spans, 0, 0, 1000) == {
        "bench.none": 100 + 50 + 100, "bench.ingest": 50 + 200,
        "swtpu.ingest": 300}


def test_scope_time_per_run():
    assert pt.in_scope("jit(f)/while/body/append/scatter", "append")
    assert not pt.in_scope("jit(f)/append", "append")   # the op's own name
    assert not pt.in_scope("jit(f)/jit(append_events)/add", "append")
    # two runs of a program; the append ops of each overlap once
    chip = tr.ChipView(
        [("a", 10, 30), ("b", 20, 40), ("c", 40, 50), ("d", 110, 120)],
        [("jit_step", 0, 60), ("jit_step", 100, 160)])
    scopes = ["jit(f)/append/x", "jit(f)/append/y", "jit(f)/merge/z",
              "jit(f)/append/w"]
    assert pt.scope_ns_per_run(chip, scopes, "append", 0, 200) == (
        (30 + 10) / 2)
    assert pt.scope_ns_per_run(chip, scopes, "merge", 0, 200) == 10
    assert pt.scope_ns_per_run(chip, scopes, "append", 0, 100) == 30
    assert pt.scope_ns_per_run(chip, scopes, "rules", 0, 200) is None


def test_kept_trace_holds_op_names_and_no_program_marks():
    """The kept trace comes from a program that writes no spans and names
    no stage scope; its ``/host:metadata`` plane holds each program's
    HLO module, and every op of the fused step gets its instruction's
    op_name from it."""
    from jax.profiler import ProfileData

    with open(DATA, "rb") as f:
        raw = gzip.decompress(f.read())
    names = pt.hlo_op_names(raw)
    assert "jit__unknown(51)" in names
    assert names["jit__unknown(51)"]["min.5"] == "jit(<unknown>)/min"
    pd = ProfileData.from_serialized_xspace(raw)
    pv = pt.program_view(pd, names)
    assert pv.spans == []
    chip, scopes = pv.chips[0], pv.scopes[0]
    assert len(scopes) == len(chip.ops)
    # the same ops as the harness's own reduction of the trace
    assert chip.ops == tr.cpu_device_view(pd).chips[0].ops
    step = [p for p in scopes if p.startswith("jit(<unknown>)/")]
    assert len(step) > len(chip.ops) // 2
    (w0, w1), = [(s, e) for n, s, e in tr.cpu_device_view(pd).host
                 if n == "bench.window"]
    assert pt.scope_ms(pv, "append", w0, w1) is None


def test_traced_cell_reads_the_program_marks(tiny_root):
    """A traced run of the test-size cell: every reader of the program's
    spans and step scopes finds them in the run's own trace."""
    res = harness.run_cell(str(tiny_root), CELL, 2**31 + 77, 2.0, True,
                           require_chip=False)
    assert res["correct"], res["checks"]
    for base in ("host_decode_ms", "wal_gate_ms", "dispatch_ms",
                 "step_wait_ms", "step_append_ms", "step_merge_ms"):
        assert res["metrics"][base + ".fleet"]["value"] > 0, base
