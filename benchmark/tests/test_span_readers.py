"""The per-layer readers of the program's spans and step scopes, each on
a synthetic context: what it reads, and that it reads nothing from a
trace without them."""

import pytest

from benchmark import harness
from benchmark import program_trace as pt
from benchmark import trace_reduce as tr
from benchmark.tests.conftest import REPO

MS = 1_000_000


def ctx(monkeypatch, spans=(), chip=None, scopes=()):
    """A context whose run's trace holds ``spans`` and one chip."""
    pv = pt.ProgramView([(n, s, e, dict(a)) for n, s, e, a in spans],
                        {0: chip or tr.ChipView([], [])}, {0: list(scopes)})
    monkeypatch.setattr(pt, "of", lambda c, reader_file: pv)
    return harness.LayerContext(tr.TraceView({}, []), 0, 1000 * MS, [],
                                {"total": 0}, {}, {}, {})


def read(name, c):
    return harness.load_reader(REPO, name + ".fleet").read(c)


# two batches: the second decodes in two chunks; both dispatch once,
# after a gate each; each waits on its step, and the barrier drains
SPANS = [("swtpu.ingest", 0, 100 * MS, {"payloads": 4}),
         ("swtpu.ingest.decode", 1 * MS, 5 * MS, {"rows": 4}),
         ("swtpu.wal.gate", 10 * MS, 12 * MS, {"batches": 1}),
         ("swtpu.step.dispatch", 12 * MS, 15 * MS, {"rows": 4}),
         ("swtpu.step.wait", 15 * MS, 80 * MS, {"depth": 1}),
         ("swtpu.ingest", 100 * MS, 200 * MS, {"payloads": 4}),
         ("swtpu.ingest.decode", 101 * MS, 103 * MS, {"rows": 2}),
         ("swtpu.ingest.decode", 104 * MS, 106 * MS, {"rows": 2}),
         ("swtpu.wal.gate", 110 * MS, 116 * MS, {"batches": 1}),
         ("swtpu.step.dispatch", 116 * MS, 121 * MS, {"rows": 4}),
         ("swtpu.step.wait", 121 * MS, 180 * MS, {"depth": 1}),
         ("swtpu.step.wait", 200 * MS, 300 * MS, {"depth": 0}),
         # outside the window: never read
         ("swtpu.ingest.decode", 2000 * MS, 2100 * MS, {"rows": 4})]


@pytest.mark.parametrize("name, want", [
    ("host_decode_ms", (4 + 2 + 2) / 2),
    ("wal_gate_ms", (2 + 6) / 2),
    ("dispatch_ms", (3 + 5) / 2),
    ("step_wait_ms", (65 + 59) / 2),
])
def test_span_reader(monkeypatch, name, want):
    assert read(name, ctx(monkeypatch, SPANS)) == pytest.approx(want)
    assert read(name, ctx(monkeypatch)) is None


@pytest.mark.parametrize("name, want", [
    ("step_append_ms", 3.0), ("step_merge_ms", 1.5)])
def test_scope_reader(monkeypatch, name, want):
    chip = tr.ChipView(
        [("f1", 0, 2 * MS), ("f2", 1 * MS, 3 * MS), ("f3", 3 * MS, 4 * MS),
         ("f4", 10 * MS, 13 * MS), ("f5", 13 * MS, 15 * MS)],
        [("jit__unknown", 0, 5 * MS), ("jit__unknown", 10 * MS, 16 * MS)])
    scopes = ["jit(<unknown>)/append/a", "jit(<unknown>)/append/b",
              "jit(<unknown>)/merge/c", "jit(<unknown>)/append/d",
              "jit(<unknown>)/merge/e"]
    assert read(name, ctx(monkeypatch, chip=chip,
                          scopes=scopes)) == pytest.approx(want)
    # a step without scopes (the parent program): nothing to read
    assert read(name, ctx(monkeypatch, chip=chip,
                          scopes=[""] * len(scopes))) is None


def test_wal_gate_reads_zero_without_a_log(monkeypatch):
    spans = [s for s in SPANS if s[0] != "swtpu.wal.gate"]
    assert read("wal_gate_ms", ctx(monkeypatch, spans)) == 0.0


@pytest.mark.parametrize("name", ["host_decode_ms", "wal_gate_ms",
                                  "dispatch_ms", "step_wait_ms",
                                  "step_append_ms", "step_merge_ms"])
def test_reader_without_a_trace_reads_nothing(tmp_path, name):
    """A run that left no trace under the reader's root: absent, no
    error."""
    import shutil

    shutil.copytree(REPO + "/benchmark/metrics",
                    tmp_path / "benchmark" / "metrics")
    c = harness.LayerContext(tr.TraceView({}, []), 0, 1000 * MS, [],
                             {"total": 0}, {}, {}, {})
    assert harness.load_reader(str(tmp_path), name + ".fleet").read(c) is None
