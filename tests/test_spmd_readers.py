"""The four-chip cell's per-layer readers (``benchmark/metrics/``), each
on a synthetic context: what it reads from the SPMD engine's spans and
the sharded step's program runs, and that it reads nothing from a trace
without them (the parent program's, or none)."""

import os

import pytest

from benchmark import harness
from benchmark import program_trace as pt
from benchmark import trace_reduce as tr
from benchmark.metrics.step_roofline_pct import step_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000
CFG = {"engine": {"batch_capacity": 100, "channels": 8}, "recent_depth": 3}
PEAKS = {"hbm_bytes_per_s": 819e9}
STEP = "jit_spmd_pipeline_step"


def ctx(monkeypatch, spans=(), chips=None, events=0, peaks=PEAKS):
    """A context whose run's trace holds ``spans`` and ``chips``."""
    chips = chips or {}
    pv = pt.ProgramView([(n, s, e, dict(a)) for n, s, e, a in spans],
                        chips, {c: [] for c in chips})
    monkeypatch.setattr(pt, "of", lambda c, reader_file: pv)
    return harness.LayerContext(tr.TraceView(chips, []), 0, 1000 * MS, [],
                                {"total": events}, CFG, {}, peaks)


def read(name, c):
    return harness.load_reader(REPO, name).read(c)


# two batches on four shards: the first routes in one pass, the second
# in two around a dispatch whose lanes overflowed
SPANS = [("swtpu.ingest", 0, 100 * MS, {"payloads": 4}),
         ("swtpu.ingest.route", 5 * MS, 8 * MS,
          {"rows": 4, "lane_max": 2, "lane_min": 0}),
         ("swtpu.ingest", 100 * MS, 200 * MS, {"payloads": 4}),
         ("swtpu.ingest.route", 101 * MS, 102 * MS,
          {"rows": 3, "lane_max": 1, "lane_min": 0}),
         ("swtpu.step.dispatch", 102 * MS, 110 * MS,
          {"rows": 300, "shards": 4, "lane_max": 100}),
         ("swtpu.ingest.route", 111 * MS, 113 * MS,
          {"rows": 1, "lane_max": 1, "lane_min": 0}),
         ("swtpu.step.dispatch", 150 * MS, 160 * MS,
          {"rows": 100, "shards": 4, "lane_max": 40}),
         # the single-chip engine's dispatch carries no shard count
         ("swtpu.step.dispatch", 170 * MS, 171 * MS, {"rows": 50}),
         # outside the window: never read
         ("swtpu.ingest.route", 2000 * MS, 2100 * MS,
          {"rows": 4, "lane_max": 1, "lane_min": 1})]


@pytest.mark.parametrize("name, want", [
    ("route_ms", (3 + 1 + 2) / 2),
    ("lane_fill_pct", (75 + 25) / 2),
])
def test_span_reader(monkeypatch, name, want):
    assert read(name, ctx(monkeypatch, SPANS)) == pytest.approx(want)
    assert read(name, ctx(monkeypatch)) is None


def test_readers_are_silent_on_the_single_chip_program(monkeypatch):
    """The parent's spans: no route pass, no dispatch with shards."""
    spans = [s for s in SPANS if s[0] != "swtpu.ingest.route"
             and "shards" not in s[3]]
    for name in ("route_ms", "lane_fill_pct"):
        assert read(name, ctx(monkeypatch, spans)) is None


def _chips(names=(STEP,)):
    """Chip 0 runs the step twice (4 + 6 ms), chip 1 twice (5 + 7 ms);
    a run past the window's end is left out."""
    return {c: tr.ChipView([], [(n, s * MS, e * MS) for n in names
                                for s, e in runs])
            for c, runs in {0: [(0, 4), (10, 16), (990, 1010)],
                            1: [(0, 5), (10, 17)]}.items()}


def test_spmd_step_ms_reads_the_slowest_chip(monkeypatch):
    assert read("spmd_step_ms", ctx(monkeypatch, chips=_chips())) == \
        pytest.approx(6.0)
    # a clone of the program keeps its name's runs
    assert read("spmd_step_ms", ctx(monkeypatch, chips=_chips(
        (STEP + ".1",)))) == pytest.approx(6.0)
    # the single-chip step, or no trace: nothing to read
    assert read("spmd_step_ms", ctx(monkeypatch, chips=_chips(
        ("jit__unknown",)))) is None
    assert read("spmd_step_ms", ctx(monkeypatch)) is None


def test_spmd_step_roofline_pct(monkeypatch):
    n = 1_000_000
    c = ctx(monkeypatch, chips=_chips(), events=n)
    need_s = step_bytes(n, 8, 3) / (2 * PEAKS["hbm_bytes_per_s"])
    got = read("spmd_step_roofline_pct", c)
    assert got == pytest.approx(100 * need_s / 12e-3)
    assert 0 < got < 100
    assert read("spmd_step_roofline_pct",
                ctx(monkeypatch, chips=_chips(), events=n, peaks={})) is None
    assert read("spmd_step_roofline_pct",
                ctx(monkeypatch, chips=_chips(("jit__unknown",)),
                    events=n)) is None


@pytest.mark.parametrize("name", ["device_idle_pct.fleet156k",
                                  "dispatch_ms.fleet156k",
                                  "step_wait_ms.fleet156k",
                                  "host_batch_ms.fleet156k"])
def test_cell_parts_fall_back_to_the_base_reader(name):
    base = name.split(".")[0]
    assert harness.load_reader(REPO, name).__file__.endswith(
        os.path.join("metrics", base + ".py"))
