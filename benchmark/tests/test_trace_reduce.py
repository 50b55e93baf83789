"""The trace reduction, checked on a small trace recorded on the CPU and
kept here (``data/cpu_trace.xplane.pb.gz``: two ingest batches of a tiny
engine and one event query, under the harness's annotations)."""

import gzip
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "cpu_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def view():
    from jax.profiler import ProfileData

    with open(DATA, "rb") as f:
        pd = ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    return tr.cpu_device_view(pd)


def window(view):
    (w0, w1), = [(s, e) for n, s, e in view.host if n == "bench.window"]
    return w0, w1


def sweep_union(intervals, w0, w1):
    """Covered length by an event sweep (a second, independent way)."""
    ev = []
    for s, e in intervals:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            ev += [(s, 1), (e, -1)]
    ev.sort()
    depth, last, tot = 0, None, 0
    for t, d in ev:
        if depth > 0:
            tot += t - last
        depth += d
        last = t
    return tot


def test_busy_union_matches_sweep(view):
    w0, w1 = window(view)
    chip = view.chips[0]
    want = sweep_union([(s, e) for _, s, e in chip.ops], w0, w1)
    assert tr.busy_ns(chip, w0, w1) == want > 0
    # overlapping operations count once: the union is below the sum
    assert want < sum(e - s for _, s, e in chip.ops)


def test_idle_gaps_complement_busy(view):
    w0, w1 = window(view)
    chip = view.chips[0]
    gaps = tr.idle_gaps(chip, w0, w1)
    assert sum(e - s for s, e in gaps) + tr.busy_ns(chip, w0, w1) == w1 - w0
    assert all(w0 <= s < e <= w1 for s, e in gaps)
    by_host = tr.gaps_by_host(view, 0, w0, w1)
    assert sum(v for _, v in by_host) == pytest.approx(
        sum(e - s for s, e in gaps) / 1e9)
    assert {n for n, _ in by_host} <= {"bench.ingest", "bench.read",
                                       "bench.none"}


def test_program_runs_per_dispatch(view):
    w0, w1 = window(view)
    chip = view.chips[0]
    # the two ingest batches ran the fused step (jitted from a partial,
    # so XLA names it jit__unknown) once each; the query ran once
    assert len(tr.program_runs(chip, ("jit__unknown",), w0, w1)) == 2
    q = tr.program_runs(chip, ("jit_query_store_batch",), w0, w1)
    assert len(q) == 1 and q[0] > 0
    # a window that ends before the query excludes it
    (r0, r1), = [(s, e) for n, s, e in view.host if n == "bench.read"]
    assert tr.program_runs(chip, ("jit_query_store_batch",), w0, r0) == []


def test_top_ops(view):
    w0, w1 = window(view)
    top = tr.top_ops(view, w0, w1, k=10)
    assert len(top) == 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= sum(e - s for _, s, e in view.chips[0].ops) / 1e9


def test_program_name():
    assert tr.program_name("jit_pipeline_step(1234)") == "jit_pipeline_step"
    assert tr.program_name("jit_multi") == "jit_multi"
