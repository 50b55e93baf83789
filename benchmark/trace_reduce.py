"""Reduction of a ``jax.profiler`` trace to the numbers the benchmark
reports: the device's busy union and idle share, device time per named
program, the device operations that took most time, and the idle gaps by
what the harness was doing on the host meanwhile.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds every operation and whose ``XLA Modules`` line holds every
program run. A CPU trace has no device plane: its operations are host
events carrying an ``hlo_module`` stat. :func:`cpu_device_view` builds
the same view from those, so the arithmetic below is checked on a small
CPU trace kept with the tests; it is never used for a reported number.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

HOST_PREFIX = "bench."   # the harness's own host annotations


@dataclasses.dataclass
class ChipView:
    ops: list          # [(name, start_ns, end_ns)] device operations
    modules: list      # [(program name, start_ns, end_ns)] program runs


@dataclasses.dataclass
class TraceView:
    chips: dict        # chip id -> ChipView
    host: list         # [(name, start_ns, end_ns)] harness annotations


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def op_name(event_name: str) -> str:
    """A TPU op event is named by its HLO text; keep the op's name and its
    result type: ``%fusion.58 = f32[25165824]{0:T(1024)} fusion(...)``
    -> ``fusion.58 f32[25165824]``."""
    m = re.match(r"%?([\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])", event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name[:80]


def program_name(event_name: str) -> str:
    """``jit_pipeline_step(1234)`` -> ``jit_pipeline_step``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _host_annotations(pd) -> list:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    return out


def tpu_view(pd) -> TraceView:
    chips = {}
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [(op_name(ev.name), int(ev.start_ns), int(ev.end_ns))
                        for ev in line.events]
            elif line.name == "XLA Modules":
                modules += [(program_name(ev.name), int(ev.start_ns),
                             int(ev.end_ns)) for ev in line.events]
        chips[int(m.group(1))] = ChipView(ops, modules)
    return TraceView(chips, _host_annotations(pd))


def cpu_device_view(pd) -> TraceView:
    """The CPU stand-in (tests only): operations are host events with an
    ``hlo_module`` stat; a program run spans its operations of one
    ``run_id``."""
    ops, runs = [], {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if "hlo_module" not in st:
                    continue
                s, e = int(ev.start_ns), int(ev.end_ns)
                ops.append((ev.name, s, e))
                key = (st["hlo_module"], st.get("run_id"))
                a, b = runs.get(key, (s, e))
                runs[key] = (min(a, s), max(b, e))
    modules = [(k[0], a, b) for k, (a, b) in runs.items()]
    return TraceView({0: ChipView(ops, modules)}, _host_annotations(pd))


# ------------------------------------------------------------- arithmetic
def merge(intervals) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, w0: int, w1: int) -> list[tuple[int, int]]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if min(e, w1) > max(s, w0)]


def busy_ns(chip: ChipView, w0: int, w1: int) -> int:
    return sum(e - s for s, e in merge(clip(
        [(s, e) for _, s, e in chip.ops], w0, w1)))


def idle_gaps(chip: ChipView, w0: int, w1: int) -> list[tuple[int, int]]:
    busy = merge(clip([(s, e) for _, s, e in chip.ops], w0, w1))
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def program_runs(chip: ChipView, names, w0: int, w1: int) -> list[int]:
    """Durations (ns) of the runs of programs whose name matches one of
    ``names`` (exact, or with a ``.<n>`` clone suffix), wholly inside
    the window."""
    pat = re.compile(r"^(%s)(\.\d+)?$" % "|".join(map(re.escape, names)))
    return [e - s for n, s, e in chip.modules
            if pat.match(n) and s >= w0 and e <= w1]


def top_ops(view: TraceView, w0: int, w1: int, k: int = 10) -> list:
    """[name, seconds] of the device operations with the most time,
    summed over chips and averaged per chip."""
    tot: dict[str, int] = {}
    for chip in view.chips.values():
        for name, s, e in chip.ops:
            d = min(e, w1) - max(s, w0)
            if d > 0:
                tot[name] = tot.get(name, 0) + d
    n = max(1, len(view.chips))
    return [[name, ns / n / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def gaps_by_host(view: TraceView, chip_id: int, w0: int, w1: int,
                 k: int = 10) -> list:
    """Idle seconds of one chip, by the harness call (other than the
    window itself) that overlaps each idle gap longest (``bench.none``
    where none is open)."""
    host = sorted((h for h in view.host if h[0] != "bench.window"),
                  key=lambda h: h[1])
    tot: dict[str, int] = {}
    for gs, ge in idle_gaps(view.chips[chip_id], w0, w1):
        best, best_ov = "bench.none", 0
        for name, s, e in host:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        tot[best] = tot.get(best, 0) + (ge - gs)
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
