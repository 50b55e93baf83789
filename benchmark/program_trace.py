"""The program's own marks in a benchmark run's ``jax.profiler`` trace:
its host spans and the named scopes of its fused step.

The program writes spans into the trace (``swtpu.<stage>`` host events,
``sitewhere_tpu.utils.tracing.stage``) with their counts as event stats,
and names the stages of its fused step with ``jax.named_scope``, which XLA
keeps in each op's ``op_name`` metadata. No stat of a TPU op event carries
that metadata; the trace's ``/host:metadata`` plane holds each program's
HLO module ("Hlo Proto"), and :func:`hlo_op_names` reads every
instruction's ``op_name`` from it, so an op gets the scope path of its
instruction in the program run it falls in.

The harness reduces the trace to a :class:`trace_reduce.TraceView`, which
keeps neither. A per-layer reader calls :func:`of` with its context and
its own file: that reads the run's trace from the harness's work
directory (``<root>/.bench/trace``) once a run, for all its readers. A
trace of a program without spans or scopes yields none, and every number
read from them is then absent.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re

from benchmark import trace_reduce as tr

SPAN_PREFIX = "swtpu."   # the program's own spans


@dataclasses.dataclass
class ProgramView:
    spans: list    # [(name, start_ns, end_ns, {count: value})]
    chips: dict    # chip id -> trace_reduce.ChipView (ops, program runs)
    scopes: dict   # chip id -> each op's scope path, "" where unknown


_cache: dict = {}   # (trace file, mtime, size) -> ProgramView


def of(ctx, reader_file: str) -> ProgramView:
    """The program's marks in the trace of the run that ``ctx`` reads;
    ``reader_file`` is the reader's ``__file__``, at
    ``<root>/benchmark/metrics/``. Empty where the run left no trace."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))
    path = trace_file(os.path.join(root, ".bench", "trace"))
    if path is None:
        return ProgramView([], {}, {})
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _cache:
        from jax.profiler import ProfileData

        with open(path, "rb") as f:
            raw = f.read()
        _cache.clear()
        _cache[key] = program_view(ProfileData.from_serialized_xspace(raw),
                                   hlo_op_names(raw))
    return _cache[key]


def trace_file(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, None if none."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


# --------------------------------------------- the programs' HLO modules
def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _first(buf, number: int):
    for f, v in _fields(buf):
        if f == number:
            return v
    return None


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace") if v is not None else ""


def hlo_op_names(xspace: bytes) -> dict:
    """``{program: {instruction: op_name}}`` from the ``Hlo Proto`` stats
    of the ``/host:metadata`` plane, ``program`` named as its runs are
    (``jit__unknown(1234)``). Field numbers: XSpace.planes 1;
    XPlane.name 2, event_metadata 4 (map entry value 2), stat_metadata 5;
    XEventMetadata.name 2, stats 5; XStat.metadata_id 1, bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1 or _str(_first(plane, 2)) != "/host:metadata":
            continue
        hlo_stat = {_first(_first(e, 2), 1) for f2, e in _fields(plane)
                    if f2 == 5 and _str(_first(_first(e, 2), 2))
                    == "Hlo Proto"}
        for f2, entry in _fields(plane):
            if f2 != 4:
                continue
            meta = _first(entry, 2)
            for f3, stat in _fields(meta):
                if f3 == 5 and _first(stat, 1) in hlo_stat:
                    out[_str(_first(meta, 2))] = _module_op_names(
                        _first(_first(stat, 6), 1))
    return out


def _module_op_names(module) -> dict:
    names = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for f2, ins in _fields(comp):
            if f2 == 2:
                md = _first(ins, 7)
                names[_str(_first(ins, 1))] = _str(
                    _first(md, 2) if md is not None else None)
    return names


# ------------------------------------------------------------------ views
def program_view(pd, op_names: dict) -> ProgramView:
    """The program's spans, and each chip's ops and program runs with the
    ops' scope paths; from the TPU planes where the trace has them, else
    from the CPU stand-in (tests only) that ``trace_reduce`` reads."""
    spans = [(ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(SPAN_PREFIX)]
    chips, scopes = {}, {}
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        ops, modules, runs = [], [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [(tr.op_name(ev.name), int(ev.start_ns),
                         int(ev.end_ns)) for ev in line.events]
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s, e = int(ev.start_ns), int(ev.end_ns)
                    modules.append((tr.program_name(ev.name), s, e))
                    runs.append((ev.name.strip(), s, e))
        progs = _run_of([s for _, s, _ in ops], runs)
        c = int(m.group(1))
        chips[c] = tr.ChipView(ops, modules)
        scopes[c] = [op_names.get(p, {}).get(n.split(" ")[0], "")
                     for p, (n, _, _) in zip(progs, ops)]
    if not chips:
        chips[0], scopes[0] = _cpu_chip(pd, op_names)
    return ProgramView(spans, chips, scopes)


def _cpu_chip(pd, op_names: dict):
    """The CPU stand-in: operations are host events with an
    ``hlo_module`` stat, an op's instruction is its ``hlo_op`` stat, and
    a program run spans its operations of one ``run_id``."""
    ops, runs, scopes = [], {}, []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if "hlo_module" not in st:
                    continue
                s, e = int(ev.start_ns), int(ev.end_ns)
                ops.append((ev.name, s, e))
                prog = f"{st['hlo_module']}({st.get('program_id')})"
                scopes.append(op_names.get(prog, {}).get(
                    st.get("hlo_op"), ""))
                key = (st["hlo_module"], st.get("run_id"))
                a, b = runs.get(key, (s, e))
                runs[key] = (min(a, s), max(b, e))
    modules = [(k[0], a, b) for k, (a, b) in runs.items()]
    return tr.ChipView(ops, modules), scopes


def _run_of(starts: list, runs: list) -> list:
    """The program of the run ``(program, start, end)`` that holds each
    op start, None where none does."""
    runs = sorted(runs, key=lambda r: r[1])
    firsts = [r[1] for r in runs]
    out = []
    for t in starts:
        i = bisect.bisect_right(firsts, t) - 1
        out.append(runs[i][0] if i >= 0 and t < runs[i][2] else None)
    return out


# ------------------------------------------------------- the program's spans
def span_durations(pv: ProgramView, name: str, w0: int,
                   w1: int) -> list[int]:
    """Durations (ns) of the spans named ``name`` wholly inside the
    window."""
    return [e - s for n, s, e, _ in pv.spans
            if n == name and s >= w0 and e <= w1]


def idle_by_span(view: tr.TraceView, spans: list, chip_id: int, w0: int,
                 w1: int) -> dict[str, int]:
    """Idle ns of one chip of ``view`` by what the host was doing: each
    idle nanosecond goes to the innermost of ``spans`` open then (the one
    opened last, on whichever thread), else to the innermost harness
    annotation other than the window itself, else to ``bench.none``."""
    named = ([(s, e, n, 0) for n, s, e, _ in spans]
             + [(s, e, n, 1) for n, s, e in view.host
                if n != "bench.window"])
    bounds = []          # (time, 0 = end / 1 = start, index)
    for i, (s, e, _, _) in enumerate(named):
        s, e = max(s, w0), min(e, w1)
        if e > s:
            bounds += [(s, 1, i), (e, 0, i)]
    bounds.sort()
    tot: dict[str, int] = {}
    heaps = ([], [])     # open spans per kind: (-start, end, index)
    alive = set()
    b = 0
    for gs, ge in tr.idle_gaps(view.chips[chip_id], w0, w1):
        t = gs
        while t < ge:
            # open and close every span that starts or ends by t
            while b < len(bounds) and bounds[b][0] <= t:
                _, opens, i = bounds[b]
                if opens:
                    s, e, _, kind = named[i]
                    heapq.heappush(heaps[kind], (-s, e, i))
                    alive.add(i)
                else:
                    alive.discard(i)
                b += 1
            nxt = min(ge, bounds[b][0]) if b < len(bounds) else ge
            name = "bench.none"
            for h in heaps:
                while h and h[0][2] not in alive:
                    heapq.heappop(h)
                if h:
                    name = named[h[0][2]][2]
                    break
            tot[name] = tot.get(name, 0) + (nxt - t)
            t = nxt
    return tot


def gaps_by_span(view: tr.TraceView, spans: list, chip_id: int, w0: int,
                 w1: int, k: int = 10) -> list:
    """[name, seconds] of :func:`idle_by_span`, the longest first: the
    form of the harness's ``breakdown.idle_gaps``."""
    tot = idle_by_span(view, spans, chip_id, w0, w1)
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


# ------------------------------------------------------ the step's scopes
def in_scope(path: str, scope: str) -> bool:
    """Whether a scope path lies under ``scope``: one of its segments
    other than the op's own (the last) is ``scope``."""
    return scope in path.split("/")[:-1]


def scope_ns_per_run(chip: tr.ChipView, scopes: list, scope: str, w0: int,
                     w1: int) -> float | None:
    """Device ns per program run of the ops under ``scope`` (their
    union), over the runs wholly inside the window that hold such ops;
    None where no op carries the scope."""
    marked = sorted((s, e) for (_, s, e), path in zip(chip.ops, scopes)
                    if path and in_scope(path, scope))
    if not marked:
        return None
    starts = [s for s, _ in marked]
    per_run = []
    for _, ms, me in chip.modules:
        if ms < w0 or me > w1:
            continue
        lo = bisect.bisect_left(starts, ms)
        hi = bisect.bisect_right(starts, me)
        inside = [(s, min(e, me)) for s, e in marked[lo:hi]]
        if inside:
            per_run.append(sum(e - s for s, e in tr.merge(inside)))
    return sum(per_run) / len(per_run) if per_run else None


def scope_ms(pv: ProgramView, scope: str, w0: int, w1: int) -> float | None:
    """:func:`scope_ns_per_run` in ms on the slowest chip."""
    per_chip = [scope_ns_per_run(c, pv.scopes.get(i, []), scope, w0, w1)
                for i, c in pv.chips.items()]
    per_chip = [x for x in per_chip if x is not None]
    return max(per_chip) / 1e6 if per_chip else None
