"""step_device_ms: device time per dispatch of the fused ingest step, from
the profiler trace: the mean duration of the step program's runs inside
the window, on the slowest chip. The step is found by the program name
below (the jitted step of ``pipeline.py``)."""

from benchmark.trace_reduce import program_runs

# the fused step is jitted from a functools.partial, which XLA names
# "jit__unknown" (no stable name yet: PERF.md, list for the tracing issue)
PROGRAMS = ("jit__unknown",)


def read(ctx):
    if ctx.view is None:
        return None
    per_chip = []
    for chip in ctx.view.chips.values():
        runs = program_runs(chip, PROGRAMS, ctx.w0, ctx.w1)
        if runs:
            per_chip.append(sum(runs) / len(runs) / 1e6)
    return max(per_chip) if per_chip else None
