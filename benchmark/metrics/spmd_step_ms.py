"""spmd_step_ms: device time per run of the sharded fused ingest step
(``SpmdEngine``'s ``shard_map`` of the single-chip step, jitted as
``spmd_pipeline_step`` in ``parallel/sharded.py``), from the profiler
trace: the mean duration of its runs inside the window, on the slowest
chip. Absent where no program of that name ran."""

from benchmark.trace_reduce import program_runs

PROGRAMS = ("jit_spmd_pipeline_step",)


def read(ctx):
    if ctx.view is None:
        return None
    per_chip = []
    for chip in ctx.view.chips.values():
        runs = program_runs(chip, PROGRAMS, ctx.w0, ctx.w1)
        if runs:
            per_chip.append(sum(runs) / len(runs) / 1e6)
    return max(per_chip) if per_chip else None
