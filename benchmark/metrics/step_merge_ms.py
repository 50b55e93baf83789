"""step_merge_ms: device time per run of the fused step of the ops
under its ``merge`` scope (the device-state merge, ``pipeline.py``), on
the slowest chip: the union of those ops' intervals inside each program
run that holds them, averaged over the runs wholly inside the window.
Source: the device trace's op metadata (device_trace); absent where the
program names no such scope."""

from benchmark import program_trace as pt


def read(ctx):
    return pt.scope_ms(pt.of(ctx, __file__), "merge", ctx.w0, ctx.w1)
