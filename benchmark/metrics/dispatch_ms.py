"""dispatch_ms: mean host time of one step dispatch (``swtpu.step.dispatch``:
the compile watchdog's shape key, the jitted call and the host-to-device
transfer of the batch) in the window. Source: the program's spans in the
profiler trace (program_span); absent where the program writes none."""

from benchmark import program_trace as pt


def read(ctx):
    xs = pt.span_durations(pt.of(ctx, __file__), "swtpu.step.dispatch",
                           ctx.w0, ctx.w1)
    return sum(xs) / len(xs) / 1e6 if xs else None
