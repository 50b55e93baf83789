"""device_idle_pct: share of the window in which no operation ran on the
device, from the profiler trace: 100 * (1 - busy union / window). On
several chips, the busiest chip's."""

from benchmark.trace_reduce import busy_ns


def read(ctx):
    if ctx.view is None or not ctx.view.chips or ctx.w1 <= ctx.w0:
        return None
    busy = max(busy_ns(c, ctx.w0, ctx.w1) for c in ctx.view.chips.values())
    if busy == 0:
        return None
    return 100.0 * (1.0 - busy / (ctx.w1 - ctx.w0))
