"""route_ms: mean host time per ingest batch that the SPMD engine spends
routing rows to their shards and scattering them into the stacked
per-shard lanes (``swtpu.ingest.route`` spans, summed per
``swtpu.ingest`` batch) in the window. Source: the program's spans in the
profiler trace (program_span); absent where the program writes none."""

from benchmark import program_trace as pt


def read(ctx):
    pv = pt.of(ctx, __file__)
    route = pt.span_durations(pv, "swtpu.ingest.route", ctx.w0, ctx.w1)
    batches = pt.span_durations(pv, "swtpu.ingest", ctx.w0, ctx.w1)
    if not route or not batches:
        return None
    return sum(route) / len(batches) / 1e6
