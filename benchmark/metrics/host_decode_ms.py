"""host_decode_ms: mean host time per ingest batch in the native decode
(``swtpu.ingest.decode`` spans, summed per ``swtpu.ingest`` batch), over
the window's batches. Source: the program's spans in the profiler trace
(program_span); absent where the program writes none."""

from benchmark import program_trace as pt


def read(ctx):
    pv = pt.of(ctx, __file__)
    batches = pt.span_durations(pv, "swtpu.ingest", ctx.w0, ctx.w1)
    if not batches:
        return None
    decode = pt.span_durations(pv, "swtpu.ingest.decode", ctx.w0, ctx.w1)
    return sum(decode) / len(batches) / 1e6
