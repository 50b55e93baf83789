"""wal_gate_ms: mean host time per step dispatch spent waiting for the
write-ahead log's group commit to make the dispatched batches durable
(``swtpu.wal.gate`` spans over ``swtpu.step.dispatch`` spans in the
window; 0 where no dispatch waits). Source: the program's spans in the
profiler trace (program_span); absent where the program writes none."""

from benchmark import program_trace as pt


def read(ctx):
    pv = pt.of(ctx, __file__)
    dispatches = pt.span_durations(pv, "swtpu.step.dispatch", ctx.w0,
                                   ctx.w1)
    if not dispatches:
        return None
    gate = pt.span_durations(pv, "swtpu.wal.gate", ctx.w0, ctx.w1)
    return sum(gate) / len(dispatches) / 1e6
