"""step_wait_ms: mean host time the ingest path waits for a dispatched
step to finish on the device, once per dispatch at the dispatch depth
(``swtpu.step.wait`` spans with a ``depth`` above 0; the barrier's drain
carries depth 0 and is left out) in the window. Source: the program's
spans in the profiler trace (program_span); absent where the program
writes none."""

from benchmark import program_trace as pt


def read(ctx):
    xs = [e - s for n, s, e, st in pt.of(ctx, __file__).spans
          if n == "swtpu.step.wait" and s >= ctx.w0 and e <= ctx.w1
          and st.get("depth", 0) > 0]
    return sum(xs) / len(xs) / 1e6 if xs else None
