"""lane_fill_pct: how much of its lanes the sharded step is paid to
carry: the mean, over the window's SPMD step dispatches, of 100 * rows /
(shards * batch_capacity), from the counts of each
``swtpu.step.dispatch`` span that carries ``shards`` (the SPMD engine's;
a lane holds ``batch_capacity`` rows, and every lane rides in every
dispatch). Source: the program's counts on its spans (program_counter);
absent where no dispatch carries them."""

from benchmark import program_trace as pt


def read(ctx):
    lane = ctx.cfg["engine"]["batch_capacity"]
    xs = [100.0 * st["rows"] / (st["shards"] * lane)
          for n, s, e, st in pt.of(ctx, __file__).spans
          if n == "swtpu.step.dispatch" and "shards" in st
          and s >= ctx.w0 and e <= ctx.w1]
    return sum(xs) / len(xs) if xs else None
