"""host_batch_ms: mean host time per device batch on the ingest host path
(native decode, arena staging, WAL append and durability gate): flight
record batch start -> ``dispatch`` mark, over the window's batches that
dispatched. Source: the program's flight records (program_span)."""


def read(ctx):
    xs = [r["stagesUs"]["dispatch"] / 1e3 for r in ctx.flight
          if "dispatch" in r.get("stagesUs", {})]
    return sum(xs) / len(xs) if xs else None
