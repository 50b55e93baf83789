"""spmd_step_roofline_pct: the sharded fused ingest step's share of its
roofline.

The least time the chips could take for the window's ingest steps is the
least bytes those events need moved (``step_roofline_pct.step_bytes``:
the same bytes per event as the single-chip step's share) over the HBM
bandwidth of every chip that runs the step, each holding its shard's
share. The share is that least time over the slowest chip's summed
device time of the step program (``spmd_step_ms``'s) in the window.
Absent where no program of that name ran.
"""

from benchmark.metrics.spmd_step_ms import PROGRAMS
from benchmark.metrics.step_roofline_pct import step_bytes
from benchmark.trace_reduce import program_runs


def read(ctx):
    if ctx.view is None or not ctx.peaks:
        return None
    times = []
    for chip in ctx.view.chips.values():
        runs = program_runs(chip, PROGRAMS, ctx.w0, ctx.w1)
        if runs:
            times.append(sum(runs) / 1e9)
    if not times or ctx.events["total"] == 0:
        return None
    need = step_bytes(ctx.events["total"], ctx.cfg["engine"]["channels"],
                      ctx.cfg["recent_depth"])
    return (100.0 * need / (len(times) * ctx.peaks["hbm_bytes_per_s"])
            / max(times))
