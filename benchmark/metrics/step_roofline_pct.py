"""step_roofline_pct: the fused ingest step's share of its roofline.

The least time the chip could take for the window's ingest steps is the
least bytes those events need moved, over the chip's HBM bandwidth (the
step does almost no arithmetic, so bandwidth bounds it). The share is that
least time over the step program's device time in the trace (slowest
chip). The bytes are the work the algorithm needs, counted from shapes
and dtypes, whatever implements it:

- reading the batch's ``EventBatch`` columns, one row per event;
- the registry probes of each event (token -> device, device tenant and
  active flag, its one active assignment and that assignment's flag,
  area, customer and asset);
- one store row written per persisted event;
- reading and writing one device-state row per event.

(No deployment here keeps analytics windows; one that does adds the rows
its measurements write to its own reader.)

Not XLA's cost analysis: that counts what the compiled program does,
including passes over the whole state the batch does not need.
"""

from benchmark.trace_reduce import program_runs

# the fused step is jitted from a functools.partial, which XLA names
# "jit__unknown" (no stable name yet: PERF.md, list for the tracing issue)
PROGRAMS = ("jit__unknown",)

I32 = F32 = 4
BOOL = 1
AUX_LANES = 2
EVENT_TYPES = 6
LOC_LANES = 3


def batch_row_bytes(c: int) -> int:
    # valid, etype, token_id, tenant_id, ts_ms, received_ms, values[C],
    # vmask[C], aux[2], seq
    return BOOL + 5 * I32 + F32 * c + BOOL * c + I32 * AUX_LANES + I32


def registry_probe_bytes() -> int:
    # token_to_device, device_active, device_tenant, device_assignments
    # (the one active slot), assignment_active, assignment area, customer,
    # asset
    return I32 + BOOL + I32 + I32 + BOOL + 3 * I32


def store_row_bytes(c: int) -> int:
    # etype, device, assignment, tenant, area, customer, asset, ts_ms,
    # received_ms, values[C], vmask[C], aux[2], valid
    return 9 * I32 + F32 * c + BOOL * c + I32 * AUX_LANES + BOOL


def state_row_bytes(c: int, r: int) -> int:
    return (I32 + I32                                  # last interaction, presence
            + F32 * c + I32 * c                        # meas_last, meas_last_ms
            + F32 * r * c + BOOL * r * c + I32 * r + BOOL * r   # recent meas
            + F32 * r * LOC_LANES + I32 * r + BOOL * r          # recent loc
            + I32 * r + I32 * r + I32 * r + BOOL * r            # recent alerts
            + I32 * EVENT_TYPES)                       # event counts


def step_bytes(n: int, c: int, r: int) -> int:
    """Least bytes ``n`` events need."""
    return n * (batch_row_bytes(c) + registry_probe_bytes()
                + store_row_bytes(c) + 2 * state_row_bytes(c, r))


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
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / max(times)
