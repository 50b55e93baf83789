"""The recent-event ring merge (`ops/window._merge_rings`).

The merge ranks the 2R candidates of each device row by pairwise compares and
selects; it must equal the stable row sort + ``take_along_axis`` merge it
replaced bit for bit, and the lowered state merge must keep no per-device
sort or gather (on a TPU v5e those took ~35 ms of a 70 ms fused step over
39,936 device rows).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.lib.mlir import ir

from sitewhere_tpu.core.state import RECENT_DEPTH, DeviceStateStore
from sitewhere_tpu.ops.window import _NEG_SAFE_MIN, _merge_rings, merge_batch_state

I32_MIN = np.iinfo(np.int32).min
CHANNELS = 8


def _merge_rings_reference(new_valid, new_ts, new_lanes, old_valid, old_ts, old_lanes):
    """The previous merge: concatenate, stable row sort on (invalid, -ts),
    then gather the top R of each row."""
    r_depth = RECENT_DEPTH
    cat_valid = jnp.concatenate([new_valid, old_valid], axis=1)
    cat_ts = jnp.concatenate([new_ts, old_ts], axis=1)
    idx = jnp.broadcast_to(jnp.arange(cat_ts.shape[1], dtype=jnp.int32), cat_ts.shape)
    _, _, order = jax.lax.sort(
        [(~cat_valid).astype(jnp.int32), -jnp.maximum(cat_ts, _NEG_SAFE_MIN), idx],
        dimension=1, num_keys=2, is_stable=True,
    )
    order = order[:, :r_depth]
    out_valid = jnp.take_along_axis(cat_valid, order, axis=1)
    out_ts = jnp.take_along_axis(cat_ts, order, axis=1)
    out_lanes = []
    for new_lane, old_lane in zip(new_lanes, old_lanes):
        cat = jnp.concatenate([new_lane, old_lane], axis=1)
        idx = order.reshape(order.shape + (1,) * (cat.ndim - 2))
        out_lanes.append(jnp.take_along_axis(cat, jnp.broadcast_to(idx, order.shape + cat.shape[2:]), axis=1))
    return out_valid, out_ts, out_lanes


def _ring(rng, n, case):
    """One side's (valid, ts, [int32 lane, f32 x3 lane, bool xC lane])."""
    r = RECENT_DEPTH
    if case == "sparse_valid":
        # whole rows invalid, and valid slots after invalid ones
        valid = rng.random((n, r)) < 0.4
        valid[rng.random(n) < 0.3] = False
    else:
        valid = rng.random((n, r)) < 0.8
    if case == "int32_min":
        ts = rng.choice(np.array([I32_MIN, I32_MIN + 1, I32_MIN + 2, 0], np.int64), size=(n, r))
    else:
        ts = rng.integers(0, 4, size=(n, r))  # few values: ties within and across rings
    ints = rng.integers(I32_MIN, np.iinfo(np.int32).max, size=(n, r), dtype=np.int64)
    floats = rng.standard_normal((n, r, 3)).astype(np.float32)
    if case == "nan_negzero":
        pick = rng.random(floats.shape)
        floats[pick < 0.3] = np.nan
        floats[(pick >= 0.3) & (pick < 0.6)] = -0.0
        floats[(pick >= 0.6) & (pick < 0.7)] = 0.0
    mask = rng.random((n, r, CHANNELS)) < 0.5
    lanes = [jnp.asarray(ints, jnp.int32), jnp.asarray(floats), jnp.asarray(mask)]
    return jnp.asarray(valid), jnp.asarray(ts, jnp.int32), lanes


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("n", [1, 7, 1024])
@pytest.mark.parametrize("case", ["random", "sparse_valid", "int32_min", "nan_negzero"])
def test_merge_rings_matches_sort_reference_bitwise(case, n):
    rng = np.random.default_rng([n, len(case)])
    new = _ring(rng, n, case)
    old = _ring(rng, n, case)
    got_valid, got_ts, got_lanes = jax.jit(_merge_rings)(*new, *old)
    want_valid, want_ts, want_lanes = jax.jit(_merge_rings_reference)(*new, *old)
    for got, want in zip([got_valid, got_ts, *got_lanes], [want_valid, want_ts, *want_lanes]):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want))


N_ROWS = 4096


def _n_row_sorts_and_gathers(fn, *args) -> list[str]:
    """Names of the sort and gather ops in ``fn``'s StableHLO with an
    operand or result whose leading dimension is N_ROWS."""
    module = jax.jit(fn).lower(*args).compiler_ir("stablehlo")
    found = []

    def visit(op):
        if op.name in ("stablehlo.sort", "stablehlo.gather"):
            for v in list(op.operands) + list(op.results):
                shape = ir.RankedTensorType(v.type).shape
                if shape and shape[0] == N_ROWS:
                    found.append(op.name)
                    break
        return ir.WalkResult.ADVANCE

    module.operation.walk(visit)
    return found


def _ring_shapes():
    r = RECENT_DEPTH
    return (
        jax.ShapeDtypeStruct((N_ROWS, r), jnp.bool_),
        jax.ShapeDtypeStruct((N_ROWS, r), jnp.int32),
        [jax.ShapeDtypeStruct((N_ROWS, r, CHANNELS), jnp.float32),
         jax.ShapeDtypeStruct((N_ROWS, r, CHANNELS), jnp.bool_)],
    )


def test_merge_batch_state_has_no_per_device_sort_or_gather():
    b = 384  # batch rows: B and B * C differ from N_ROWS, so batch-sized ops never match
    state = jax.eval_shape(lambda: DeviceStateStore.zeros(N_ROWS, CHANNELS))
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32)
    args = (
        state, i32, jax.ShapeDtypeStruct((b,), jnp.bool_), i32, i32, i32,
        jax.ShapeDtypeStruct((b, CHANNELS), jnp.float32),
        jax.ShapeDtypeStruct((b, CHANNELS), jnp.bool_),
        jax.ShapeDtypeStruct((b, 2), jnp.int32),
    )
    assert _n_row_sorts_and_gathers(merge_batch_state, *args) == []


def test_structural_guard_flags_the_sort_reference():
    # the guard above would have caught the merge it replaced
    found = _n_row_sorts_and_gathers(_merge_rings_reference, *_ring_shapes(), *_ring_shapes())
    assert "stablehlo.sort" in found and "stablehlo.gather" in found
