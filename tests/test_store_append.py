"""The event-store append (`ops/persist.append_events`).

The append writes each arena's rows of a batch through two contiguous ring
windows; it must equal the compaction sort + per-row scatter it replaced bit
for bit (rows, cursor, epoch, count), and the lowered append must keep no
scatter into a store column (on a TPU v5e those took ~25 ms of a 39 ms fused
step over a 2^22-row store).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.lib.mlir import ir

from sitewhere_tpu.core.store import EventStore
from sitewhere_tpu.core.types import AUX_LANES, NULL_ID
from sitewhere_tpu.ops.persist import append_events
from sitewhere_tpu.ops.segment import lex_argsort, segment_ranks

I32_MIN = np.iinfo(np.int32).min
I32_MAX = np.iinfo(np.int32).max
INT_COLUMNS = ("etype", "device", "assignment", "tenant", "area", "customer",
               "asset", "ts_ms", "received_ms")


def _append_reference(store, valid, etype, device, assignment, tenant, area,
                      customer, asset, ts_ms, received_ms, values, vmask, aux):
    """The previous append: compaction sort by arena, then one scatter of
    all E rows into every store column (padding rows dropped)."""
    s = store.capacity
    a_n = store.arenas
    acap = store.arena_capacity
    arena = jnp.where(valid & (tenant >= 0), tenant % a_n,
                      jnp.where(valid, 0, a_n))
    sorted_keys, perm = lex_argsort([arena])
    s_arena = sorted_keys[0]
    rank, _ = segment_ranks(s_arena)
    arena_safe = jnp.clip(s_arena, 0, a_n - 1)
    cur = store.cursor[arena_safe]
    pos = jnp.where(s_arena < a_n, arena_safe * acap + (cur + rank) % acap, s)
    counts = jnp.sum(
        (s_arena[:, None] == jnp.arange(a_n)[None, :]).astype(jnp.int32),
        axis=0)
    n = jnp.sum(valid[perm].astype(jnp.int32))
    rows = dict(etype=etype, device=device, assignment=assignment,
                tenant=tenant, area=area, customer=customer, asset=asset,
                ts_ms=ts_ms, received_ms=received_ms, values=values,
                vmask=vmask, aux=aux)
    cols = {k: getattr(store, k).at[pos].set(v[perm], mode="drop")
            for k, v in rows.items()}
    new = EventStore(
        cursor=(store.cursor + counts) % jnp.int32(acap),
        epoch=store.epoch + (store.cursor + counts) // jnp.int32(acap),
        valid=store.valid.at[pos].set(True, mode="drop"),
        **cols,
    )
    return new, n


# case: (arenas, arena capacity, E, channels, start cursor, valid rows)
CASES = {
    "a1_interleaved": (1, 64, 16, 8, 5, "interleaved"),
    "a1_all_valid": (1, 64, 16, 8, 0, "all"),
    "a1_none_valid": (1, 64, 16, 8, 7, "none"),
    "a1_wrap_mid_batch": (1, 64, 16, 8, 64 - 8, "all"),
    "a1_e_eq_acap": (1, 32, 32, 8, 13, "interleaved"),
    "a1_overlapping_windows": (1, 32, 24, 1, 29, "all"),
    "a1_null_tenants": (1, 64, 16, 1, 60, "null_tenants"),
    "a4_interleaved": (4, 64, 16, 8, 0, "interleaved"),
    "a4_null_tenants": (4, 64, 16, 1, 55, "null_tenants"),
    "a4_wrap_mid_batch": (4, 32, 16, 8, 32 - 8, "all"),
    "a4_overlapping_windows": (4, 16, 12, 8, 11, "all"),
    "a4_e_eq_acap": (4, 16, 16, 1, 3, "interleaved"),
}


def _ints(rng, shape):
    x = rng.integers(I32_MIN, I32_MAX, size=shape, dtype=np.int64)
    x[rng.random(shape) < 0.1] = I32_MIN
    return x.astype(np.int32)


def _floats(rng, shape):
    x = rng.standard_normal(shape).astype(np.float32)
    pick = rng.random(shape)
    x[pick < 0.15] = np.nan
    x[(pick >= 0.15) & (pick < 0.3)] = -0.0
    return x


def _filled_store(rng, arenas, acap, channels, cursor):
    """A store whose every row holds data, so untouched rows must survive."""
    s = arenas * acap
    store = EventStore.zeros(s, channels, arenas)
    cols = {k: jnp.asarray(_ints(rng, (s,))) for k in INT_COLUMNS}
    return dataclasses.replace(
        store,
        cursor=jnp.asarray(cursor, jnp.int32),
        epoch=jnp.asarray(rng.integers(0, 5, arenas), jnp.int32),
        values=jnp.asarray(_floats(rng, (s, channels))),
        vmask=jnp.asarray(rng.random((s, channels)) < 0.5),
        aux=jnp.asarray(_ints(rng, (s, AUX_LANES))),
        valid=jnp.asarray(rng.random(s) < 0.5),
        **cols,
    )


def _batch(rng, e, channels, arenas, rows):
    if rows == "all":
        valid = np.ones(e, bool)
    elif rows == "none":
        valid = np.zeros(e, bool)
    else:
        valid = rng.random(e) < 0.6
    tenant = rng.integers(0, 3 * arenas, e).astype(np.int32)
    if rows == "null_tenants":
        tenant[rng.random(e) < 0.4] = NULL_ID
    batch = {k: _ints(rng, (e,)) for k in INT_COLUMNS}
    batch.update(tenant=tenant, values=_floats(rng, (e, channels)),
                 vmask=rng.random((e, channels)) < 0.5,
                 aux=_ints(rng, (e, AUX_LANES)))
    return dict(valid=jnp.asarray(valid),
                **{k: jnp.asarray(v) for k, v in batch.items()})


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("case", list(CASES))
def test_append_matches_scatter_reference_bitwise(case):
    arenas, acap, e, channels, cursor, rows = CASES[case]
    rng = np.random.default_rng(list(map(ord, case)))
    start = [(cursor + 5 * a) % acap for a in range(arenas)]
    got = want = _filled_store(rng, arenas, acap, channels, start)
    new_fn, ref_fn = jax.jit(append_events), jax.jit(_append_reference)
    for _ in range(3):  # consecutive batches: cursors advance and wrap
        batch = _batch(rng, e, channels, arenas, rows)
        got, got_n = new_fn(got, **batch)
        want, want_n = ref_fn(want, **batch)
        assert int(got_n) == int(want_n)
        for field in dataclasses.fields(EventStore):
            g, w = getattr(got, field.name), getattr(want, field.name)
            assert g.dtype == w.dtype and g.shape == w.shape, field.name
            np.testing.assert_array_equal(_bits(g), _bits(w),
                                          err_msg=field.name)


S_ROWS = 1 << 22
E_ROWS = 65536


def _store_scatters(fn) -> list[str]:
    """Scatter ops in ``fn``'s StableHLO (append at S = 2^22, E = 65,536)
    whose operand has the store's S rows."""
    store = jax.eval_shape(lambda: EventStore.zeros(S_ROWS))
    i32 = jax.ShapeDtypeStruct((E_ROWS,), jnp.int32)
    channels = store.values.shape[1]
    args = dict(
        valid=jax.ShapeDtypeStruct((E_ROWS,), jnp.bool_),
        values=jax.ShapeDtypeStruct((E_ROWS, channels), jnp.float32),
        vmask=jax.ShapeDtypeStruct((E_ROWS, channels), jnp.bool_),
        aux=jax.ShapeDtypeStruct((E_ROWS, AUX_LANES), jnp.int32),
        **{k: i32 for k in INT_COLUMNS},
    )
    module = jax.jit(fn).lower(store, **args).compiler_ir("stablehlo")
    found = []

    def visit(op):
        if op.name == "stablehlo.scatter":
            shape = ir.RankedTensorType(op.operands[0].type).shape
            if shape and shape[0] == S_ROWS:
                found.append(op.name)
        return ir.WalkResult.ADVANCE

    module.operation.walk(visit)
    return found


def test_append_has_no_scatter_into_store_columns():
    assert _store_scatters(append_events) == []


def test_structural_guard_flags_the_scatter_reference():
    # the guard above would have caught the append it replaced: one
    # scatter per store column
    assert len(_store_scatters(_append_reference)) == 13
