"""Windowed device-state aggregation + merge kernel.

This is the TPU replacement for the reference's device-state path ("hot loop
#3", SURVEY.md §3.3): Kafka Streams ``groupByKey -> 5s tumbling window ->
DeviceStateAggregator`` (service-device-state/.../kafka/DeviceStatePipeline.java:80-88,
DeviceStateAggregator.java:29-68) followed by a per-assignment JPA merge that
keeps the latest value plus the 3 most recent events per event class
(persistence/rdb/RdbDeviceStateMergeStrategy.java:41-120).

One call merges one batch/window of events into the HBM-resident
``DeviceStateStore``:
  * recent-event rings (depth R=3, most-recent-first) per class are updated
    with a batch sort + rank-from-end + masked scatter into a batch ring,
    then a row-wise top-R merge against the existing ring that ranks the 2R
    candidates by pairwise compares and selects — elementwise over devices,
    no per-device sort or gather, no data-dependent shapes.
  * latest-per-channel measurement values use an argmax-scatter over
    (device, channel) segments — exact even with duplicate timestamps
    (batch sequence breaks ties), robust under at-least-once replay.
  * last-interaction / presence / per-type counters are plain max/add scatters.

Correctness does not depend on batch boundaries aligning with wall-clock
windows: merging two half-windows yields the same state as one full window
(tested against a numpy oracle in tests/test_pipeline.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from sitewhere_tpu.core.state import LOC_LANES, RECENT_DEPTH, DeviceStateStore
from sitewhere_tpu.core.types import NUM_EVENT_TYPES, EventType, PresenceState
from sitewhere_tpu.ops.segment import INT32_MAX, INT32_MIN, lex_argsort, segment_ranks

_NEG_SAFE_MIN = INT32_MIN + 1


def _batch_recent_ring(
    n_devices: int,
    take: jax.Array,     # bool[B] rows of this event class
    dev: jax.Array,      # int32[B]
    ts: jax.Array,       # int32[B]
    seq: jax.Array,      # int32[B]
    lanes: list[jax.Array],  # per-row payload lanes to carry into the ring
) -> tuple[jax.Array, jax.Array, list[jax.Array]]:
    """Extract the up-to-R most recent events per device from the batch.

    Returns (ring_valid[N,R], ring_ts[N,R], ring_lanes) with slot 0 = newest.
    """
    r_depth = RECENT_DEPTH
    dev_key = jnp.where(take, dev, n_devices)  # invalid rows sort to the end
    sorted_keys, perm = lex_argsort([dev_key, ts, seq])
    s_devkey = sorted_keys[0]
    s_ts = ts[perm]
    s_lanes = [lane[perm] for lane in lanes]
    _, rank_end = segment_ranks(s_devkey)
    live = (s_devkey < n_devices) & (rank_end < r_depth)
    # rank_end==0 is the newest -> slot 0
    slot = rank_end
    d_w = jnp.where(live, s_devkey, n_devices)  # OOB -> dropped
    ring_valid = jnp.zeros((n_devices, r_depth), jnp.bool_).at[d_w, slot].set(True, mode="drop")
    ring_ts = jnp.full((n_devices, r_depth), INT32_MIN, jnp.int32).at[d_w, slot].set(s_ts, mode="drop")
    ring_lanes = []
    for lane in s_lanes:
        shape = (n_devices, r_depth) + lane.shape[1:]
        ring_lanes.append(jnp.zeros(shape, lane.dtype).at[d_w, slot].set(lane, mode="drop"))
    return ring_valid, ring_ts, ring_lanes


def _merge_rings(
    new_valid: jax.Array, new_ts: jax.Array, new_lanes: list[jax.Array],
    old_valid: jax.Array, old_ts: jax.Array, old_lanes: list[jax.Array],
) -> tuple[jax.Array, jax.Array, list[jax.Array]]:
    """Row-wise top-R merge of batch ring + existing ring (most-recent-first).

    New entries are preferred on timestamp ties (later arrival wins, matching
    the reference merge strategy's replace-on-merge behavior).

    Candidate i of a row is new slot i, then old slot i - R_new. Its sort key
    is (invalid, -max(ts, INT32_MIN + 1), i): invalid last, newest first,
    ties by position. Rank each candidate by counting the candidates whose
    key is smaller (pairwise compares of [N] vectors; the ranks are a
    permutation), then fill output slot r with the candidate of rank r by a
    select chain. Everything is elementwise over rows, so it fuses into one
    pass over the rings with no row sort and no gather, and values are copied
    bit for bit (NaN, -0.0 and int lanes unchanged)."""
    def candidates(new: jax.Array, old: jax.Array) -> list[jax.Array]:
        # device axis last and minor-most ([..., N]), as the TPU lays out the
        # state: a candidate is a contiguous slice and a rank broadcasts over
        # its lanes. Left free, the compiler lays the selects out
        # channel-minor, each [8]-lane row padded to 128 lanes.
        def device_minor(x: jax.Array) -> jax.Array:
            x = jnp.moveaxis(x, 0, -1)
            return with_layout_constraint(x, Layout(tuple(range(x.ndim))))

        new_t, old_t = device_minor(new), device_minor(old)
        return [new_t[i] for i in range(new_t.shape[0])] + [old_t[i] for i in range(old_t.shape[0])]

    valid = candidates(new_valid, old_valid)  # bool[N] each
    ts = candidates(new_ts, old_ts)           # int32[N] each
    # two keys, not one packed int32: packing would collide real
    # near-INT32_MIN timestamps with the invalid sentinel
    key_ts = [jnp.maximum(t, _NEG_SAFE_MIN) for t in ts]
    k = len(ts)
    rank = [jnp.zeros_like(ts[0]) for _ in range(k)]
    for i in range(k):
        for j in range(i):
            # candidate j (earlier) sorts before i unless i wins on the keys
            j_first = (valid[j] & ~valid[i]) | ((valid[j] == valid[i]) & (key_ts[j] >= key_ts[i]))
            rank[i] = rank[i] + j_first.astype(jnp.int32)
            rank[j] = rank[j] + (~j_first).astype(jnp.int32)

    def select(cands: list[jax.Array]) -> jax.Array:
        """[N, R, ...]: slot r takes the candidate whose rank is r."""
        slots = []
        for r in range(RECENT_DEPTH):
            out = cands[-1]
            for i in range(k - 2, -1, -1):
                out = jnp.where(rank[i] == r, cands[i], out)
            slots.append(out)
        return jnp.moveaxis(jnp.stack(slots), -1, 0)

    out_lanes = [select(candidates(n, o)) for n, o in zip(new_lanes, old_lanes)]
    return select(valid), select(ts), out_lanes


def merge_batch_state(
    state: DeviceStateStore,
    dev: jax.Array,      # int32[B] dense device id (found events only)
    found: jax.Array,    # bool[B]
    etype: jax.Array,    # int32[B]
    ts_ms: jax.Array,    # int32[B]
    seq: jax.Array,      # int32[B]
    values: jax.Array,   # float32[B, C]
    vmask: jax.Array,    # bool[B, C]
    aux: jax.Array,      # int32[B, AUX]
) -> DeviceStateStore:
    """Merge one batch of looked-up events into the device state store."""
    n = state.device_capacity
    c = values.shape[1]
    dev_safe = jnp.where(found, dev, n)  # OOB -> dropped in scatters

    # --- measurements -----------------------------------------------------
    take_m = found & (etype == EventType.MEASUREMENT)
    m_valid, m_ts, (m_vals, m_mask) = _batch_recent_ring(
        n, take_m, dev, ts_ms, seq, [values, vmask]
    )
    rm_valid, rm_ts, (rm_vals, rm_mask) = _merge_rings(
        m_valid, m_ts, [m_vals, m_mask],
        state.recent_meas_valid, state.recent_meas_ms,
        [state.recent_meas, state.recent_meas_mask],
    )

    # latest value per (device, channel): argmax-scatter with (ts, seq) key
    ch_take = take_m[:, None] & vmask                     # bool[B, C]
    flat_seg = (dev_safe[:, None] * c + jnp.arange(c, dtype=jnp.int32)[None, :])
    flat_seg = jnp.where(ch_take, flat_seg, n * c).reshape(-1)
    flat_ts = jnp.broadcast_to(ts_ms[:, None], ch_take.shape).reshape(-1)
    flat_seq = jnp.broadcast_to(seq[:, None], ch_take.shape).reshape(-1)
    flat_val = values.reshape(-1)
    flat_take = ch_take.reshape(-1)
    k1 = jnp.where(flat_take, flat_ts, INT32_MIN)
    max_ts = jnp.full((n * c,), INT32_MIN, jnp.int32).at[flat_seg].max(k1, mode="drop")
    on_max = flat_take & (flat_ts == max_ts.at[flat_seg].get(mode="fill", fill_value=INT32_MIN))
    k2 = jnp.where(on_max, flat_seq, INT32_MIN)
    max_seq = jnp.full((n * c,), INT32_MIN, jnp.int32).at[flat_seg].max(k2, mode="drop")
    winner = on_max & (flat_seq == max_seq.at[flat_seg].get(mode="fill", fill_value=INT32_MIN))
    w_seg = jnp.where(winner, flat_seg, n * c)
    # only overwrite when the batch value is at least as new as the stored one
    cand_val = jnp.full((n * c,), 0.0, jnp.float32).at[w_seg].set(flat_val, mode="drop")
    cand_ts = jnp.full((n * c,), INT32_MIN, jnp.int32).at[w_seg].set(flat_ts, mode="drop")
    cand_val = cand_val.reshape(n, c)
    cand_ts = cand_ts.reshape(n, c)
    newer = cand_ts >= state.meas_last_ms
    meas_last = jnp.where(newer & (cand_ts > INT32_MIN), cand_val, state.meas_last)
    meas_last_ms = jnp.maximum(state.meas_last_ms, cand_ts)

    # --- locations --------------------------------------------------------
    # vmask lane 0 gates the ring: a LOCATION event decoded without
    # coordinates (null lat/lon) counts in event_counts but must not record
    # a (0, 0) null-island row
    take_l = found & (etype == EventType.LOCATION) & vmask[:, 0]
    l_valid, l_ts, (l_vals,) = _batch_recent_ring(
        n, take_l, dev, ts_ms, seq, [values[:, :LOC_LANES]]
    )
    rl_valid, rl_ts, (rl_vals,) = _merge_rings(
        l_valid, l_ts, [l_vals],
        state.recent_loc_valid, state.recent_loc_ms, [state.recent_loc],
    )

    # --- alerts -----------------------------------------------------------
    take_a = found & (etype == EventType.ALERT)
    a_valid, a_ts, (a_level, a_type) = _batch_recent_ring(
        n, take_a, dev, ts_ms, seq,
        [values[:, 0].astype(jnp.int32), aux[:, 0]],
    )
    ra_valid, ra_ts, (ra_level, ra_type) = _merge_rings(
        a_valid, a_ts, [a_level, a_type],
        state.recent_alert_valid, state.recent_alert_ms,
        [state.recent_alert_level, state.recent_alert_type],
    )

    # --- presence / interaction / counters --------------------------------
    last_inter = state.last_interaction_ms.at[dev_safe].max(
        jnp.where(found, ts_ms, INT32_MIN), mode="drop"
    )
    presence = state.presence.at[dev_safe].set(
        jnp.where(found, jnp.int32(PresenceState.PRESENT), jnp.int32(PresenceState.UNKNOWN)),
        mode="drop",
    )
    et_safe = jnp.clip(etype, 0, NUM_EVENT_TYPES - 1)
    counts = state.event_counts.at[dev_safe, et_safe].add(
        found.astype(jnp.int32), mode="drop"
    )

    return DeviceStateStore(
        last_interaction_ms=last_inter,
        presence=presence,
        meas_last=meas_last,
        meas_last_ms=meas_last_ms,
        recent_meas=rm_vals,
        recent_meas_mask=rm_mask,
        recent_meas_ms=rm_ts,
        recent_meas_valid=rm_valid,
        recent_loc=rl_vals,
        recent_loc_ms=rl_ts,
        recent_loc_valid=rl_valid,
        recent_alert_level=ra_level,
        recent_alert_type=ra_type,
        recent_alert_ms=ra_ts,
        recent_alert_valid=ra_valid,
        event_counts=counts,
    )


def presence_sweep(
    state: DeviceStateStore,
    device_active: jax.Array,  # bool[N] registered devices
    now_ms: jax.Array,
    missing_interval_ms: jax.Array,
) -> tuple[DeviceStateStore, jax.Array]:
    """Mark devices presence-MISSING when last interaction is too old.

    Vectorized analog of DevicePresenceManager's periodic scan
    (service-device-state/.../presence/DevicePresenceManager.java:103-160,
    default missing interval 8h). Returns (state, newly_missing mask) so the
    host can fire presence-missing notifications exactly once per transition
    (the reference's PresenceNotificationStrategies SendOnce semantics)."""
    seen = state.last_interaction_ms > INT32_MIN
    stale = seen & (state.last_interaction_ms < now_ms - missing_interval_ms)
    was_present = state.presence == PresenceState.PRESENT
    newly_missing = device_active & stale & was_present
    presence = jnp.where(
        device_active & stale, jnp.int32(PresenceState.MISSING), state.presence
    )
    import dataclasses

    return dataclasses.replace(state, presence=presence), newly_missing
