"""Sharded pipeline: the full event engine over an ICI device mesh.

Every shard owns a contiguous slice of the token space and device-row space
(parallel/mesh.py), so after routing, each shard runs the identical fused
pipeline (pipeline.py) on its local slice — Kafka partition-locality without
the broker. The engine state is a *stacked* pytree with a leading
``[n_shards, ...]`` axis sharded over the mesh; ``shard_map`` maps the
single-chip step over it. Optional on-device re-routing (exchange=True) runs
the ICI all-to-all first (BASELINE.json config #5, multi-shard fan-in).

Host contract: per-shard batches carry **local** token ids
(global_token = shard * tokens_per_shard + local_token); the ingest router
(parallel/router.py) computes the shard from the global token id, exactly
like the reference's token-keyed Kafka partitioner
(EventSourcesManager.java:183).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import numpy as np

from sitewhere_tpu.core.events import EventBatch
from sitewhere_tpu.core.types import (
    NULL_ID,
    NUM_EVENT_TYPES,
    EventType,
    PresenceState,
)
from sitewhere_tpu.pipeline import (
    PipelineConfig,
    PipelineState,
    StepOutput,
    pipeline_step,
)
from sitewhere_tpu.parallel.exchange import exchange_events
from sitewhere_tpu.parallel.mesh import SHARD_AXIS, make_mesh, stack_sharding


def create_stacked_state(
    mesh,
    device_capacity_per_shard: int,
    token_capacity_per_shard: int,
    assignment_capacity_per_shard: int,
    store_capacity_per_shard: int,
    channels: int = 8,
) -> PipelineState:
    """Create engine state stacked over the mesh's shard axis and placed
    shard-per-device."""
    n = mesh.devices.size

    def stacked() -> PipelineState:
        single = PipelineState.create(
            device_capacity_per_shard,
            token_capacity_per_shard,
            assignment_capacity_per_shard,
            store_capacity_per_shard,
            channels,
        )
        return jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (n,) + leaf.shape), single
        )

    state = jax.jit(stacked, out_shardings=stack_sharding(mesh, jax.eval_shape(stacked)))()
    return state


@functools.partial(
    jax.jit,
    static_argnames=("config", "mesh", "exchange", "tokens_per_shard", "bucket"),
    donate_argnums=(0,),
)
def _sharded_step(
    state: PipelineState,
    batch: EventBatch,  # stacked [n_shards, B_local, ...]
    *,
    config: PipelineConfig,
    mesh,
    exchange: bool,
    tokens_per_shard: int,
    bucket: int,
):
    n_shards = mesh.devices.size

    def local_step(state_blk, batch_blk):
        # strip the leading stacked axis of this shard's block
        lstate = jax.tree_util.tree_map(lambda x: x[0], state_blk)
        lbatch = jax.tree_util.tree_map(lambda x: x[0], batch_blk)
        n_overflow = jnp.zeros((), jnp.int32)
        if exchange:
            res = exchange_events(lbatch, n_shards, tokens_per_shard, bucket)
            lbatch, n_overflow = res.batch, res.n_overflow
        new_state, out = pipeline_step(lstate, lbatch, config)
        out = out._replace(n_missed=out.n_missed + n_overflow)
        new_state = dataclasses.replace(
            new_state,
            metrics=dataclasses.replace(
                new_state.metrics, missed=new_state.metrics.missed + n_overflow
            ),
        )
        return (
            jax.tree_util.tree_map(lambda x: x[None], new_state),
            jax.tree_util.tree_map(lambda x: x[None], out),
        )

    return jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )(state, batch)


class ShardedEngine:
    """Host handle for the sharded engine: owns the mesh, compiled step, and
    stacked state. The reference analog is the full multi-service deployment
    (one Streams task per partition per service); here it is one object."""

    def __init__(
        self,
        n_shards: int | None = None,
        device_capacity_per_shard: int = 4096,
        token_capacity_per_shard: int = 8192,
        assignment_capacity_per_shard: int = 8192,
        store_capacity_per_shard: int = 1 << 16,
        channels: int = 8,
        config: PipelineConfig | None = None,
        exchange: bool = False,
        bucket_capacity: int | None = None,
    ):
        self.mesh = make_mesh(n_shards)
        self.n_shards = self.mesh.devices.size
        self.tokens_per_shard = token_capacity_per_shard
        self.config = config or PipelineConfig()
        self.exchange = exchange
        self.bucket = bucket_capacity or 0
        self.channels = channels
        self.state = create_stacked_state(
            self.mesh,
            device_capacity_per_shard,
            token_capacity_per_shard,
            assignment_capacity_per_shard,
            store_capacity_per_shard,
            channels,
        )

    def shard_of_token(self, global_token: int) -> tuple[int, int]:
        """(shard, local_token) for a global token id — the host-side
        partitioner."""
        return global_token // self.tokens_per_shard, global_token % self.tokens_per_shard

    def step(self, stacked_batch: EventBatch) -> StepOutput:
        """Run one sharded step; returns stacked per-shard outputs."""
        if self.exchange and not self.bucket:
            raise ValueError("exchange=True requires bucket_capacity")
        self.state, out = _sharded_step(
            self.state,
            stacked_batch,
            config=self.config,
            mesh=self.mesh,
            exchange=self.exchange,
            tokens_per_shard=self.tokens_per_shard,
            bucket=self.bucket,
        )
        return out

    def global_metrics(self):
        """Sum per-shard metrics (host-side psum analog for reporting).
        Only scalar counters ([S] after stacking) fold here — the packed
        per-tenant counter grid is served whole by the engine's
        tenant_pipeline_counters accessor, not as one meaningless sum."""
        m = self.state.metrics
        return {
            f.name: int(jnp.sum(getattr(m, f.name)))
            for f in dataclasses.fields(m)
            if jnp.ndim(getattr(m, f.name)) <= 1
        }

    # --------------------------------------------------------------- queries
    def query_events(
        self,
        etype: EventType | None = None,
        tenant_id: int | None = None,
        since_ms: int | None = None,
        until_ms: int | None = None,
        limit: int = 100,
    ) -> dict:
        """Global newest-first event query: every shard scans its local ring
        in parallel (vmapped on-device filter + top-k), then the per-shard
        pages merge on the host. The reference analog is a scatter-gather
        query across per-partition stores."""
        imin, imax = -(2**31), 2**31 - 1
        res = _stacked_query(
            self.state.store,
            jnp.int32(int(etype) if etype is not None else NULL_ID),
            jnp.int32(tenant_id if tenant_id is not None else NULL_ID),
            jnp.int32(since_ms if since_ms is not None else imin),
            jnp.int32(until_ms if until_ms is not None else imax),
            limit=limit,
        )
        total = int(np.sum(np.asarray(res.total)))
        # one device->host transfer per field, not per row
        ns = np.asarray(res.n)
        ts = np.asarray(res.ts_ms)
        etypes = np.asarray(res.etype)
        devices = np.asarray(res.device)
        assignments = np.asarray(res.assignment)
        tenants = np.asarray(res.tenant)
        # vectorized k-way merge of the per-shard pages: flatten the valid
        # (shard, slot) pairs and argsort once — no per-row Python even at
        # scatter-gather page sizes
        valid = np.arange(ts.shape[1])[None, :] < ns[:, None]
        s_idx, i_idx = np.nonzero(valid)
        order = np.argsort(-ts[s_idx, i_idx], kind="stable")[:limit]
        sel_s, sel_i = s_idx[order], i_idx[order]
        events = [
            {
                "shard": int(s),
                "type": EventType(int(etypes[s, i])).name,
                "device": int(devices[s, i]),
                "assignmentId": int(assignments[s, i]),
                "tenant": int(tenants[s, i]),
                "eventDateMs": int(ts[s, i]),
            }
            for s, i in zip(sel_s, sel_i)
        ]
        return {"total": total, "events": events}

    def presence_sweep(self, now_ms: int, missing_ms: int) -> list[tuple[int, int]]:
        """Mark stale devices MISSING on every shard at once; returns
        (shard, local_device_id) pairs newly missing."""
        self.state, newly = _stacked_sweep(
            self.state, jnp.int32(now_ms), jnp.int32(missing_ms))
        out = np.asarray(newly)
        return [(int(s), int(d)) for s, d in zip(*np.nonzero(out))]

    def device_state_summary(self, shard: int, device_id: int) -> dict:
        """Read back one device's aggregated state from its owning shard —
        three device->host transfers total, not one per scalar."""
        ds = self.state.device_state
        presence = int(jax.device_get(ds.presence[shard, device_id]))
        last = int(jax.device_get(ds.last_interaction_ms[shard, device_id]))
        counts = np.asarray(jax.device_get(ds.event_counts[shard, device_id]))
        return {
            "shard": shard,
            "device": device_id,
            "presence": PresenceState(presence).name,
            "lastInteractionMs": last,
            "eventCounts": {
                EventType(e).name: int(counts[e])
                for e in range(NUM_EVENT_TYPES)
            },
        }

    # ----------------------------------------------------------- durability
    def save(self, directory) -> dict:
        """Snapshot the stacked state (all shards) to a directory."""
        import json
        import pathlib

        from sitewhere_tpu.utils.checkpoint import _flatten_state

        directory = pathlib.Path(directory)
        directory.mkdir(parents=True, exist_ok=True)
        arrays = _flatten_state(self.state)
        np.savez_compressed(directory / "sharded_state.npz", **arrays)
        manifest = {
            "format": 1,
            "n_shards": self.n_shards,
            "tokens_per_shard": self.tokens_per_shard,
            "channels": self.channels,
            "metrics": self.global_metrics(),
        }
        (directory / "sharded_manifest.json").write_text(json.dumps(manifest))
        return manifest

    def restore(self, directory) -> None:
        """Load a snapshot saved by :meth:`save` into this engine's mesh
        (shard count must match; resharding is a host-side reshape away)."""
        import json
        import pathlib

        directory = pathlib.Path(directory)
        manifest = json.loads(
            (directory / "sharded_manifest.json").read_text())
        for key, have in (("n_shards", self.n_shards),
                          ("tokens_per_shard", self.tokens_per_shard),
                          ("channels", self.channels)):
            if manifest[key] != have:
                raise ValueError(
                    f"snapshot {key}={manifest[key]} != engine {key}={have}")
        data = np.load(directory / "sharded_state.npz")
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.state)
        sharding = stack_sharding(self.mesh, self.state)
        shardings_flat = jax.tree_util.tree_leaves(sharding)
        leaves = []
        for (p, cur), sh in zip(flat, shardings_flat):
            key = jax.tree_util.keystr(p)
            if key.startswith(".metrics.") and key not in data.files:
                # a counter added after the snapshot was written (e.g.
                # tenant_counters, PR 3): keep the fresh zeros — restore
                # old history rather than refusing it
                leaves.append(cur)
                continue
            arr = data[key]
            if arr.shape != cur.shape:
                raise ValueError(
                    f"snapshot leaf {key} shape {arr.shape} != engine "
                    f"{cur.shape} (capacity mismatch)")
            leaves.append(jax.device_put(arr, sh))
        self.state = jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnames=("limit",))
def _stacked_query(store, etype, tenant, t0, t1, *, limit, device=None,
                   device_shard=None, assignment=None, assignment_shard=None,
                   aux0=None, aux1=None, area=None, customer=None):
    """Per-shard ring query vmapped over the stacked shard axis; XLA keeps
    each shard's scan on its own device (no cross-shard traffic until the
    host merges the top pages). ``device``/``device_shard`` (and the
    analogous ``assignment``/``assignment_shard``) restrict the scan to one
    row on its owning shard (other shards match nothing); the remaining
    optional filters pass straight through to query_store on every shard."""
    from sitewhere_tpu.ops.query import query_store

    n_shards = jax.tree_util.tree_leaves(store)[0].shape[0]

    def one(st, sidx):
        dev = jnp.int32(NULL_ID) if device is None else device
        if device_shard is not None:
            # -2 is matched by no store row (valid rows have device >= 0,
            # and padding rows are masked by store.valid)
            dev = jnp.where(sidx == device_shard, dev, jnp.int32(-2))
        asn = None
        if assignment is not None:
            asn = assignment
            if assignment_shard is not None:
                asn = jnp.where(sidx == assignment_shard, asn, jnp.int32(-2))
        return query_store(st, dev, etype, tenant, t0, t1, limit=limit,
                           assignment=asn, aux0=aux0, aux1=aux1, area=area,
                           customer=customer)

    return jax.vmap(one)(store, jnp.arange(n_shards, dtype=jnp.int32))


@functools.partial(jax.jit, donate_argnums=(0,))
def _stacked_sweep(state: PipelineState, now_ms, missing_ms):
    from sitewhere_tpu.ops.window import presence_sweep

    def one(ds, active):
        return presence_sweep(ds, active, now_ms, missing_ms)

    ds, newly = jax.vmap(one)(state.device_state, state.registry.device_active)
    return dataclasses.replace(state, device_state=ds), newly


# devicewatch (ISSUE 11): the SPMD program families. Call sites resolve
# these module globals at dispatch time, so the end-of-module shims
# cover every ShardedEngine/DistributedEngine. Unbudgeted (one process
# serves many mesh/capacity configs across tests); the ROADMAP-2
# pjit/shard_map work inherits this seam as its instrument panel.
from sitewhere_tpu.utils.devicewatch import watched_jit  # noqa: E402

_sharded_step = watched_jit(
    _sharded_step, family="sharded.step",
    static_argnames=("config", "mesh", "exchange", "tokens_per_shard",
                     "bucket"))
_stacked_query = watched_jit(_stacked_query, family="sharded.query",
                             static_argnames=("limit",))
_stacked_sweep = watched_jit(_stacked_sweep, family="sharded.sweep")


# ===========================================================================
# SPMD backend of the REAL engine (ISSUE 16 tentpole). Everything above is
# the stripped-down prototype (kept: DistributedEngine and the multi-host
# demo ride it); everything below promotes the stacked-state idea into a
# first-class Engine subclass with every host surface intact — WAL, QoS,
# tracing, flight records, conservation ledger, CEP rules, devicewatch.
# ===========================================================================

import time  # noqa: E402

from sitewhere_tpu.core.events import HostEventBuffer  # noqa: E402
from sitewhere_tpu.core.types import DeviceAssignmentStatus  # noqa: E402
from sitewhere_tpu.core.registry import MAX_ACTIVE_ASSIGNMENTS  # noqa: E402
from sitewhere_tpu.engine import (  # noqa: E402
    DeviceInfo,
    Engine,
    EngineConfig,
    QueryBatcher,
    _admin_add_assignment,
    _admin_create_device,
    _admin_set_assignment_status,
    _admin_set_device_active,
    _admin_set_parent,
    _admin_update_assignment,
    _admin_update_device,
    _fetch_query_result,
    _merge_summaries,
    tenant_cap,
    tenant_counts_dict,
)
from sitewhere_tpu.parallel.placement import (  # noqa: E402
    DEFAULT_SLOTS_PER_RANK,
    slot_for_token,
)
from sitewhere_tpu.utils.shardobs import ShardHeatTracker  # noqa: E402
from sitewhere_tpu.utils.tracing import stage  # noqa: E402

# budgeted per-engine scope names for the fused SPMD programs (distinct
# from the unbudgeted module-global shims above: an SpmdEngine dispatches
# ONE program per family in steady state, so these carry real budgets)
SPMD_FAMILY_STEP = "sharded.step"
SPMD_FAMILY_QUERY = "sharded.query"
SPMD_FAMILY_SWEEP = "sharded.sweep"
SPMD_FAMILY_SCAN = "sharded.scan_step"


def _make_spmd_step(mesh, config: PipelineConfig):
    """The fused cross-shard ingest step: ONE jit program that shard_maps
    the single-chip pipeline step over the stacked ``[S, ...]`` state and
    a stacked ``[S, B, ...]`` batch. Identical math per shard — the fused
    program IS ``pipeline_step``, once per chip, in one dispatch. It runs
    as ``jit_spmd_pipeline_step``, the name a device trace shows."""
    def local_step(state_blk, batch_blk):
        lstate = jax.tree_util.tree_map(lambda x: x[0], state_blk)
        lbatch = jax.tree_util.tree_map(lambda x: x[0], batch_blk)
        new_state, out = pipeline_step(lstate, lbatch, config)
        return (
            jax.tree_util.tree_map(lambda x: x[None], new_state),
            jax.tree_util.tree_map(lambda x: x[None], out),
        )

    fused = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    def spmd_pipeline_step(state, batch):
        return fused(state, batch)

    return jax.jit(spmd_pipeline_step, donate_argnums=(0,))


def _make_spmd_scan_step(mesh, config: PipelineConfig, capacity: int,
                         k: int):
    """K-chunk packed variant of :func:`_make_spmd_step`: each shard's
    ``[k * capacity]`` arena lane reshapes to ``[k, capacity]`` INSIDE the
    jitted program and consumes as one ``lax.scan`` — K single-chip steps
    per shard in ONE dispatch (one transfer group + one program launch,
    fused across the mesh). Only the state
    donates; the stacked batch rides in whole, exactly the single-chip
    ``make_arena_scan_step`` donation discipline. It runs as
    ``jit_spmd_scan_step``."""
    def local_step(state_blk, batch_blk):
        lstate = jax.tree_util.tree_map(lambda x: x[0], state_blk)
        lbatch = jax.tree_util.tree_map(lambda x: x[0], batch_blk)
        chunks = jax.tree_util.tree_map(
            lambda col: col.reshape((k, capacity) + col.shape[1:]), lbatch)

        def body(st, one):
            return pipeline_step(st, one, config)

        new_state, outs = jax.lax.scan(body, lstate, chunks)
        return (
            jax.tree_util.tree_map(lambda x: x[None], new_state),
            jax.tree_util.tree_map(lambda x: x[None], outs),
        )

    fused = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )

    def spmd_scan_step(state, batch):
        return fused(state, batch)

    return jax.jit(spmd_scan_step, donate_argnums=(0,))


def _spmd_sweep(state: PipelineState, now_ms, missing_ms):
    """Presence sweep over every shard in one program (vmapped; XLA keeps
    each shard's scan on its own device)."""
    from sitewhere_tpu.ops.window import presence_sweep

    ds, newly = jax.vmap(presence_sweep, in_axes=(0, 0, None, None))(
        state.device_state, state.registry.device_active, now_ms, missing_ms)
    return dataclasses.replace(state, device_state=ds), newly


@functools.partial(jax.jit, static_argnames=("t_cap",))
def _spmd_tenant_counts(state: PipelineState, t_cap: int):
    """Stacked mirror of engine._tenant_event_counts: per-shard one-hot
    segment-sum, folded over the shard axis — [t_cap, E] like single-chip."""

    def one(active, tenant, counts):
        tenant = jnp.where(active, tenant, -1)
        t_ids = jnp.arange(t_cap)
        onehot = (tenant[:, None] == t_ids[None, :]).astype(jnp.int32)
        return jnp.einsum("nt,ne->te", onehot, counts)

    per = jax.vmap(one)(state.registry.device_active,
                        state.registry.device_tenant,
                        state.device_state.event_counts)
    return per.sum(axis=0)


@jax.jit
def _device_rows(device_state, device):
    """Row ``device`` of every device-state field on every shard, in one
    program: each chip slices its own block, with no cross-chip traffic
    (one dispatch and one small transfer, not one per field); the caller
    keeps its shard's row."""
    return jax.tree_util.tree_map(lambda x: x[:, device], device_state)


def _broadcast_tree(tree, n: int):
    """Replicate every array leaf with a leading ``[n]`` axis (static
    pytree metadata — e.g. the rules layout — passes through untouched)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(jnp.asarray(a), (n,) + jnp.shape(a)), tree)


class SpmdQueryBatcher(QueryBatcher):
    """QueryBatcher whose fused round program spans every shard: per-shard
    filtered top-k in ONE vmapped pass (each shard scans its local ring on
    its own chip), then a host-side k-way merge to the exact single-chip
    page (ops.query.merge_shard_pages). Device/assignment predicates arrive
    in the GLOBAL id space (shard * capacity + local) and are localized to
    the owning shard inside the program — other shards match nothing."""

    def _compiled_for(self, qpad: int, limit: int):
        from sitewhere_tpu.ops.query import QueryParams, query_store_batch

        key = (qpad, limit)
        fn = self._programs.get(key)
        if fn is None:
            eng = self.engine
            n_shards = eng.n_shards
            dcap = eng._device_cap
            acap = eng._assignment_cap
            shard_sh = jax.NamedSharding(eng.mesh, P(SHARD_AXIS))
            repl_sh = jax.NamedSharding(eng.mesh, P())

            def spmd_query(store, params):
                def one(st, sidx):
                    def localize(col, cap):
                        # -2 is matched by no store row (valid rows carry
                        # ids >= 0; invalid rows are masked by store.valid)
                        loc = col - sidx * cap
                        return jnp.where(
                            col == NULL_ID, jnp.int32(NULL_ID),
                            jnp.where(col // cap == sidx, loc,
                                      jnp.int32(-2)))

                    p = params._replace(
                        device=localize(params.device, dcap),
                        assignment=localize(params.assignment, acap))
                    return query_store_batch(st, p, limit=limit)

                return jax.vmap(one)(
                    store, jnp.arange(n_shards, dtype=jnp.int32))

            store_struct = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=shard_sh),
                self._store_struct)
            pstruct = QueryParams(*(
                jax.ShapeDtypeStruct((qpad,), jnp.int32, sharding=repl_sh)
                for _ in QueryParams._fields))
            t0 = time.perf_counter()
            compiled = jax.jit(spmd_query).lower(store_struct,
                                                 pstruct).compile()
            dt = time.perf_counter() - t0

            def fn(store, params, _c=compiled, _s=shard_sh, _r=repl_sh):
                # no-op when already placed; insurance against an admin
                # program having handed back a differently-laid-out store
                store = jax.tree_util.tree_map(
                    lambda x: jax.device_put(x, _s), store)
                params = jax.device_put(params, _r)
                return _c(store, params)

            self._programs[key] = fn
            watch = getattr(self.engine, "devicewatch", None)
            if watch is not None:
                watch.record_aot(SPMD_FAMILY_QUERY, key=key, bucket=key,
                                 seconds=dt, compiled=compiled)
        return fn

    def _unpack_round(self, entries: list[dict], res, cursors) -> None:
        from sitewhere_tpu.ops.query import merge_shard_pages

        host = _fetch_query_result(res)   # every field [S, Q, ...]
        eng = self.engine
        off = np.arange(eng.n_shards, dtype=np.int64).reshape(-1, 1, 1)
        dev = np.asarray(host.device)
        asn = np.asarray(host.assignment)
        host = host._replace(
            device=np.where(dev >= 0, dev + off * eng._device_cap,
                            dev).astype(dev.dtype),
            assignment=np.where(asn >= 0, asn + off * eng._assignment_cap,
                                asn).astype(asn.dtype))
        for q, entry in enumerate(entries):
            pages = type(host)(*(np.asarray(col)[:, q] for col in host))
            entry["result"] = merge_shard_pages(pages, entry["limit"])
            entry["cursors"] = cursors
            entry["q"] = len(entries)
            entry["event"].set()


class SpmdEngine(Engine):
    """The real engine with its device plane sharded over the mesh.

    One engine object, one host surface (ingest_json_batch / query_events /
    register_device / metrics / rules — the full Engine API), N chips:

    - ``PipelineState`` is stacked ``[n_shards, ...]`` and sharded over a
      1-D mesh; PR 15's fixed slot space is the sharding axis —
      ``shard_for_token(token, N)`` routes exactly where the cluster's
      genesis ``owner_rank`` map would place the token.
    - Batch ingest decodes the wire batch ONCE (native scanner when
      available, else the vectorized numpy decode path), routes every row
      to its placement slot's shard vectorized, and scatters rows into the
      per-shard lanes of a stacked ``[n_shards, rows]`` staging arena
      whose device transfer matches the mesh sharding — zero host copies
      per batch, same discipline as the single-chip arena path. The
      per-row host router (:meth:`_stage_row`) survives as the slow path
      for admin/registration rows and ``arena=False`` contrast runs; one
      dispatch feeds ALL lanes to one ``shard_map``-fused
      ``pipeline_step`` program (WAL/fsync-before-dispatch, donation,
      dispatch-depth pipelining all preserved). ``scan_chunk > 1`` packs
      K chunks per shard into one ``lax.scan`` program per flush, and
      ``ingest_arenas`` depth > 1 overlaps decode of batch N+1 with
      device execution of batch N under arena-recycle backpressure.
    - Queries run per-shard top-k fused in one program per round
      (SpmdQueryBatcher) and merge on the host, byte-identical to the
      single-chip page whenever ts ties do not span shards.
    - CEP rules broadcast into every shard's slice of the fused step;
      harvest merges the per-shard pending rings (shard-major group axis).

    Id spaces: token ids stay global (one interner); device/assignment
    ids are shard-qualified — ``gid = shard * capacity + local_id`` — so
    every host mirror and REST surface speaks one flat id space while
    store rows carry local ids on device.

    v1 limits (explicit): no archive tier, no analytics window, no
    fair_tenancy, tenant_arenas == 1, single-shard device parenting, no
    precompiled rule swap, and ``search_device_states``/``get_event``/
    outbound feeds are not yet shard-aware."""

    def __init__(self, config: EngineConfig | None = None,
                 n_shards: int | None = None, arena: bool = True):
        """``n_shards``, when given, overrides ``config.shards``; either
        way ``self.config.shards`` is the number of devices spanned."""
        cfg0 = config or EngineConfig()
        if n_shards is None:
            n_shards = cfg0.shards
        for bad, why in (
                (cfg0.archive_dir, "archive tier"),
                (cfg0.analytics_devices, "analytics window"),
                (cfg0.tenant_arenas != 1, "tenant_arenas != 1"),
                (cfg0.fair_tenancy, "fair_tenancy"),
                (cfg0.autotune, "autotune")):
            if bad:
                raise ValueError(f"SpmdEngine does not support {why} (v1)")
        mesh = make_mesh(n_shards)
        n = mesh.devices.size
        if n != n_shards:
            raise ValueError(f"SpmdEngine over {n_shards} shards needs as "
                             f"many devices; JAX sees {n}")
        # arena=False keeps the per-row host router on the batch path —
        # the byte-identity oracle the arena path is tested against
        self._spmd_arena = bool(arena) and cfg0.ingest_arenas >= 0
        # the interner spans every shard's tokens; everything else in the
        # base constructor is host machinery the SPMD engine keeps as-is
        # (the stacked arena pool is built at the END of __init__, once
        # the mesh exists — _build_arena_machinery defers until then)
        super().__init__(dataclasses.replace(
            cfg0, use_native=cfg0.use_native and self._spmd_arena,
            token_capacity=cfg0.token_capacity * n, shards=n))
        c = self.config
        self.mesh = mesh
        self.n_shards = n
        self._device_cap = cfg0.device_capacity
        self._token_cap = cfg0.token_capacity
        self._assignment_cap = cfg0.assignment_capacity
        self._store_cap = cfg0.store_capacity
        # replace the single-chip state with the stacked mesh-sharded one
        self.state = create_stacked_state(
            mesh, cfg0.device_capacity, cfg0.token_capacity,
            cfg0.assignment_capacity, cfg0.store_capacity, c.channels)
        # fused SPMD programs under BUDGETED per-engine scopes (satellite:
        # one steady-state program per family; rule/zone swaps grant
        # allowance through the same devicewatch.allow seam as single-chip)
        self._step = self.devicewatch.wrap(
            _make_spmd_step(mesh, PipelineConfig(
                auto_register=c.auto_register, default_device_type=0)),
            SPMD_FAMILY_STEP, cost=True)
        self._sweep = self.devicewatch.wrap(
            jax.jit(_spmd_sweep, donate_argnums=(0,)), SPMD_FAMILY_SWEEP)
        # host router: one staging lane per shard (slot -> shard space);
        # token routes cache as (shard, local_token_id)
        self._shard_bufs = [HostEventBuffer(c.batch_capacity, c.channels)
                            for _ in range(n)]
        self._shard_tokens: list[list[int]] = [[] for _ in range(n)]
        self._tid_route: dict[int, tuple[int, int]] = {}
        # vectorized mirrors of _tid_route for the arena scatter: the
        # batch path gathers shard/ltid for EVERY row in two indexed
        # loads instead of a per-row dict probe (c.token_capacity is
        # already the global, xN space)
        self._route_shard = np.full(c.token_capacity, -1, np.int32)
        self._route_ltid = np.full(c.token_capacity, -1, np.int32)
        self._next_local_device = [0] * n
        self._next_local_assignment = [0] * n
        # shard observability plane (ISSUE 18): host-side per-shard flow
        # counters piggyback on the exact sites the conservation ledger
        # already counts (same ledger.enabled gate, so the per-shard
        # breakdown sums to the folded staging equations by
        # construction); the token->slot route mirror and the heat/skew
        # tracker carry the EXTRA accounting (slot bincount, dispatch
        # skew note, staged HWM) that shard_heat.enabled toggles
        self._shard_rows_routed = np.zeros(n, np.int64)
        self._shard_rows_dispatched = np.zeros(n, np.int64)
        self._shard_staged_hwm = np.zeros(n, np.int64)
        self._route_slot = np.full(c.token_capacity, -1, np.int32)
        self._slot_rows = np.zeros(n * DEFAULT_SLOTS_PER_RANK, np.int64)
        self.shard_heat = ShardHeatTracker(n, n * DEFAULT_SLOTS_PER_RANK)
        self._admin_spmd: dict[int, object] = {}
        # shard-aware query plane (keeps any WFQ the base ctor attached)
        old = self._query_batcher
        self._query_batcher = SpmdQueryBatcher(self,
                                               max_batch=c.query_coalesce)
        self._query_batcher._wfq = old._wfq
        # stacked arena pool + packed scan step (deferred from the base
        # constructor: both need the mesh)
        if self._spmd_arena:
            self._build_arena_machinery(max(1, c.scan_chunk))

    # ------------------------------------------------------------- routing
    def _build_arena_machinery(self, k: int) -> None:
        if not hasattr(self, "mesh"):
            # called from the base constructor before the mesh exists;
            # the SPMD pool is built at the end of __init__ instead
            return
        from sitewhere_tpu.ingest.arena import ArenaPool, ShardedStagingArena

        c = self.config
        n_arenas = c.ingest_arenas or max(1, c.dispatch_depth) + 2
        rows = c.batch_capacity * k
        self._arena_pool = ArenaPool(
            n_arenas, rows, c.channels, lanes=k,
            factory=lambda: ShardedStagingArena(
                self.n_shards, rows, c.channels, lanes=k))
        self._arena_step = None
        if k > 1:
            # fresh watch scope per rebuild: a scan-chunk retune is a
            # DECLARED program change, not shape churn
            self._arena_step = self.devicewatch.wrap(
                _make_spmd_scan_step(self.mesh, PipelineConfig(
                    auto_register=c.auto_register, default_device_type=0),
                    c.batch_capacity, k),
                SPMD_FAMILY_SCAN, cost=True)
    def _route_token(self, token_id: int) -> tuple[int, int]:
        """(shard, local_token_id) for a global interned token — the slot
        space of parallel/placement decides the shard, local ids allocate
        densely per shard in first-seen order (byte-identical to a
        single-chip engine fed this shard's substream)."""
        route = self._tid_route.get(token_id)
        if route is None:
            slot = slot_for_token(self.tokens.token(token_id),
                                  self.n_shards)
            shard = slot % self.n_shards     # == shard_for_token(token, N)
            locs = self._shard_tokens[shard]
            ltid = len(locs)
            if ltid >= self._token_cap:
                raise RuntimeError("token capacity exhausted")
            locs.append(token_id)
            route = (shard, ltid)
            self._tid_route[token_id] = route
            if token_id < len(self._route_shard):
                self._route_shard[token_id] = route[0]
                self._route_ltid[token_id] = route[1]
                self._route_slot[token_id] = slot
        return route

    def _route_new(self, tids: np.ndarray) -> None:
        """Route the batch's unseen global token ids through
        :meth:`_route_token` in FIRST-OCCURRENCE order, so local ids
        allocate exactly as the per-row router would — the store
        byte-identity invariant. Afterwards ``_route_shard`` and
        ``_route_ltid`` give every row's route by two indexed loads."""
        miss = tids[self._route_shard[tids] < 0]
        if miss.size:
            _, first = np.unique(miss, return_index=True)
            for t in miss[np.sort(first)]:
                self._route_token(int(t))

    # -------------------------------------------------------------- ingest
    def _stage_row(self, et, token_id, tenant_id, ts, now, values, mask,
                   aux0, aux1):
        self.host_counters["staged_copy_rows"] = \
            self.host_counters.get("staged_copy_rows", 0) + 1
        self.ledger.add("staged_rows", 1)
        shard, ltid = self._route_token(token_id)
        if self.ledger.enabled:
            self._shard_rows_routed[shard] += 1
        if self.shard_heat.enabled and token_id < len(self._route_slot):
            self._slot_rows[self._route_slot[token_id]] += 1
        buf = self._shard_bufs[shard]
        i = len(buf)
        if not buf.append(et, ltid, tenant_id, ts, now, (), aux0, aux1):
            self.flush_async()
            i = len(buf)
            buf.append(et, ltid, tenant_id, ts, now, (), aux0, aux1)
        if mask is not None and mask.any():
            buf.values[i, :] = values
            buf.vmask[i, :] = mask
        if buf.full:
            self.flush_async()

    def _ingest_batch_inner(self, payloads, tenant, tag, dec, native_fn,
                            binary, rec, gate_ctx=None) -> dict:
        """Batch skeleton with the SPMD arena path swapped in: the wire
        batch decodes ONCE (native scanner, else the vectorized numpy
        fallback) and scatters into the stacked per-shard arena lanes.
        Branch order and lock/WAL discipline mirror the base skeleton
        verbatim; ``arena=False`` engines fall straight through to the
        per-row router path."""
        import contextlib

        if gate_ctx is None:
            gate_ctx = contextlib.nullcontext()
        if self._arena_pool is None:
            return super()._ingest_batch_inner(payloads, tenant, tag, dec,
                                               native_fn, binary, rec,
                                               gate_ctx)
        if native_fn is None:
            with gate_ctx, self.lock:
                try:
                    with stage("ingest.decode", mark="decode",
                               rows=len(payloads)) as sp:
                        res = self._decode_batch_py(payloads, dec)
                        if res is not None:
                            sp.set_metadata(
                                failed=int(np.sum(res.rtype < 0)))
                    if res is None:
                        # mixed/stream envelopes: whole batch takes the
                        # per-request path (exact single-chip semantics)
                        predecoded = self._strict_predecode(payloads, dec)
                        self._wal_append(tag, payloads, tenant)
                        with stage("ingest.commit", mark="commit") as sp:
                            summary = self._ingest_python_fallback(
                                payloads, tenant, dec, predecoded)
                            rec.mark("decode")
                            sp.set_metadata(
                                staged=summary.get("staged", 0))
                        return summary
                    self._wal_append(tag, payloads, tenant)
                    return self._ingest_decoded_spmd(res, payloads, tenant,
                                                     dec, rec)
                finally:
                    self._clear_now_pin()
        if self.config.strict_channels:
            with gate_ctx, self.lock:
                try:
                    names_before = len(self.channel_map.names)
                    res = self._native_decode(native_fn, payloads)
                    self._check_strict_native(res, names_before)
                    self._wal_append(tag, payloads, tenant)
                    return self._ingest_decoded_spmd(res, payloads, tenant,
                                                     dec, rec)
                finally:
                    self._clear_now_pin()
        # lenient fast path: native decode OUTSIDE the lock (and the WFQ
        # turn) so concurrent receivers decode in parallel
        res = self._native_decode(native_fn, payloads)
        with gate_ctx, self.lock:
            try:
                self._wal_append(tag, payloads, tenant)
                return self._ingest_decoded_spmd(res, payloads, tenant,
                                                 dec, rec)
            finally:
                self._clear_now_pin()

    def _decode_batch_py(self, payloads, dec):
        """Vectorized-fallback decode: one pass turns a uniform wire batch
        into the native decoder's SoA ``DecodedArrays`` layout so the
        arena scatter runs identically with or without the C++ scanner.
        Interning happens in strict payload order (token, then the row's
        string fields, then alternate id — exactly :meth:`process`), so
        every interner id matches the per-request path byte for byte.
        Returns None when any payload is not a single mappable request
        (stream envelopes, multi-request frames) — the caller falls back
        to the per-request path for the whole batch. Caller holds the
        lock."""
        from sitewhere_tpu.ingest.fast_decode import (
            RT_ACK,
            RT_ALERT,
            RT_MAP,
            RT_MEASUREMENT,
            RT_REGISTER,
            RT_STATE_CHANGE,
            RTYPE_TO_ETYPE,
            DecodedArrays,
        )
        from sitewhere_tpu.ingest.fast_decode import RT_LOCATION
        from sitewhere_tpu.ingest.requests import RequestType

        rt_of = {
            RequestType.REGISTER_DEVICE: RT_REGISTER,
            RequestType.DEVICE_MEASUREMENT: RT_MEASUREMENT,
            RequestType.DEVICE_LOCATION: RT_LOCATION,
            RequestType.DEVICE_ALERT: RT_ALERT,
            RequestType.DEVICE_STATE_CHANGE: RT_STATE_CHANGE,
            RequestType.ACKNOWLEDGE: RT_ACK,
            RequestType.MAP_DEVICE: RT_MAP,
        }
        n = len(payloads)
        reqs: list = []
        names: list[str] = []
        for p in payloads:
            try:
                decoded = dec.decode(p, {})
            except Exception:
                reqs.append(None)   # failed row (rtype -1)
                continue
            if len(decoded) != 1 or decoded[0].type not in rt_of:
                return None
            reqs.append(decoded[0])
            if decoded[0].measurements:
                names.extend(decoded[0].measurements)
        if self.channel_map.strict:
            # reject BEFORE interning/WAL so a refused batch leaks nothing
            self.channel_map.validate(names)
        c = self.config.channels
        rtype = np.full(n, -1, np.int32)
        token_id = np.full(n, -1, np.int32)
        ts64 = np.full(n, -1, np.int64)
        values = np.zeros((n, c), np.float32)
        chmask = np.zeros((n, c), np.bool_)
        aux0 = np.full(n, NULL_ID, np.int32)
        aux1 = np.full(n, NULL_ID, np.int32)
        level = np.zeros(n, np.int32)
        for i, req in enumerate(reqs):
            if req is None:
                continue
            try:
                rt = rt_of[req.type]
                token_id[i] = self.tokens.intern(req.device_token)
                if req.event_ts_ms is not None:
                    ts64[i] = req.event_ts_ms
                et = RTYPE_TO_ETYPE[rt]
                if et == int(EventType.MEASUREMENT) and req.measurements:
                    for name, val in req.measurements.items():
                        ch = self.channel_map.channel_of(name)
                        values[i, ch] = val
                        chmask[i, ch] = True
                elif et == int(EventType.LOCATION):
                    if (req.latitude is not None
                            and req.longitude is not None):
                        values[i, 0] = req.latitude
                        values[i, 1] = req.longitude
                        values[i, 2] = req.elevation or 0.0
                        chmask[i, :3] = True
                elif et == int(EventType.ALERT):
                    level[i] = int(req.alert_level)
                    chmask[i, 0] = True
                    aux0[i] = self.alert_types.intern(
                        req.alert_type or "alert")
                elif (et == int(EventType.COMMAND_RESPONSE)
                        and req.originating_event_id):
                    aux0[i] = self.event_ids.intern(
                        req.originating_event_id)
                elif (et == int(EventType.STATE_CHANGE)
                        and (req.attribute or req.state_type)):
                    aux0[i] = self.event_ids.intern(
                        f"{req.attribute or ''}:{req.state_type or ''}")
                if rt not in (RT_REGISTER, RT_MAP) \
                        and req.alternate_id is not None:
                    aux1[i] = self.event_ids.intern(req.alternate_id)
                rtype[i] = rt
            except Exception:
                rtype[i] = -1   # row-level failure, same as native
        return DecodedArrays(
            n_ok=int(np.sum(rtype >= 0)), rtype=rtype, token_id=token_id,
            ts_ms64=ts64, values=values, chmask=chmask, aux0=aux0,
            aux1=aux1, level=level, collisions=0)

    def _ingest_decoded(self, res, payloads, tenant, reg_decoder) -> dict:
        # the decode-worker-pool absorb seam: externally decoded SoA
        # batches take the same stacked-arena scatter as in-process decode
        if self._arena_pool is None:
            return super()._ingest_decoded(res, payloads, tenant,
                                           reg_decoder)
        return self._ingest_decoded_spmd(res, payloads, tenant,
                                         reg_decoder, self.flight.current())

    def _ingest_decoded_spmd(self, res, payloads, tenant, reg_decoder,
                             rec) -> dict:
        """Scatter a decoded SoA batch into the per-shard lanes of the
        stacked fill arena: shard/local-id routing is two indexed loads
        over the whole batch, the scatter is one fancy-indexed store per
        column — no per-row Python on the batch path. Registration/map/
        ack envelopes re-route through the per-request slow path exactly
        like single-chip (:meth:`Engine._decode_prologue`); their tokens
        pre-route in payload order so local token ids allocate exactly as
        the per-row router would — the store byte-identity invariant.

        Spans: ``swtpu.ingest.commit`` (``staged``) over the whole call,
        and one ``swtpu.ingest.route`` per pass of routing and scatter
        (``rows`` staged, ``lane_max``/``lane_min``: the most and fewest
        of them that went to one shard); a lane that overflows dispatches
        between two passes, outside either."""
        from sitewhere_tpu.ingest.fast_decode import RT_MAP

        rec.add("path", "arena")
        with self.lock, stage("ingest.commit", mark="commit") as span:
            now = self.epoch.now_ms()
            base_ms = int(self.epoch.base_unix_s * 1000)
            tids = res.token_id
            # route every token the row-router would route, in payload
            # order: event + ack rows (staged) and register rows (routed
            # by register_device). MAP rows never allocate a route.
            routable = (tids >= 0) & (res.rtype != RT_MAP)
            if routable.any():
                self._route_new(tids[routable])
            rec.mark("route")
            etype, ok, ts_rel, values, failed, n_reg_ok = \
                self._decode_prologue(res, payloads, tenant, reg_decoder,
                                      now, base_ms)
            idxs = np.nonzero(ok)[0]
            tenant_id = self.tenants.intern(tenant)
            staged = 0
            rem = idxs
            while rem.size:
                arena = self._arena_fill
                if arena is None:
                    arena = self._arena_fill = \
                        self._acquire_arena(tenant, int(rem.size))
                with stage("ingest.route") as route:
                    rt = tids[rem]
                    rs = self._route_shard[rt]
                    # per-shard running offsets within this pass
                    # (<= n_shards groups, never per-row Python)
                    cum = np.empty(rem.size, np.int64)
                    for s in np.unique(rs):
                        m = rs == s
                        cum[m] = np.arange(int(m.sum()))
                    dst = arena.cursors[rs] + cum
                    fit = dst < arena.rows
                    rows_f, rs_f, dst_f = rem[fit], rs[fit], dst[fit]
                    arena.etype[rs_f, dst_f] = etype[rows_f]
                    arena.token_id[rs_f, dst_f] = self._route_ltid[rt[fit]]
                    arena.tenant_id[rs_f, dst_f] = tenant_id
                    arena.ts_ms[rs_f, dst_f] = ts_rel[rows_f]
                    arena.received_ms[rs_f, dst_f] = now
                    arena.values[rs_f, dst_f] = values[rows_f]
                    arena.vmask[rs_f, dst_f] = res.chmask[rows_f]
                    arena.aux[rs_f, dst_f, 0] = res.aux0[rows_f]
                    arena.aux[rs_f, dst_f, 1] = res.aux1[rows_f]
                    arena.valid[rs_f, dst_f] = True
                    binc = np.bincount(rs_f, minlength=self.n_shards)
                    arena.cursors += binc
                    route.set_metadata(rows=int(rows_f.size),
                                       lane_max=int(binc.max()),
                                       lane_min=int(binc.min()))
                if self.ledger.enabled:
                    self._shard_rows_routed += binc
                if self.shard_heat.enabled and rows_f.size:
                    self._slot_rows += np.bincount(
                        self._route_slot[tids[rows_f]],
                        minlength=self._slot_rows.size)
                staged += int(rows_f.size)
                rec.mark("arena_fill")
                if rec.trace_id is not None and (
                        not arena.traces or arena.traces[-1] is not rec):
                    arena.traces.append(rec)
                if rows_f.size < rem.size:
                    # a shard lane overflowed: dispatch and re-scatter the
                    # remainder into a fresh arena
                    self._dispatch_arena()
                    rem = rem[~fit]
                else:
                    rem = rem[:0]
            arena = self._arena_fill
            if arena is not None and \
                    int(arena.cursors.min()) >= arena.rows:
                self._dispatch_arena()   # every lane exactly full
            self.channel_map.collisions += res.collisions
            self.host_counters["arena_rows"] = \
                self.host_counters.get("arena_rows", 0) + staged
            self.ledger.add("staged_rows", staged)
            span.set_metadata(staged=staged)
        return {"decoded": staged + n_reg_ok, "failed": failed,
                "staged": staged}

    def _dispatch_arena(self) -> None:
        """Dispatch the stacked fill arena: mask lanes past each shard's
        cursor invalid (free padding), gate on WAL durability, place the
        ``[S, rows]`` batch over the mesh and run the fused step —
        packed ``lax.scan`` program when ``scan_chunk > 1``. Caller holds
        the lock."""
        arena = self._arena_fill
        if arena is None or not arena.cursors.any():
            return
        if self.shard_heat.enabled:
            self._shard_staged_hwm = np.maximum(
                self._shard_staged_hwm, self._shard_staged_now())
        arena.valid &= (np.arange(arena.rows)[None, :]
                        < arena.cursors[:, None])
        per_shard = arena.valid.sum(axis=1)
        self.ledger.add("dispatched_rows", int(per_shard.sum()))
        if self.ledger.enabled:
            self._shard_rows_dispatched += per_shard
        skew = (self.shard_heat.note_dispatch(per_shard)
                if self.shard_heat.enabled else None)
        traces, arena.traces = arena.traces, []
        self._wal_gate(traces)
        for rec in traces:
            rec.mark("dispatch")
            if skew is not None:
                # straggler attribution on the trace itself: which lane
                # carried how much of the batch this record rode in
                rec.add("shard_rows",
                        "/".join(str(int(x)) for x in per_shard))
                rec.add("skew", round(skew, 3))
        step = self._arena_step or self._step
        with stage("step.dispatch", rows=int(per_shard.sum()),
                   shards=self.n_shards, lane_max=int(per_shard.max())):
            batch = arena.view_batch()
            batch = jax.device_put(batch, stack_sharding(self.mesh, batch))
            self.state, out = step(self.state, batch)
        self._enqueue_out(out, traces)
        # the recycle wait that proves the transfer completed ALSO proves
        # the device program ran: device_ready harvests there, free
        self._arena_pool.retire(arena, out.n_persisted, traces)
        self._archive_account(arena.cursor * MAX_ACTIVE_ASSIGNMENTS)
        self._arena_fill = None
        self._arena_dispatches += 1
        self._last_flush = time.monotonic()
        if self._autotuner is not None:
            self._autotuner.note_dispatch()

    def flush_async(self) -> None:
        """One SPMD dispatch: emit EVERY shard lane (empty lanes ride as
        all-invalid rows — the program shape never changes), stack to
        ``[S, B, ...]``, place over the mesh, run the fused step."""
        with self.lock:
            staged = self.staged_count
            if staged > self._backlog_hwm:
                self._backlog_hwm = staged
            # arena rows precede copy-staged rows within one flush; a
            # partially filled arena flushes too — but never mid-commit
            if (self._arena_fill is not None and self._arena_fill.cursor
                    and not self._arena_committing):
                self._dispatch_arena()
            lens = np.array([len(b) for b in self._shard_bufs], np.int64)
            n_staged = int(lens.sum())
            if not n_staged:
                return
            if self.ledger.enabled:
                self._shard_rows_dispatched += lens
            if self.shard_heat.enabled:
                self._shard_staged_hwm = np.maximum(
                    self._shard_staged_hwm, lens)
                self.shard_heat.note_dispatch(lens)
            batches = [b.emit() for b in self._shard_bufs]
            batch = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                           *batches)
            traces, self._staged_traces = self._staged_traces, []
            self._wal_gate(traces)
            for rec in traces:
                rec.mark("dispatch")
            self.ledger.add("dispatched_rows", n_staged)
            with stage("step.dispatch", rows=n_staged, shards=self.n_shards,
                       lane_max=int(lens.max())):
                batch = jax.device_put(batch,
                                       stack_sharding(self.mesh, batch))
                self.state, out = self._step(self.state, batch)
            self._enqueue_out(out, traces)
            self._last_flush = time.monotonic()

    @property
    def staged_count(self) -> int:
        return (sum(len(b) for b in self._shard_bufs) + len(self._buf)
                + self._fair_queued
                + (self._arena_fill.cursor
                   if self._arena_fill is not None else 0))

    def _arena_backlogged(self) -> bool:
        return (self._arena_fill is not None and self._arena_fill.cursor
                and not self._arena_committing)

    def _sync_mirrors(self) -> None:
        while (any(len(b) for b in self._shard_bufs)
               or self._arena_backlogged()):
            self.flush_async()
        if self._pending_outs:
            self.drain()

    def maybe_flush(self) -> dict | None:
        with self.lock:
            expired = (time.monotonic() - self._last_flush
                       >= self.config.flush_interval_s)
            if (any(len(b) for b in self._shard_bufs)
                    or self._arena_backlogged()) and expired:
                with stage("flush"):
                    return self.flush()
            if self._pending_outs and expired:
                with stage("flush"):
                    return _merge_summaries(self.drain())
            return None

    def barrier(self) -> None:
        with self.lock:
            while (any(len(b) for b in self._shard_bufs)
                   or self._arena_backlogged()):
                self.flush_async()
            if self._pending_outs:
                last = self._pending_outs[-1].n_persisted
                with stage("step.wait", depth=0, ready=int(last.is_ready())):
                    jax.block_until_ready(last)

    def drain(self) -> list[dict]:
        with self.lock:
            if not self._pending_outs:
                return [{"found": 0, "missed": 0, "registered": 0,
                         "persisted": 0, "new_tokens": [],
                         "dead_tokens": []}]
            outs, self._pending_outs = self._pending_outs, []
            trace_lists, self._pending_traces = self._pending_traces, []
            scalars = jax.device_get([
                (o.n_found, o.n_missed, o.n_registered, o.n_persisted)
                for o in outs])
            for recs in trace_lists:
                for rec in recs:
                    if "device_ready" not in rec.stages:
                        rec.mark("device_ready")
                    rec.mark("readback")
            summaries = []
            for out, s in zip(outs, scalars):
                for shard in range(self.n_shards):
                    sub = jax.tree_util.tree_map(
                        lambda x, _s=shard: x[_s], out)
                    if np.ndim(s[0]) == 1:        # [S] single-step out
                        summaries.append(self._absorb_shard(
                            shard, sub, *(int(x[shard]) for x in s)))
                    else:                          # [S, K] packed scan out
                        for kk in range(np.shape(s[0])[1]):
                            subk = jax.tree_util.tree_map(
                                lambda x, _k=kk: x[_k], sub)
                            summaries.append(self._absorb_shard(
                                shard, subk,
                                *(int(x[shard, kk]) for x in s)))
            return summaries

    def _absorb_shard(self, shard: int, out: StepOutput, n_found: int,
                      n_missed: int, n_registered: int,
                      n_persisted: int) -> dict:
        """Per-shard mirror of Engine._absorb_output: local token/device/
        assignment ids translate through the shard's route tables into the
        global spaces the host mirrors speak."""
        toks = self._shard_tokens[shard]
        new_tokens = []
        if n_registered:
            new_tokens = [toks[int(t)] for t in
                          jax.device_get(out.new_tokens[:n_registered])]
        new_ldids = []
        new_ids = []   # (global_tid, global_did, global_aid)
        for gtid in new_tokens:
            ldid = self._next_local_device[shard]
            laid = self._next_local_assignment[shard]
            self._next_local_device[shard] = ldid + 1
            self._next_local_assignment[shard] = laid + 1
            gdid = shard * self._device_cap + ldid
            gaid = shard * self._assignment_cap + laid
            self.token_device[gtid] = gdid
            new_ldids.append(ldid)
            new_ids.append((gtid, gdid, gaid))
        if new_ldids:
            tenants = np.asarray(jax.device_get(
                self.state.registry.device_tenant[
                    shard, np.asarray(new_ldids)]))
            for (gtid, gdid, gaid), ten in zip(new_ids, tenants):
                tenant = (self.tenants.token(int(ten))
                          if int(ten) != NULL_ID else "default")
                self.devices[gdid] = DeviceInfo(
                    token=self.tokens.token(gtid),
                    device_type=self.config.default_device_type,
                    tenant=tenant,
                    auto_registered=True,
                )
                self._record_assignment(gaid, gdid, slot=0)
        dead = []
        if n_missed:
            dead = [toks[int(t)] if int(t) < len(toks) else int(t)
                    for t in jax.device_get(out.dead_tokens[:n_missed])]
        self.dead_letters.extend(dead)
        summary = {
            "found": n_found,
            "missed": n_missed,
            "registered": n_registered,
            "persisted": n_persisted,
            "new_tokens": new_tokens,
            "dead_tokens": dead,
        }
        self.outputs.append(summary)
        del self.outputs[:-256]
        return summary

    # --------------------------------------------------------------- admin
    def _stacked_admin_apply(self, shard: int, fn, *args) -> None:
        """Apply a single-chip admin updater to ONE shard's slice of the
        stacked state, on device: slice -> update -> scatter back, jitted
        and donated. Shares the base engine's (unbudgeted) ``admin``
        watch family — admin writes are rare-path by contract."""
        apply = self._admin_spmd.get(id(fn))
        if apply is None:
            def _apply(state, shard_idx, *a, _fn=fn):
                sub = jax.tree_util.tree_map(lambda x: x[shard_idx], state)
                sub = _fn(sub, *a)
                return jax.tree_util.tree_map(
                    lambda x, y: x.at[shard_idx].set(y), state, sub)

            apply = self.devicewatch.wrap(
                jax.jit(_apply, donate_argnums=(0,)), "admin", bucket=None)
            self._admin_spmd[id(fn)] = apply
        self.state = apply(self.state, jnp.int32(shard), *args)

    def register_device(self, token: str, device_type: str | None = None,
                        tenant: str = "default", area: str | None = None,
                        customer: str | None = None,
                        metadata: dict | None = None) -> int:
        with self.lock:
            self._sync_mirrors()
            token_id = self.tokens.intern(token)
            existing = self.token_device.get(token_id)
            if existing is not None:
                return existing
            shard, ltid = self._route_token(token_id)
            ldid = self._next_local_device[shard]
            laid = self._next_local_assignment[shard]
            if ldid >= self._device_cap:
                raise RuntimeError("device capacity exhausted")
            if laid >= self._assignment_cap:
                raise RuntimeError("assignment capacity exhausted")
            type_name = device_type or self.config.default_device_type
            self._wal_admin_register(token, type_name, tenant, area,
                                     customer)
            self._next_local_device[shard] = ldid + 1
            self._next_local_assignment[shard] = laid + 1
            self._stacked_admin_apply(
                shard, _admin_create_device,
                jnp.int32(ltid), jnp.int32(ldid), jnp.int32(laid),
                jnp.int32(self.device_types.intern(type_name)),
                jnp.int32(self.tenants.intern(tenant)),
                jnp.int32(self.areas.intern(area) if area else NULL_ID),
                jnp.int32(self.customers.intern(customer)
                          if customer else NULL_ID),
            )
            gdid = shard * self._device_cap + ldid
            gaid = shard * self._assignment_cap + laid
            self.token_device[token_id] = gdid
            self.devices[gdid] = DeviceInfo(
                token=token, device_type=type_name, tenant=tenant,
                area=area, customer=customer, metadata=metadata or {},
            )
            self._record_assignment(gaid, gdid, slot=0, area=area,
                                    customer=customer)
            return gdid

    def delete_device(self, token: str) -> bool:
        with self.lock:
            tid = self.tokens.lookup(token)
            did = self.token_device.get(tid)
            if did is None:
                return False
            shard, ldid = divmod(did, self._device_cap)
            self._stacked_admin_apply(shard, _admin_set_device_active,
                                      jnp.int32(ldid), False)
            return True

    def map_device(self, child_token: str, parent_token: str) -> DeviceInfo:
        with self.lock:
            self._sync_mirrors()
            ctid = self.tokens.lookup(child_token)
            cdid = self.token_device.get(ctid)
            if cdid is None:
                raise KeyError(f"device {child_token!r} not registered")
            ptid = self.tokens.lookup(parent_token)
            pdid = self.token_device.get(ptid)
            if pdid is None:
                raise KeyError(
                    f"parent device {parent_token!r} not registered")
            if cdid == pdid:
                raise ValueError("device cannot be its own parent")
            cshard, cldid = divmod(cdid, self._device_cap)
            pshard, pldid = divmod(pdid, self._device_cap)
            if cshard != pshard:
                raise ValueError(
                    "SPMD engine: parent and child must share a shard "
                    "(token placement decides the shard)")
            info = self.devices[cdid]
            info.metadata = dict(info.metadata) | {
                "parentToken": parent_token}
            self._stacked_admin_apply(cshard, _admin_set_parent,
                                      jnp.int32(cldid), jnp.int32(pldid))
            return info

    def update_device(self, token: str, device_type: str | None = None,
                      area: str | None = None, customer: str | None = None,
                      metadata: dict | None = None) -> DeviceInfo:
        with self.lock:
            self._sync_mirrors()
            tid = self.tokens.lookup(token)
            did = self.token_device.get(tid)
            if did is None:
                raise KeyError(f"device {token!r} not registered")
            shard, ldid = divmod(did, self._device_cap)
            info = self.devices[did]
            type_id = jnp.int32(self.device_types.intern(
                device_type if device_type is not None
                else info.device_type))
            new_area = area if area is not None else info.area
            area_id = jnp.int32(
                self.areas.intern(new_area) if new_area else NULL_ID)
            new_customer = (customer if customer is not None
                            else info.customer)
            customer_id = jnp.int32(
                self.customers.intern(new_customer)
                if new_customer else NULL_ID)
            parent_update = None   # (new metadata, LOCAL parent id or NULL)
            if metadata is not None:
                old_parent = info.metadata.get("parentToken")
                metadata = dict(metadata)
                if "parentToken" not in metadata and old_parent is not None:
                    metadata["parentToken"] = old_parent
                new_parent = metadata.get("parentToken")
                if new_parent != old_parent:
                    if new_parent is None:
                        metadata.pop("parentToken", None)
                        parent_update = (metadata, NULL_ID)
                    else:
                        pdid = self.token_device.get(
                            self.tokens.lookup(new_parent))
                        if pdid is None:
                            raise KeyError(
                                f"parent device {new_parent!r} "
                                "not registered")
                        if pdid == did:
                            raise ValueError(
                                "device cannot be its own parent")
                        pshard, pldid = divmod(pdid, self._device_cap)
                        if pshard != shard:
                            raise ValueError(
                                "SPMD engine: parent and child must "
                                "share a shard")
                        parent_update = (metadata, pldid)
                else:
                    if new_parent is None:
                        metadata.pop("parentToken", None)
                    parent_update = (metadata, None)
            if device_type is not None:
                info.device_type = device_type
            if area is not None:
                info.area = area
            if customer is not None:
                info.customer = customer
            if parent_update is not None:
                info.metadata, pldid = parent_update
                if pldid is not None:
                    self._stacked_admin_apply(
                        shard, _admin_set_parent,
                        jnp.int32(ldid), jnp.int32(pldid))
            self._stacked_admin_apply(shard, _admin_update_device,
                                      jnp.int32(ldid), type_id, area_id,
                                      customer_id)
            return info

    def create_assignment(self, device_token: str, token: str | None = None,
                          asset: str | None = None, area: str | None = None,
                          customer: str | None = None,
                          metadata: dict | None = None):
        with self.lock:
            self._sync_mirrors()
            tid = self.tokens.lookup(device_token)
            did = self.token_device.get(tid)
            if did is None:
                raise KeyError(f"device {device_token!r} not registered")
            if token is not None and token in self.assignment_tokens:
                raise ValueError(
                    f"assignment token {token!r} already exists")
            shard, ldid = divmod(did, self._device_cap)
            slots = self.device_slots.setdefault(
                did, [NULL_ID] * MAX_ACTIVE_ASSIGNMENTS)
            try:
                slot = slots.index(NULL_ID)
            except ValueError:
                raise ValueError(
                    f"device {device_token!r} already has "
                    f"{MAX_ACTIVE_ASSIGNMENTS} active assignments") from None
            laid = self._next_local_assignment[shard]
            if laid >= self._assignment_cap:
                raise RuntimeError("assignment capacity exhausted")
            self._next_local_assignment[shard] = laid + 1
            self._stacked_admin_apply(
                shard, _admin_add_assignment,
                jnp.int32(ldid), jnp.int32(laid), jnp.int32(slot),
                jnp.int32(self.assets.intern(asset) if asset else NULL_ID),
                jnp.int32(self.areas.intern(area) if area else NULL_ID),
                jnp.int32(self.customers.intern(customer)
                          if customer else NULL_ID),
            )
            gaid = shard * self._assignment_cap + laid
            info = self._record_assignment(
                gaid, did, slot, token=token, asset=asset, area=area,
                customer=customer, metadata=metadata)
            self._assignment_trigger(device_token, "assignment.created",
                                     info.tenant)
            return info

    def update_assignment(self, token: str, asset: str | None = None,
                          area: str | None = None,
                          customer: str | None = None,
                          metadata: dict | None = None):
        with self.lock:
            self._sync_mirrors()
            aid = self.assignment_tokens.get(token)
            if aid is None:
                raise KeyError(f"assignment {token!r} not found")
            shard, laid = divmod(aid, self._assignment_cap)
            info = self.assignments[aid]
            new_asset = asset if asset is not None else info.asset
            new_area = area if area is not None else info.area
            new_customer = (customer if customer is not None
                            else info.customer)
            asset_id = jnp.int32(
                self.assets.intern(new_asset) if new_asset else NULL_ID)
            area_id = jnp.int32(
                self.areas.intern(new_area) if new_area else NULL_ID)
            customer_id = jnp.int32(
                self.customers.intern(new_customer)
                if new_customer else NULL_ID)
            self._stacked_admin_apply(shard, _admin_update_assignment,
                                      jnp.int32(laid), asset_id, area_id,
                                      customer_id)
            info.asset, info.area, info.customer = (new_asset, new_area,
                                                    new_customer)
            if metadata is not None:
                info.metadata = metadata
            return info

    def _set_assignment_status(self, token: str,
                               status: DeviceAssignmentStatus):
        with self.lock:
            self._sync_mirrors()
            aid = self.assignment_tokens.get(token)
            if aid is None:
                raise KeyError(f"assignment {token!r} not found")
            shard, laid = divmod(aid, self._assignment_cap)
            active = status is not DeviceAssignmentStatus.RELEASED
            self._stacked_admin_apply(shard, _admin_set_assignment_status,
                                      jnp.int32(laid), jnp.int32(status),
                                      active)
            info = self.assignments[aid]
            info.status = status.name
            if not active:
                info.released_ms = self.epoch.now_ms()
                tid = self.tokens.lookup(info.device_token)
                did = self.token_device.get(tid)
                if did is not None and did in self.device_slots:
                    slots = self.device_slots[did]
                    self.device_slots[did] = [
                        NULL_ID if s == aid else s for s in slots]
            self._assignment_trigger(
                info.device_token, f"assignment.{status.name.lower()}",
                info.tenant)
            return info

    # ------------------------------------------------------------- queries
    def get_device_state(self, token: str) -> dict | None:
        from sitewhere_tpu.core.state import RECENT_DEPTH

        with self.lock:
            self._sync_mirrors()
            tid = self.tokens.lookup(token)
            did = self.token_device.get(tid)
            if did is None:
                return None
            s, d = divmod(did, self._device_cap)
            ds = jax.tree_util.tree_map(
                lambda x, _s=s: x[_s],
                jax.device_get(_device_rows(self.state.device_state, d)))
            chans = {}
            for name, nid in self.channel_map.names.items():
                ch = nid % self.config.channels
                ts = int(ds.meas_last_ms[ch])
                if ts > -(2 ** 31) + 10:
                    chans[name] = {"value": float(ds.meas_last[ch]),
                                   "ts_ms": ts}
            recent_locs = [
                {"latitude": float(ds.recent_loc[r, 0]),
                 "longitude": float(ds.recent_loc[r, 1]),
                 "elevation": float(ds.recent_loc[r, 2]),
                 "ts_ms": int(ds.recent_loc_ms[r])}
                for r in range(RECENT_DEPTH)
                if bool(ds.recent_loc_valid[r])
            ]
            recent_alerts = [
                {"level": int(ds.recent_alert_level[r]),
                 "type": self.alert_types.token(
                     int(ds.recent_alert_type[r])),
                 "ts_ms": int(ds.recent_alert_ms[r])}
                for r in range(RECENT_DEPTH)
                if bool(ds.recent_alert_valid[r])
            ]
            return {
                "device": self.devices[did].token,
                "presence": PresenceState(int(ds.presence)).name,
                "last_interaction_ms": int(ds.last_interaction_ms),
                "measurements": chans,
                "recent_locations": recent_locs,
                "recent_alerts": recent_alerts,
                "event_counts": {
                    EventType(e).name: int(ds.event_counts[e])
                    for e in range(NUM_EVENT_TYPES)
                },
            }

    def search_device_states(self, *a, **kw):
        raise NotImplementedError(
            "SpmdEngine: search_device_states is not shard-aware yet (v1)")

    def get_event(self, *a, **kw):
        raise NotImplementedError(
            "SpmdEngine: get_event ring positions are per-shard (v1)")

    def make_feed_consumer(self, *a, **kw):
        raise NotImplementedError(
            "SpmdEngine: outbound feeds are not shard-aware yet (v1)")

    # ---------------------------------------------------- sweep & counters
    def presence_sweep(self) -> list[str]:
        with self.lock:
            self._sync_mirrors()
            now = jnp.int32(self.epoch.now_ms())
            missing_ms = jnp.int32(
                int(self.config.presence_missing_s * 1000))
            self.state, newly = self._sweep(self.state, now, missing_ms)
            out = np.asarray(jax.device_get(newly))     # [S, dcap]
            toks = []
            for s, ld in zip(*np.nonzero(out)):
                info = self.devices.get(
                    int(s) * self._device_cap + int(ld))
                if info is not None:
                    toks.append(info.token)
            return toks

    presence_sweep_local = presence_sweep

    def tenant_metrics(self) -> dict[str, dict[str, int]]:
        with self.lock:
            self._sync_mirrors()
            n_tenants = len(self.tenants)
            counts = np.asarray(_spmd_tenant_counts(
                self.state, tenant_cap(n_tenants)))
        return tenant_counts_dict(counts, self.tenants, n_tenants)

    def metrics(self) -> dict:
        # heat/skew series stay OUT of this dict (dispatch-shape
        # equality pin — the SPMD metrics() dict is pinned equal to
        # single-chip); they live on shard_flow/spmd_heat and the
        # swtpu_shard_* exposition only
        out = super().metrics()
        out["staged"] = sum(len(b) for b in self._shard_bufs)
        return out

    # ------------------------------------------------- shard observability
    def _shard_staged_now(self) -> np.ndarray:
        """Rows currently staged per shard lane (host bufs + the fill
        arena's cursors). Caller holds the lock."""
        lens = np.array([len(b) for b in self._shard_bufs], np.int64)
        fill = self._arena_fill
        if fill is not None:
            lens = lens + np.asarray(fill.cursors, np.int64)
        return lens

    def take_shard_staged_hwm(self, reset: bool = True) -> list[int]:
        """Worst per-shard staged-rows backlog since the last take —
        RESET on scrape (the PR-11 arena-HWM discipline), so each
        sample reads "worst one-lane pileup this scrape window". Fixes
        the swtpu_shard_staged_rows blind spot: a transient pileup that
        drained before the scrape is visible after the fact."""
        with self.lock:
            now = self._shard_staged_now()
            hwm = np.maximum(self._shard_staged_hwm, now)
            if reset:
                self._shard_staged_hwm = now
            return [int(x) for x in hwm]

    def shard_flow(self) -> dict:
        """Per-shard flow breakdown (ISSUE 18): the device tenant
        counter grid read UNFOLDED — a plain device_get of the already
        materialized ``[S, T, lanes]`` stack; ``_spmd_tenant_counts``
        folds the shard axis away for the single-chip-shaped surfaces,
        this is the shard-axis view, no new program — plus the host
        router's routed/dispatched/backlog counters. The conservation
        ledger embeds this as its "spmd" stage; per-shard lanes sum
        EXACTLY to the folded device stage (no new slack)."""
        from sitewhere_tpu.pipeline import TENANT_COUNTER_LANES

        with self.lock:
            grid = np.asarray(jax.device_get(
                self.state.metrics.tenant_counters))       # [S, T, L]
            proc = np.asarray(jax.device_get(
                self.state.metrics.processed))             # [S]
            routed = self._shard_rows_routed.copy()
            dispatched = self._shard_rows_dispatched.copy()
            backlog = np.array([len(b) for b in self._shard_bufs],
                               np.int64)
            fill = self._arena_fill
            if fill is not None:
                # valid rows only, exactly conservation._backlog_rows
                for s, cnt in enumerate(fill.cursors):
                    backlog[s] += int(np.sum(fill.valid[s, :int(cnt)]))
            counting = self.ledger.enabled
        lanes = grid.sum(axis=1)                           # [S, L]
        per = []
        for s in range(self.n_shards):
            row = {"shard": s, "processed": int(proc[s]),
                   "routed_rows": int(routed[s]),
                   "dispatched_rows": int(dispatched[s]),
                   "backlog_rows": int(backlog[s])}
            row.update({lane: int(lanes[s, i])
                        for i, lane in enumerate(TENANT_COUNTER_LANES)})
            per.append(row)
        doc = {"shards": self.n_shards, "counting": counting,
               "perShard": per}
        # attached persistent-connection edges are the feeder stage of
        # this flow — embed their aggregate so one scrape of the shard
        # doc shows socket->arena->shard end to end (stays out of
        # metrics(): dispatch-shape equality pin)
        if getattr(self, "wire_edges", None):
            from sitewhere_tpu.ingest.wire_edge import aggregate_wire_snapshot

            wire = aggregate_wire_snapshot(self)
            if wire is not None:
                doc["wire"] = wire
        return doc

    def harvest_shard_heat(self, now_s: float | None = None):
        """Scrape-seam heat harvest: device_get the unfolded counter
        grid (already materialized by the fused step — no new program,
        so the zero-steady-state-recompile gate holds with the plane
        on) and EWMA-update the tracker from the counter deltas.
        Returns the tracker. ``now_s`` injects a clock for the
        determinism tests; None reads time.monotonic()."""
        t = time.monotonic() if now_s is None else float(now_s)
        with self.lock:
            grid = np.asarray(jax.device_get(
                self.state.metrics.tenant_counters))
            self.shard_heat.harvest(grid, self._slot_rows, t)
        return self.shard_heat

    def spmd_heat(self) -> dict:
        """The heat/skew document (shardobs.spmd_heat_payload) — same
        name the ClusterEngine facade fans out, so REST/RPC duck-type
        one attribute for both shapes."""
        from sitewhere_tpu.utils.shardobs import spmd_heat_payload

        return spmd_heat_payload(self)

    # ------------------------------------------------------- zones & rules
    def set_geofence_zones(self, polygons, max_vertices: int = 16) -> None:
        from sitewhere_tpu.ops.geofence import pack_zones
        from sitewhere_tpu.pipeline import ZoneTable

        with self.lock:
            old = self.state.zones
            if not polygons:
                if old is not None:
                    self.devicewatch.allow(1)
                    self._swap_epoch += 1
                    self.state = dataclasses.replace(self.state, zones=None)
                return
            verts, valid = pack_zones(polygons, max_vertices)
            stacked = (self.n_shards,) + verts.shape
            if old is None or tuple(old.verts.shape) != stacked:
                self.devicewatch.allow(1)
                self._swap_epoch += 1
            zones = ZoneTable(
                jnp.broadcast_to(jnp.asarray(verts), stacked),
                jnp.broadcast_to(jnp.asarray(valid),
                                 (self.n_shards,) + valid.shape))
            self.state = dataclasses.replace(
                self.state,
                zones=jax.device_put(zones,
                                     stack_sharding(self.mesh, zones)))

    def set_rules(self, rules_state, *, precompiled=None,
                  preserve_state: bool = False) -> None:
        """Broadcast the rule tables into every shard's slice of the fused
        step. Each shard evaluates the FULL rule set against its local
        substream — group keys (device/assignment scope) land whole on the
        owning shard, so per-rule fire totals equal single-chip for
        device-scoped rules (tenant-scoped windows that span shards
        legitimately partition; see README)."""
        if precompiled is not None:
            raise NotImplementedError(
                "SpmdEngine: precompiled rule swap not supported (v1)")
        if rules_state is not None:
            rules_state = _broadcast_tree(rules_state, self.n_shards)
            rules_state = jax.device_put(
                rules_state, stack_sharding(self.mesh, rules_state))
        super().set_rules(rules_state, preserve_state=preserve_state)

    def precompile_rules(self, rules_state):
        """No AOT compile-before-swap in v1: the fused SPMD step
        recompiles under the declared ``devicewatch.allow`` grant that
        every rule-shape change carries (same discipline, no shim)."""
        return None

    def _rollup_tables(self, p: int, scope: str):
        """Fold the stacked ``[S, P, G, B]`` rollup tables into the
        single-chip ``[G', B]`` read layout: device-scope groups relocate
        to the shard-qualified device-id space; area/tenant groups (global
        interner ids, per-shard partial aggregates) merge per bucket —
        count/sum add, min/max fold, windows align on the newest wid."""
        ro = self.state.rules.rollups
        wid, cnt, vsum, vmin, vmax = (
            np.asarray(a) for a in jax.device_get(
                (ro.wid[:, p], ro.cnt[:, p], ro.vsum[:, p],
                 ro.vmin[:, p], ro.vmax[:, p])))          # each [S, G, B]
        s_n, g_n, b_n = cnt.shape
        if scope == "device":
            g_out = max(s_n * self._device_cap, g_n)
            span = min(g_n, self._device_cap)
            out = tuple(np.zeros((g_out, b_n), a.dtype)
                        for a in (wid, cnt, vsum, vmin, vmax))
            for s in range(s_n):
                lo = s * self._device_cap
                for dst, src in zip(out, (wid, cnt, vsum, vmin, vmax)):
                    dst[lo:lo + span] = src[s, :span]
            return out
        live = cnt > 0
        top = np.where(live, wid, np.iinfo(wid.dtype).min).max(axis=0)
        on = live & (wid == top[None])                    # [S, G, B]
        mcnt = np.where(on, cnt, 0).sum(axis=0)
        return (np.where(mcnt > 0, top, 0).astype(wid.dtype),
                mcnt.astype(cnt.dtype),
                np.where(on, vsum, 0.0).sum(axis=0).astype(vsum.dtype),
                np.where(on, vmin, np.inf).min(axis=0).astype(vmin.dtype),
                np.where(on, vmax, -np.inf).max(axis=0).astype(vmax.dtype))

    def poll_rule_fires(self):
        """Harvest every shard's pending ring in ONE donated program, then
        merge scope-aware on the host (ops.rules.merge_shard_harvests):
        device-scope rings relocate to the shard-qualified device-id
        space; area/tenant rings fold per global group. Returns the
        single-chip ``(pend_key, pend_val, pend_w, pend_h)`` contract."""
        from sitewhere_tpu.ops.rules import (harvest_fires,
                                             merge_shard_harvests)
        from sitewhere_tpu.pipeline import FAMILY_RULES_HARVEST

        with self.lock:
            rs = self.state.rules
            if rs is None or rs.rules is None:
                return None
            layout = rs.rules.layout
            self._sync_mirrors()
            if self._rules_harvest_fn is None:
                def _harvest(state: PipelineState):
                    def one(rules):
                        new_rules, *fires = harvest_fires(rules)
                        return new_rules, tuple(fires)

                    new_rules, fires = jax.vmap(one)(state.rules)
                    return (dataclasses.replace(state, rules=new_rules),
                            fires)

                self._rules_harvest_fn = self.devicewatch.wrap(
                    jax.jit(_harvest, donate_argnums=(0,)),
                    FAMILY_RULES_HARVEST)
            self.state, out = self._rules_harvest_fn(self.state)
        return merge_shard_harvests(*jax.device_get(out),
                                    layout=layout,
                                    device_cap=self._device_cap)
