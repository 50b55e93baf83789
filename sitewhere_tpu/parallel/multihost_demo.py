"""Two-process execution harness: the system actually RUNNING multi-host.

The reference scales horizontally with service replicas over partitioned
Kafka consumer groups (KafkaOutboundConnectorHost.java:43-257, README
Deployment); the TPU-native equivalent is one global mesh spanning
processes — each process stages batches for the shards whose devices it
addresses (multihost.local_shard_ids), the stacked shard_map step runs as
one SPMD program, and cross-process reductions ride the same collectives
that span DCN on a real pod.

``worker_main`` is one process of the job (rank r of N over the CPU
backend with ``devices_per_proc`` virtual devices each);
``spawn_two_process_demo`` launches and checks a 2-process run — used by
both tests/test_multihost.py and __graft_entry__.dryrun_multichip so the
multi-process path is exercised in CI and in the driver's dry run.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys


def worker_main(rank: int, nproc: int, port: int,
                devices_per_proc: int = 4) -> None:
    """One process of the multi-host job. Prints one MULTIHOST_OK line on
    success; any assertion failure exits nonzero."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", devices_per_proc)

    from sitewhere_tpu.parallel import multihost

    assert multihost.initialize(f"localhost:{port}", nproc, rank)
    assert jax.process_count() == nproc, jax.process_count()
    n_global = nproc * devices_per_proc
    assert len(jax.devices()) == n_global

    import jax.numpy as jnp

    from sitewhere_tpu.core.events import HostEventBuffer
    from sitewhere_tpu.core.types import EventType
    from sitewhere_tpu.parallel.sharded import ShardedEngine

    eng = ShardedEngine(
        device_capacity_per_shard=64, token_capacity_per_shard=128,
        assignment_capacity_per_shard=128, store_capacity_per_shard=512,
        channels=4)
    assert eng.n_shards == n_global
    local = multihost.local_shard_ids(eng.mesh)
    assert len(local) == devices_per_proc, local
    # disjoint ownership: rank r owns exactly its devices' shard rows
    assert all(
        (eng.mesh.devices.flat[s].process_index == rank) for s in local)

    # each process ingests events ONLY for its own shards (the partitioned
    # consumer-group analog): 8 events per shard, shard-local device ids.
    # THREE steps: registration (miss path), lookup hits on the same
    # devices, then a later-timestamped round — exercising the steady
    # state, not just cold start, as one SPMD program per step.
    per_shard = 8

    def make_stacked(ts0: int) -> object:
        batches = {}
        for s in local:
            buf = HostEventBuffer(16, channels=4)
            for k in range(per_shard):
                buf.append(EventType.MEASUREMENT, token_id=k, tenant_id=0,
                           ts_ms=ts0 + k, received_ms=ts0 + k,
                           values=[float(s * 100 + k)])
            batches[s] = buf.emit()
        return multihost.assemble_stacked_batch(eng.mesh, batches)

    for step_i, ts0 in enumerate((1000, 2000, 3000)):
        eng.step(make_stacked(ts0))
        # global metrics after EVERY step: SPMD reduction over the whole
        # mesh — all processes must compute identical replicated totals
        m = eng.global_metrics()
        expect = per_shard * n_global * (step_i + 1)
        assert m["persisted"] == expect, (step_i, m)
    assert m["registered"] == per_shard * n_global, m   # first step only
    # "found" counts every resolved event, including just-registered ones
    # re-looked-up within their own step — so all three steps contribute
    assert m["found"] == 3 * per_shard * n_global, m

    # global store scan (query agreement) from EVERY process
    store = eng.state.store
    n_valid = int(jnp.sum(store.valid))
    n_late = int(jnp.sum(store.valid & (store.ts_ms >= 3000)))
    assert n_valid == 3 * per_shard * n_global, n_valid
    assert n_late == per_shard * n_global, n_late

    # presence sweep as a mesh-wide collective pass: with a 0ms horizon
    # every registered device on every shard goes MISSING consistently
    # (the private _stacked_sweep is deliberate: the public presence_sweep
    # does a host readback that is not multi-host-safe)
    from sitewhere_tpu.parallel.sharded import _stacked_sweep

    eng.state, newly = _stacked_sweep(eng.state, jnp.int32(10_000),
                                      jnp.int32(0))
    n_missing = int(jnp.sum(newly))
    assert n_missing == per_shard * n_global, n_missing
    print(f"MULTIHOST_OK rank={rank}/{nproc} shards={local} "
          f"persisted={m['persisted']} store_valid={n_valid} "
          f"found={m['found']} missing={n_missing}", flush=True)


def _spawn_once(devices_per_proc: int, timeout_s: float) -> list[str]:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c",
             "from sitewhere_tpu.parallel.multihost_demo import worker_main;"
             f"worker_main({r}, 2, {port}, {devices_per_proc})"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for r in range(2)
    ]
    lines = []
    errs = []
    for r, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            # one rank failing fast leaves the other stuck in a collective
            # barrier — kill it but keep the FAILED rank's output, which is
            # the root cause the operator needs
            for q in procs:
                q.kill()
                q.wait()
            raise RuntimeError(
                f"rank {r} timed out after {timeout_s}s"
                + ("; earlier failures:\n" + "\n".join(errs) if errs else ""))
        ok = [ln for ln in out.splitlines() if ln.startswith("MULTIHOST_OK")]
        if p.returncode != 0 or not ok:
            errs.append(f"rank {r} rc={p.returncode}\n{out}\n{err[-2000:]}")
        else:
            lines.append(ok[0])
    if errs:
        raise RuntimeError("multi-process demo failed:\n" + "\n".join(errs))
    return lines


def spawn_two_process_demo(devices_per_proc: int = 4,
                           timeout_s: float = 240.0,
                           attempts: int = 3) -> list[str]:
    """Launch the 2-process job and return the two MULTIHOST_OK lines.
    Retries on coordinator-port races (the ephemeral port is probed then
    released before jax.distributed binds it — another process can steal
    it in between); genuine worker failures raise after ``attempts``."""
    last: RuntimeError | None = None
    for _ in range(attempts):
        try:
            return _spawn_once(devices_per_proc, timeout_s)
        except RuntimeError as e:
            last = e
            transient = any(tok in str(e) for tok in
                            ("in use", "UNAVAILABLE", "DEADLINE_EXCEEDED",
                             "failed to connect"))
            if not transient:
                raise
    raise last
