"""The bytes function of ``step_roofline_pct`` at a small shape: its row
widths are the widths of the program's own arrays, and it counts exactly
the rows one batch touches (a batch of distinct devices, so that every
event is its own state row)."""

import importlib.util
import os

import jax
import numpy as np

from sitewhere_tpu.core.events import HostEventBuffer
from sitewhere_tpu.core.types import EventType
from sitewhere_tpu.pipeline import (PipelineConfig, PipelineState,
                                    make_pipeline_step)

HERE = os.path.dirname(os.path.abspath(__file__))


def metric():
    path = os.path.join(os.path.dirname(HERE), "metrics",
                        "step_roofline_pct.py")
    spec = importlib.util.spec_from_file_location("srp", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def row_bytes(tree, rows: int) -> int:
    """Bytes of one row of every leaf whose leading axis has ``rows``."""
    return sum(leaf.dtype.itemsize * int(np.prod(leaf.shape[1:]))
               for leaf in jax.tree_util.tree_leaves(tree)
               if leaf.ndim and leaf.shape[0] == rows)


def test_widths_are_the_programs():
    m = metric()
    n_dev, cap, b, c = 64, 512, 32, 8
    state = PipelineState.create(n_dev, 2 * n_dev, 2 * n_dev, cap, c)
    assert m.state_row_bytes(c, 3) == row_bytes(state.device_state, n_dev)
    # the store row: every [S] column except the ring bookkeeping
    assert m.store_row_bytes(c) == row_bytes(state.store, cap)
    batch = HostEventBuffer(b, c).emit()
    assert m.batch_row_bytes(c) == row_bytes(batch, b)


def test_counts_the_rows_a_batch_touches():
    m = metric()
    n_dev, cap, b, c, n = 64, 512, 32, 8, 20
    state = PipelineState.create(n_dev, 2 * n_dev, 2 * n_dev, cap, c)
    step = make_pipeline_step(PipelineConfig(auto_register=True))
    buf = HostEventBuffer(b, c)
    kinds = [EventType.MEASUREMENT] * 14 + [EventType.LOCATION] * 4 + [
        EventType.ALERT] * 2
    for tok in range(n):   # a first batch registers devices 0..19
        buf.append(EventType.MEASUREMENT, token_id=tok, tenant_id=0,
                   ts_ms=10, received_ms=10, values=[1.0])
    state, _ = step(state, buf.emit())
    before = jax.device_get(state)
    for tok, kind in enumerate(kinds):
        vals = [2.0, 3.0, 4.0] if kind == EventType.LOCATION else [5.0]
        buf.append(kind, token_id=tok, tenant_id=0, ts_ms=20 + tok,
                   received_ms=20, values=vals)
    state, out = step(state, buf.emit())
    after = jax.device_get(state)
    # store rows written, device-state rows changed
    store_rows = int(out.n_persisted)
    changed = np.zeros(n_dev, bool)
    for a, z in zip(jax.tree_util.tree_leaves(before.device_state),
                    jax.tree_util.tree_leaves(after.device_state)):
        diff = np.asarray(a) != np.asarray(z)
        changed |= diff.reshape(n_dev, -1).any(axis=1)
    assert store_rows == n and changed.sum() == n
    want = n * (m.batch_row_bytes(c) + m.registry_probe_bytes()
                + m.store_row_bytes(c) + 2 * m.state_row_bytes(c, 3))
    assert m.step_bytes(n, c, 3) == want
