"""The harness end to end on the CPU at test size: the look for a chip
is skipped, everything else is the run the driver makes."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.tests.conftest import CELL, make_root


def run(root, cell, seed=2**31 + 77, seconds=2.0, trace=False, **kw):
    return harness.run_cell(str(root), cell, seed, seconds, trace,
                            require_chip=False, **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(tiny_root, trace):
    cell = CELL
    res = run(tiny_root, cell, trace=trace)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())
    bench = json.load(open(tiny_root / "BENCHMARK.json"))
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in harness.metrics_for(bench, section, cell)}
    if trace:
        assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        # CPU traces have no device planes named like a TPU's, so the
        # trace readers may stay silent here; flight records still read
        assert set(res["metrics"]) <= want
    else:
        assert set(res["metrics"]) == want
        assert all(m["value"] > 0 for m in res["metrics"].values())
    # the window outruns the tiny pool's stamps: repeated stamps are
    # checked too
    assert res["diag"]["stamps_repeated"]


def test_controls_fail_the_comparison(tiny_root):
    """Each control, put in the program's place, fails a compared
    number; the program itself passes on the same run."""
    res = run(tiny_root, CELL, controls=True)
    assert res["correct"]
    for name, readings in res["controls"].items():
        assert any(v > 0 for v in readings.values()), (name, readings)


def _fault(kind):
    def hook(eng):
        real = eng._step

        def step(state, batch):
            if kind == "unchanged":
                keep = jax.tree_util.tree_map(jnp.copy, state)
                _, out = real(state, batch)
                return keep, out
            valid = np.array(batch.valid)
            if kind == "half_batch":
                valid[np.nonzero(valid)[0][1::2]] = False
                return real(state, dataclasses.replace(batch, valid=valid))
            vals = np.array(batch.values)
            idx = np.nonzero(valid)[0]
            if len(idx):
                vals[idx[-1]] += 0.5
            return real(state, dataclasses.replace(batch, values=vals))

        eng._step = step
    return hook


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "altered"])
def test_planted_fault_is_not_correct(tiny_root, kind):
    res = run(tiny_root, CELL, engine_hook=_fault(kind))
    assert not res["correct"], res["checks"]


def test_new_files_found_by_name(tmp_path):
    """A new deployment, traffic mix and per-layer metric are new files
    and entries only; the harness finds them by name."""
    root, bench = make_root(tmp_path)
    cfg = json.load(open(root / "benchmark" / "configs" / "tiny.json"))
    cfg.update(name="tiny_fewer", registered_devices=300)
    json.dump(cfg, open(root / "benchmark" / "configs" / "tiny_fewer.json",
                        "w"))
    mix = json.load(open(root / "benchmark" / "traffic" /
                         "tiny_backlog.json"))
    mix.update(pool_events=2048)
    json.dump(mix, open(root / "benchmark" / "traffic" / "small_pool.json",
                        "w"))
    (root / "benchmark" / "metrics" / "flight_batches.py").write_text(
        "def read(ctx):\n    return float(len(ctx.flight)) or None\n")
    cell = "tiny_fewer.small_pool"
    bench["configs"].append({"name": "tiny_fewer", "source": "test",
                             "why": "test", "reduced": [],
                             "file": "benchmark/configs/tiny_fewer.json"})
    bench["workloads"].append({"name": cell, "config": "tiny_fewer",
                               "traffic": "small_pool", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "ingest_eps.small", "unit":
                                "events/s", "better": "higher", "bound": 0.1,
                                "source": "host_clock", "workloads": [cell]})
    # a new metric with a reader of its own, and one of an existing
    # quantity (read by ``step_device_ms.py``) named for the new cell
    for name in ("flight_batches", "step_device_ms.small"):
        bench["per_layer"].append({"name": name, "unit": "1",
                                   "better": "higher",
                                   "source": "program_span",
                                   "layer": "ingest host path",
                                   "moves": "ingest_eps.small",
                                   "workloads": [cell]})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    res = run(root, cell, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["flight_batches"]["value"] > 0
    # read by step_device_ms.py from the CPU trace's step runs
    assert res["metrics"]["step_device_ms.small"]["value"] > 0
    res = run(root, cell)
    assert set(res["metrics"]) == {"ingest_eps.small", "setup_s"}


def test_no_chip_refused(tiny_root):
    with pytest.raises(harness.NoChip):
        harness.run_cell(str(tiny_root), "tiny.tiny_backlog", 1, 1.0, False)
    assert not os.path.exists(tiny_root / ".bench")
