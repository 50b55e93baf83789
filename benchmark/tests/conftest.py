"""The benchmark's own tests run on the CPU at test size:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``."""

import json
import os
import shutil

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
CELL = "tiny.tiny_backlog"
LIKE = "fleet_39k.backlog"   # the chip cell whose metrics the tiny one reports


def make_root(tmp_path):
    """A checkout-shaped directory: a copy of ``benchmark/`` with the
    test-size deployment and mix added as new files, and a BENCHMARK.json
    whose fleet metrics also name the tiny cell."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(HERE, "data", "tiny.json"),
                root / "benchmark" / "configs" / "tiny.json")
    shutil.copy(os.path.join(HERE, "data", "tiny_backlog.json"),
                root / "benchmark" / "traffic" / "tiny_backlog.json")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "test", "why": "test",
                             "file": "benchmark/configs/tiny.json",
                             "reduced": []})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny_backlog", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root, bench


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)[0]
