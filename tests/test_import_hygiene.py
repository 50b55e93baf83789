"""Import hygiene (ISSUE 11 satellite): offline tooling stays jax-free.

Generalizes the PR-10 "trace2perfetto imports without jax" pin: every
module under ``scripts/`` plus ``sitewhere_tpu/utils/metrics.py`` (the
exposition/lint layer offline tools build on) must import in a
subprocess where importing jax RAISES — an accidental module-level jax
import in offline tooling would force the full accelerator runtime onto
laptops and CI boxes that only want to convert a trace or lint an
exposition."""

import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

_DRIVER = r"""
import importlib.util
import sys

class _JaxBlocker:
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError(
                f"BLOCKED: offline module tried to import {name!r}")
        return None

sys.meta_path.insert(0, _JaxBlocker())

failures = []
for kind, target in [t.split("=", 1) for t in sys.argv[1:]]:
    try:
        if kind == "file":
            spec = importlib.util.spec_from_file_location(
                "offline_under_test", target)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        else:
            importlib.import_module(target)
    except BaseException as e:          # incl. SystemExit from argparse
        if isinstance(e, SystemExit):
            continue                     # a CLI main() guard fired: fine
        failures.append(f"{target}: {type(e).__name__}: {e}")
print("\n".join(failures))
sys.exit(1 if failures else 0)
"""


def test_offline_modules_import_with_jax_blocked():
    scripts = sorted((REPO / "scripts").glob("*.py"))
    assert scripts, "scripts/ has no modules to check"
    targets = [f"file={p}" for p in scripts]
    targets.append("mod=sitewhere_tpu.utils.metrics")
    # the conservation checker (ISSUE 14): offline tooling evaluates
    # ledger documents (debug-bundle triage) without jax
    targets.append("mod=sitewhere_tpu.utils.conservation")
    # the shard heat tracker (ISSUE 18): heat/skew documents are
    # numpy + stdlib — the engine hands in plain host arrays
    targets.append("mod=sitewhere_tpu.utils.shardobs")
    # the fleet-analytics job manager (ISSUE 19): module level is
    # numpy + stdlib — jax, the window-fill op and the model stack
    # import lazily inside the job thread, so the REST/RPC job surface
    # and the conservation stage exist on accelerator-free boxes
    targets.append("mod=sitewhere_tpu.models.analytics")
    res = subprocess.run(
        [sys.executable, "-c", _DRIVER, *targets],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, (
        "offline module(s) grew a jax import:\n"
        f"{res.stdout}\n{res.stderr}")
