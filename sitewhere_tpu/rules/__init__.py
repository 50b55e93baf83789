"""On-device streaming rules engine (the Siddhi-analog CEP tier).

``model`` — declarative rule sets (threshold / windowed aggregate /
sequence / absence over device/area/tenant groups) + continuous-rollup
specs, validated and lowered to the device tables in ops/rules.py.
``manager`` — the host runtime: compile-before-swap installs, mtime
hot-reload, dedup-keyed alert emission through the normal ingest
pipeline, rollup reads. ``oracle`` — host-side reference semantics used
by the tests as the parity reference.
"""

from sitewhere_tpu.rules.manager import RuleSetWatcher, RulesManager
from sitewhere_tpu.rules.model import RuleSet, RuleSetError

__all__ = ["RuleSet", "RuleSetError", "RulesManager", "RuleSetWatcher"]
