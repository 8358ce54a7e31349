#!/usr/bin/env python
"""Single CI entry point: every graft-lint check family over the package.

Equivalent to ``python tools/graft_lint.py --checks all --strict-baseline``
with the default tree. Runs the PR-6 JAX-hazard checks (host-sync,
jit-recompile, donated-reuse, knob) and the dist checks (collective-axis,
divergent-collective, lock-order) in one pass, and fails on stale
baseline entries so the suppression file can never drift from reality.

Exit code 0 = the repo is clean.
"""

import importlib.util
import os
import sys

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "graft_lint_cli", os.path.join(_TOOLS_DIR, "graft_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _replay_smoke() -> int:
    """Record an 8-request serving run and oracle-replay it (opt-in:
    ``--replay-smoke``)."""
    spec = importlib.util.spec_from_file_location(
        "replay_cli", os.path.join(_TOOLS_DIR, "replay.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod.main(["smoke"])


def _profile_smoke() -> int:
    """Capture an 8-request fused serving run through the device-timeline
    profiler, parse it, and assert nonzero device time and a well-formed
    waterfall (opt-in: ``--profile-smoke``)."""
    spec = importlib.util.spec_from_file_location(
        "trace_report_cli", os.path.join(_TOOLS_DIR, "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod.main(["smoke"])


def main(argv=None) -> int:
    extra = list(argv) if argv is not None else sys.argv[1:]
    smoke = "--replay-smoke" in extra
    profile_smoke = "--profile-smoke" in extra
    extra = [a for a in extra if a not in ("--replay-smoke", "--profile-smoke")]
    rc = _load_cli().main(["--checks", "all", "--strict-baseline"] + extra)
    if rc == 0 and smoke:
        rc = _replay_smoke()
    if rc == 0 and profile_smoke:
        rc = _profile_smoke()
    return rc


if __name__ == "__main__":
    sys.exit(main())
