"""Every script outside the package, the tests and the benchmark compiles.

The package is imported by its tests, ``tests/`` by their collection and
``perf/`` by ``perf/tests``; the operators' scripts under ``benchmarks/`` and
the repo-root entry points are imported by nothing that runs every PR, and one
of them stood unparsable for 38 PRs. harmonylint's knob pass reads the same
files as syntax trees (``analysis/passes/knobs.py``) and leans on this.
"""
from __future__ import annotations

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the repo's directories beside the package, the tests and the benchmark —
#: named, not discovered, so that a scratch directory in a checkout cannot
#: give two workers different cases to collect
BESIDE = ("benchmarks", "bin", "deploy", "docs", "native")


def _scripts():
    found = sorted(n for n in os.listdir(ROOT) if n.endswith(".py"))
    for top in BESIDE:
        for path, dirs, names in os.walk(os.path.join(ROOT, top)):
            dirs[:] = sorted(d for d in dirs if not d.startswith((".", "__")))
            found += [os.path.relpath(os.path.join(path, n), ROOT)
                      for n in sorted(names) if n.endswith(".py")]
    return found


SCRIPTS = _scripts()


def test_the_entry_points_and_the_operators_scripts_are_found():
    assert {"chip_smoke.py", "__graft_entry__.py",
            os.path.join("benchmarks", "common.py")} <= set(SCRIPTS)


@pytest.mark.parametrize("script", SCRIPTS)
def test_script_compiles(script):
    with open(os.path.join(ROOT, script), encoding="utf-8") as f:
        compile(f.read(), script, "exec")
