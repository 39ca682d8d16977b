"""``perf/tests/test_step_mfu.py`` holds every LM cell of ``BENCHMARK.json``
against a table of hand counts (``HAND``) that names the configurations PR 41
knew; a later ``model_config`` PR adds a cell and may not edit that file. Such
a PR keeps its hand count beside its own checks, a ``HAND = {...}`` at the top
level of its ``perf/tests/test_<config>.py``: every such file is found by that
line, no list names it, and the counts join the table once the cases are
collected, before any runs."""
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def later_hands():
    """``{configuration: hand count}`` of every ``test_*.py`` beside this
    file that defines a ``HAND`` of its own (``test_step_mfu.py``'s is the
    table they join)."""
    from perf.run import load_by_path

    hands = {}
    for path in sorted(glob.glob(os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "test_*.py"))):
        name = os.path.basename(path)[:-3]
        with open(path) as f:
            if name == "test_step_mfu" or "\nHAND = " not in f.read():
                continue
        hands.update(load_by_path("tests", name).HAND)
    return hands


def pytest_collection_modifyitems(items):
    # the file itself, and the copy ``test_step_scopes.py`` loads by path to
    # gather its cases for tier-1 (``_mfu``)
    found = [m for i in items for m in (i.module, getattr(i.module, "_mfu", None))
             if m is not None and m.__name__.endswith("test_step_mfu")]
    tables = {id(m): m for m in found}
    if not tables:
        return
    for module in tables.values():
        module.HAND.update(later_hands())
