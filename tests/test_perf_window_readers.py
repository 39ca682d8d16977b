"""The benchmark's window readers under tier-1: ``perf/tests`` is run by hand
and does not count, so the checks of ``perf/layer_metrics/_windows.py`` and
the three metrics built on it (the program's own record of every drained
window: ``window_stall_s``, ``window_stall_unnamed_share``,
``window_wall_spread``) are collected here too, from the same file."""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perf", "tests", "test_window_metrics.py")
_spec = importlib.util.spec_from_file_location("perf_test_window_metrics", _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_") or name == "scripted"})
