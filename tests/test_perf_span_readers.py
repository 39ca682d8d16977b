"""The benchmark's span readers under tier-1: ``perf/tests`` is run by hand
and does not count, so the checks of ``perf/layer_metrics/_host_spans.py``
(a cause for every idle gap, from the program's own spans in the profiler's
trace) are collected here too, from the same file."""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perf", "tests", "test_span_metrics.py")
_spec = importlib.util.spec_from_file_location("perf_test_span_metrics", _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_") or name == "recorded"})
