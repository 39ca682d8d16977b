"""The benchmark's step-scope readers under tier-1: ``perf/tests`` is run by
hand and does not count, so the checks of ``perf/layer_metrics/
_step_scopes.py`` and the seven metrics built on it (the scope table of a
capture, read through ``harmony_tpu/tracing/stepscopes.py``) are collected
here too, from the same file."""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "perf", "tests", "test_step_scopes.py")
_spec = importlib.util.spec_from_file_location("perf_test_step_scopes", _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_") or name == "on_fixture"})
