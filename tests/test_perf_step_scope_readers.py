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
# the whole step's share is held against a table of hand counts that names
# the configurations PR 41 knew; a configuration added since keeps its count
# beside its own checks (``perf/tests/conftest.py`` says why and finds them)
_mod._mfu.HAND.update(_mod.load_by_path("tests", "conftest").later_hands())


def test_benchmark_entries_for_the_new_metrics(tmp_path, monkeypatch):
    """``perf/tests``' check of PR 33's nine entries, on the benchmark as
    later PRs append to it: it asserts they are the LAST nine of
    ``per_layer``, which holds only until the next appended entry (PR 35's
    ``moe_chunks_per_call``), and a PR may not edit a benchmark file. So it
    runs here on a view cut after the last of the nine: they still have to
    be nine, whole, and with nothing put between them."""
    import json

    with open(os.path.join(_mod.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    last = max(i for i, m in enumerate(bench["per_layer"])
               if m["name"].split(".")[0] in _mod.NEW)
    bench["per_layer"] = bench["per_layer"][:last + 1]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(_mod, "ROOT", str(tmp_path))
    _mod.test_benchmark_entries_for_the_new_metrics()
