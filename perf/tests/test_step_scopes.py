"""Rehearsal of the step-scope readers (``perf/layer_metrics/_step_scopes.py``
and the seven metrics built on it): on the CPU, where a trace has no device
plane, each reports nothing and raises nothing; on the trace
``record_scope_fixture.py`` recorded on the chip each reports the value
worked out here from the fixture's own rows; and ``BENCHMARK.json``'s
entries for them load. Run by hand with the rest of ``perf/tests``."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from harmony_tpu.tracing import stepscopes  # noqa: E402
from perf.layer_metrics import _host_spans, _step_scopes  # noqa: E402
from perf.run import load_by_path  # noqa: E402

FIXTURE = os.path.join(HERE, "fixture_scopes.xplane.pb")
SHARES = {"table_path_time_share": "table_path", "mixer_time_share": "mixer",
          "ffn_time_share": "ffn", "moe_routing_time_share": "moe_routing",
          "head_loss_time_share": "head_loss",
          "unscoped_time_share": "unscoped"}
NEW = sorted(SHARES) + ["dense_matmul_roofline_share"]
OBS = {"trace": {"busy_s": 1.0}, "phases": {}}


@pytest.fixture()
def on_fixture(monkeypatch):
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded scope fixture")
    monkeypatch.setattr(_host_spans, "trace_path", lambda cell=None: FIXTURE)
    monkeypatch.setattr(_step_scopes, "_printed", set())
    return stepscopes.step_rows(stepscopes.reduce_file(FIXTURE)[0])


@pytest.mark.parametrize("name", NEW)
def test_no_trace_nothing_reported(name, monkeypatch, tmp_path):
    reader = load_by_path("layer_metrics", name)
    assert reader.read({"trace": None}) is None
    # a trace without a device plane or the metadata plane (a CPU rehearsal)
    bare = tmp_path / "bare.xplane.pb"
    bare.write_bytes(b"")
    monkeypatch.setattr(_host_spans, "trace_path", lambda cell=None: str(bare))
    assert reader.read(OBS) is None
    monkeypatch.setattr(_host_spans, "trace_path", lambda cell=None: None)
    assert reader.read(OBS) is None


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_on_the_fixture(name, on_fixture, capsys):
    rows, seconds, _ = on_fixture
    want = {
        "table_path": lambda r: r.scope.startswith("table."),
        "mixer": lambda r: False,  # the fixture's block has no mixer
        "ffn": lambda r: r.scope == "blk*/ffn",
        "moe_routing": lambda r: False,
        "head_loss": lambda r: r.scope in ("head", "loss"),
        "unscoped": lambda r: r.scope.startswith("unscoped:"),
    }[SHARES[name]]
    by_hand = 100.0 * sum(r.seconds for r in rows if want(r)) / seconds
    value = load_by_path("layer_metrics", name).read(OBS)
    assert value == pytest.approx(by_hand)
    line = json.loads(capsys.readouterr().out.splitlines()[0])
    assert line["line"] == "step_scopes"
    assert sum(line["partition"].values()) == pytest.approx(100.0)
    assert line["partition"][SHARES[name]] == pytest.approx(by_hand)


def test_fixture_partition_by_hand(on_fixture):
    """The known program: matmuls of ffn (5) and head (3) dominate a step
    of D = 256, F = 1024, B = 512; nothing is left unnamed."""
    found = _step_scopes.table()
    share = {g: 100.0 * s / found["seconds"]
             for g, s in found["groups"].items()}
    assert share["mixer"] == share["moe_routing"] == 0.0
    assert share["expert_kernels"] == 0.0
    assert share["unscoped"] < 5.0
    assert share["ffn"] > share["head_loss"] > 0.0
    assert share["table_path"] > 0.0
    assert sum(share.values()) == pytest.approx(100.0)


def test_dense_matmul_roofline_on_the_fixture(on_fixture, monkeypatch, capsys):
    import jax

    rows, executions = on_fixture[0], 4
    peak = 197e12
    monkeypatch.setattr(jax, "devices", lambda *a: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    value = load_by_path("layer_metrics",
                         "dense_matmul_roofline_share").read(OBS)
    mat = [r for r in rows if r.klass == "matmul"]
    # eight matmuls of 2 B D F a step, in the seconds their fusions took
    assert sum(r.flops for r in mat) / executions == pytest.approx(
        8 * 2 * 512 * 256 * 1024)
    assert value == pytest.approx(
        100.0 * sum(r.flops for r in mat)
        / (sum(r.seconds for r in mat) * peak))
    assert 0.0 < value <= 100.0
    line = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if "dense_matmul_roofline" in x][0]
    assert line["highest_single_fusion"][1] <= 100.0


def test_benchmark_entries_for_the_new_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    mine = [m for m in bench["per_layer"] if m["name"].split(".")[0] in NEW]
    assert len(mine) == 9
    assert {m["name"] for m in mine} == set(NEW) | {
        "table_path_time_share.keyed", "unscoped_time_share.keyed"}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] == "device_trace" and m["unit"] == "%"
        assert set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        assert m["layer"] in layers
        reader = load_by_path("layer_metrics", m["name"].split(".")[0])
        assert reader.LAYER == m["layer"] and reader.UNIT == m["unit"]
        assert reader.SOURCE == m["source"]
    # appended together, nothing put between them; later PRs append after
    at = [bench["per_layer"].index(m) for m in mine]
    assert at == list(range(at[0], at[0] + 9))


# -- tier-1 -----------------------------------------------------------------
# ``tests/test_perf_step_scope_readers.py`` collects this file's ``test_*``
# names into the driver's run, which does not collect ``perf/tests``; a
# benchmark PR may add no file under ``tests/``. So the cases of the whole
# step's share (PR 41, ``test_step_mfu.py``: the count that bounds every later
# claim on ``lm_tokens_per_s``) are gathered here, to count there too. A later
# PR gives them a collector of their own under ``tests/`` and drops these lines.
_mfu = load_by_path("tests", "test_step_mfu")
assert not {k for k in vars(_mfu) if k.startswith("test_")} & set(globals())
globals().update({k: v for k, v in vars(_mfu).items()
                  if k.startswith("test_")})
