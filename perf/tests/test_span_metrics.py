"""Checks of the span readers (``perf/layer_metrics/_host_spans.py``) on
the trace ``record_span_fixture.py`` recorded on the chip, and on made-up
events. Run by hand with the rest of ``perf/tests``; ``tests/
test_perf_span_readers.py`` runs the same under tier-1."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perf import trace_reduce  # noqa: E402
from perf.layer_metrics import _host_spans  # noqa: E402

FIXTURE = os.path.join(HERE, "fixture_spans.xplane.pb")


class _E:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Plane:
    def __init__(self, name, lines):
        self.name = name
        self.lines = [type("L", (), {"name": n, "events": ev})()
                      for n, ev in lines]


def _profile(host_lines, device_events):
    return type("P", (), {"planes": [
        _Plane("/host:CPU", host_lines),
        _Plane("/device:TPU:0", [("XLA Ops", device_events)])]})()


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded span fixture")
    return _host_spans.attribute(trace_reduce.load(FIXTURE))


def test_recorded_gaps_lie_under_sleep(recorded):
    assert recorded is not None
    long_gaps = [g for g in recorded["gaps"] if g[1] > 0.015]
    assert len(long_gaps) == 4
    assert all(0.019 < g[1] < 0.035 and g[2] == "sleep" for g in long_gaps)
    by = recorded["by_cause"]
    assert 0.076 < by["sleep"] < 0.09  # four sleeps of 20 ms, less the waits
    # under ``work`` the device idles only from dispatch to its first
    # operation and from its last to block_until_ready's return
    assert by.get("work", 0.0) < 0.2 * by["sleep"]


def test_recorded_trace_leaves_nothing_unnamed(recorded):
    assert recorded["unnamed_idle_s"] < 0.01 * recorded["idle_s"]
    assert recorded["grant_idle_s"] == 0.0 and recorded["drain_idle_s"] == 0.0
    assert abs(sum(recorded["by_cause"].values()) - recorded["idle_s"]) < 1e-9
    assert recorded["span_s"]["sleep"] > 0.08


def test_innermost_span_names_the_instant():
    host = [("python", [_E("harmony/dolphin.worker", 0, 1000),
                        _E("harmony/dolphin.metric_drain", 400, 300),
                        _E("harmony/drain.d2h", 500, 100),
                        _E("PjitFunction(step)", 100, 10)])]
    segs = _host_spans.thread_segments(_profile(host, []))["python#0"]
    assert segs == [(400.0, 500.0, "dolphin.metric_drain"),
                    (500.0, 600.0, "drain.d2h"),
                    (600.0, 700.0, "dolphin.metric_drain")]


def test_cause_prefers_doing_over_waiting_over_bystanding():
    host = [("python", [_E("harmony/taskunit.wait", 100, 400)]),
            ("python", [_E("harmony/taskunit.wait", 100, 100),
                        _E("harmony/drain.d2h", 200, 200)]),
            ("python", [_E("harmony/jobserver.status", 0, 1000)])]
    dev = [_E("%a = f32[] add(x)", 0, 100), _E("%b = f32[] add(x)", 600, 100)]
    found = _host_spans.attribute(_profile(host, dev))
    ns = 1e-9
    # idle 100..600: both workers wait 100..200, one drains 200..400, the
    # other still waits 400..500, only STATUS runs 500..600
    assert found["grant_idle_s"] == pytest.approx(100 * ns)
    assert found["drain_idle_s"] == pytest.approx(200 * ns)
    assert found["by_cause"] == pytest.approx({
        "drain.d2h": 200 * ns, "taskunit.wait": 200 * ns,
        "jobserver.status": 100 * ns})
    assert found["unnamed_idle_s"] == 0.0
    # the one gap is named after what holds most of it (a tie: the first)
    assert found["gaps"][0][2] in ("drain.d2h", "taskunit.wait")


def test_no_span_no_device_nothing_reported(monkeypatch):
    assert _host_spans.attribute(_profile([], [_E("%a", 0, 10)])) is None
    assert _host_spans.attribute(_profile(
        [("python", [_E("harmony/sleep", 0, 10)])], [])) is None
    monkeypatch.setattr(sys, "argv", ["run.py"])
    assert _host_spans.trace_path() is None
    assert _host_spans.idle_share("drain_idle_s") is None


def test_cell_comes_from_the_workload_argument(monkeypatch, tmp_path):
    monkeypatch.setattr(_host_spans, "ROOT", str(tmp_path))
    d = tmp_path / "chiprun_out" / "trace" / "c.solo" / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "c.solo"])
    assert _host_spans.trace_path() == str(d / "vm.xplane.pb")
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload=other"])
    assert _host_spans.trace_path() is None
