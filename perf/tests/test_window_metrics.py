"""Checks of the window readers (``perf/layer_metrics/_windows.py`` and the
three metrics built on it) on a scripted registry and store, and of the
straddle test on the capture ``record_window_fixture.py`` recorded. Run by
hand with the rest of ``perf/tests``; ``tests/test_perf_window_readers.py``
runs the same under tier-1."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perf import trace_reduce  # noqa: E402
from perf.layer_metrics import (  # noqa: E402
    _host_spans,
    _windows,
    window_stall_s,
    window_stall_unnamed_share,
    window_wall_spread,
)

FIXTURE = os.path.join(HERE, "fixture_windows.xplane.pb")
READERS = (window_stall_s, window_stall_unnamed_share, window_wall_spread)


def _record(n, wall, **spans):
    return {"window": n, "epoch": 8 * n, "epochs": 8, "steps": 32,
            "start_ns": 10 ** 9 * n, "end_ns": 10 ** 9 * n + int(wall * 1e9),
            "wall_s": wall, "compile_s": 0.0, "first": n == 0,
            "spans": {k.replace("_", ".", 1): v for k, v in spans.items()}}


@pytest.fixture()
def scripted(monkeypatch):
    """A fresh registry and store holding two measured jobs: ``fixture-a``
    with ten regular windows and two late ones (epoch 32: 2 s under no
    span; epoch 48: 1 s under ``drain.d2h``), ``fixture-b`` regular
    throughout; and this run's trace is the recorded capture."""
    from harmony_tpu.metrics import phases, registry

    old = registry.get_registry()
    registry.set_registry(registry.MetricRegistry())
    phases.reset_budget()
    _windows._cache.clear()
    monkeypatch.setattr(_host_spans, "trace_path", lambda cell=None: FIXTURE)
    store = phases.budget()
    walls = [0.96, 1.0, 1.04, 1.0]  # quartiles 0.97 / 1.0 / 1.03
    late = {4: (3.0, dict(step_dispatch=0.3, drain_d2h=0.6)),
            6: (2.0, dict(step_dispatch=0.3, drain_d2h=1.6))}
    for job in ("fixture-a", "fixture-b"):
        for n in range(13):
            if n == 0:
                rec = _record(0, 9.0, step_dispatch=8.0)
            elif job == "fixture-a" and n in late:
                rec = _record(n, late[n][0], **late[n][1])
            else:
                rec = _record(n, walls[n % 4], step_dispatch=0.3,
                              drain_d2h=0.6)
            phases.count_window(job, rec, store.observe_window(
                job, job + "@a0", "w0", rec))
    yield {"phases": {"fixture-a": None, "fixture-b": None}}
    registry.set_registry(old)
    phases.reset_budget()
    _windows._cache.clear()


def test_a_program_without_the_ledger_reports_nothing(monkeypatch):
    from harmony_tpu.metrics import phases

    _windows._cache.clear()
    monkeypatch.setattr(phases, "budget", lambda: object())  # the parent's
    obs = {"phases": {"some-job": None}}
    assert _windows.summary(obs) is None
    for reader in READERS:
        assert reader.read(obs) is None and reader.read({}) is None
    _windows._cache.clear()


def test_a_measured_job_without_a_record_reports_nothing(scripted):
    _windows._cache.clear()
    obs = {"phases": {"fixture-a": None, "never-fed": None}}
    assert all(reader.read(obs) is None for reader in READERS)


def test_the_three_readers_on_a_scripted_run(scripted, capsys):
    # fixture-a lost (3.0 - 1.0) under no span and (2.0 - 1.0) under
    # drain.d2h; fixture-b nothing: the mean over the two tenants
    assert window_stall_s.read(scripted) == pytest.approx(1.5, abs=0.02)
    assert window_stall_unnamed_share.read(scripted) == pytest.approx(
        0.5 * 100.0 * 2.0 / 3.0, abs=1.0)
    spread = window_wall_spread.read(scripted)
    assert 2.0 < spread < 8.0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"line": "window_stalls"')]
    assert len(lines) == 1  # three readers, one line
    a, b = lines[0]["jobs"]["fixture-a"], lines[0]["jobs"]["fixture-b"]
    assert b["late"] == [] and b["stall_s"] == {}
    assert b["median_wall_s"] == pytest.approx(1.0 / 8, abs=0.01)
    assert (a["windows"], b["regular"]) == (13, 12)
    assert [(w["window"], w["cause"]) for w in a["late"]] \
        == [(4, "unnamed"), (6, "drain.d2h")]
    assert a["stall_s"] == pytest.approx({"unnamed": 2.0, "drain.d2h": 1.0},
                                         abs=0.05)
    for w in a["late"]:
        assert len(w["excess"]) <= 3 and w["lost_s"] > 0
        assert w["at_s"] == pytest.approx(w["window"] - 9.0, abs=1e-6)
    # the capture holds fixture-a's windows of epochs 24..40 whole: the
    # window of epoch 32 straddles neither end, the one of epoch 48 the stop
    assert [(w["straddles_trace_start"], w["from_trace_start"],
             w["from_trace_stop"]) for w in a["late"]] \
        == [(False, 2, -2), (False, 4, 0)]
    # late or not, the windows that held the profiler's start and its stop
    assert {end: w["window"] for end, w in a["at_trace"].items()} \
        == {"start": 2, "stop": 6}
    assert a["at_trace"]["stop"]["over_median_s"] == pytest.approx(1.0,
                                                                   abs=0.05)
    assert abs(a["at_trace"]["start"]["over_median_s"]) < 0.05
    assert a["longest_window_s"] == 3.0 and b["longest_window_s"] == 1.04
    assert b["usual_s"] == pytest.approx(
        {"step.dispatch": 0.3, "drain.d2h": 0.6, "unnamed": 0.1}, abs=0.05)
    assert a["elapsed_s"] == pytest.approx(12.96 - 9.0, abs=1e-6)


def test_the_straddle_test_on_the_recorded_capture():
    traced = _windows.traced_epochs(trace_reduce.load(FIXTURE))
    assert traced == {"fixture-a": (24, 40, 8), "fixture-b": (16, 32, 8)}
    a = traced["fixture-a"]
    held = [e for e in (8, 16, 24, 32, 40, 48, 56)
            if _windows.straddles_start({"epoch": e, "epochs": 8}, a)]
    assert held == [16, 24]
    assert _windows.straddles_start({"epoch": 16, "epochs": 8}, None) is None


def test_the_recorded_events_carry_the_window_stat():
    """What ``dolphin/worker.py`` annotates its container span with lands
    in the capture as event stats: ``window`` beside ``job_id`` / ``epoch``
    / ``epochs``."""
    profile = trace_reduce.load(FIXTURE)
    seen = []
    for plane in profile.planes:
        if plane.name != _host_spans.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in _windows.WINDOW_EVENTS:
                    stats = dict(e.stats)
                    seen.append((stats["job_id"], stats["window"],
                                 stats["epoch"], stats["epochs"]))
    assert sorted(seen) == sorted(
        [("fixture-a", n, 8 * n, 8) for n in (3, 4, 5)]
        + [("fixture-b", n, 8 * n, 8) for n in (2, 3, 4)])
