"""Step-phase time budget + cross-worker critical-path attribution
(ISSUE 13): the split math and its invariant, the budget store's
barrier join and shrink clamping, the worker's real budget feed, the
critpath classifier, the doctor's comm_bound/dispatch_bound rules, the
profiler-capture surfaces, the shared obs endpoint resolution — and
the fault-injected acceptance through the REAL stack (jobserver →
history → critpath → TCP STATUS → ``harmony-tpu obs critpath``)."""
from __future__ import annotations

import json
import os
import time

import jax
import pytest

from harmony_tpu.config.params import JobConfig, TrainerParams
from harmony_tpu.jobserver import joblog
from harmony_tpu.metrics import accounting, critpath, phases
from harmony_tpu.metrics.phases import (
    PHASES,
    RESIDUAL,
    PhaseBudgetStore,
    split_device_phases,
)
from harmony_tpu.metrics.registry import (
    MetricRegistry,
    get_registry,
    lint_exposition,
    set_registry,
)
from harmony_tpu.runtime import progcache

#: the budget invariant's tolerance (acceptance criterion: phases +
#: residual == wall within 5%)
TOL = 0.05


@pytest.fixture()
def fresh_phase():
    """Fresh registry + ledger + budget store + program cache + joblog:
    the phase plane owns process-global state on all five."""
    reg = set_registry(MetricRegistry())
    accounting.reset_ledger()
    phases.reset_budget()
    progcache.clear()
    joblog.clear_events()
    yield reg
    set_registry(MetricRegistry())
    accounting.reset_ledger()
    phases.reset_budget()
    progcache.clear()
    joblog.clear_events()


def _assert_invariant(row):
    """sum(phases incl residual) == wall within TOL, every fraction in
    [0, 1], fractions sum to ~1 — per tenant AND per worker."""
    wall = row["wall_sec"]
    s = sum(row["phases"].values())
    assert abs(s - wall) <= TOL * max(wall, 1e-9), (s, wall)
    for v in row["phases"].values():
        assert v >= 0.0
    for f in row["fractions"].values():
        assert 0.0 <= f <= 1.0
    if wall > 0:
        assert sum(row["fractions"].values()) == pytest.approx(1.0,
                                                               abs=TOL)
    for wrow in row["per_worker"].values():
        ws = sum(wrow["phases"].values())
        assert abs(ws - wrow["wall_sec"]) <= TOL * max(
            wrow["wall_sec"], 1e-9)


class TestSplitMath:
    def test_probe_off_charges_compute_conservatively(self):
        out = split_device_phases(2.0, 4, probe_split=(0.0, 0.0))
        assert out == {"pull_comm": 0.0, "compute": 2.0,
                       "push_comm": 0.0}
        # no probe at all, same answer
        out = split_device_phases(2.0, 4)
        assert out["compute"] == 2.0

    def test_flop_floor_refines_an_overestimating_probe(self):
        """On tiny tables the probe's sub-ms measurements can rival the
        step wall; compute must never drop below its FLOP-seconds floor
        — pull/push scale down to fit."""
        out = split_device_phases(
            1.0, 10, probe_split=(0.2, 0.1),  # 3.0s of "comm" in 1s
            flops_per_step=1e9, peak_flops=2e10, devices=1)
        floor = 1e9 * 10 / 2e10  # 0.5s
        assert out["compute"] >= floor
        assert sum(out.values()) == pytest.approx(1.0, abs=1e-9)
        # probe proportions preserved under the scale-down
        assert out["pull_comm"] == pytest.approx(2 * out["push_comm"])

    def test_dispatch_subtracts_from_available_work(self):
        out = split_device_phases(1.0, 4, dispatch_sec=0.4,
                                  probe_split=(0.05, 0.05))
        assert sum(out.values()) == pytest.approx(0.6, abs=1e-9)

    def test_degenerate_inputs_yield_zeros(self):
        assert split_device_phases(0.0, 4)["compute"] == 0.0
        assert split_device_phases(1.0, 0)["compute"] == 0.0
        neg = split_device_phases(1.0, 2, probe_split=(-0.5, 0.1))
        assert neg["pull_comm"] == 0.0


class TestBudgetStore:
    def test_invariant_and_residual(self, fresh_phase):
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j", "w0", 0, 1.0,
                            {"compute": 0.5, "pull_comm": 0.2})
        row = store.snapshot(window_sec=60.0)["j"]
        _assert_invariant(row)
        assert row["phases"][RESIDUAL] == pytest.approx(0.3)
        assert row["fractions"]["compute"] == pytest.approx(0.5)

    def test_shrink_mid_window_never_negative_or_over_100(
            self, fresh_phase):
        """Elastic shrink truncating the epoch: measured phases exceed
        the observed wall — the feed scales to fit, no phase goes
        negative, no fraction exceeds 1, the invariant holds."""
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j@a1", "w0", 3, 0.4,
                            {"compute": 0.5, "pull_comm": 0.2,
                             "host_dispatch": -0.1})
        row = store.snapshot(window_sec=60.0)["j"]
        _assert_invariant(row)
        assert row["wall_sec"] == pytest.approx(0.4)
        assert row["attempt"] == "j@a1"
        assert row["phases"]["host_dispatch"] == 0.0
        assert row["fractions"]["compute"] <= 1.0

    def test_barrier_is_the_chief_observed_gap(self, fresh_phase):
        """Two workers, same epoch: the fast worker's barrier_wait is
        exactly the gap to the gating sibling's wall, and both workers'
        budgets close against the JOB epoch span."""
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j", "w0", 0, 1.0, {"compute": 1.0})
        store.observe_epoch("j", "j", "w1", 0, 3.0, {"compute": 3.0})
        row = store.snapshot(window_sec=60.0)["j"]
        _assert_invariant(row)
        w0 = row["per_worker"]["w0"]
        assert w0["phases"]["barrier_wait"] == pytest.approx(2.0)
        assert w0["wall_sec"] == pytest.approx(3.0)
        assert row["per_worker"]["w1"]["phases"]["barrier_wait"] == 0.0
        assert row["epoch_walls"]["0"]["w1"] == pytest.approx(3.0)

    def test_barrier_join_never_mixes_attempts(self, fresh_phase):
        """An elastic restart re-runs the same epoch indices under a
        new attempt key: the barrier join is partitioned by the LIVE
        attempt, so attempt 1's epoch-0 wall can never charge phantom
        barrier seconds to attempt 2's epoch-0 (stale-attempt samples
        drop out of the snapshot entirely)."""
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j@a1", "w0", 0, 5.0, {"compute": 5.0})
        store.observe_epoch("j", "j@a2", "w0", 0, 1.0, {"compute": 1.0})
        row = store.snapshot(window_sec=60.0)["j"]
        assert row["attempt"] == "j@a2"
        w0 = row["per_worker"]["w0"]
        assert w0["phases"]["barrier_wait"] == 0.0
        assert w0["wall_sec"] == pytest.approx(1.0)
        assert row["epoch_walls"]["0"]["w0"] == pytest.approx(1.0)

    def test_memoized_snapshot_invalidates_on_feed(self, fresh_phase):
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j", "w0", 0, 1.0, {"compute": 1.0})
        first = store.snapshot_memoized(window_sec=60.0)
        assert store.snapshot_memoized(window_sec=60.0) is first
        store.observe_epoch("j", "j", "w0", 1, 1.0, {"compute": 1.0})
        fresh = store.snapshot_memoized(window_sec=60.0)
        assert fresh is not first
        assert fresh["j"]["epochs"] == 2

    def test_window_expiry(self, fresh_phase):
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j", "w0", 0, 1.0, {"compute": 1.0})
        time.sleep(0.05)
        assert "j" not in store.snapshot(window_sec=0.01)
        assert "j" in store.snapshot(window_sec=60.0)

    def test_exposition_gauge_and_lint(self, fresh_phase):
        phases.budget().observe_epoch("j", "j", "w0", 0, 1.0,
                                      {"compute": 0.6,
                                       "pull_comm": 0.1})
        text = get_registry().expose()
        assert "harmony_phase_budget_seconds" in text
        assert 'phase="residual"' in text
        assert lint_exposition(text) == []


class TestCritpath:
    def test_classification_thresholds_and_precedence(self):
        assert critpath.classify({"input_wait": 0.5}) == "input-bound"
        assert critpath.classify(
            {"pull_comm": 0.3, "push_comm": 0.15}) == "comm-bound"
        assert critpath.classify(
            {"host_dispatch": 0.35}) == "dispatch-bound"
        assert critpath.classify({"compute": 0.7}) == "compute-bound"
        assert critpath.classify({"compute": 0.4,
                                  "residual": 0.6}) == "balanced"
        # precedence: fix the earliest pipeline stage first
        assert critpath.classify(
            {"input_wait": 0.4, "pull_comm": 0.5}) == "input-bound"

    def test_epoch_critical_path_names_worker_and_phase(
            self, fresh_phase):
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j", "w0", 0, 1.0, {"compute": 0.9})
        store.observe_epoch("j", "j", "w1", 0, 2.0,
                            {"pull_comm": 1.5, "compute": 0.4})
        row = store.snapshot(window_sec=60.0)["j"]
        cp = critpath.epoch_critical_path(row)
        assert cp == [{"epoch": 0, "worker": "w1", "wall_sec": 2.0,
                       "phase": "pull_comm"}]

    def test_analyze_enriches_with_stragglers(self, fresh_phase):
        store = PhaseBudgetStore()
        store.observe_epoch("j", "j", "w0", 0, 1.0, {"compute": 0.9})
        out = critpath.analyze(store.snapshot(window_sec=60.0),
                               stragglers={"j": {"ratio": 2.5}})
        assert out["j"]["classification"] == "compute-bound"
        assert out["j"]["dominant_phase"] == "compute"
        assert out["j"]["straggler_ratio"] == 2.5
        assert out["j"]["critical_path"]


def _run_worker(job_id, *, num_epochs=3, features=64, classes=8, n=64,
                batches=2, devices=2):
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
    from harmony_tpu.dolphin.data import TrainingDataProvider
    from harmony_tpu.dolphin.trainer import TrainerContext
    from harmony_tpu.dolphin.worker import WorkerTasklet
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.table import DenseTable, TableSpec

    mesh = build_mesh(jax.devices()[:devices], data=devices)
    trainer = MLRTrainer(num_classes=classes, num_features=features,
                         features_per_partition=features // 2)
    table = DenseTable(TableSpec(trainer.model_table_config(num_blocks=8)),
                       mesh)
    x, y = make_synthetic(n, features, classes)
    w = WorkerTasklet(
        job_id,
        TrainerContext(params=TrainerParams(num_epochs=num_epochs,
                                            num_mini_batches=batches),
                       model_table=table),
        trainer,
        TrainingDataProvider([x, y], batches),
        mesh,
    )
    w.run()
    return w


class TestWorkerBudget:
    """Fixed-seed real runs: the budget invariant holds through the
    REAL worker path, and the comm split flows through the table's typed
    accessor, not a private-attr poke."""

    def test_fused_run_feeds_an_invariant_budget(self, devices,
                                                 fresh_phase):
        w = _run_worker("fused-j")
        row = phases.peek_budget().snapshot(window_sec=300.0)["fused-j"]
        _assert_invariant(row)
        assert row["epochs"] == 3
        assert row["phases"]["compute"] > 0.0
        # the probe published through the typed accessor
        assert w.ctx.model_table.comm_split() is not None

    def test_ledger_join_carries_phases_and_class(self, devices,
                                                  fresh_phase):
        from harmony_tpu.metrics.manager import MetricManager

        _run_worker("join-j")
        mgr = MetricManager()
        rows = mgr.tenant_ledger()
        assert rows["join-j"]["phases"] is not None
        assert sum(rows["join-j"]["phases"].values()) == pytest.approx(
            1.0, abs=TOL)
        assert rows["join-j"]["phase_class"] in (
            "balanced", "compute-bound", "comm-bound",
            "dispatch-bound", "input-bound")
        pb = mgr.phase_budget()
        assert pb["join-j"]["critical_path"]


class TestHistoryFold:
    def test_scraper_folds_tenant_phase_series(self, fresh_phase,
                                               monkeypatch):
        from harmony_tpu.metrics.history import HistoryScraper, HistoryStore

        monkeypatch.setenv("HARMONY_OBS_RESOLUTION", "0.01")
        store = HistoryStore(window_sec=900.0, resolution_sec=0.01)

        def ledger_fn():
            return {"j": {"attempt": "j", "mfu": None,
                          "phases": {"pull_comm": 0.5, "compute": 0.3,
                                     "residual": None}}}

        s = HistoryScraper(store, targets_fn=dict, ledger_fn=ledger_fn,
                           period=3600.0)
        s.poll_once()
        got = store.latest("tenant.phase.pull_comm", {"job": "j"})
        assert got and got[0][2] == 0.5
        # None stays unknown, never 0
        assert not store.latest("tenant.phase.residual")


def _feed(store, name, job, values, now=None, spacing=5.0):
    now = time.time() if now is None else now
    t0 = now - spacing * len(values)
    for i, v in enumerate(values):
        store.ingest(name, {"job": job, "attempt": job}, v,
                     ts=t0 + i * spacing)


class TestDoctorPhaseRules:
    def test_comm_bound_fires_and_stays_silent_when_healthy(self):
        from harmony_tpu.metrics.doctor import Doctor
        from harmony_tpu.metrics.history import HistoryStore

        store = HistoryStore(window_sec=900.0, resolution_sec=1.0)
        _feed(store, "tenant.phase.pull_comm", "hot-j",
              [0.4, 0.45, 0.5])
        _feed(store, "tenant.phase.push_comm", "hot-j",
              [0.1, 0.1, 0.1])
        _feed(store, "tenant.phase.pull_comm", "cool-j",
              [0.05, 0.05, 0.05])
        doc = Doctor(store, events_fn=dict)
        diags = doc.diagnose()
        comm = [d for d in diags if d.rule == "comm_bound"]
        assert len(comm) == 1 and comm[0].job == "hot-j"
        assert comm[0].evidence["points"]
        assert comm[0].evidence["comm_fraction"] >= 0.4

    def test_dispatch_bound_fires_with_evidence(self):
        from harmony_tpu.metrics.doctor import Doctor
        from harmony_tpu.metrics.history import HistoryStore

        store = HistoryStore(window_sec=900.0, resolution_sec=1.0)
        _feed(store, "tenant.phase.host_dispatch", "slow-j",
              [0.35, 0.4, 0.5])
        _feed(store, "tenant.phase.host_dispatch", "ok-j",
              [0.01, 0.02, 0.01])
        doc = Doctor(store, events_fn=dict)
        diags = doc.diagnose()
        disp = [d for d in diags if d.rule == "dispatch_bound"]
        assert len(disp) == 1 and disp[0].job == "slow-j"
        assert disp[0].evidence["median"] >= 0.3
        assert not any(d.job == "ok-j" for d in diags)


class TestRuleDocParity:
    def test_new_rules_are_declared_and_cataloged(self):
        """The doctor-rule doc-parity lint direction covers the two new
        rules: both are shipped through doctor_rule() AND carry a Rule-
        catalog row (the full both-ways check is the metric-conventions
        pass, tier-1 via the harmonylint gate — this pins the rows the
        new rules specifically depend on)."""
        from harmony_tpu.metrics.doctor import all_rules

        names = {r.name for r in all_rules()}
        doc = open(os.path.join(os.path.dirname(__file__), "..",
                                "docs", "OBSERVABILITY.md")).read()
        catalog = doc[doc.index("### Rule catalog"):]
        catalog = catalog[:catalog.index("### ", 4)]
        for rule in ("comm_bound", "dispatch_bound"):
            assert rule in names
            assert f"`{rule}`" in catalog


class TestProfilerSurfaces:
    def test_newest_capture_is_per_process(self, tmp_path):
        from harmony_tpu.tracing import profiler

        assert profiler.newest_capture(str(tmp_path / "absent")) is None
        for i in range(3):
            d = tmp_path / f"job-e{i}-123"
            d.mkdir()
            (d / "dump.xplane").write_bytes(b"x" * 10)
            os.utime(d, (1000 + i, 1000 + i))
        # a FOREIGN process's newer capture must never be reported as
        # this process's (the default dir is shared across runs)
        got = profiler.newest_capture(str(tmp_path), pid=123)
        assert got.endswith("job-e2-123")
        assert profiler.newest_capture(str(tmp_path)) is None
        # pid=0 matches every capture (operator-facing "anything here?")
        assert profiler.newest_capture(str(tmp_path),
                                       pid=0).endswith("job-e2-123")

    def test_rotation_is_oldest_first_across_many_epochs(self,
                                                         tmp_path):
        """The satellite's pin: captures landing epoch after epoch
        under a byte cap delete OLDEST first, and the newest capture
        always survives — even when the cap is smaller than one
        capture."""
        from harmony_tpu.tracing import profiler

        for e in range(12):
            d = tmp_path / f"job-e{e}"
            d.mkdir()
            (d / "dump.xplane").write_bytes(b"x" * 100)
            os.utime(d, (1000 + e, 1000 + e))
            profiler.rotate_profile_dir(str(tmp_path), max_bytes=350)
            left = sorted(p.name for p in tmp_path.iterdir())
            # never more than the cap's worth (3 captures), and the
            # survivors are always the NEWEST epochs
            assert len(left) <= 3
            want = [f"job-e{i}"
                    for i in range(max(0, e - 2), e + 1)][-len(left):]
            assert left == sorted(want)
        # cap below one capture: the newest still survives
        removed = profiler.rotate_profile_dir(str(tmp_path),
                                              max_bytes=10)
        assert (tmp_path / "job-e11").exists()
        assert removed >= 1

    def test_status_lists_newest_capture(self, fresh_phase, tmp_path,
                                         monkeypatch):
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.metrics.doctor import set_doctor

        cap = tmp_path / f"job-e0-{os.getpid()}"
        cap.mkdir()
        (cap / "dump.xplane").write_bytes(b"x")
        monkeypatch.setenv("HARMONY_PROFILE_DIR", str(tmp_path))
        srv = JobServer(num_executors=1)
        try:
            assert srv._status()["profile_capture"] == str(cap)
        finally:
            set_doctor(None)


class TestObsEndpointResolution:
    def _args(self, what, port=None, url=None):
        import argparse

        return argparse.Namespace(what=what, port=port, url=url)

    def test_url_commands_error_names_the_knob(self, monkeypatch,
                                               capsys):
        from harmony_tpu.cli import _cmd_obs_inner

        monkeypatch.delenv("HARMONY_METRICS_URL", raising=False)
        monkeypatch.delenv("HARMONY_DASHBOARD_URL", raising=False)
        assert _cmd_obs_inner(self._args("metrics")) == 2
        assert "HARMONY_METRICS_URL" in capsys.readouterr().err
        assert _cmd_obs_inner(self._args("trace")) == 2
        assert "HARMONY_DASHBOARD_URL" in capsys.readouterr().err

    def test_env_knobs_resolve(self, monkeypatch):
        from harmony_tpu.cli import _resolve_obs_endpoint

        monkeypatch.setenv("HARMONY_METRICS_URL", "http://x:1/")
        assert _resolve_obs_endpoint(self._args("metrics")) == (
            "url", "http://x:1")
        monkeypatch.setenv("HARMONY_DASHBOARD_URL", "http://d:2")
        assert _resolve_obs_endpoint(self._args("trace")) == (
            "url", "http://d:2")
        monkeypatch.setenv("HARMONY_JOBSERVER_PORT", "5555")
        assert _resolve_obs_endpoint(self._args("critpath")) == (
            "port", 5555)
        # the explicit flag always wins
        assert _resolve_obs_endpoint(
            self._args("top", port=7777)) == ("port", 7777)
        assert _resolve_obs_endpoint(
            self._args("metrics", url="http://y:3")) == (
            "url", "http://y:3")

    def test_default_port_without_env(self, monkeypatch):
        from harmony_tpu.cli import _resolve_obs_endpoint

        monkeypatch.delenv("HARMONY_JOBSERVER_PORT", raising=False)
        assert _resolve_obs_endpoint(self._args("doctor")) == (
            "port", 43110)

    def test_bad_port_env_is_a_usage_error(self, monkeypatch):
        from harmony_tpu.cli import _resolve_obs_endpoint

        monkeypatch.setenv("HARMONY_JOBSERVER_PORT", "nope")
        with pytest.raises(SystemExit):
            _resolve_obs_endpoint(self._args("top"))

    def test_render_critpath_waterfall(self):
        from harmony_tpu.cli import _render_critpath

        budget = {"j": {
            "attempt": "j@a1", "classification": "comm-bound",
            "wall_sec": 2.0, "epochs": 2,
            "phases": {p: 0.0 for p in (*PHASES, RESIDUAL)},
            "fractions": {**{p: 0.0 for p in (*PHASES, RESIDUAL)},
                          "pull_comm": 0.6, "compute": 0.4},
            "per_worker": {"w0": {}},
            "critical_path": [{"epoch": 0, "worker": "w0",
                               "phase": "pull_comm",
                               "wall_sec": 1.0}],
            "straggler_ratio": 1.0,
        }}
        text = "\n".join(_render_critpath(budget))
        assert "comm-bound" in text and "j@a1" in text
        assert "pull" in text and "e0:w0(pull_comm)" in text
        assert _render_critpath({}) == [
            "(no phase budget recorded — no worker fed the "
            "budget store in the window)"]


class TestDashboardCritpath:
    def test_api_and_panel(self, fresh_phase):
        from harmony_tpu.dashboard.server import DashboardServer
        import urllib.request

        srv = DashboardServer().start()
        try:
            row = {"job": "p-j", "phases": {"compute": 0.7,
                                            "residual": 0.3},
                   "phase_class": "compute-bound"}
            srv.insert("p-j", "tenant", row)
            srv.insert("p-j", "tenant", {"job": "p-j", "phases": None})
            api = json.loads(urllib.request.urlopen(
                srv.url + "/api/critpath?job_id=p-j", timeout=10).read())
            assert len(api["rows"]) == 1  # budget-less rows skipped
            assert api["rows"][0]["classification"] == "compute-bound"
            html = urllib.request.urlopen(
                srv.url + "/critpath?job_id=p-j", timeout=10
            ).read().decode()
            assert "compute-bound" in html and "residual" in html
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url + "/critpath",
                                       timeout=10)
            assert e.value.code == 400
        finally:
            srv.stop()


def _job_cfg(job_id, *, features=8, classes=4, n=16, workers=1,
             epochs=3, batches=4):
    return JobConfig(
        job_id=job_id, app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=epochs, num_mini_batches=batches,
            app_params={"num_classes": classes, "num_features": features,
                        "features_per_partition": features // 2}),
        num_workers=workers,
        user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
              "data_args": {"n": n, "num_features": features,
                            "num_classes": classes}},
    )


@pytest.mark.faults
class TestAcceptance:
    """Fault-injected acceptance (ISSUE 13) through the REAL stack —
    jobserver → history → critpath → TCP STATUS → ``obs critpath``:
    an injected comm delay (the blockmove.send delay-rule precedent,
    at the new ``worker.pull`` site) classifies its tenant comm-bound
    and names it as the epoch critical path; an injected host stall
    (``worker.dispatch``) classifies dispatch-bound; a healthy
    multi-worker control stays balanced — each diagnosis exactly once
    per window with non-empty evidence."""

    def test_three_scenarios_end_to_end(self, devices, capsys,
                                        monkeypatch, fresh_phase):
        from harmony_tpu import faults
        from harmony_tpu.cli import main as cli_main
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel.mesh import DevicePool

        faults.reset_counters()
        monkeypatch.setenv("HARMONY_OBS_RESOLUTION", "0.01")
        faults.arm(faults.FaultPlan([
            faults.FaultRule("worker.pull", match={"job": "comm-j"},
                             count=-1, action="delay", delay_sec=0.05),
            faults.FaultRule("worker.dispatch",
                             match={"job": "disp-j"},
                             count=-1, action="delay", delay_sec=0.05),
        ]))
        server = JobServer(num_executors=2,
                           device_pool=DevicePool(jax.devices()[:2]))
        server._history_scraper.period = 3600.0  # polls driven by hand
        server.start()
        try:
            # the same shapes once without a fault: a cold process puts
            # the step's and the probe's compiles into the injected
            # tenants' walls (pull_comm 0.37 of it against the 0.4
            # threshold; 0.58 warm) and the verdict would hang on which
            # tests this process ran before
            server.submit(_job_cfg("warm-j")).result(timeout=300)
            server.submit(_job_cfg("comm-j")).result(timeout=300)
            server.submit(_job_cfg("disp-j")).result(timeout=300)
            faults.disarm()
            # the healthy control: two workers — also exercises the
            # chief-observed barrier join on a REAL run. Heavy enough
            # (vs the injected tenants' tiny shapes) that timing noise
            # on a loaded machine cannot push a sub-millisecond probe
            # or placement over a classification threshold of its wall.
            server.submit(_job_cfg("ok-j", workers=2, features=64,
                                   classes=8, n=128)).result(
                timeout=300)
            server._history_scraper.poll_once()
            time.sleep(0.05)  # past the (test-sized) resolution bucket
            server._history_scraper.poll_once()
            time.sleep(0.05)
            server._history_scraper.poll_once()  # dedupe: no re-fire
            port = server.serve_tcp(0)

            # critpath over the TCP STATUS wire, via the CLI
            assert cli_main(["obs", "critpath", "--port", str(port),
                             "--json"]) == 0
            budget = json.loads(capsys.readouterr().out)
            comm, disp, ok = (budget["comm-j"], budget["disp-j"],
                              budget["ok-j"])
            for row in (comm, disp, ok):
                _assert_invariant(row)
            assert comm["classification"] == "comm-bound"
            assert disp["classification"] == "dispatch-bound"
            assert ok["classification"] == "balanced"
            # the comm tenant's worker is NAMED as the epoch critical
            # path, gated by pull_comm — who AND why
            assert comm["critical_path"]
            for entry in comm["critical_path"]:
                assert entry["worker"] == "comm-j/w0"
                assert entry["phase"] == "pull_comm"
            assert all(e["phase"] == "host_dispatch"
                       for e in disp["critical_path"])
            # the control's 2 workers both budgeted; someone paid a
            # real (chief-observed) barrier wait
            assert len(ok["per_worker"]) == 2

            # the doctor's verdicts: exactly once per window each,
            # with non-empty evidence, and the control untouched
            assert cli_main(["obs", "doctor", "--port", str(port),
                             "--json"]) == 0
            diags = json.loads(capsys.readouterr().out)["diagnoses"]
            by_rule = {}
            for d in diags:
                by_rule.setdefault(d["rule"], []).append(d)
            assert len(by_rule.get("comm_bound", [])) == 1, diags
            assert len(by_rule.get("dispatch_bound", [])) == 1, diags
            cb = by_rule["comm_bound"][0]
            assert cb["job"] == "comm-j"
            assert cb["evidence"]["points"]
            assert cb["evidence"]["comm_fraction"] >= 0.4
            db = by_rule["dispatch_bound"][0]
            assert db["job"] == "disp-j"
            assert db["evidence"]["points"]
            assert not any(
                d.get("job") == "ok-j"
                and d["rule"] in ("comm_bound", "dispatch_bound")
                for d in diags)

            # text rendering sanity (the non-json face)
            assert cli_main(["obs", "critpath", "--port",
                             str(port)]) == 0
            text = capsys.readouterr().out
            assert "comm-bound" in text and "comm-j" in text
            assert "critical path" in text
        finally:
            faults.disarm()
            server.shutdown(timeout=60)
            faults.reset_counters()
