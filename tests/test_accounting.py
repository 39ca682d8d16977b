"""Per-tenant device cost accounting (ISSUE 8): compile telemetry in
the program cache, the tenant ledger (device-seconds / FLOPs / MFU /
resident HBM / input-wait / SLO attainment), STATUS + flight + obs-top
surfaces, and the sampled continuous profiler.

The None-vs-zero distinction is load-bearing throughout: a backend
without a cost model yields flops=None and mfu=None — never 0.0, which
is reserved for real zeros — and every renderer must show such rows as
'-', not crash, not zero.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
from harmony_tpu.config.params import JobConfig, TrainerParams
from harmony_tpu.dolphin import (
    TrainerContext,
    TrainingDataProvider,
    WorkerTasklet,
)
from harmony_tpu.jobserver import joblog
from harmony_tpu.metrics import accounting
from harmony_tpu.metrics.registry import (
    MetricRegistry,
    get_registry,
    lint_exposition,
    set_registry,
)
from harmony_tpu.parallel import build_mesh
from harmony_tpu.runtime import progcache
from harmony_tpu.table import DenseTable, TableSpec


@pytest.fixture()
def fresh_obs():
    """Fresh registry + ledger + program cache + joblog events: the
    accounting plane owns process-global state on all four."""
    reg = set_registry(MetricRegistry())
    accounting.reset_ledger()
    progcache.clear()
    joblog.clear_events()
    yield reg
    set_registry(MetricRegistry())
    accounting.reset_ledger()
    progcache.clear()
    joblog.clear_events()


def _run_worker(job_id, *, num_epochs=1, target_sps=0.0, features=8,
                classes=4, n=16, batches=2, devices=2):
    mesh = build_mesh(jax.devices()[:devices], data=devices)
    trainer = MLRTrainer(num_classes=classes, num_features=features,
                         features_per_partition=features // 2)
    table = DenseTable(TableSpec(trainer.model_table_config(num_blocks=8)),
                       mesh)
    x, y = make_synthetic(n, features, classes)
    w = WorkerTasklet(
        job_id,
        TrainerContext(
            params=TrainerParams(num_epochs=num_epochs,
                                 num_mini_batches=batches,
                                 target_samples_per_sec=target_sps),
            model_table=table),
        trainer,
        TrainingDataProvider([x, y], batches),
        mesh,
    )
    result = w.run()
    return w, result


class TestCompileTelemetry:
    def test_cost_table_records_flops_and_compile_seconds(self, fresh_obs):
        key = ("ct-key", "step")

        def build():
            return jax.jit(lambda a: (a @ a).sum())

        fn = progcache.get_or_build(key, build)
        out = fn(jnp.ones((64, 64)))
        assert float(out) != 0.0
        cost = progcache.program_cost(key)
        assert cost is not None
        assert cost.tag == "step"
        assert cost.compile_seconds > 0
        # the CPU backend exposes cost analysis: a matmul has real FLOPs
        assert cost.flops is not None and cost.flops > 0
        assert cost.argument_bytes == 64 * 64 * 4
        # ... and the compile landed in the scrape surface
        text = get_registry().expose()
        assert "harmony_compile_seconds" in text
        assert lint_exposition(text) == []

    def test_steady_state_reuses_the_measured_executable(self, fresh_obs):
        calls = []

        def build():
            def f(a):
                calls.append(1)
                return a * 2

            return jax.jit(f)

        fn = progcache.get_or_build(("ss-key", "step"), build)
        a = jnp.ones((8,))
        first = np.asarray(fn(a))
        traces_after_first = len(calls)
        for _ in range(3):
            np.testing.assert_array_equal(np.asarray(fn(a)), first)
        # no retracing after the instrumented first call: the AOT
        # executable (or the jit cache) serves steady state
        assert len(calls) == traces_after_first

    def test_shape_drift_falls_back_to_plain_jit(self, fresh_obs):
        fn = progcache.get_or_build(("drift-key", "step"),
                                    lambda: jax.jit(lambda a: a + 1))
        np.testing.assert_array_equal(np.asarray(fn(jnp.zeros((4,)))),
                                      np.ones(4))
        # a different shape under the same key: must compute, not raise
        out = fn(jnp.zeros((9,)))
        np.testing.assert_array_equal(np.asarray(out), np.ones(9))
        # and stays on the fallback path from then on
        np.testing.assert_array_equal(np.asarray(fn(jnp.zeros((4,)))),
                                      np.ones(4))

    def test_non_stage_builder_records_wall_time_only(self, fresh_obs):
        fn = progcache.get_or_build(("plain-key", "table_init"),
                                    lambda: (lambda a: a + 1))
        assert fn(1) == 2 and fn(2) == 3
        cost = progcache.program_cost(("plain-key", "table_init"))
        assert cost is not None
        assert cost.compile_seconds >= 0
        assert cost.flops is None  # no executable to analyse: explicit None

    def test_drop_evicts_cost_rows_with_their_executables(self, fresh_obs):
        key = ("dropped-key", "step")
        fn = progcache.get_or_build(key, lambda: jax.jit(lambda a: a + 1))
        fn(jnp.zeros((4,)))
        assert progcache.program_cost(key) is not None
        progcache.drop(lambda k: k[0] == "dropped-key")
        # the reshard path discarded the executable: its cost row must
        # not keep showing in program_costs()/STATUS
        assert progcache.program_cost(key) is None

    def test_cost_analysis_raising_or_empty_yields_none(self):
        class RaisingCompiled:
            def cost_analysis(self):
                raise NotImplementedError("backend has no cost model")

            def memory_analysis(self):
                return None

        cost = progcache._extract_cost("step", 0.5, RaisingCompiled())
        assert cost.flops is None and cost.bytes_accessed is None
        assert cost.temp_bytes is None

        class EmptyCompiled:
            def cost_analysis(self):
                return []

            def memory_analysis(self):
                raise RuntimeError("nope")

        cost = progcache._extract_cost("step", 0.5, EmptyCompiled())
        assert cost.flops is None and cost.argument_bytes is None


class TestLedgerStore:
    def test_window_excludes_old_samples(self, fresh_obs):
        store = accounting.ledger()
        store.observe_steps("w-j", "w-j", "w0", steps=4, device_sec=0.4,
                            examples=100, flops_per_step=10.0)
        time.sleep(0.06)
        store.observe_steps("w-j", "w-j", "w0", steps=2, device_sec=0.1,
                            examples=50, flops_per_step=10.0)
        narrow = store.snapshot(window_sec=0.05)["w-j"]
        assert narrow["steps"] == 2 and narrow["examples"] == 50
        # cumulative totals never window away
        assert narrow["steps_total"] == 6
        wide = store.snapshot(window_sec=60.0)["w-j"]
        assert wide["steps"] == 6 and wide["examples"] == 150

    def test_mfu_requires_both_flops_and_peak(self, fresh_obs, monkeypatch):
        store = accounting.ledger()
        store.observe_steps("m-a", "m-a", "w0", steps=10, device_sec=1.0,
                            examples=10, flops_per_step=1e10, devices=1)
        store.observe_steps("m-b", "m-b", "w0", steps=10, device_sec=1.0,
                            examples=10, flops_per_step=None, devices=1)
        # no chip peak (CPU): MFU is None for everyone — explicitly, not 0
        snap = store.snapshot()
        assert snap["m-a"]["mfu"] is None
        assert snap["m-b"]["mfu"] is None
        # with a peak, MFU exists EXACTLY where the cost model did
        monkeypatch.setattr(accounting, "_peak_flops", lambda: 1e12)
        snap = store.snapshot()
        assert snap["m-a"]["mfu"] == pytest.approx(0.1)
        assert snap["m-b"]["mfu"] is None
        assert snap["m-b"]["model_flops"] is None

    def test_device_count_tracks_the_live_mesh(self, fresh_obs,
                                               monkeypatch):
        """Elastic shrink: the MFU denominator must follow the CURRENT
        mesh, not the widest the job ever held (last-wins, not max)."""
        store = accounting.ledger()
        monkeypatch.setattr(accounting, "_peak_flops", lambda: 1e12)
        store.observe_steps("sh-j", "sh-j", "w0", steps=1, device_sec=1.0,
                            examples=1, flops_per_step=1e11, devices=8)
        assert store.snapshot()["sh-j"]["devices"] == 8
        store.observe_steps("sh-j", "sh-j@a1", "w0", steps=1,
                            device_sec=1.0, examples=1,
                            flops_per_step=1e11, devices=4)
        row = store.snapshot()["sh-j"]
        assert row["devices"] == 4
        # mfu = 2e11 / 2.0s / (1e12 * 4), NOT / (1e12 * 8)
        assert row["mfu"] == pytest.approx(0.025)

    def test_multi_worker_busy_floor_does_not_deflate_rate(self,
                                                           fresh_obs):
        """Two workers' busy seconds overlap in wall time: the rate
        floor divides by the worker count, so a 2-worker tenant is not
        reported at half its real samples/sec."""
        store = accounting.ledger()
        store.observe_steps("mw-j", "mw-j", "w0", steps=1, device_sec=10.0,
                            examples=100)
        store.observe_steps("mw-j", "mw-j", "w1", steps=1, device_sec=10.0,
                            examples=100)
        row = store.snapshot()
        # wall span ~0; floor = 20s busy / 2 workers = 10s -> 20 sps
        assert row["mw-j"]["samples_per_sec"] == pytest.approx(20.0,
                                                               rel=0.05)

    def test_byte_attribution_through_table_binding(self, fresh_obs):
        store = accounting.ledger()
        store.bind_table("tab-1", "b-j", "b-j@a1")
        store.record_table_bytes("tab-1", "move", 1000)
        store.record_table_bytes("unbound-tab", "move", 999)  # dropped
        store.record_job_bytes("b-j", "chkp_write", 500)
        snap = store.snapshot()
        assert snap["b-j"]["bytes"] == {"move": 1000, "chkp_write": 500}
        assert snap["b-j"]["attempt"] == "b-j@a1"
        assert "unbound-tab" not in snap

    def test_hbm_share_sums_to_one(self, fresh_obs):
        store = accounting.ledger()
        store.set_resident("h-a", "h-a", "table", 300)
        store.set_resident("h-b", "h-b", "table", 100)
        snap = store.snapshot()
        assert snap["h-a"]["hbm_share"] == pytest.approx(0.75)
        assert snap["h-b"]["hbm_share"] == pytest.approx(0.25)


class TestWorkerLedgerFeeds:
    def test_worker_run_populates_the_ledger(self, fresh_obs):
        _w, result = _run_worker("feed-j", num_epochs=2)
        assert len(result["losses"]) == 2
        row = accounting.ledger().snapshot()["feed-j"]
        assert row["steps"] == 4  # 2 epochs x 2 batches
        assert row["examples"] == 32
        assert row["device_seconds"] > 0
        # CPU exposes cost analysis -> flops known; no peak -> MFU None
        assert row["flops_per_step"] is not None and row["flops_per_step"] > 0
        assert row["mfu"] is None
        assert row["resident"]["table"] > 0
        assert row["resident"]["input"] > 0
        # exposition carries the tenant gauges and stays lint-clean
        text = get_registry().expose()
        assert 'harmony_tenant_samples_per_sec{attempt="feed-j"' in text
        assert lint_exposition(text) == []
        # MFU is absent from the scrape (None is omitted, not zeroed)
        assert "harmony_tenant_mfu{" not in text

    def test_mfu_appears_when_peak_is_known(self, fresh_obs, monkeypatch):
        _run_worker("mfu-j")
        monkeypatch.setattr(accounting, "_peak_flops", lambda: 1e12)
        row = accounting.ledger().snapshot()["mfu-j"]
        assert row["mfu"] is not None and 0 < row["mfu"] < 1


class TestSLO:
    def test_sustained_breach_fires_one_event(self, fresh_obs):
        # an impossible target: every epoch breaches; the event fires
        # exactly once at the SLO_WINDOW_EPOCHS-th epoch
        _w, _ = _run_worker("slo-j", num_epochs=5, target_sps=1e15)
        events = joblog.job_events("slo-j")
        slo = [e for e in events if e["kind"] == "slo"]
        assert len(slo) == 1, events
        ev = slo[0]
        assert ev["target_sps"] == 1e15
        assert ev["achieved_sps"] > 0
        assert ev["attainment"] < 0.9
        assert ev["window_epochs"] == WorkerTasklet.SLO_WINDOW_EPOCHS
        assert ev["epoch"] == WorkerTasklet.SLO_WINDOW_EPOCHS - 1
        row = accounting.ledger().snapshot()["slo-j"]
        assert row["slo"]["events"] == 1
        assert row["slo"]["target_sps"] == 1e15
        assert row["slo"]["attainment"] is not None

    def test_attaining_job_fires_nothing(self, fresh_obs):
        _run_worker("ok-j", num_epochs=4, target_sps=0.001)
        assert [e for e in joblog.job_events("ok-j")
                if e["kind"] == "slo"] == []

    def test_recovery_rearms_the_event(self, fresh_obs):
        w, _ = _run_worker("re-j", num_epochs=1, target_sps=1e15)
        # drive the boundary check directly: breach window -> event,
        # recovery -> re-armed, second sustained breach -> second event
        joblog.clear_events("re-j")
        w._slo_below = 0
        w._slo_fired = False
        for epoch in range(3):
            w._check_slo(epoch, epoch_examples=1, epoch_sec=1.0)
        assert len([e for e in joblog.job_events("re-j")
                    if e["kind"] == "slo"]) == 1
        w._check_slo(3, epoch_examples=10 ** 18, epoch_sec=1.0)  # recovers
        for epoch in range(4, 7):
            w._check_slo(epoch, epoch_examples=1, epoch_sec=1.0)
        assert len([e for e in joblog.job_events("re-j")
                    if e["kind"] == "slo"]) == 2

    def test_env_override_wins(self, fresh_obs, monkeypatch):
        monkeypatch.setenv(accounting.ENV_SLO, "12345.0")
        w, _ = _run_worker("env-j", num_epochs=1, target_sps=0.0)
        assert w._slo_target == 12345.0


class TestObsTop:
    def test_none_rows_render_as_dashes(self):
        from harmony_tpu.cli import _render_tenant_top

        tenants = {
            "nulls-j": {
                "job": "nulls-j", "attempt": "nulls-j@a2", "workers": 1,
                "device_seconds": 1.5, "samples_per_sec": None,
                "mfu": None, "resident_bytes": None, "hbm_share": None,
                "input_wait_frac": None,
                "slo": {"target_sps": None, "attainment": None,
                        "events": 0},
                "straggler_ratio": None,
            },
        }
        lines = _render_tenant_top(tenants)
        row = [ln for ln in lines if ln.startswith("nulls-j")][0]
        # every unknown column is a dash — never a zero
        assert row.split()[4:] == ["-", "-", "-", "-", "-", "-", "-"]

    def test_empty_ledger_renders(self):
        from harmony_tpu.cli import _render_tenant_top

        lines = _render_tenant_top({})
        assert any("no tenant activity" in ln for ln in lines)

    def test_breached_slo_is_marked(self):
        from harmony_tpu.cli import _render_tenant_top

        tenants = {"s": {"job": "s", "attempt": "s", "workers": 1,
                         "device_seconds": 1.0, "samples_per_sec": 10.0,
                         "mfu": 0.41, "resident_bytes": 2048,
                         "hbm_share": 1.0, "input_wait_frac": 0.25,
                         "slo": {"target_sps": 100.0, "attainment": 0.1,
                                 "events": 2},
                         "straggler_ratio": 1.0}}
        row = [ln for ln in _render_tenant_top(tenants)
               if ln.startswith("s")][0]
        assert "0.10!" in row
        assert "41.00%" in row  # MFU as a percent
        assert "2.0KiB" in row


class TestTwoTenantAcceptance:
    """The ISSUE 8 acceptance run: two tenants of deliberately different
    weight on one jobserver — the ledger must tell them apart in the
    right direction, the SLO event must fire for the under-target job,
    and obs top must render the same numbers STATUS carries."""

    def test_two_tenant_ledger_and_obs_top(self, fresh_obs):
        from harmony_tpu.cli import _render_tenant_top
        from harmony_tpu.jobserver.client import CommandSender
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel.mesh import DevicePool

        def cfg(job_id, features, classes, n, target=0.0):
            return JobConfig(
                job_id=job_id, app_type="dolphin",
                trainer="harmony_tpu.apps.mlr:MLRTrainer",
                params=TrainerParams(
                    num_epochs=4, num_mini_batches=2,
                    target_samples_per_sec=target,
                    app_params={"num_classes": classes,
                                "num_features": features,
                                "features_per_partition": features // 2}),
                num_workers=1,
                user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                      "data_args": {"n": n, "num_features": features,
                                    "num_classes": classes}},
            )

        # the weight gap must dominate fixed costs (compile, dispatch)
        # on CPU, or the device-second separation drowns in noise:
        # heavy's per-epoch matmuls are ~25 GFLOP vs light's ~100 KFLOP
        # (at ~3 GFLOP heavy read 0.34 s and light, eight steps under five
        # other test workers' load, 0.64-0.71 s: PR 40's full runs of the whole suite)
        heavy = cfg("tenant-heavy", features=4096, classes=64, n=8192)
        light = cfg("tenant-light", features=32, classes=4, n=32,
                    target=1e15)  # deliberately unattainable SLO
        server = JobServer(num_executors=2,
                           device_pool=DevicePool(jax.devices()[:2]))
        server.start()
        port = server.serve_tcp(0)
        try:
            server.submit(heavy).result(timeout=300)
            server.submit(light).result(timeout=300)
            status = CommandSender(port).send_status_command()
        finally:
            server.shutdown(timeout=60)
        assert status["ok"]
        tenants = status["tenants"]
        h, l = tenants["tenant-heavy"], tenants["tenant-light"]
        # cost separation, in the right direction
        assert h["device_seconds"] > l["device_seconds"]
        assert h["flops_per_step"] is not None
        assert l["flops_per_step"] is not None
        assert h["flops_per_step"] > l["flops_per_step"]
        assert h["model_flops"] > l["model_flops"]
        assert h["resident_bytes"] > l["resident_bytes"]
        assert h["hbm_share"] + l["hbm_share"] == pytest.approx(1.0)
        # MFU: the CPU backend exposes cost analysis but no chip peak —
        # non-None exactly when BOTH exist, so here it must be None
        assert h["mfu"] is None and l["mfu"] is None
        assert h["peak_flops"] is None
        # the under-target tenant's SLO event fired and rides STATUS
        slo_events = [e for e in status["job_events"].get(
            "tenant-light", []) if e["kind"] == "slo"]
        assert len(slo_events) == 1
        assert l["slo"]["events"] == 1
        assert h["slo"]["target_sps"] is None  # no target: no attainment
        assert h["slo"]["attainment"] is None
        # straggler join is present (single-worker jobs: ratio 1.0)
        assert h["straggler_ratio"] == pytest.approx(1.0)
        # obs top renders THESE numbers: the table built from the STATUS
        # payload carries each tenant's windowed device seconds verbatim
        rendered = "\n".join(_render_tenant_top(tenants))
        assert f"{h['device_seconds']:.2f}" in rendered
        assert f"{l['device_seconds']:.2f}" in rendered
        assert "tenant-heavy" in rendered and "tenant-light" in rendered
        # exposition lint stays green with every tenant instrument live
        assert lint_exposition(get_registry().expose()) == []

    def test_obs_top_cli_against_live_server(self, fresh_obs, capsys):
        from harmony_tpu.cli import main
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel.mesh import DevicePool

        cfg = JobConfig(
            job_id="cli-top-j", app_type="dolphin",
            trainer="harmony_tpu.apps.mlr:MLRTrainer",
            params=TrainerParams(
                num_epochs=1, num_mini_batches=2,
                app_params={"num_classes": 4, "num_features": 8,
                            "features_per_partition": 4}),
            num_workers=1,
            user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                  "data_args": {"n": 16, "num_features": 8,
                                "num_classes": 4}},
        )
        server = JobServer(num_executors=2,
                           device_pool=DevicePool(jax.devices()[:2]))
        server.start()
        port = server.serve_tcp(0)
        try:
            server.submit(cfg).result(timeout=300)
            assert main(["obs", "top", "--port", str(port)]) == 0
            out = capsys.readouterr().out
            assert "TENANT" in out and "cli-top-j" in out
            assert "MFU" in out
            # CPU: MFU column is a dash for the row, never 0
            row = [ln for ln in out.splitlines()
                   if ln.startswith("cli-top-j")][0]
            assert " - " in row
            assert main(["obs", "top", "--port", str(port),
                         "--json"]) == 0
            raw = json.loads(capsys.readouterr().out)
            assert raw["cli-top-j"]["mfu"] is None
        finally:
            server.shutdown(timeout=60)


class TestProfilerSampling:
    def test_cadence_and_chief_gating(self, tmp_path, monkeypatch):
        from harmony_tpu.tracing import profiler

        captures = []

        import contextlib

        @contextlib.contextmanager
        def fake_session(logdir):
            captures.append(logdir)
            yield

        monkeypatch.setattr(profiler, "profile_session", fake_session)
        monkeypatch.setenv(profiler.ENV_EVERY_N, "2")
        monkeypatch.setenv(profiler.ENV_DIR, str(tmp_path))
        for epoch in range(5):
            with profiler.maybe_profile_epoch(epoch, "cad-j"):
                pass
        assert len(captures) == 3  # epochs 0, 2, 4
        assert all("cad-j-e" in c for c in captures)
        # non-chief workers capture nothing
        captures.clear()
        with profiler.maybe_profile_epoch(0, "cad-j", enabled=False):
            pass
        assert captures == []
        # a window spanning a sampled epoch captures once
        with profiler.maybe_profile_epoch(3, "cad-j", span=2):
            pass
        assert len(captures) == 1

    def test_off_by_default(self, tmp_path, monkeypatch):
        from harmony_tpu.tracing import profiler

        monkeypatch.delenv(profiler.ENV_EVERY_N, raising=False)
        monkeypatch.setenv(profiler.ENV_DIR, str(tmp_path / "off"))
        with profiler.maybe_profile_epoch(0, "off-j"):
            pass
        assert not (tmp_path / "off").exists()

    def test_rotation_keeps_newest_within_cap(self, tmp_path):
        from harmony_tpu.tracing import profiler

        for i in range(4):
            d = tmp_path / f"job-e{i}-1"
            d.mkdir()
            (d / "trace.pb").write_bytes(b"x" * 100)
            os.utime(d, (i + 1, i + 1))
        removed = profiler.rotate_profile_dir(str(tmp_path), max_bytes=250)
        assert removed == 2  # oldest two go; 200 bytes remain
        left = sorted(p.name for p in tmp_path.iterdir())
        assert left == ["job-e2-1", "job-e3-1"]
        # a cap smaller than one capture still keeps the newest
        removed = profiler.rotate_profile_dir(str(tmp_path), max_bytes=10)
        assert removed == 1
        assert [p.name for p in tmp_path.iterdir()] == ["job-e3-1"]

    def test_real_capture_writes_something(self, tmp_path, monkeypatch):
        """End-to-end with the real jax profiler (CPU): the capture dir
        exists and rotation bounds it — tolerant of profiler-less
        builds, where the contract degrades to an empty logdir."""
        from harmony_tpu.tracing import profiler

        monkeypatch.setenv(profiler.ENV_EVERY_N, "1")
        monkeypatch.setenv(profiler.ENV_DIR, str(tmp_path))
        with profiler.maybe_profile_epoch(0, "real-j"):
            jnp.ones((8, 8)).sum().block_until_ready()
        entries = list(tmp_path.iterdir())
        assert len(entries) == 1
        assert entries[0].name.startswith("real-j-e0-")


class TestFlightAndDashboardSurfaces:
    def test_flight_dump_snapshots_tenants(self, fresh_obs, tmp_path,
                                           monkeypatch):
        from harmony_tpu.tracing import flight

        monkeypatch.setenv("HARMONY_FLIGHT_DIR", str(tmp_path))
        flight.reset_recorder()
        try:
            accounting.ledger().observe_steps(
                "fl-j", "fl-j@a1", "w0", steps=2, device_sec=0.2,
                examples=10, flops_per_step=5.0)
            path = flight.get_recorder().dump("test-reason")
            assert path is not None
            body = json.loads(open(path).read())
            assert body["tenants"]["fl-j"]["steps"] == 2
            assert body["tenants"]["fl-j"]["attempt"] == "fl-j@a1"
        finally:
            flight.reset_recorder()

    def test_dashboard_tenants_api_and_html(self, fresh_obs):
        import urllib.request

        from harmony_tpu.dashboard.server import DashboardServer

        server = DashboardServer().start()
        try:
            for jid, dev, mfu in (("d-heavy", 3.0, 0.5),
                                  ("d-light", 1.0, None)):
                row = {"job": jid, "attempt": jid, "device_seconds": dev,
                       "samples_per_sec": 100.0, "mfu": mfu,
                       "resident_bytes": 1024, "hbm_share": 0.5,
                       "input_wait_frac": 0.1,
                       "slo": {"target_sps": None, "attainment": None,
                               "events": 0}}
                req = urllib.request.Request(
                    server.url + "/api/metrics",
                    data=json.dumps({"job_id": jid, "kind": "tenant",
                                     "payload": row}).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=5).read()
            rows = json.loads(urllib.request.urlopen(
                server.url + "/api/tenants", timeout=5).read())
            assert [r["job"] for r in rows] == ["d-heavy", "d-light"]
            html = urllib.request.urlopen(server.url + "/",
                                          timeout=5).read().decode()
            assert "tenants" in html
            assert "50.00%" in html   # d-heavy's MFU as a percent
            assert "d-light" in html
        finally:
            server.stop()

    def test_jobserver_posts_tenant_rows(self, fresh_obs):
        """The rate-limited epoch-cadence tee: after a real run against
        a dashboard, the dashboard holds a tenant row for the job."""
        from harmony_tpu.dashboard.server import DashboardServer
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel.mesh import DevicePool

        dash = DashboardServer().start()
        server = JobServer(num_executors=2,
                           device_pool=DevicePool(jax.devices()[:2]),
                           dashboard_url=dash.url)
        server.start()
        try:
            cfg = JobConfig(
                job_id="tee-j", app_type="dolphin",
                trainer="harmony_tpu.apps.mlr:MLRTrainer",
                params=TrainerParams(
                    num_epochs=2, num_mini_batches=2,
                    app_params={"num_classes": 4, "num_features": 8,
                                "features_per_partition": 4}),
                num_workers=1,
                user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                      "data_args": {"n": 16, "num_features": 8,
                                    "num_classes": 4}},
            )
            server.submit(cfg).result(timeout=300)
            deadline = time.monotonic() + 10
            rows = []
            while time.monotonic() < deadline:
                rows = dash.tenants()
                if any(r.get("job") == "tee-j" for r in rows):
                    break
                time.sleep(0.1)
            assert any(r.get("job") == "tee-j" for r in rows), rows
        finally:
            server.shutdown(timeout=60)
            dash.stop()
