"""Tracing spans + dashboard tests (SURVEY.md §5.1 / §5.5 parity)."""
import json
import time
import urllib.request

import pytest

from harmony_tpu.dashboard import DashboardConnector, DashboardServer
from harmony_tpu.tracing import (
    InMemorySpanReceiver,
    LocalFileSpanReceiver,
    SpanContext,
    Tracing,
    current_span,
    set_tracing,
    trace_span,
)
from harmony_tpu.tracing.span import wire_context


@pytest.fixture()
def tracing():
    t = set_tracing(Tracing(process_id="test-proc"))
    rec = t.add_receiver(InMemorySpanReceiver())
    yield rec
    set_tracing(Tracing())


class TestSpans:
    def test_nesting_and_emission(self, tracing):
        with trace_span("outer") as outer:
            with trace_span("inner") as inner:
                assert inner.parent_id == outer.span_id
                assert inner.trace_id == outer.trace_id
                assert current_span() is inner
            assert current_span() is outer
        assert current_span() is None
        descs = [s.description for s in tracing.spans]
        assert descs == ["inner", "outer"]  # children close first
        assert all(s.stop_sec is not None for s in tracing.spans)

    def test_wire_propagation(self, tracing):
        """The TraceInfo-codec analogue: a remote child re-parents onto the
        sender's span across a (simulated) message boundary."""
        with trace_span("master-op") as master:
            wire = wire_context()
        ctx = SpanContext.from_wire(wire)
        with trace_span("worker-op", parent=ctx):
            pass
        worker = tracing.by_description("worker-op")[0]
        assert worker.parent_id == master.span_id
        assert worker.trace_id == master.trace_id

    def test_annotations(self, tracing):
        with trace_span("op", table="t0") as s:
            s.annotate("blocks", 4)
        s = tracing.by_description("op")[0]
        assert s.annotations == {"table": "t0", "blocks": 4}

    def test_sampled_out(self):
        t = set_tracing(Tracing(sample_rate=0.0))
        rec = t.add_receiver(InMemorySpanReceiver())
        with trace_span("never") as s:
            assert s is None
        assert rec.spans == []
        set_tracing(Tracing())

    def test_file_receiver(self, tmp_path):
        t = set_tracing(Tracing())
        path = str(tmp_path / "spans.jsonl")
        t.add_receiver(LocalFileSpanReceiver(path))
        with trace_span("filed"):
            pass
        t.close()
        lines = [json.loads(l) for l in open(path)]
        assert lines[0]["description"] == "filed"
        set_tracing(Tracing())

    def test_trace_span_wraps_device_work(self, tracing):
        """What ``device_trace`` was: one call records the host span AND
        opens the profiler annotation (tests/test_span_clock.py looks for
        the event in a captured xplane)."""
        import jax.numpy as jnp

        from harmony_tpu.tracing import span as span_mod

        with trace_span("devop", job_id="j"):
            jnp.ones(4).sum()
        assert tracing.by_description("devop")
        assert span_mod._annotation_cls is not None  # jax is imported here


class TestDashboard:
    def test_post_query_roundtrip(self):
        server = DashboardServer().start()
        try:
            body = json.dumps(
                {"job_id": "j0", "kind": "BatchMetrics", "payload": {"loss": 0.5}}
            ).encode()
            req = urllib.request.Request(
                server.url + "/api/metrics", data=body,
                headers={"Content-Type": "application/json"},
            )
            assert json.loads(urllib.request.urlopen(req).read())["ok"]
            rows = json.loads(
                urllib.request.urlopen(server.url + "/api/metrics?job_id=j0").read()
            )
            assert rows[0]["payload"]["loss"] == 0.5
            jobs = json.loads(urllib.request.urlopen(server.url + "/api/jobs").read())
            assert jobs[0]["job_id"] == "j0" and jobs[0]["last_loss"] == 0.5
            html = urllib.request.urlopen(server.url + "/").read().decode()
            assert "j0" in html
        finally:
            server.stop()

    def test_recovery_events_surface_in_job_summary(self):
        """Recovery observability (elastic shrink/re-grow): kind=recovery
        posts back the summary's recoveries count + last event kind, and
        the HTML view grows the column — a degraded tenant is visible at
        a glance, not only in leader logs."""
        server = DashboardServer().start()
        try:
            for kind, payload in (
                ("EpochMetrics", {"loss": 0.9}),
                ("recovery", {"kind": "elastic_shrink", "attempt": 1}),
                ("recovery", {"kind": "elastic_regrow", "attempt": 2}),
            ):
                body = json.dumps({"job_id": "el-j", "kind": kind,
                                   "payload": payload}).encode()
                req = urllib.request.Request(
                    server.url + "/api/metrics", data=body,
                    headers={"Content-Type": "application/json"},
                )
                assert json.loads(urllib.request.urlopen(req).read())["ok"]
            (job,) = json.loads(
                urllib.request.urlopen(server.url + "/api/jobs").read())
            assert job["job_id"] == "el-j"
            assert job["recoveries"] == 2
            assert job["last_recovery"] == "elastic_regrow"
            assert job["last_loss"] == 0.9  # loss rows unaffected
            html = urllib.request.urlopen(server.url + "/").read().decode()
            assert "recoveries" in html and "elastic_regrow" in html
        finally:
            server.stop()

    def test_healthy_job_summary_has_zero_recoveries(self):
        server = DashboardServer().start()
        try:
            body = json.dumps({"job_id": "ok-j", "kind": "EpochMetrics",
                               "payload": {"loss": 0.1}}).encode()
            req = urllib.request.Request(
                server.url + "/api/metrics", data=body,
                headers={"Content-Type": "application/json"},
            )
            urllib.request.urlopen(req)
            (job,) = json.loads(
                urllib.request.urlopen(server.url + "/api/jobs").read())
            assert job["recoveries"] == 0 and job["last_recovery"] is None
        finally:
            server.stop()

    def test_status_json_carries_fault_counters_and_events(self, devices):
        """The jobserver STATUS payload (satellite: recovery
        observability) exposes the PR-2 fault counters and the
        structured per-job event log."""
        from harmony_tpu import faults
        from harmony_tpu.jobserver import joblog
        from harmony_tpu.jobserver.server import JobServer

        srv = JobServer(num_executors=2)
        srv.start()
        try:
            faults.reset_counters()
            faults.arm(faults.FaultPlan([faults.FaultRule(
                "obs.site", count=1, action="skip")]))
            faults.site("obs.site")
            joblog.job_logger("obs-j").event("elastic_shrink", attempt=1)
            status = srv._status()
            assert status["fault_counters"].get("obs.site:skip") == 1
            evs = status["job_events"]["obs-j"]
            assert evs[-1]["kind"] == "elastic_shrink"
            assert evs[-1]["attempt"] == 1 and "ts" in evs[-1]
            # the payload is JSON-serializable end to end (it rides the
            # TCP STATUS endpoint verbatim)
            json.dumps(status)
        finally:
            faults.disarm()
            joblog.clear_events("obs-j")
            srv.shutdown(timeout=60)

    def test_bad_payload_is_400(self):
        server = DashboardServer().start()
        try:
            req = urllib.request.Request(
                server.url + "/api/metrics", data=b"not json",
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req)
            assert e.value.code == 400
        finally:
            server.stop()

    def test_connector_async_delivery(self):
        from harmony_tpu.metrics.collector import BatchMetrics

        server = DashboardServer().start()
        conn = DashboardConnector(server.url)
        try:
            conn.post("j1", "EpochMetrics", {"loss": 1.25})
            conn.metric_sink(BatchMetrics(job_id="j1", loss=0.75))
            # plain-dict custom metrics (MetricCollector.flush emits them
            # undecorated) must forward, not crash the sink
            conn.metric_sink({"job_id": "j1", "bytes_sent": 10.0})
            conn.metric_sink(object())  # unknown record types are skipped
            deadline = time.time() + 5
            while time.time() < deadline and conn.sent < 3:
                time.sleep(0.02)
            assert conn.sent == 3
            rows = json.loads(
                urllib.request.urlopen(server.url + "/api/metrics?job_id=j1").read()
            )
            assert len(rows) == 3
            assert {r["kind"] for r in rows} == {
                "EpochMetrics", "BatchMetrics", "custom"}
        finally:
            conn.close()
            server.stop()

    def test_jobserver_tees_metrics_to_dashboard(self, devices):
        """JobServer(dashboard_url=...) — the reference's DolphinDriver ->
        Flask dashboard wiring (DashboardConnector.java:30-100): a trained
        job's metrics must land as queryable rows over HTTP, and the
        manager (optimizer's source) must still have them too."""
        from harmony_tpu.config.params import JobConfig, TrainerParams
        from harmony_tpu.jobserver import JobServer
        from harmony_tpu.parallel import DevicePool

        dash = DashboardServer().start()
        server = JobServer(2, device_pool=DevicePool(devices[:2]),
                           dashboard_url=dash.url)
        server.start()
        try:
            cfg = JobConfig(
                job_id="dash-mlr", app_type="dolphin",
                trainer="harmony_tpu.apps.mlr:MLRTrainer",
                params=TrainerParams(
                    num_epochs=2, num_mini_batches=2,
                    app_params={"num_classes": 2, "num_features": 8,
                                "features_per_partition": 4},
                ),
                num_workers=1,
                user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                      "data_args": {"n": 32, "num_features": 8,
                                    "num_classes": 2}},
            )
            server.submit(cfg).result(timeout=300)
            assert server.metrics.worker_batch_metrics(job_id="dash-mlr")
            server.shutdown(timeout=60)  # close() flushes the connector
            rows = json.loads(urllib.request.urlopen(
                dash.url + "/api/metrics?job_id=dash-mlr").read())
            kinds = {r["kind"] for r in rows}
            assert any("Batch" in k or "Epoch" in k for k in kinds), kinds
        finally:
            if server.state != "CLOSED":
                server.shutdown(timeout=60)
            dash.stop()

    def test_history_api_serves_series_and_diagnoses(self):
        """PR 11: /api/history turns the posted kind='tenant' ledger
        rows into a time series (oldest first) and carries the job's
        kind='diagnosis' rows beside it; /history renders the sparkline
        + diagnosis-timeline panel."""
        server = DashboardServer().start()
        try:
            def post(kind, payload):
                body = json.dumps({"job_id": "h-j", "kind": kind,
                                   "payload": payload}).encode()
                req = urllib.request.Request(
                    server.url + "/api/metrics", data=body,
                    headers={"Content-Type": "application/json"},
                )
                assert json.loads(urllib.request.urlopen(req).read())["ok"]

            for sps in (100.0, 120.0, 90.0):
                post("tenant", {"job": "h-j", "samples_per_sec": sps,
                                "mfu": None})
            now = time.time()
            post("diagnosis", {"rule": "input_bound",
                               "verdict": "input_bound",
                               "summary": "tenant h-j is input-bound",
                               "window": [now - 30, now]})
            data = json.loads(urllib.request.urlopen(
                server.url + "/api/history?job_id=h-j").read())
            assert [v for _, v in data["points"]] == [100.0, 120.0, 90.0]
            assert data["field"] == "samples_per_sec"
            assert data["diagnoses"][0]["rule"] == "input_bound"
            # mfu was None in every row: no points, not zeros
            mfu = json.loads(urllib.request.urlopen(
                server.url + "/api/history?job_id=h-j&field=mfu").read())
            assert mfu["points"] == []
            # without a job: the discovery listing
            jobs = json.loads(urllib.request.urlopen(
                server.url + "/api/history").read())
            assert "h-j" in jobs["jobs"] and "mfu" in jobs["fields"]
            # unknown field: a 400, never a KeyError-shaped 500
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    server.url + "/api/history?job_id=h-j&field=evil")
            assert e.value.code == 400
            html = urllib.request.urlopen(
                server.url + "/history?job_id=h-j").read().decode()
            assert "<svg" in html and "input_bound" in html
            # a malformed client-POSTed diagnosis row (non-numeric
            # window) must not break the panel for every future view
            post("diagnosis", {"rule": "mangled",
                               "window": ["not", "numbers"]})
            html = urllib.request.urlopen(
                server.url + "/history?job_id=h-j").read().decode()
            assert "mangled" in html  # rendered (degraded), not a 500
            # the jobs page links each tenant to its panel
            root = urllib.request.urlopen(server.url + "/").read().decode()
            assert "/history?job_id=h-j" in root
        finally:
            server.stop()

    def test_connector_survives_dead_dashboard(self):
        conn = DashboardConnector("http://127.0.0.1:1")  # nothing listens
        conn.post("j", "k", {})
        deadline = time.time() + 5
        while time.time() < deadline and conn.errors < 1:
            time.sleep(0.02)
        assert conn.errors >= 1  # swallowed, training path unaffected
        conn.close()


class TestWorkerSpans:
    def test_epoch_spans_emitted(self, mesh8):
        """The worker hot loop emits one dolphin.epoch span per epoch with
        job/worker/epoch annotations (the HTrace-style wiring, SURVEY §5.1)."""
        from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
        from harmony_tpu.config.params import TrainerParams
        from harmony_tpu.dolphin import (
            TrainerContext,
            TrainingDataProvider,
            WorkerTasklet,
        )
        from harmony_tpu.table import DenseTable, TableSpec
        from harmony_tpu.tracing import InMemorySpanReceiver, get_tracing

        recv = get_tracing().add_receiver(InMemorySpanReceiver())
        try:
            trainer = MLRTrainer(2, 8, 4, step_size=0.5)
            x, y = make_synthetic(64, 8, 2)
            table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
            w = WorkerTasklet(
                "span-job",
                TrainerContext(
                    params=TrainerParams(num_epochs=3, num_mini_batches=2),
                    model_table=table,
                ),
                trainer,
                TrainingDataProvider([x, y], 2),
                mesh8,
            )
            w.run()
            # probe-once cadence: after the epoch-0 probe the remaining
            # epochs fuse into one multi-epoch window span (per-epoch
            # metrics still replay; see TestEpochWindow)
            spans = recv.by_description("dolphin.epoch_window")
            assert len(spans) == 1, [s.description for s in recv.spans]
            s = spans[0]
            assert s.annotations["epochs"] == 3
            assert s.annotations["job_id"] == "span-job"
            assert s.duration_sec > 0
        finally:
            get_tracing().remove_receiver(recv)


class TestServerMetricsEmission:
    """Training emits real per-executor ServerMetrics (ref: the ET
    MetricReportMsg built-ins — block counts, pull counts, pulled bytes —
    that feed the optimizer's cost models). Before this, only tests ever
    constructed ServerMetrics; the optimizer loop ran on synthetic data."""

    def test_job_emits_per_executor_table_metrics(self, devices):
        from harmony_tpu.config.params import JobConfig, TrainerParams
        from harmony_tpu.jobserver import JobServer
        from harmony_tpu.parallel import DevicePool

        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        cfg = JobConfig(
            job_id="met-mlr", app_type="dolphin",
            trainer="harmony_tpu.apps.mlr:MLRTrainer",
            params=TrainerParams(
                # probes off => the 3 epochs run as ONE fused window; the
                # per-epoch assertions below then pin that op deltas are
                # accounted per epoch, not lumped onto the window's first
                # report (the callbacks replay after the single drain)
                num_epochs=3, num_mini_batches=4, comm_probe_period=0,
                app_params={"num_classes": 4, "num_features": 16,
                            "features_per_partition": 4, "step_size": 0.5},
            ),
            num_workers=1,
            user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                  "data_args": {"n": 128, "num_features": 16,
                                "num_classes": 4, "seed": 2}},
        )
        server.submit(cfg).result(timeout=300)
        sm = [m for m in server.metrics.server_metrics() if m.job_id == "met-mlr"]
        server.shutdown(timeout=60)
        assert sm, "no ServerMetrics emitted during training"
        # both executors report; blocks sum to the table's block count
        by_window = {}
        for m in sm:
            by_window.setdefault(m.window_idx, []).append(m)
        # one report per epoch + the end-of-job closing window (tail ops of
        # SSP-lagging peers land there)
        assert sorted(by_window) == [0, 1, 2, 3]
        for window, ms in by_window.items():
            assert len(ms) == 2  # both owning executors
            assert sum(m.num_blocks for m in ms) > 0
        # op counters carry real traffic: 4 pulls/pushes per epoch split
        # across executors (block-proportional shares) — in EVERY epoch
        # window, not just the first (windowed runs must not lump the
        # whole window's ops onto its first report)
        for window in (0, 1, 2):
            ms = by_window[window]
            assert sum(m.pull_count for m in ms) >= 3, window
            assert sum(m.pull_bytes for m in ms) > 0, window

    def test_shared_table_jobs_do_not_double_count(self, devices):
        """Two jobs sharing one model table by id: each job's ServerMetrics
        must carry only ITS OWN traffic (worker-side counters), not the
        table's combined totals."""
        from harmony_tpu.config.params import JobConfig, TableConfig, TrainerParams
        from harmony_tpu.jobserver import JobServer
        from harmony_tpu.parallel import DevicePool

        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        # must match MLRTrainer's schema: num_classes*(features/fpp) = 16
        # partitions of width fpp=4
        shared = TableConfig(table_id="shared-m", capacity=16,
                             value_shape=(4,), num_blocks=8)

        def job(jid):
            return JobConfig(
                job_id=jid, app_type="dolphin",
                trainer="harmony_tpu.apps.mlr:MLRTrainer",
                tables=[shared],
                params=TrainerParams(
                    num_epochs=2, num_mini_batches=4,
                    app_params={"num_classes": 4, "num_features": 16,
                                "features_per_partition": 4, "step_size": 0.1},
                ),
                num_workers=1,
                user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                      "data_args": {"n": 64, "num_features": 16,
                                    "num_classes": 4, "seed": 1}},
            )

        f1, f2 = server.submit(job("sh-a")), server.submit(job("sh-b"))
        f1.result(timeout=300), f2.result(timeout=300)
        server.shutdown(timeout=60)
        for jid in ("sh-a", "sh-b"):
            total = sum(m.pull_count
                        for m in server.metrics.server_metrics()
                        if m.job_id == jid)
            # own traffic EXACTLY: 2 epochs x 4 batches = 8 pulls
            # (largest-remainder apportionment + end-of-job final window
            # lose nothing; the other job's 8 are never claimed)
            assert total == 8, (jid, total)
