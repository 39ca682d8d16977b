"""Unified telemetry plane (ISSUE 4): instrument registry + Prometheus
exposition, end-to-end control-plane trace threading, the crash-correlated
flight recorder, and the dashboard's span store / hardening."""
import json
import os
import time
import urllib.error
import urllib.request

import pytest

from harmony_tpu import faults
from harmony_tpu.metrics.registry import (
    MetricRegistry,
    STEP_TIME_BUCKETS,
    TRANSFER_SIZE_BUCKETS,
    counters_monotone,
    get_registry,
    lint_exposition,
    parse_exposition,
    set_registry,
)
from harmony_tpu.tracing import flight
from harmony_tpu.tracing.span import (
    InMemorySpanReceiver,
    Span,
    Tracing,
    get_tracing,
    set_tracing,
    trace_span,
)


@pytest.fixture()
def fresh_registry():
    reg = set_registry(MetricRegistry())
    yield reg
    set_registry(MetricRegistry())


@pytest.fixture()
def fresh_recorder(tmp_path, monkeypatch):
    monkeypatch.setenv("HARMONY_FLIGHT_DIR", str(tmp_path / "flight"))
    flight.reset_recorder()
    yield flight.get_recorder()
    flight.reset_recorder()


class TestRegistry:
    def test_counter_gauge_histogram_semantics(self, fresh_registry):
        reg = fresh_registry
        c = reg.counter("harmony_x_total", "x", ("job",))
        c.labels(job="a").inc()
        c.labels(job="a").inc(2)
        c.labels(job="b").inc()
        assert c.labels(job="a").value == 3
        with pytest.raises(ValueError):
            c.labels(job="a").inc(-1)  # counters only go up
        g = reg.gauge("harmony_depth", "d")
        g.set(4)
        g.dec()
        assert g.value == 3
        h = reg.histogram("harmony_t_seconds", "t", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        counts, total, n = h._solo().snapshot()
        assert counts == [1, 0, 1] and n == 2 and total == 5.05

    def test_get_or_create_and_mismatch(self, fresh_registry):
        reg = fresh_registry
        a = reg.counter("harmony_same_total", "x", ("job",))
        assert reg.counter("harmony_same_total", "x", ("job",)) is a
        with pytest.raises(ValueError):
            reg.gauge("harmony_same_total")  # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("harmony_same_total", labelnames=("other",))
        with pytest.raises(ValueError):
            a.labels(wrong="x")  # undeclared label key

    def test_callback_instruments_and_expose(self, fresh_registry):
        reg = fresh_registry
        reg.register_callback("harmony_cb", "callback gauge", "gauge",
                              lambda: 7.5)
        reg.register_callback(
            "harmony_cb_labeled", "labeled", "gauge",
            lambda: [({"site": "s1"}, 1.0), ({"site": "s2"}, 2.0)],
        )
        text = reg.expose()
        assert lint_exposition(text) == [], lint_exposition(text)
        fams = parse_exposition(text)
        assert fams["harmony_cb"]["samples"][0][2] == 7.5
        sites = {s[1]["site"] for s in fams["harmony_cb_labeled"]["samples"]}
        assert sites == {"s1", "s2"}
        # the pid const label is stamped on every sample
        assert all(s[1].get("pid")
                   for f in fams.values() for s in f["samples"])

    def test_label_escaping_round_trips(self, fresh_registry):
        reg = fresh_registry
        weird = 'he said "hi"\nback\\slash'
        reg.counter("harmony_esc_total", "e", ("v",)).labels(v=weird).inc()
        text = reg.expose()
        assert lint_exposition(text) == [], lint_exposition(text)
        (sample,) = parse_exposition(text)["harmony_esc_total"]["samples"]
        # the parsed (still-escaped) value decodes back to the original
        decoded = (sample[1]["v"].replace("\\n", "\n")
                   .replace('\\"', '"').replace("\\\\", "\\"))
        assert decoded == weird


def test_metric_declarations_satisfy_exposition_conventions():
    """The static half of the exposition lint — since PR 7 the naming
    rules lint_exposition enforces at scrape time (harmony_ prefix,
    counters end _total, histograms carry a unit, non-empty HELP) are
    pinned at every instrument DECLARATION site by harmonylint's
    ``metric-conventions`` pass, so a bad family fails tier-1 even if
    no test ever scrapes it."""
    from lint_helpers import tree_findings

    findings = tree_findings("metric-conventions")
    assert not findings, "\n".join(f.format() for f in findings)


class TestExporter:
    def test_metrics_endpoint_passes_format_lint_and_monotone(
            self, fresh_registry):
        """The tier-1 exposition contract: scrape twice with activity in
        between; both scrapes parse, lint clean, and every counter is
        monotone across them (an unscrapeable or regressing /metrics is
        how a fleet loses its eyes)."""
        from harmony_tpu.metrics.exporter import MetricsExporter

        reg = fresh_registry
        reg.counter("harmony_scrapes_total", "s", ("phase",)).labels(
            phase="warm").inc()
        reg.histogram("harmony_step_time_seconds", "st",
                      ("job",), buckets=STEP_TIME_BUCKETS).labels(
            job="lint-j").observe(0.02)
        exp = MetricsExporter(0, registry=reg).start()
        try:
            t1 = urllib.request.urlopen(exp.url + "/metrics").read().decode()
            assert lint_exposition(t1) == [], lint_exposition(t1)
            reg.counter("harmony_scrapes_total", "s", ("phase",)).labels(
                phase="warm").inc(3)
            reg.histogram("harmony_step_time_seconds", "st",
                          ("job",)).labels(job="lint-j").observe(3.0)
            t2 = urllib.request.urlopen(exp.url + "/metrics").read().decode()
            assert lint_exposition(t2) == [], lint_exposition(t2)
            assert counters_monotone(t1, t2) == []
            # histogram grammar: cumulative buckets ending at +Inf
            fams = parse_exposition(t2)
            assert fams["harmony_step_time_seconds"]["type"] == "histogram"
            # health endpoint + 404s
            assert urllib.request.urlopen(
                exp.url + "/healthz").read() == b"ok\n"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(exp.url + "/nope")
        finally:
            exp.stop()

    def test_exporter_from_env(self, fresh_registry, monkeypatch):
        from harmony_tpu.metrics import exporter as me

        monkeypatch.delenv("HARMONY_METRICS_PORT", raising=False)
        assert me.exporter_from_env() is None
        monkeypatch.setenv("HARMONY_METRICS_PORT", "junk")
        assert me.exporter_from_env() is None
        # out-of-range port raises OverflowError (not OSError) from bind:
        # must degrade to an ephemeral port, never kill the process
        monkeypatch.setenv("HARMONY_METRICS_PORT", "70000")
        exp_of = me.exporter_from_env(registry=fresh_registry)
        try:
            assert exp_of is not None and 0 < exp_of.port < 65536
        finally:
            exp_of.stop()
        monkeypatch.setenv("HARMONY_METRICS_PORT", "0")
        exp = me.exporter_from_env(registry=fresh_registry)
        try:
            assert exp is not None and exp.port > 0
            # a taken fixed port degrades to ephemeral, never dies
            monkeypatch.setenv("HARMONY_METRICS_PORT", str(exp.port))
            exp2 = me.exporter_from_env(registry=fresh_registry)
            try:
                assert exp2 is not None and exp2.port != exp.port
            finally:
                exp2.stop()
        finally:
            exp.stop()


class TestFlightRecorder:
    def test_fault_trip_dumps_exactly_once_per_site_with_attempt_key(
            self, fresh_recorder):
        rec = fresh_recorder
        faults.reset_counters()
        faults.arm(faults.FaultPlan([faults.FaultRule(
            "telemetry.trip", count=3, action="skip")]))
        try:
            with trace_span("trip-span") as sp:
                for _ in range(3):
                    assert faults.site("telemetry.trip", job="tj",
                                       attempt=2) == "skip"
        finally:
            faults.disarm()
        # the trip is annotated on the ambient span
        assert sp.annotations.get("fault:telemetry.trip") == "skip"
        dumps = [d for d in rec.records()
                 if d["reason"] == "fault:telemetry.trip"]
        assert len(dumps) == 1, rec.records()  # once per site, not per fire
        assert dumps[0]["meta"]["attempt_key"] == "tj@a2"
        body = json.load(open(dumps[0]["path"]))
        assert body["meta"]["site"] == "telemetry.trip"
        assert body["meta"]["attempt_key"] == "tj@a2"
        trips = [r for r in body["records"]
                 if r.get("event") == "fault_trip"]
        assert len(trips) >= 1

    def test_ring_is_bounded_and_dump_correlates_trace_ids(self, tmp_path):
        rec = flight.FlightRecorder(capacity=16, out_dir=str(tmp_path))
        tracing = set_tracing(Tracing(process_id="flight-test"))
        tracing.add_receiver(rec)
        try:
            for i in range(40):
                with trace_span(f"s{i}"):
                    pass
            assert rec.ring_size() == 16  # bounded
            with trace_span("marker") as sp:
                marker_trace = sp.trace_id
            path = rec.dump("unit-test", note=1)
            body = json.load(open(path))
            assert marker_trace in body["trace_ids"]
            assert body["process_id"] == "flight-test"
            assert len(body["records"]) == 16
        finally:
            set_tracing(Tracing())

    def test_status_surfaces_flight_records(self, fresh_recorder, devices):
        from harmony_tpu.jobserver.server import JobServer

        srv = JobServer(num_executors=2)
        srv.start()
        try:
            flight.get_recorder().dump("status-test")
            status = srv._status()
            reasons = [d["reason"] for d in status["flight_records"]]
            assert "status-test" in reasons
            json.dumps(status)  # STATUS rides the TCP endpoint verbatim
        finally:
            srv.shutdown(timeout=60)


class TestFileReceiverLifecycle:
    def test_rotation_at_size_cap(self, tmp_path):
        from harmony_tpu.tracing.span import LocalFileSpanReceiver

        path = str(tmp_path / "spans.jsonl")
        recv = LocalFileSpanReceiver(path, max_bytes=600)
        tracing = set_tracing(Tracing())
        tracing.add_receiver(recv)
        try:
            for i in range(30):
                with trace_span(f"rot-{i}"):
                    pass
        finally:
            tracing.close()
            set_tracing(Tracing())
        assert os.path.exists(path + ".1"), "no rotation at the cap"
        # every surviving line is a whole JSON record (no torn writes)
        for p in (path, path + ".1"):
            for line in open(p):
                assert json.loads(line)["description"].startswith("rot-")
        assert os.path.getsize(path) <= 600

    def test_close_is_idempotent_and_post_close_receive_drops(self, tmp_path):
        from harmony_tpu.tracing.span import LocalFileSpanReceiver

        recv = LocalFileSpanReceiver(str(tmp_path / "s.jsonl"))
        recv.close()
        recv.close()  # idempotent (atexit + Tracing.close may both run)
        recv.receive(Span("t", "s", None, "after-close", 0.0))  # no raise


class TestStragglerReport:
    def test_slowest_vs_median_ratio(self):
        from harmony_tpu.metrics.collector import BatchMetrics
        from harmony_tpu.metrics.manager import MetricManager

        mm = MetricManager()
        mm.start_collection()
        for wid, t in (("j/w0", 0.010), ("j/w1", 0.050), ("j/w2", 0.011)):
            for _ in range(3):
                mm.on_metric(BatchMetrics(job_id="strag-j", worker_id=wid,
                                          batch_time_sec=t))
        rep = mm.straggler_report()
        assert rep["strag-j"]["slowest"] == "j/w1"
        assert rep["strag-j"]["ratio"] == pytest.approx(0.050 / 0.011,
                                                        rel=0.05)
        assert set(rep["strag-j"]["workers"]) == {"j/w0", "j/w1", "j/w2"}
        # single-worker jobs: ratio degenerates to 1.0, never a div/0
        mm.on_metric(BatchMetrics(job_id="solo-j", worker_id="s/w0",
                                  batch_time_sec=0.02))
        assert mm.straggler_report()["solo-j"]["ratio"] == 1.0


class TestTracerSatellite:
    def test_record_blocks_on_the_given_value(self, monkeypatch):
        """record(block_on=...) waits for the device value before it
        stops the clock (jax.block_until_ready), so async device work is
        charged to this phase and not the next."""
        import jax

        from harmony_tpu.metrics.tracer import Tracer

        waited = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: waited.append(x) or x)
        tr = Tracer()
        tr.start()
        token = object()
        tr.record(block_on=token)
        assert waited == [token]
        tr.start()
        tr.record()  # nothing to wait for
        assert waited == [token]

    def test_instrumented_record_feeds_histogram(self, fresh_registry):
        from harmony_tpu.metrics.tracer import Tracer

        tr = Tracer(instrument="unit.pull")
        tr.start()
        tr.record(num_elems=4)
        tr.reset()
        assert tr.instrument == "unit.pull"  # reset keeps the wiring
        text = fresh_registry.expose()
        fams = parse_exposition(text)
        samples = fams["harmony_phase_seconds"]["samples"]
        assert any(s[1].get("phase") == "unit.pull" for s in samples)


class TestDashboardTelemetry:
    def _post(self, url, path, obj):
        req = urllib.request.Request(
            url + path, data=json.dumps(obj).encode(),
            headers={"Content-Type": "application/json"},
        )
        return json.loads(urllib.request.urlopen(req).read())

    def test_span_store_trace_api_and_timeline(self):
        from harmony_tpu.dashboard.server import DashboardServer

        server = DashboardServer().start()
        try:
            t0 = time.time()
            spans = [
                {"trace_id": "tr1", "span_id": "p1", "parent_id": None,
                 "description": "jobserver.dispatch",
                 "start_sec": t0, "stop_sec": t0 + 1.0,
                 "process_id": "proc-0",
                 "annotations": {"job_id": "dash-j"}},
                {"trace_id": "tr1", "span_id": "c1", "parent_id": "p1",
                 "description": "dolphin.worker",
                 "start_sec": t0 + 0.1, "stop_sec": t0 + 0.9,
                 "process_id": "proc-1",
                 "annotations": {"job_id": "dash-j", "attempt": "dash-j"}},
            ]
            assert self._post(server.url, "/api/spans",
                              {"spans": spans})["stored"] == 2
            rows = json.loads(urllib.request.urlopen(
                server.url + "/api/trace?trace_id=tr1").read())
            assert [r["span_id"] for r in rows] == ["p1", "c1"]  # by start
            assert rows[1]["annotations"]["attempt"] == "dash-j"
            by_job = json.loads(urllib.request.urlopen(
                server.url + "/api/trace?job_id=dash-j").read())
            assert len(by_job) == 2
            html = urllib.request.urlopen(
                server.url + "/trace?trace_id=tr1").read().decode()
            assert "dolphin.worker" in html and "timeline" in html
            # the job summary links its newest trace
            self._post(server.url, "/api/metrics",
                       {"job_id": "dash-j", "kind": "EpochMetrics",
                        "payload": {"loss": 0.1}})
            (job,) = json.loads(urllib.request.urlopen(
                server.url + "/api/jobs").read())
            assert job["trace_id"] == "tr1"
            # missing selector is a 400, not a 500/hang
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(server.url + "/api/trace")
            assert e.value.code == 400
            # malformed span is a 400
            req = urllib.request.Request(
                server.url + "/api/spans",
                data=json.dumps({"spans": [{"no": "ids"}]}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req)
            assert e.value.code == 400
        finally:
            server.stop()

    def test_limit_clamped_and_bad_limit_400(self):
        from harmony_tpu.dashboard.server import DashboardServer

        server = DashboardServer().start()
        try:
            for i in range(5):
                self._post(server.url, "/api/metrics",
                           {"job_id": "lim-j", "kind": "k",
                            "payload": {"i": i}})
            # non-positive clamps to 1 (never rides raw into SQL)
            rows = json.loads(urllib.request.urlopen(
                server.url + "/api/metrics?limit=-5").read())
            assert len(rows) == 1
            # huge clamps to the cap; still serves
            rows = json.loads(urllib.request.urlopen(
                server.url + "/api/metrics?limit=99999999").read())
            assert len(rows) == 5
            # non-integer is a proper 400 with a JSON error body
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    server.url + "/api/metrics?limit=abc")
            assert e.value.code == 400
            assert "limit" in json.loads(e.value.read())["error"]
        finally:
            server.stop()

    def test_file_backed_db_uses_wal(self, tmp_path):
        from harmony_tpu.dashboard.server import DashboardServer

        server = DashboardServer(db_path=str(tmp_path / "dash.db")).start()
        try:
            (row,) = server._read_rows("PRAGMA journal_mode")
            assert row[0] == "wal"
            # per-request read connections serve against the writer
            server.insert("wal-j", "k", {"x": 1})
            assert server.query(job_id="wal-j")[0]["payload"]["x"] == 1
        finally:
            server.stop()

    def test_timeline_survives_partial_spans_and_escapes_html(self):
        """Hardening: a span stored with no start/stop must not crash the
        HTML timeline, and client-POSTed span text renders escaped (span
        descriptions are untrusted input)."""
        from harmony_tpu.dashboard.server import DashboardServer

        server = DashboardServer().start()
        try:
            self._post(server.url, "/api/spans", {"spans": [
                {"trace_id": "h1", "span_id": "a",
                 "description": "<script>alert(1)</script>"},
            ]})
            html = urllib.request.urlopen(
                server.url + "/trace?trace_id=h1").read().decode()
            assert "<script>" not in html
            assert "&lt;script&gt;" in html
            # the index page escapes client data too (incl. last_loss,
            # an arbitrary JSON value)
            self._post(server.url, "/api/metrics",
                       {"job_id": "h-j", "kind": "k",
                        "payload": {"loss": "<script>y</script>"}})
            index = urllib.request.urlopen(server.url + "/").read().decode()
            assert "<script>" not in index
        finally:
            server.stop()

    def test_job_trace_view_returns_whole_traces(self):
        """?job_id= resolves the job's traces and returns them WHOLE:
        checkpoint/blockmove spans annotate chkp_id, not job_id, and the
        per-job view must not show a submission with holes."""
        from harmony_tpu.dashboard.server import DashboardServer

        server = DashboardServer().start()
        try:
            self._post(server.url, "/api/spans", {"spans": [
                {"trace_id": "w1", "span_id": "a", "description": "root",
                 "start_sec": 1.0, "stop_sec": 3.0,
                 "annotations": {"job_id": "whole-j"}},
                {"trace_id": "w1", "span_id": "b", "parent_id": "a",
                 "description": "checkpoint.commit", "start_sec": 2.0,
                 "stop_sec": 2.5, "annotations": {"chkp_id": "c-1"}},
            ]})
            rows = json.loads(urllib.request.urlopen(
                server.url + "/api/trace?job_id=whole-j").read())
            assert {r["description"] for r in rows} == {
                "root", "checkpoint.commit"}
        finally:
            server.stop()

    def test_nan_renders_scrapeable(self, fresh_registry):
        fresh_registry.gauge("harmony_nan_gauge", "n").set(float("nan"))
        text = fresh_registry.expose()
        assert lint_exposition(text) == [], lint_exposition(text)
        (sample,) = parse_exposition(text)["harmony_nan_gauge"]["samples"]
        assert sample[2] != sample[2]  # parsed back as NaN

    def test_dashboard_metrics_endpoint_lints(self, fresh_registry):
        from harmony_tpu.dashboard.server import DashboardServer

        fresh_registry.counter("harmony_dash_total", "d").inc()
        server = DashboardServer().start()
        try:
            text = urllib.request.urlopen(
                server.url + "/metrics").read().decode()
            assert lint_exposition(text) == [], lint_exposition(text)
            assert "harmony_dash_total" in text
        finally:
            server.stop()


class TestTracePropagationE2E:
    def test_tcp_submit_one_trace_to_worker_and_checkpoint(
            self, devices, tmp_path, fresh_registry):
        """The tentpole's acceptance leg that runs in tier-1: a REAL
        jobserver TCP submit made inside a client span; the worker-side
        spans (dolphin.worker / epochs) and the checkpoint write/commit
        spans all carry the CLIENT's trace_id — one connected trace from
        the submission through training to the chain on disk — and the
        step-time histogram lands labeled per job on /metrics."""
        from harmony_tpu.config.params import JobConfig, TrainerParams
        from harmony_tpu.jobserver.client import CommandSender
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel import DevicePool

        recv = get_tracing().add_receiver(InMemorySpanReceiver())
        server = JobServer(2, device_pool=DevicePool(devices[:2]),
                           chkp_root=str(tmp_path / "chkp"))
        server.start()
        port = server.serve_tcp(0)
        try:
            cfg = JobConfig(
                job_id="trace-mlr", app_type="dolphin",
                trainer="harmony_tpu.apps.mlr:MLRTrainer",
                params=TrainerParams(
                    num_epochs=2, num_mini_batches=2, model_chkp_period=1,
                    app_params={"num_classes": 2, "num_features": 8,
                                "features_per_partition": 4},
                ),
                num_workers=1,
                user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                      "data_args": {"n": 32, "num_features": 8,
                                    "num_classes": 2}},
            )
            with trace_span("cli.submit", job_id=cfg.job_id) as root:
                client_trace = root.trace_id
                resp = CommandSender(port).send_job_submit_command(cfg)
            assert resp.get("ok"), resp
            server._jobs[cfg.job_id].future.result(timeout=300)
            # one trace_id from the client through dispatch to the worker
            (submit_span,) = recv.by_description("jobserver.submit")
            assert submit_span.trace_id == client_trace
            (dispatch_span,) = recv.by_description("jobserver.dispatch")
            assert dispatch_span.trace_id == client_trace
            (worker_span,) = recv.by_description("dolphin.worker")
            assert worker_span.trace_id == client_trace
            assert worker_span.annotations["attempt"] == "trace-mlr"
            epoch_like = [
                s for s in recv.spans
                if s.description.startswith("dolphin.epoch")
            ]
            assert epoch_like
            assert all(s.trace_id == client_trace for s in epoch_like)
            # checkpoint chain spans (async writers included) connect too
            chkp = [s for s in recv.spans
                    if s.description.startswith("checkpoint.")]
            assert any(s.description in ("checkpoint.write",
                                         "checkpoint.write_async")
                       for s in chkp)
            assert any(s.description == "checkpoint.commit" for s in chkp)
            assert all(s.trace_id == client_trace for s in chkp), [
                (s.description, s.trace_id) for s in chkp]
            # per-tenant step-time histogram reached the registry
            text = fresh_registry.expose()
            fams = parse_exposition(text)
            st = fams.get("harmony_step_time_seconds")
            assert st is not None
            assert any(s[1].get("job") == "trace-mlr"
                       and s[1].get("attempt") == "trace-mlr"
                       for s in st["samples"])
            # straggler report covers the job
            assert "trace-mlr" in server.metrics.straggler_report()
        finally:
            get_tracing().remove_receiver(recv)
            server.shutdown(timeout=60)

    def test_in_process_submit_roots_trace_from_ambient_span(
            self, devices):
        """server.submit() inside a span (the `run` CLI path) threads the
        ambient context without any TCP hop."""
        from harmony_tpu.config.params import JobConfig, TrainerParams
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel import DevicePool

        recv = get_tracing().add_receiver(InMemorySpanReceiver())
        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        try:
            cfg = JobConfig(
                job_id="ambient-mlr", app_type="dolphin",
                trainer="harmony_tpu.apps.mlr:MLRTrainer",
                params=TrainerParams(
                    num_epochs=1, num_mini_batches=2,
                    app_params={"num_classes": 2, "num_features": 8,
                                "features_per_partition": 4},
                ),
                num_workers=1,
                user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                      "data_args": {"n": 32, "num_features": 8,
                                    "num_classes": 2}},
            )
            with trace_span("cli.run") as root:
                fut = server.submit(cfg)
            fut.result(timeout=300)
            (worker_span,) = recv.by_description("dolphin.worker")
            assert worker_span.trace_id == root.trace_id
        finally:
            get_tracing().remove_receiver(recv)
            server.shutdown(timeout=60)


class TestBlockmoveSpan:
    def test_move_blocks_emits_span(self, devices):
        from harmony_tpu.config.params import TableConfig
        from harmony_tpu.runtime.master import ETMaster

        recv = get_tracing().add_receiver(InMemorySpanReceiver())
        try:
            master = ETMaster()
            from harmony_tpu.parallel.mesh import DevicePool

            master = ETMaster(DevicePool(devices[:2]))
            e1, e2 = [e.id for e in master.add_executors(2)]
            handle = master.create_table(
                TableConfig(table_id="span-t", capacity=16,
                            value_shape=(4,), num_blocks=8), [e1, e2])
            handle.move_blocks(e1, e2, 2)
            spans = recv.by_description("table.blockmove")
            assert spans and spans[0].annotations["blocks"] == 2
            assert spans[0].annotations["table"] == "span-t"
        finally:
            get_tracing().remove_receiver(recv)


@pytest.mark.slow
@pytest.mark.faults
def test_elastic_crash_leaves_connected_trace_and_flight_records(tmp_path):
    """The full acceptance run (ISSUE 4): submit → train → checkpoint →
    elastic shrink via an injected follower crash, on a REAL 2-process
    pod. Asserts the cross-process telemetry contract:

      * the dying follower's flight dump (written by the fault trip
        BEFORE os._exit) is correlated: its trace_ids contain the
        CLIENT's trace_id (checkpoint/epoch spans that closed on the
        follower were re-parented across CLI→leader→follower hops) and
        its meta names the tripped site;
      * exactly ONE fault dump per tripped site;
      * the leader's STATUS surfaces a follower_death flight record,
        also carrying the client trace;
      * the submission still completes in place (attempts == 2) — the
        telemetry plane observed the recovery, never perturbed it."""
    from tests.test_elastic_pod import _elastic_cfg
    from tests.test_multihost import PodHarness, _mlr_job

    flight_dir = tmp_path / "flight"
    plan = faults.FaultPlan([faults.FaultRule(
        "worker.step", match={"proc": 1}, after=20, count=1,
        action="crash", exit_code=86,
    )])
    pod = PodHarness(2, 2, scheduler="pod_carve:1",
                     env_extra={"HARMONY_POD_CHKP_ROOT": str(tmp_path),
                                "HARMONY_POD_HB_TIMEOUT": "5",
                                "HARMONY_POD_HB_PERIOD": "0.5",
                                "HARMONY_FLIGHT_DIR": str(flight_dir),
                                faults.ENV_VAR: plan.to_json()})
    try:
        pod.wait_ready()
        filler = _mlr_job("tele-filler", seed=1, epochs=1)
        filler.params.num_mini_batches = 2
        victim = _elastic_cfg("tele-victim", 24)
        assert pod.sender.send_job_submit_command(filler).get("ok")
        with trace_span("cli.submit", job_id=victim.job_id) as root:
            client_trace = root.trace_id
            assert pod.sender.send_job_submit_command(victim).get("ok")
        pod.drain(timeout=300)
        status = pod.sender.send_status_command()
        pod.sender.send_shutdown_command()
        out, err = pod.procs[0].communicate(timeout=120)
        lead = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert lead, (out, err[-2000:])
        result = json.loads(lead[0][len("RESULT "):])
        assert pod.procs[1].wait(timeout=60) == 86  # died OF the injection
    finally:
        pod.kill()
    vres = result["local_results"]["tele-victim"]
    assert "error" not in vres, vres
    assert vres["elastic"]["attempts"] == 2  # recovered in place
    # the follower's black box: one dump for the tripped site, written
    # before the injected os._exit, correlated to the client's trace
    dumps = [json.load(open(os.path.join(flight_dir, f)))
             for f in os.listdir(flight_dir)]
    fault_dumps = [d for d in dumps if d["reason"] == "fault:worker.step"]
    assert len(fault_dumps) == 1, [d["reason"] for d in dumps]
    crash = fault_dumps[0]
    assert crash["meta"]["site"] == "worker.step"
    assert crash["meta"]["action"] == "crash"
    assert crash["meta"]["attempt_key"] == "tele-victim"  # attempt 0
    assert client_trace in crash["trace_ids"], (
        client_trace, crash["trace_ids"])
    # spans that closed on the follower before death carry the trace
    follower_descs = {r["description"] for r in crash["records"]
                      if r.get("kind") == "span"
                      and r.get("trace_id") == client_trace}
    assert any(d.startswith("checkpoint.") or d.startswith("dolphin.")
               for d in follower_descs), follower_descs
    # the leader observed the death and dumped its own correlated record
    reasons = {d["reason"]: d for d in status["flight_records"]}
    (death,) = [d for r, d in reasons.items()
                if r.startswith("follower_death")]
    assert client_trace in death["trace_ids"]
    # straggler report covered the recovered tenant
    assert "tele-victim" in status["stragglers"]


class TestObsCli:
    def test_obs_metrics_and_trace(self, fresh_registry, capsys):
        from harmony_tpu.cli import main
        from harmony_tpu.dashboard.server import DashboardServer
        from harmony_tpu.metrics.exporter import MetricsExporter

        fresh_registry.counter("harmony_clismoke_total", "c").inc()
        exp = MetricsExporter(0, registry=fresh_registry).start()
        try:
            assert main(["obs", "metrics", "--url", exp.url]) == 0
            out = capsys.readouterr().out
            assert "harmony_clismoke_total [counter]" in out
        finally:
            exp.stop()
        ds = DashboardServer().start()
        try:
            body = json.dumps({"spans": [
                {"trace_id": "cli-t", "span_id": "a", "description": "root",
                 "start_sec": 1.0, "stop_sec": 2.0,
                 "annotations": {"job_id": "cli-j"}},
            ]}).encode()
            urllib.request.urlopen(urllib.request.Request(
                ds.url + "/api/spans", data=body,
                headers={"Content-Type": "application/json"}))
            assert main(["obs", "trace", "--url", ds.url,
                         "--trace-id", "cli-t"]) == 0
            assert "root" in capsys.readouterr().out
        finally:
            ds.stop()
        assert main(["obs", "metrics"]) == 2  # missing --url is usage


class TestMetricsRegistryWiring:
    def test_fault_fire_feeds_counter(self, fresh_registry, fresh_recorder):
        faults.reset_counters()
        faults.arm(faults.FaultPlan([faults.FaultRule(
            "reg.wire", count=2, action="skip")]))
        try:
            faults.site("reg.wire")
            faults.site("reg.wire")
        finally:
            faults.disarm()
        fams = parse_exposition(fresh_registry.expose())
        samples = fams["harmony_fault_fires_total"]["samples"]
        (v,) = [s[2] for s in samples
                if s[1].get("site") == "reg.wire"]
        assert v == 2

    def test_checkpoint_reads_feed_counters(self, fresh_registry, devices,
                                            tmp_path):
        from harmony_tpu.checkpoint.manager import CheckpointManager
        from harmony_tpu.config.params import TableConfig
        from harmony_tpu.parallel.mesh import DevicePool
        from harmony_tpu.runtime.master import ETMaster

        master = ETMaster(DevicePool(devices[:2]))
        execs = [e.id for e in master.add_executors(2)]
        handle = master.create_table(
            TableConfig(table_id="rd-t", capacity=16, value_shape=(4,),
                        num_blocks=8), execs)
        mgr = CheckpointManager(str(tmp_path / "t"), str(tmp_path / "c"))
        cid = mgr.checkpoint(handle, commit=True)
        handle.drop()
        mgr.restore(master, cid, execs)
        fams = parse_exposition(fresh_registry.expose())
        assert fams["harmony_checkpoint_blocks_read_total"][
            "samples"][0][2] >= 8
        assert fams["harmony_checkpoint_read_bytes_total"][
            "samples"][0][2] > 0
        # fixed transfer-size boundaries stay importable constants
        assert TRANSFER_SIZE_BUCKETS[0] == 1024.0
