"""Root-cause doctor tests (PR 11; docs/OBSERVABILITY.md §8): every
shipped rule fires on its synthetic scenario and stays silent on
healthy traces; diagnoses dedupe to once per (rule, subject) per
window; they land as structured joblog events and in flight dumps; and
the fault-injected acceptance drives four distinct scenarios through a
real JobServer + TCP STATUS + ``harmony-tpu obs doctor``."""
import json
import time

import pytest

from harmony_tpu.metrics.doctor import (
    Doctor,
    all_rules,
    peek_doctor,
    set_doctor,
)
from harmony_tpu.metrics.history import HistoryStore


def _store(window=600.0):
    return HistoryStore(window_sec=window, resolution_sec=0.01)


def _feed(store, name, labels, values, spacing=1.0, kind="gauge",
          target=None):
    t0 = time.time() - spacing * len(values)
    for i, v in enumerate(values):
        store.ingest(name, labels, v, ts=t0 + i * spacing, kind=kind,
                     target=target)


class TestRuleCatalog:
    def test_shipped_rules_present_in_order(self):
        names = [r.name for r in all_rules()]
        # slo_breach joins the others and must stay LAST (declaration
        # order is evaluation order); the PR-13 phase rules sit before it
        assert names == ["input_bound", "straggler", "mfu_collapse",
                         "compile_storm", "infra_suspect", "comm_bound",
                         "dispatch_bound", "leader_flap",
                         "rebalance_ineffective", "control_overload",
                         "serving_slo_breach", "slo_breach"]
        assert all(r.description for r in all_rules())

    def test_input_bound_fires_and_names_tenant(self):
        s = _store()
        _feed(s, "tenant.input_wait_frac", {"job": "slow-j"},
              [0.7, 0.8, 0.75])
        _feed(s, "tenant.input_wait_frac", {"job": "ok-j"},
              [0.05, 0.1, 0.02])
        out = Doctor(s, events_fn=dict).diagnose()
        assert [d.rule for d in out] == ["input_bound"]
        d = out[0]
        assert d.job == "slow-j"
        assert d.evidence["points"]  # non-empty evidence excerpt
        assert d.evidence["median"] == pytest.approx(0.75)

    def test_input_bound_silent_on_healthy_trace(self):
        s = _store()
        _feed(s, "tenant.input_wait_frac", {"job": "ok-j"},
              [0.1, 0.2, 0.15])
        assert Doctor(s, events_fn=dict).diagnose() == []

    def test_straggler_fires_with_worker_attribution(self):
        s = _store()
        _feed(s, "tenant.straggler_ratio", {"job": "lag-j"},
              [2.5, 3.0, 2.8])
        strag = {"lag-j": {"slowest": "w3",
                           "workers": {"w0": 0.1, "w3": 0.3},
                           "ratio": 2.8}}
        out = Doctor(s, events_fn=dict,
                     stragglers_fn=lambda: strag).diagnose()
        (d,) = out
        assert d.rule == "straggler" and d.job == "lag-j"
        assert d.evidence["slowest_worker"] == "w3"

    def test_straggler_silent_when_ratio_healthy(self):
        s = _store()
        _feed(s, "tenant.straggler_ratio", {"job": "j"}, [1.0, 1.1, 1.05])
        assert Doctor(s, events_fn=dict).diagnose() == []

    def test_mfu_collapse_needs_layout_change_correlation(self):
        s = _store()
        drop = [0.5, 0.5, 0.5, 0.1, 0.1, 0.1]
        _feed(s, "tenant.mfu", {"job": "m-j"}, drop)
        # no layout bump in window: the drop alone must NOT fire
        assert Doctor(s, events_fn=dict).diagnose() == []
        _feed(s, "harmony_table_layout_changes_total",
              {"target": "leader"}, [3.0, 4.0], kind="counter",
              target="leader")
        (d,) = Doctor(s, events_fn=dict).diagnose()
        assert d.rule == "mfu_collapse" and d.job == "m-j"
        assert d.evidence["layout_changes"] == 1.0
        assert d.evidence["late_mean"] < d.evidence["early_mean"]

    def test_mfu_collapse_silent_on_flat_mfu_despite_layout_change(self):
        s = _store()
        _feed(s, "tenant.mfu", {"job": "m-j"}, [0.5] * 6)
        _feed(s, "harmony_table_layout_changes_total",
              {"target": "leader"}, [3.0, 4.0], kind="counter")
        assert Doctor(s, events_fn=dict).diagnose() == []

    def test_compile_storm_fires_per_target_with_pid(self):
        s = _store(window=60.0)
        # 2 compile-seconds per wall second, all misses, on pod:2
        _feed(s, "harmony_compile_seconds_sum",
              {"target": "pod:2", "program": "step"},
              [0.0, 2.0, 4.0, 6.0], kind="counter", target="pod:2")
        _feed(s, "harmony_progcache_events_total",
              {"target": "pod:2", "result": "miss"},
              [0.0, 1.0, 2.0, 3.0], kind="counter", target="pod:2")
        with s._lock:  # pid attribution comes from target metadata
            s._target_meta["pod:2"] = {"pid": "4242", "start_time": None}
        (d,) = Doctor(s, window=60.0, events_fn=dict).diagnose()
        assert d.rule == "compile_storm"
        assert d.target == "pod:2" and d.pid == "4242"
        assert d.evidence["compile_seconds_rate"] >= 0.25

    def test_compile_storm_silent_when_cache_hits(self):
        s = _store(window=60.0)
        _feed(s, "harmony_compile_seconds_sum",
              {"target": "pod:2", "program": "step"},
              [0.0, 2.0, 4.0], kind="counter", target="pod:2")
        # no miss rate: warm cache, compiles are legitimate first-builds
        assert Doctor(s, window=60.0, events_fn=dict).diagnose() == []

    def test_infra_suspect_names_the_bursting_target(self):
        s = _store()
        _feed(s, "harmony_retry_events_total",
              {"target": "pod:1", "op": "blockmove.send",
               "kind": "retries"},
              [0.0, 3.0, 7.0], kind="counter", target="pod:1")
        _feed(s, "harmony_retry_events_total",
              {"target": "pod:3", "op": "blockmove.send",
               "kind": "retries"},
              [0.0, 0.0, 1.0], kind="counter", target="pod:3")
        (d,) = Doctor(s, events_fn=dict).diagnose()
        assert d.rule == "infra_suspect" and d.target == "pod:1"
        assert d.evidence["events_in_window"] == 7.0

    def test_infra_suspect_ignores_the_scrapers_own_retries(self):
        """The doctor must not diagnose itself: a dead scrape target
        produces obs.scrape retry events on the LEADER every cycle —
        already reported as gap marks — and counting them as an infra
        burst would blame the wrong process once per window forever."""
        s = _store()
        _feed(s, "harmony_retry_events_total",
              {"target": "leader", "op": "obs.scrape",
               "kind": "retries"},
              [0.0, 120.0, 360.0], kind="counter", target="leader")
        assert Doctor(s, events_fn=dict).diagnose() == []

    def test_slo_breach_joins_to_its_cause(self):
        from harmony_tpu.jobserver import joblog

        s = _store()
        _feed(s, "tenant.input_wait_frac", {"job": "slo-j"},
              [0.8, 0.9, 0.85])
        joblog.clear_events("slo-j")
        joblog.record_event("slo-j", "slo", attainment=0.4,
                            target_sps=100.0)
        try:
            out = Doctor(s).diagnose()
            rules = {d.rule: d for d in out}
            assert set(rules) == {"input_bound", "slo_breach"}
            b = rules["slo_breach"]
            assert b.job == "slo-j"
            assert b.evidence["cause_rule"] == "input_bound"
            assert b.confidence > 0.5
        finally:
            joblog.clear_events("slo-j")

    def test_slo_breach_without_cause_is_unattributed(self):
        from harmony_tpu.jobserver import joblog

        joblog.clear_events("lone-j")
        joblog.record_event("lone-j", "slo", attainment=0.5)
        try:
            (d,) = Doctor(_store()).diagnose()
            assert d.rule == "slo_breach"
            assert d.evidence["cause_rule"] is None
            assert "unattributed" in d.summary
        finally:
            joblog.clear_events("lone-j")


class TestDoctorCommBoundSteadyState:
    """comm_bound ignores the compile-bearing first sample."""

    @staticmethod
    def _phase_store(job, pull, push):
        store = HistoryStore(window_sec=900.0, resolution_sec=1.0)
        labels = {"job": job, "attempt": job}
        _feed(store, "tenant.phase.pull_comm", labels, pull, spacing=5.0)
        _feed(store, "tenant.phase.push_comm", labels, push, spacing=5.0)
        return store

    def test_compile_bearing_first_sample_excluded(self):
        """One compile-inflated pull sample followed by a healthy one
        must NOT diagnose comm-bound (the pre-fix median of [0.85, 0.1]
        is 0.475 — a false positive off one cold sample)."""
        store = self._phase_store("cold-j", [0.85, 0.1], [0.1, 0.05])
        doc = Doctor(store, events_fn=dict)
        assert not [d for d in doc.diagnose() if d.rule == "comm_bound"]

    def test_steady_comm_bound_still_fires(self):
        """The exclusion must not kill the rule: a tenant whose steady
        samples are ALSO comm-heavy still diagnoses."""
        store = self._phase_store("hot-j", [0.7, 0.5, 0.5],
                                  [0.1, 0.1, 0.1])
        doc = Doctor(store, events_fn=dict)
        comm = [d for d in doc.diagnose() if d.rule == "comm_bound"]
        assert len(comm) == 1 and comm[0].job == "hot-j"


class TestEngineSemantics:
    def test_once_per_window_then_rearms(self):
        s = _store(window=30.0)
        _feed(s, "tenant.input_wait_frac", {"job": "j"}, [0.9, 0.9, 0.9])
        doc = Doctor(s, window=30.0, events_fn=dict)
        now = time.time()
        assert len(doc.diagnose(now=now)) == 1
        # same condition, same window: exactly once
        assert doc.diagnose(now=now + 1) == []
        assert doc.diagnose(now=now + 15) == []
        # the window has passed and the condition persists: re-diagnose
        # (points stamped inside the NEXT window, as live scrapes would)
        s.ingest("tenant.input_wait_frac", {"job": "j"}, 0.9,
                 ts=now + 30.2)
        s.ingest("tenant.input_wait_frac", {"job": "j"}, 0.9,
                 ts=now + 30.6)
        assert len(doc.diagnose(now=now + 31)) == 1
        assert len(doc.recent()) == 2
        # expired dedup entries are pruned, not leaked: only the fresh
        # emission's key survives the re-arm
        assert len(doc._seen) == 1

    def test_diagnosis_lands_as_joblog_event(self):
        from harmony_tpu.jobserver import joblog

        s = _store()
        _feed(s, "tenant.input_wait_frac", {"job": "ev-j"}, [0.9, 0.9])
        joblog.clear_events("ev-j")
        try:
            Doctor(s, events_fn=dict).diagnose()
            evs = [e for e in joblog.job_events("ev-j")
                   if e["kind"] == "diagnosis"]
            assert len(evs) == 1
            assert evs[0]["rule"] == "input_bound"
            assert evs[0]["verdict"] == "input_bound"
            assert evs[0]["evidence"]["points"]
            json.dumps(evs)  # rides STATUS verbatim
        finally:
            joblog.clear_events("ev-j")

    def test_sink_sees_fresh_diagnoses_and_cannot_break_engine(self):
        s = _store()
        _feed(s, "tenant.input_wait_frac", {"job": "j"}, [0.9, 0.9])
        seen = []

        def bad_sink(d):
            seen.append(d)
            raise RuntimeError("sink bug")

        out = Doctor(s, events_fn=dict, sinks=(bad_sink,)).diagnose()
        assert len(out) == 1 and seen == out

    def test_broken_rule_does_not_silence_the_rest(self, monkeypatch):
        from harmony_tpu.metrics import doctor as doc_mod

        s = _store()
        _feed(s, "tenant.input_wait_frac", {"job": "j"}, [0.9, 0.9])

        def boom(ctx):
            raise RuntimeError("rule bug")

        monkeypatch.setitem(
            doc_mod._RULES, "straggler",
            doc_mod.DoctorRule("straggler", "broken for test", boom))
        out = Doctor(s, events_fn=dict).diagnose()
        assert [d.rule for d in out] == ["input_bound"]

    def test_flight_dump_snapshots_diagnoses(self, tmp_path):
        from harmony_tpu.tracing.flight import FlightRecorder

        s = _store()
        _feed(s, "tenant.input_wait_frac", {"job": "fl-j"}, [0.9, 0.9])
        doc = Doctor(s, events_fn=dict)
        doc.diagnose()
        prev = peek_doctor()
        set_doctor(doc)
        try:
            rec = FlightRecorder(capacity=16, out_dir=str(tmp_path))
            path = rec.dump("test")
            body = json.load(open(path))
            assert body["diagnoses"]
            assert body["diagnoses"][-1]["rule"] == "input_bound"
        finally:
            set_doctor(prev)


class TestPodTargetDiscovery:
    def test_heartbeat_ports_become_scrape_targets(self, devices):
        """The leader's scraper discovers followers from the heartbeat
        plumbing: advertised metrics ports become HTTP targets keyed by
        pid; dead/silenced followers are skipped (their gap IS the
        signal); the ports ride STATUS for operators."""
        from harmony_tpu.jobserver.pod import PodJobServer
        from harmony_tpu.metrics.doctor import set_doctor

        srv = PodJobServer(num_executors=2, num_followers=0)
        try:
            with srv._pod_cond:
                srv._hb_metrics_ports[1] = 9464
                srv._follower_hosts[1] = "10.0.0.9"
                srv._hb_metrics_ports[2] = 9000  # dead: must be skipped
                srv._dead_followers.add(2)
                srv._hb_metrics_ports[3] = 9001  # no host seen yet
            targets = srv._scrape_targets()
            assert targets["pod:1"] == "http://10.0.0.9:9464/metrics"
            assert "pod:2" not in targets
            assert targets["pod:3"] == "http://127.0.0.1:9001/metrics"
            assert callable(targets["leader"])  # in-process, no HTTP
            ports = srv._status()["pod"]["metrics_ports"]
            assert ports == {"1": 9464, "2": 9000, "3": 9001}
        finally:
            set_doctor(None)

    def test_extra_env_targets_reach_the_provider(self, devices,
                                                  monkeypatch):
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.metrics.doctor import set_doctor
        from harmony_tpu.metrics.history import ENV_EXTRA_TARGETS

        monkeypatch.setenv(ENV_EXTRA_TARGETS, "inputsvc=10.1.2.3:9464")
        srv = JobServer(num_executors=1)
        try:
            t = srv._scrape_targets()
            assert t["inputsvc"] == "http://10.1.2.3:9464/metrics"
        finally:
            set_doctor(None)


@pytest.mark.faults
class TestAcceptance:
    """Fault-injected acceptance (ISSUE 11): four distinct injected
    scenarios — input stall, straggler, fault burst, SLO breach —
    must each yield the correct verdict with correct tenant/pid
    attribution and non-empty evidence, exactly once per window,
    through the REAL stack: jobserver scraper -> store -> doctor ->
    STATUS over TCP -> ``harmony-tpu obs doctor``."""

    def test_four_scenarios_end_to_end(self, devices, capsys,
                                       monkeypatch):
        from harmony_tpu import faults
        from harmony_tpu.config.params import RetryPolicy
        from harmony_tpu.faults.retry import RetryError, call_with_retry
        from harmony_tpu.jobserver import joblog
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.metrics.accounting import ledger, reset_ledger
        from harmony_tpu.metrics.collector import BatchMetrics
        from harmony_tpu.cli import main as cli_main

        reset_ledger()
        joblog.clear_events()
        faults.reset_counters()
        # fine-grained buckets so back-to-back polls in this test are
        # distinct points (prod default is 5s — scrape-period scale)
        monkeypatch.setenv("HARMONY_OBS_RESOLUTION", "0.01")
        server = JobServer(num_executors=2)
        # keep the background loop out of the way; we drive polls by hand
        server._history_scraper.period = 3600.0
        server.start()
        try:
            led = ledger()
            # scenario 1 — INPUT STALL on tenant stall-j: device seconds
            # dwarfed by injected prefetch consumer-stall seconds
            led.observe_steps("stall-j", "stall-j", "w0", steps=10,
                              device_sec=1.0, examples=100,
                              input_wait_sec=9.0)
            # scenario 2 — STRAGGLER on tenant lag-j: worker w1 runs 3x
            # slower than its peers
            led.observe_steps("lag-j", "lag-j", "w0", steps=10,
                              device_sec=1.0, examples=100)
            for w, dt in (("w0", 0.1), ("w1", 0.3), ("w2", 0.1)):
                for b in range(3):
                    server.metrics.on_metric(BatchMetrics(
                        job_id="lag-j", worker_id=w, batch_idx=b,
                        num_examples=8, batch_time_sec=dt))
            # healthy control tenant: must receive NO diagnosis
            led.observe_steps("ok-j", "ok-j", "w0", steps=10,
                              device_sec=1.0, examples=100,
                              input_wait_sec=0.1)
            server._history_scraper.poll_once()
            # scenario 3 — FAULT BURST on this process ("leader"): an
            # armed fault plan fires a site repeatedly + a retry loop
            # exhausts, exactly the heartbeat-adjacent burst shape
            faults.arm(faults.FaultPlan([faults.FaultRule(
                "pod.heartbeat", count=8, action="skip")]))
            for _ in range(6):
                faults.site("pod.heartbeat", pid=0)
            faults.disarm()
            with pytest.raises(RetryError):
                call_with_retry(
                    lambda: (_ for _ in ()).throw(OSError("injected")),
                    RetryPolicy(max_attempts=3, base_delay_sec=0.001,
                                max_delay_sec=0.002),
                    op="pod.report")
            # scenario 4 — SLO BREACH on stall-j (joined to its stall)
            joblog.record_event("stall-j", "slo", attainment=0.4,
                                target_sps=500.0, epoch=3)
            time.sleep(0.05)  # past the (test-sized) resolution bucket
            server._history_scraper.poll_once()
            time.sleep(0.05)
            server._history_scraper.poll_once()  # dedupe: no re-fire
            port = server.serve_tcp(0)

            assert cli_main(["obs", "doctor", "--port", str(port),
                             "--json"]) == 0
            out = json.loads(capsys.readouterr().out)
            diags = out["diagnoses"]
            by_rule = {}
            for d in diags:
                by_rule.setdefault(d["rule"], []).append(d)
            # each scenario: correct verdict, exactly once
            for rule in ("input_bound", "straggler", "infra_suspect",
                         "slo_breach"):
                assert len(by_rule.get(rule, [])) == 1, (rule, diags)
            # correct tenant/pid attribution + non-empty evidence
            assert by_rule["input_bound"][0]["job"] == "stall-j"
            assert by_rule["input_bound"][0]["evidence"]["points"]
            assert by_rule["straggler"][0]["job"] == "lag-j"
            assert (by_rule["straggler"][0]["evidence"]["slowest_worker"]
                    == "w1")
            infra = by_rule["infra_suspect"][0]
            assert infra["target"] == "leader"
            import os

            assert infra["pid"] == str(os.getpid())
            assert infra["evidence"]["events_in_window"] >= 5
            breach = by_rule["slo_breach"][0]
            assert breach["job"] == "stall-j"
            assert breach["evidence"]["cause_rule"] == "input_bound"
            # the healthy tenant got no verdict
            assert not any(d.get("job") == "ok-j" for d in diags)
            # the store header the text view renders is populated too
            assert out["history"]["series"] > 0
            # text rendering sanity (the non-json face)
            assert cli_main(["obs", "doctor", "--port", str(port)]) == 0
            text = capsys.readouterr().out
            assert "input_bound" in text and "stall-j" in text
        finally:
            faults.disarm()
            server.shutdown(timeout=60)
            joblog.clear_events()
            reset_ledger()
            faults.reset_counters()
