"""Telemetry-history store + hardened scraper tests (PR 11 tentpole;
docs/OBSERVABILITY.md §8): ring eviction/downsampling invariants,
label-filtered queries, rate-vs-reset math, gap markers (no
interpolation), scrape-client hardening (a dead follower never wedges
the loop), and the counter-reset → ``process_restart`` contract — an
exporter restart mid-window produces exactly one structured event and
no negative rates."""
import time

import pytest

from harmony_tpu.metrics import history as hist
from harmony_tpu.metrics.history import (
    HistoryScraper,
    HistoryStore,
    ScrapeClient,
    extra_targets,
)
from harmony_tpu.metrics.registry import MetricRegistry, set_registry


@pytest.fixture()
def fresh_registry():
    reg = set_registry(MetricRegistry())
    yield reg
    set_registry(MetricRegistry())


class TestStoreRings:
    def test_ring_eviction_bounded_by_window(self):
        s = HistoryStore(window_sec=10.0, resolution_sec=1.0)
        t0 = time.time()
        for i in range(50):
            s.ingest("g", {"job": "j"}, float(i), ts=t0 + i)
        ((labels, pts),) = s.range("g")
        # capacity = window/resolution + 1: old points evicted, newest kept
        assert len(pts) == 11
        assert pts[-1][1] == 49.0
        assert pts[0][1] == 39.0

    def test_downsampling_last_wins_within_bucket(self):
        s = HistoryStore(window_sec=100.0, resolution_sec=10.0)
        t0 = 1000.0
        s.ingest("g", {}, 1.0, ts=t0 + 1)
        s.ingest("g", {}, 2.0, ts=t0 + 5)   # same 10s bucket
        s.ingest("g", {}, 3.0, ts=t0 + 12)  # next bucket
        ((_, pts),) = s.range("g")
        assert [v for _, v in pts] == [2.0, 3.0]

    def test_series_cap_drops_and_counts(self, monkeypatch):
        monkeypatch.setattr(hist, "_MAX_SERIES", 3)
        s = HistoryStore(window_sec=10, resolution_sec=1)
        for i in range(5):
            s.ingest("g", {"k": str(i)}, 1.0)
        assert s.stats()["series"] == 3
        assert s.stats()["dropped_series"] == 2

    def test_churned_out_series_evicted_never_saturate_the_cap(
            self, monkeypatch):
        """Tenant churn: window-expired series of dead tenants are
        evicted (periodically and under cap pressure) so a NEW
        tenant's series always gets in — the store must not go
        permanently blind after enough short jobs."""
        monkeypatch.setattr(hist, "_MAX_SERIES", 2)
        s = HistoryStore(window_sec=10, resolution_sec=1)
        t_old = time.time() - 100  # far outside the window
        s.ingest("g", {"job": "dead1"}, 1.0, ts=t_old)
        s.ingest("g", {"job": "dead2"}, 1.0, ts=t_old + 1)
        assert s.stats()["series"] == 2  # cap reached by dead tenants
        s.ingest("g", {"job": "live"}, 5.0)  # now: must evict, not drop
        ((lab, pts),) = s.range("g", labels={"job": "live"})
        assert pts[-1][1] == 5.0
        st = s.stats()
        assert st["series"] == 1
        assert st["evicted_series"] == 2
        assert st["dropped_series"] == 0

    def test_a_saturated_store_scans_once_a_timestamp(self, monkeypatch):
        """Past the cap every new sample used to rescan all series: a
        5,146-line exposition (two jobs' 5 x 512 expert counters) cost
        1,034 scans and 0.6 s under the GIL every scrape period. A
        scrape's samples share one timestamp, and nothing more can expire
        at the same instant: one scan, the same drops."""
        monkeypatch.setattr(hist, "_MAX_SERIES", 4)
        s = HistoryStore(window_sec=100, resolution_sec=1)
        scans = []
        prune = s._prune_locked
        monkeypatch.setattr(
            s, "_prune_locked", lambda now: (scans.append(now), prune(now)))
        t = time.time()
        for i in range(10):
            s.ingest("g", {"k": str(i)}, 1.0, ts=t)
        assert s.stats()["series"] == 4
        assert s.stats()["dropped_series"] == 6
        assert len(scans) <= 2  # the periodic one, and one at the cap
        for i in range(10, 14):  # a later scrape scans again, once
            s.ingest("g", {"k": str(i)}, 1.0, ts=t + 5)
        assert len(scans) <= 3 and s.stats()["dropped_series"] == 10


class TestQueries:
    def test_label_filtered_range_and_latest(self):
        s = HistoryStore(window_sec=100, resolution_sec=1)
        t0 = time.time()
        for i in range(3):
            s.ingest("tenant.mfu", {"job": "a", "attempt": "a"},
                     0.1 * i, ts=t0 + i)
            s.ingest("tenant.mfu", {"job": "b", "attempt": "b"},
                     0.5, ts=t0 + i)
        assert len(s.range("tenant.mfu")) == 2
        ((labels, pts),) = s.range("tenant.mfu", labels={"job": "a"})
        assert labels["job"] == "a" and len(pts) == 3
        ((lab, _ts, v),) = s.latest("tenant.mfu", labels={"job": "b"})
        assert lab["job"] == "b" and v == 0.5
        # subset match: a label nobody carries matches nothing
        assert s.range("tenant.mfu", labels={"job": "a", "x": "y"}) == []

    def test_since_clips(self):
        s = HistoryStore(window_sec=100, resolution_sec=1)
        t0 = time.time()
        for i in range(10):
            s.ingest("g", {}, float(i), ts=t0 + i)
        ((_, pts),) = s.range("g", since=t0 + 5)
        assert all(t >= t0 + 5 for t, _ in pts)


class TestRateMath:
    def test_counter_rate(self):
        s = HistoryStore(window_sec=100, resolution_sec=1)
        t0 = time.time() - 5
        for i, v in enumerate((0.0, 10.0, 20.0)):
            s.ingest("c_total", {}, v, ts=t0 + i, kind="counter")
        ((_, r),) = s.rate("c_total")
        assert r == pytest.approx(10.0)

    def test_reset_detected_and_never_negative(self):
        s = HistoryStore(window_sec=100, resolution_sec=1)
        t0 = time.time() - 10
        vals = (0.0, 10.0, 3.0, 13.0)  # 10 -> 3 is a restart
        resets = [s.ingest("c_total", {}, v, ts=t0 + i, kind="counter")
                  for i, v in enumerate(vals)]
        assert resets == [False, False, True, False]
        assert s.resets() == 1
        ((_, r),) = s.rate("c_total")
        # the reset interval contributes nothing: (10-0)/1 and (13-3)/1
        assert r == pytest.approx(10.0)
        assert r >= 0

    def test_rate_refuses_to_interpolate_across_gap(self):
        s = HistoryStore(window_sec=100, resolution_sec=1)
        t0 = time.time() - 10
        s.ingest("c_total", {"target": "t"}, 0.0, ts=t0, kind="counter",
                 target="t")
        s.mark_gap("t", ts=t0 + 2)  # missed scrapes in between
        s.ingest("c_total", {"target": "t"}, 100.0, ts=t0 + 4,
                 kind="counter", target="t")
        ((_, r),) = s.rate("c_total")
        assert r is None  # two points, but the only interval spans a gap
        (gap,) = s.gaps("t")
        assert gap == pytest.approx(t0 + 2, abs=1.0)  # bucket-floored

    def test_gap_honored_when_scrapes_outpace_resolution(self):
        """The code-review repro: with the scrape period FINER than the
        resolution, a raw-timestamp gap mark could fall strictly
        between two bucket floors and never match an interval — marks
        are bucket-floored now, same clock as the points."""
        s = HistoryStore(window_sec=100, resolution_sec=5.0)
        t0 = time.time() - 20
        t0 -= t0 % 5.0  # align so the samples straddle one boundary
        s.ingest("c_total", {"target": "t"}, 0.0, ts=t0 + 4,
                 kind="counter", target="t")
        s.mark_gap("t", ts=t0 + 6)
        s.ingest("c_total", {"target": "t"}, 32.0, ts=t0 + 8,
                 kind="counter", target="t")
        ((_, r),) = s.rate("c_total")
        assert r is None  # the marked gap is honored, not bypassed
        assert s.increase("c_total") == [({"target": "t"}, 0.0)]

    def test_rate_none_under_two_points(self):
        s = HistoryStore(window_sec=100, resolution_sec=1)
        s.ingest("c_total", {}, 5.0, kind="counter")
        ((_, r),) = s.rate("c_total")
        assert r is None


class TestExpositionIngest:
    def _text(self, reg):
        return reg.expose()

    def test_families_fold_in_and_pid_is_lifted(self, fresh_registry):
        reg = fresh_registry
        reg.counter("harmony_x_total", "x", ("op",)).labels(op="a").inc(3)
        reg.gauge("harmony_depth", "d").set(7)
        reg.histogram("harmony_t_seconds", "t").observe(0.5)
        s = HistoryStore(window_sec=100, resolution_sec=1)
        info = s.ingest_exposition("tgt", self._text(reg))
        assert info["samples"] > 0 and not info["restart"]
        names = s.series_names()
        assert "harmony_x_total" in names
        assert "harmony_depth" in names
        # histogram per-le buckets are skipped; _sum/_count kept
        assert "harmony_t_seconds_bucket" not in names
        assert "harmony_t_seconds_sum" in names
        assert "harmony_t_seconds_count" in names
        ((labels, _pts),) = s.range("harmony_x_total")
        assert labels == {"op": "a", "target": "tgt"}  # pid lifted off

    def test_exposition_target_label_survives_under_exported_target(
            self, fresh_registry):
        """The code-review repro: the leader's own registry carries
        harmony_obs_scrape_total{target=...}; clobbering that label
        with the scrape-target name collapsed every per-target counter
        into ONE series whose interleaved values tripped reset
        detection (a spurious process_restart every cycle)."""
        reg = fresh_registry
        c = reg.counter("harmony_obs_scrape_total", "x",
                        ("target", "result"))
        c.labels(target="leader", result="ok").inc(100)
        c.labels(target="pod:5", result="ok").inc(60)
        s = HistoryStore(window_sec=100, resolution_sec=0.01)
        t0 = time.time() - 4
        for i in range(4):
            info = s.ingest_exposition("leader", reg.expose(), ts=t0 + i)
            assert not info["restart"], (i, info)
        series = s.range("harmony_obs_scrape_total")
        assert len(series) == 2  # one per exported target, not merged
        exported = {lab["exported_target"] for lab, _ in series}
        assert exported == {"leader", "pod:5"}
        assert all(lab["target"] == "leader" for lab, _ in series)
        assert s.stats()["restarts"] == 0

    def test_vanished_target_bookkeeping_pruned_with_its_series(
            self, fresh_registry):
        """Follower churn mints a new pod:<pid> target name per
        replacement: meta, gap rings and scraper last-errors for names
        that stopped scraping must follow their series out instead of
        growing forever."""
        reg = fresh_registry
        reg.counter("harmony_x_total", "x").inc(3)
        s = HistoryStore(window_sec=10, resolution_sec=0.01)
        t_old = time.time() - 100  # a follower that died long ago
        s.ingest_exposition("pod:9001", reg.expose(), ts=t_old)
        s.mark_gap("pod:9001", ts=t_old + 1)
        s.ingest_exposition("pod:9002", reg.expose())  # the live one
        # the live ingest triggered the periodic prune
        st = s.stats()
        assert st["targets"] == ["pod:9002"]
        assert s.gaps("pod:9001") == []
        assert s.target_pid("pod:9001") is None
        # scraper side: a target gone from the provider drops its error
        scraper = HistoryScraper(
            s, targets_fn=lambda: {}, period=1000.0)
        with scraper._lock:
            scraper._last_errors["pod:9001"] = "ConnectionRefusedError"
        scraper.poll_once()
        assert scraper.stats()["last_errors"] == {}

    def test_restart_detected_once_via_counter_reset(self, fresh_registry):
        reg_a = fresh_registry
        reg_a.counter("harmony_x_total", "x").inc(5)
        s = HistoryStore(window_sec=100, resolution_sec=0.01)
        t0 = time.time() - 3
        assert not s.ingest_exposition("t", reg_a.expose(), ts=t0)["restart"]
        # "restarted" process: fresh registry, counter back near zero
        reg_b = MetricRegistry()
        reg_b.counter("harmony_x_total", "x").inc(1)
        info = s.ingest_exposition("t", reg_b.expose(), ts=t0 + 1)
        assert info["restart"] and info["resets"] == 1
        # subsequent scrapes of the restarted process: no new restart
        reg_b.counter("harmony_x_total", "x").inc(1)
        assert not s.ingest_exposition(
            "t", reg_b.expose(), ts=t0 + 2)["restart"]
        assert s.stats()["restarts"] == 1

    def test_lazily_reappearing_counter_is_not_a_second_restart(self):
        """The code-review repro: a counter absent from the restart
        scrape (not exercised yet post-restart) that reappears a few
        scrapes later at a low value must NOT trip reset detection
        against its pre-restart baseline — one restart, ONE event."""
        reg_a = MetricRegistry()
        reg_a.counter("harmony_x_total", "x").inc(50)
        reg_a.counter("harmony_y_total", "y").inc(7)
        s = HistoryStore(window_sec=100, resolution_sec=0.01)
        t0 = time.time() - 5
        assert not s.ingest_exposition("t", reg_a.expose(), ts=t0)["restart"]
        # restart: the new process has only exercised x so far
        reg_b = MetricRegistry()
        reg_b.counter("harmony_x_total", "x").inc(1)
        assert s.ingest_exposition("t", reg_b.expose(),
                                   ts=t0 + 1)["restart"]
        # y reappears two scrapes later at 2 < its stale baseline 7
        reg_b.counter("harmony_y_total", "y").inc(2)
        info = s.ingest_exposition("t", reg_b.expose(), ts=t0 + 2)
        assert not info["restart"], info
        assert s.stats()["restarts"] == 1
        for _labels, r in s.rate("harmony_y_total"):
            assert r is None or r >= 0


class TestScraperHardening:
    """Satellite: a dead/slow target must cost a bounded timeout and a
    gap mark, never a wedged loop or skewed series."""

    def test_dead_target_marks_gap_and_loop_continues(self, fresh_registry):
        reg = fresh_registry
        reg.counter("harmony_live_total", "x").inc()
        s = HistoryStore(window_sec=100, resolution_sec=0.01)
        from harmony_tpu.config.params import RetryPolicy

        client = ScrapeClient(timeout=0.5, policy=RetryPolicy(
            max_attempts=2, base_delay_sec=0.01, max_delay_sec=0.02))
        scraper = HistoryScraper(
            s, targets_fn=lambda: {
                "dead": "http://127.0.0.1:1/metrics",  # nothing listens
                "live": reg.expose,
            },
            client=client, period=1000.0)
        t0 = time.monotonic()
        report = scraper.poll_once()
        assert time.monotonic() - t0 < 10.0  # bounded, not wedged
        assert report["targets"]["dead"] == "gap"
        assert report["targets"]["live"]["samples"] > 0
        assert len(s.gaps("dead")) == 1
        assert "harmony_live_total" in s.series_names()
        assert "dead" in scraper.stats()["last_errors"]
        # per-target outcome counters (the scrape-client contract)
        fam = reg.counter("harmony_obs_scrape_total",
                          "", ("target", "result"))
        assert fam.labels(target="dead", result="error").value >= 1
        assert fam.labels(target="live", result="ok").value == 1

    def test_bounded_body_read_caps_size_and_wall_clock(self):
        """A misdirected target (log tail, streaming endpoint) must
        fail the poll: reads are capped in bytes AND wall time — the
        per-socket-op urllib timeout alone never fires on a trickling
        sender."""
        from harmony_tpu.metrics.history import _read_bounded

        class Endless:
            def read(self, n):
                return b"x" * n  # never EOF

        with pytest.raises(ValueError):  # size cap
            _read_bounded(Endless(), deadline=time.monotonic() + 60,
                          cap=1024)

        class Trickle:
            def read(self, n):
                return b"x"  # one byte per recv, forever

        with pytest.raises(TimeoutError):  # wall deadline
            _read_bounded(Trickle(), deadline=time.monotonic() + 0.05,
                          cap=1 << 30)

    def test_scraper_restarts_after_stop(self):
        """stop() then start() must actually poll again — the stop
        event is cleared, not inherited by the new loop thread."""
        s = HistoryStore(window_sec=10, resolution_sec=0.01)
        scraper = HistoryScraper(s, targets_fn=dict, period=1000.0)
        scraper.start()
        scraper.stop()
        assert scraper._thread is None
        scraper.start()
        try:
            assert not scraper._stop_ev.is_set()
            assert scraper._thread is not None
            assert scraper._thread.is_alive()
        finally:
            scraper.stop()

    def test_broken_targets_fn_does_not_kill_the_poll(self):
        s = HistoryStore(window_sec=10, resolution_sec=1)

        def boom():
            raise RuntimeError("no targets for you")

        scraper = HistoryScraper(s, targets_fn=boom, period=1000.0)
        report = scraper.poll_once()
        assert "targets_error" in report

    def test_ledger_rows_become_tenant_series(self):
        s = HistoryStore(window_sec=100, resolution_sec=0.01)
        rows = {"j1": {"attempt": "j1@a1", "samples_per_sec": 120.0,
                       "mfu": None,  # unknown stays unknown, never 0
                       "input_wait_frac": 0.7,
                       "device_seconds": 3.2,
                       "straggler_ratio": 1.0, "workers": 2,
                       "slo": {"attainment": 0.8}}}
        scraper = HistoryScraper(
            s, targets_fn=dict, ledger_fn=lambda: rows, period=1000.0)
        scraper.poll_once()
        ((lab, _t, v),) = s.latest("tenant.samples_per_sec")
        assert lab == {"job": "j1", "attempt": "j1@a1"} and v == 120.0
        assert s.range("tenant.mfu") == []  # None was not ingested
        ((_, _t2, att),) = s.latest("tenant.slo_attainment")
        assert att == 0.8

    def test_extra_targets_parsing(self, monkeypatch):
        monkeypatch.setenv(hist.ENV_EXTRA_TARGETS,
                           "inputsvc=10.0.0.5:9464, 10.0.0.6:9464, bad")
        t = extra_targets()
        assert t["inputsvc"] == "http://10.0.0.5:9464/metrics"
        assert any(u == "http://10.0.0.6:9464/metrics"
                   for u in t.values())
        assert len(t) == 2  # "bad" (no port) dropped, never fatal
        # operators naturally paste full endpoints: the scheme strips
        # instead of building a broken double-scheme URL
        monkeypatch.setenv(hist.ENV_EXTRA_TARGETS,
                           "svc=http://10.0.0.2:9464")
        assert extra_targets() == {"svc": "http://10.0.0.2:9464/metrics"}

    def test_rate_and_increase_honor_a_driven_until(self):
        """diagnose(now=t) must see ONE window across every query
        primitive: rate()/increase() anchor to the caller's clock, not
        the wall clock."""
        s = HistoryStore(window_sec=30, resolution_sec=0.01)
        t0 = time.time() - 3600  # replayed data far behind the wall clock
        for i, v in enumerate((0.0, 10.0, 20.0)):
            s.ingest("c_total", {}, v, ts=t0 + i, kind="counter")
        # wall-clock window sees nothing; a driven window sees the data
        assert s.rate("c_total") == [({}, None)]
        assert s.increase("c_total") == []
        ((_, r),) = s.rate("c_total", until=t0 + 2)
        assert r == pytest.approx(10.0)
        ((_, inc),) = s.increase("c_total", until=t0 + 2)
        assert inc == pytest.approx(20.0)


class TestExporterRestartAcceptance:
    """Satellite pin: an exporter restart mid-window produces EXACTLY
    ONE structured ``kind="process_restart"`` joblog event naming the
    target, and no negative rates — end to end over real HTTP."""

    def test_restart_one_event_no_negative_rates(self, fresh_registry):
        from harmony_tpu.jobserver import joblog
        from harmony_tpu.metrics.exporter import MetricsExporter

        joblog.clear_events("exp")
        reg_a = MetricRegistry()
        reg_a.counter("harmony_steps_total", "s").inc(50)
        exp = MetricsExporter(0, registry=reg_a)
        exp.start()
        s = HistoryStore(window_sec=100, resolution_sec=0.01)
        url = exp.url + "/metrics"
        scraper = HistoryScraper(s, targets_fn=lambda: {"exp": url},
                                 period=1000.0)
        try:
            scraper.poll_once()
            reg_a.counter("harmony_steps_total", "s").inc(10)
            scraper.poll_once()
        finally:
            exp.stop()
        # the process "restarts": fresh registry (counters from zero),
        # fresh exporter — the scraper keeps polling the same target
        reg_b = MetricRegistry()
        reg_b.counter("harmony_steps_total", "s").inc(2)
        exp2 = MetricsExporter(0, registry=reg_b)
        exp2.start()
        url = exp2.url + "/metrics"
        try:
            scraper.poll_once()
            reg_b.counter("harmony_steps_total", "s").inc(3)
            scraper.poll_once()
        finally:
            exp2.stop()
        events = [e for e in joblog.job_events("exp")
                  if e["kind"] == "process_restart"]
        assert len(events) == 1, events
        assert events[0]["target"] == "exp"
        assert events[0]["pid"] is not None
        for _labels, r in s.rate("harmony_steps_total"):
            assert r is None or r >= 0
        joblog.clear_events("exp")
