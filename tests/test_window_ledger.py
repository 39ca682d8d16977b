"""The window ledger (docs/OBSERVABILITY.md §9, metrics/phases.py): one
record a drained window — wall, steps and self seconds by span on the spans'
own clock —, the late windows judged and named after the span that grew, and
the three ``harmony_window_*`` families, STATUS and the flight dump that show
them."""
import json
import time
import types

import pytest

from harmony_tpu.metrics import phases
from harmony_tpu.metrics.phases import PhaseBudgetStore
from harmony_tpu.tracing import (
    InMemorySpanReceiver,
    Tracing,
    set_tracing,
    trace_span,
)


# -- the record: self time by span, closed where the wall is taken ----------

def _tasklet(job="ledger-unit"):
    """A ``WorkerTasklet`` with just the state the ledger's methods touch."""
    from harmony_tpu.dolphin.worker import WorkerTasklet

    t = WorkerTasklet.__new__(WorkerTasklet)
    t.job_id, t.attempt_key, t.global_init = job, job + "@a0", False
    t.ctx = types.SimpleNamespace(worker_id="w0")
    t._phase_ctl = {"grant_wait": 0.0, "probe": 0.0, "bookkeeping": 0.0}
    t._win_spans, t._span_total, t._window = {}, 0.0, 0
    t._compile_mark, t._budget_mark = 0.0, time.monotonic_ns()
    return t


@pytest.mark.parametrize("earlier_acc", [False, True],
                         ids=["bare", "chained"])
def test_a_records_spans_and_unnamed_partition_its_wall(earlier_acc):
    """Nested spans book SELF time — a parent's seconds less its children's,
    through an untracked container too — so the spans and ``unnamed_s`` sum
    to the wall; a span that had an ``acc`` before still feeds it whole."""
    phases.reset_budget()
    t = _tasklet()
    whole = []
    also = whole.append if earlier_acc else None
    with trace_span("window.bookkeeping",
                    acc=t._span_acc("window.bookkeeping", also)):
        time.sleep(0.02)
        with trace_span("dolphin.metric_drain"):  # a container: no acc
            with trace_span("taskunit.wait", record=False,
                            acc=t._span_acc("taskunit.wait", also)):
                time.sleep(0.03)
        with trace_span("taskunit.wait", record=False,
                        acc=t._span_acc("taskunit.wait", also)):
            time.sleep(0.01)
    with trace_span("step.dispatch", record=False,
                    acc=t._span_acc("step.dispatch")):
        time.sleep(0.01)
    time.sleep(0.02)  # under no span
    wall, _ctl = t._take_budget_feed(2, epoch=4, steps=7)
    rec = phases.budget().window_ledger(t.job_id)["windows"][-1]
    spans = rec["spans"]
    assert set(spans) == {"window.bookkeeping", "taskunit.wait",
                          "step.dispatch"}
    assert 0.04 <= spans["taskunit.wait"] < 0.06
    assert 0.02 <= spans["window.bookkeeping"] < 0.04  # NOT its 0.06 whole
    assert 0.01 <= spans["step.dispatch"] < 0.03
    assert rec["unnamed_s"] >= 0.02
    assert sum(spans.values()) + rec["unnamed_s"] == pytest.approx(
        rec["wall_s"], abs=2e-6)
    assert wall == pytest.approx(rec["wall_s"] / 2, abs=1e-6)
    assert (rec["window"], rec["epoch"], rec["epochs"], rec["steps"],
            rec["first"]) == (0, 4, 2, 7, True)
    assert rec["wall_s"] == pytest.approx(
        (rec["end_ns"] - rec["start_ns"]) * 1e-9, abs=1e-6)
    if earlier_acc:  # the chained accumulators got the spans' WHOLE seconds
        assert len(whole) == 3 and max(whole) >= 0.06
    # the next record starts where this one ended, with nothing carried over
    t._take_budget_feed(1, epoch=6, steps=1)
    nxt = phases.budget().window_ledger(t.job_id)["windows"][-1]
    assert nxt["start_ns"] == rec["end_ns"] and nxt["spans"] == {}
    assert (nxt["window"], nxt["first"]) == (1, False)


# -- the verdict ------------------------------------------------------------

def _rec(n, wall, *, k=8, first=False, compile_s=0.0, **spans):
    spans = {name.replace("_", ".", 1): s for name, s in spans.items()}
    return {"window": n, "epoch": n * k, "epochs": k, "steps": 4 * k,
            "start_ns": 10 ** 9 * n, "end_ns": 10 ** 9 * n + int(wall * 1e9),
            "wall_s": wall, "spans": spans, "compile_s": compile_s,
            "first": first}


def _regular(n, wall=1.0, **kw):
    return _rec(n, wall, step_dispatch=0.30 * wall, drain_d2h=0.60 * wall,
                **kw)


SCRIPTS = {
    # name: (records in order, [(window, cause)] of the verdicts expected)
    "no_verdict_before_three_regular_windows": (
        [_regular(0, 9.0, first=True), _regular(1), _regular(2),
         _regular(3, 5.0)], []),
    "first_is_never_judged_nor_in_the_median": (
        [_regular(1), _regular(2), _regular(3), _regular(0, 9.0, first=True)]
        + [_regular(0, 0.1, first=True) for _ in range(4)]
        + [_regular(4, 1.4)], []),
    "named_after_the_span_that_grew": (
        [_regular(0, 9.0, first=True), _regular(1), _regular(2), _regular(3),
         _rec(4, 3.0, step_dispatch=0.31, drain_d2h=2.55)],
        [(4, "drain.d2h")]),
    "a_span_the_regular_windows_never_had": (
        [_regular(1), _regular(2), _regular(3),
         _rec(4, 2.0, step_dispatch=0.30, drain_d2h=0.60,
              dolphin_comm_probe=0.95)],
        [(4, "dolphin.comm_probe")]),
    "unnamed_when_no_span_grew": (
        [_regular(1), _regular(2), _regular(3),
         _rec(4, 2.5, step_dispatch=0.32, drain_d2h=0.61)],
        [(4, "unnamed")]),
    "compile_when_it_covers_most_of_the_loss": (
        [_regular(1), _regular(2), _regular(3),
         _rec(4, 3.0, step_dispatch=2.3, drain_d2h=0.6, compile_s=1.2)],
        [(4, "compile")]),
    "a_late_window_stays_out_of_the_next_median": (
        [_regular(1), _regular(2), _regular(3)]
        + [_rec(n, 4.0, step_dispatch=3.3, drain_d2h=0.6)
           for n in range(4, 10)],
        [(n, "step.dispatch") for n in range(4, 10)]),
    "a_short_window_loses_nothing": (
        [_regular(1), _regular(2), _regular(3), _regular(4, 0.2),
         _regular(5, 1.49)], []),
    "per_epoch_not_per_window": (
        [_regular(1), _regular(2), _regular(3),
         _rec(4, 0.4, k=2, step_dispatch=0.27, drain_d2h=0.12)],
        [(4, "step.dispatch")]),
}


@pytest.mark.parametrize("name", list(SCRIPTS))
def test_observe_window_judges_a_scripted_series(name):
    records, expected = SCRIPTS[name]
    store = PhaseBudgetStore()
    verdicts = [v for v in (store.observe_window("j", "j@a0", "w0", r)
                            for r in records) if v is not None]
    assert [(v["window"], v["cause"]) for v in verdicts] == expected
    ledger = store.window_ledger("j")
    assert ledger["stalls"] == verdicts
    assert [w["window"] for w in ledger["windows"] if w["late"]] \
        == [w for w, _ in expected]
    for v in verdicts:
        assert v["lost_s"] > 0.0  # a negative loss is impossible
        assert set(v) == {"window", "epoch", "epochs", "lost_s", "cause",
                          "excess", "start_sec"}
        assert all(s > 0 for s in v["excess"].values())
        assert v["cause"] in (*v["excess"], "compile")


def test_the_loss_is_the_wall_over_the_medians():
    store = PhaseBudgetStore()
    for n, wall in enumerate([1.0, 1.1, 0.9, 1.0], start=1):
        assert store.observe_window("j", "a", "w0", _regular(n, wall)) is None
    v = store.observe_window("j", "a", "w0", _rec(
        5, 3.5, step_dispatch=0.3, drain_d2h=0.6, taskunit_wait=2.5))
    assert v["lost_s"] == pytest.approx(2.5) and v["cause"] == "taskunit.wait"
    assert v["excess"] == pytest.approx({"taskunit.wait": 2.5})


# -- the ring ----------------------------------------------------------------

def test_the_ring_is_bounded_a_worker_and_goes_with_the_tenant():
    store = PhaseBudgetStore()
    for n in range(300):
        store.observe_window("j", "j@a0", "w0", _regular(n))
        store.observe_window("j", "j@a0", "w1", _regular(n))
    store.observe_window("other", "o@a0", "w0", _regular(0))
    whole = store.window_ledger("j", newest=10 ** 6)["windows"]
    assert len(whole) == 2 * phases._MAX_WINDOWS
    assert {w["worker"] for w in whole} == {"w0", "w1"}
    assert min(w["window"] for w in whole) == 300 - phases._MAX_WINDOWS
    assert len(store.window_ledger("j")["windows"]) == phases._MAX_WINDOWS
    # another attempt's walls are not this one's: its ring starts anew
    store.observe_window("j", "j@a1", "w0", _regular(0, 5.0))
    w0 = [w for w in store.window_ledger("j", newest=10 ** 6)["windows"]
          if w["worker"] == "w0"]
    assert [(w["attempt"], w["late"]) for w in w0] == [("j@a1", False)]
    store.clear()
    assert store.window_ledger("j") == {"windows": [], "stalls": []}
    assert store.window_ledger("other")["windows"] == []


# -- through the jobserver ---------------------------------------------------

def _config(job, epochs, seed=0):
    from harmony_tpu.config.params import JobConfig, TrainerParams

    return JobConfig(
        job_id=job, app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=epochs, num_mini_batches=4,
            app_params={"num_classes": 4, "num_features": 16,
                        "features_per_partition": 4}),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
              "data_args": {"n": 256, "num_features": 16,
                            "num_classes": 4, "seed": seed}})


def _run(configs):
    from harmony_tpu.jobserver.server import JobServer

    server = JobServer(num_executors=1)
    server.start()
    try:
        for c in configs:
            server.submit(c).result(timeout=300)
        return server._status()
    finally:
        server.shutdown()


def _family(name):
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    return parse_exposition(get_registry().expose()).get(name)


@pytest.mark.parametrize("epoch_window,container,windows",
                         [(None, "dolphin.epoch_window", [(0, 8), (8, 8)]),
                          ("1", "dolphin.epoch", [(0, 1), (1, 1)])],
                         ids=["windowed", "one_epoch"])
def test_a_two_window_job_leaves_its_records_everywhere(
        monkeypatch, epoch_window, container, windows):
    """Both paths that take a window's wall (the windowed one and the
    one-epoch one) close a record: STATUS ``phase_budget.<job>.windows`` /
    ``.stalls``, the histogram's exposition, the ``window`` stat on the
    container span's annotation — and the per-epoch budget as before."""
    if epoch_window is not None:
        monkeypatch.setenv("HARMONY_EPOCH_WINDOW", epoch_window)
    tracing = set_tracing(Tracing(process_id="ledger-test"))
    receiver = tracing.add_receiver(InMemorySpanReceiver())
    phases.reset_budget()
    job = "ledger-" + container.split(".")[1]
    epochs = sum(k for _, k in windows)
    try:
        status = _run([_config(job, epochs)])
    finally:
        set_tracing(Tracing())
    row = status["phase_budget"][job]
    assert [(w["window"], w["epoch"], w["epochs"], w["first"], w["late"])
            for w in row["windows"]] \
        == [(n, e, k, n == 0, False) for n, (e, k) in enumerate(windows)]
    assert row["stalls"] == []
    json.dumps(row["windows"])  # STATUS crosses TCP as JSON
    for w in row["windows"]:
        assert w["steps"] == 4 * w["epochs"]
        assert w["wall_s"] == pytest.approx(
            (w["end_ns"] - w["start_ns"]) * 1e-9, abs=1e-5)
        assert {"step.dispatch", "drain.stack", "drain.d2h",
                "drain.emit", "epoch.turnover"} <= set(w["spans"])
        assert all(s >= 0.0 for s in w["spans"].values())
        assert -1e-5 <= w["unnamed_s"] < w["wall_s"]
    first, second = row["windows"]
    assert second["start_ns"] == first["end_ns"]
    assert first["compile_s"] > 0.0 and second["compile_s"] == 0.0
    assert "window.bookkeeping" in second["spans"]  # window 0's, by the clock
    # the histogram holds every window but the first, as wall an epoch
    counts = [v for name, labels, v in
              _family("harmony_window_seconds")["samples"]
              if name.endswith("_count") and labels["job"] == job]
    assert counts == [1.0]
    # the container span's annotation carries the window's number
    spans = [s for s in receiver.by_description(container)
             if s.annotations.get("job_id") == job]
    assert [(s.annotations["window"], s.annotations["epoch"])
            for s in spans] == [(n, e) for n, (e, _) in enumerate(windows)]
    # the budget invariant, and its per-epoch feeds, are as they were
    assert row["epochs"] == epochs and len(row["epoch_walls"]) == epochs
    assert sum(row["phases"].values()) == pytest.approx(
        row["wall_sec"], rel=1e-3, abs=1e-4)
    assert row["wall_sec"] == pytest.approx(
        sum(w["wall_s"] for w in row["windows"]), rel=1e-3, abs=1e-4)


def test_a_held_dispatch_is_a_late_window_named_step_dispatch(tmp_path):
    """A training thread held inside one enqueue (an injected 0.4 s at the
    ``worker.dispatch`` site of window 5) is one late window: the verdict
    names ``step.dispatch``, the chief counts it, STATUS and a flight dump
    show it."""
    from harmony_tpu import faults
    from harmony_tpu.tracing import flight

    phases.reset_budget()
    job = "ledger-held"
    faults.reset_counters()
    faults.arm(faults.FaultPlan([faults.FaultRule(
        "worker.dispatch", match={"job": job}, after=5 * 32 + 3, count=1,
        action="delay", delay_sec=0.4)]))
    try:
        status = _run([_config(job, 64)])
    finally:
        faults.disarm()
        faults.reset_counters()
    row = status["phase_budget"][job]
    held = [s for s in row["stalls"] if s["window"] == 5]
    assert len(held) == 1
    stall = held[0]
    assert stall["cause"] == "step.dispatch" and stall["epoch"] == 40
    assert 0.35 <= stall["lost_s"] <= 0.6
    assert stall["excess"]["step.dispatch"] == pytest.approx(0.4, abs=0.05)
    assert abs(stall["start_sec"] - time.time()) < 600.0
    assert [w["window"] for w in row["windows"] if w["late"]] \
        == [s["window"] for s in row["stalls"]]
    seconds = {labels["cause"]: v for _n, labels, v in
               _family("harmony_window_stall_seconds_total")["samples"]
               if labels["job"] == job}
    count = {labels["cause"]: v for _n, labels, v in
             _family("harmony_window_stalls_total")["samples"]
             if labels["job"] == job}
    assert seconds["step.dispatch"] >= stall["lost_s"] - 1e-6
    assert sum(count.values()) == len(row["stalls"])
    assert sum(seconds.values()) == pytest.approx(
        sum(s["lost_s"] for s in row["stalls"]), abs=1e-5)
    recorder = flight.FlightRecorder(out_dir=str(tmp_path))
    path = recorder.dump("ledger-test")
    with open(path) as f:
        dumped = json.load(f)["phase_budget"][job]
    assert dumped["stalls"] == json.loads(json.dumps(row["stalls"]))
    assert len(dumped["windows"]) == 8
