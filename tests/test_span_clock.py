"""One clock for host and device (docs/OBSERVABILITY.md §1, §5, §9):
``trace_span`` is the one instrument — receivers, the profiler's trace, the
open/longest watch and the phase budget all read the same two clock reads."""
import glob
import threading
import time

import pytest

from harmony_tpu.metrics.phases import PHASES, RESIDUAL, PhaseBudgetStore
from harmony_tpu.tracing import (
    InMemorySpanReceiver,
    Tracing,
    flight,
    job_stage,
    longest_spans,
    open_spans,
    record_span,
    set_tracing,
    trace_span,
)
from harmony_tpu.tracing import span as span_mod


@pytest.fixture()
def receiver():
    t = set_tracing(Tracing(process_id="clock-test"))
    rec = t.add_receiver(InMemorySpanReceiver())
    span_mod.reset_span_watch()
    yield rec
    set_tracing(Tracing())


# -- the clock ---------------------------------------------------------------

def test_duration_survives_a_wall_clock_jump(receiver, monkeypatch):
    """NTP steps ``time.time`` by an hour mid-span: the duration comes from
    the monotonic clock, and start/stop stay one anchor apart."""
    with trace_span("stepped") as s:
        real = time.time
        monkeypatch.setattr(time, "time", lambda: real() + 3600.0)
        time.sleep(0.01)
    assert 0.009 < s.duration_sec < 1.0
    assert s.stop_sec - s.start_sec == pytest.approx(s.duration_sec, abs=1e-6)
    d = s.to_dict()
    assert set(d) == {"trace_id", "span_id", "parent_id", "description",
                      "start_sec", "stop_sec", "annotations", "process_id"}
    assert abs(d["start_sec"] - real()) < 60.0  # wall seconds, not monotonic


def test_ids_are_made_only_when_somebody_reads_them():
    set_tracing(Tracing())  # no receiver
    try:
        with trace_span("outer") as outer:
            with trace_span("inner") as inner:
                pass
        assert outer._span_id is None and inner._trace_id is None
        # a late reader still sees one consistent family
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id
    finally:
        set_tracing(Tracing())


def test_record_span_back_dates_a_cross_thread_region(receiver):
    t0 = time.monotonic_ns()
    with trace_span("jobserver.dispatch", job_id="j") as parent:
        s = record_span("job.grant_wait", t0 - 2_000_000_000, t0, job_id="j")
    got = receiver.by_description("job.grant_wait")[0]
    assert got is s and got.parent_id == parent.span_id
    assert got.duration_sec == pytest.approx(2.0)


# -- the profiler sink -------------------------------------------------------

def test_span_lands_in_the_profilers_host_plane(tmp_path, receiver):
    """Under a ``jax.profiler`` session with the harness's own options
    (Python tracer off) both span forms are events named ``harmony/<name>``
    on the calling thread's line of ``/host:CPU``."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace_span("clock.full", job_id="j9", epoch=3):
            with trace_span("clock.light", record=False, kind="COMP"):
                jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    if not files:
        pytest.skip("the CPU profiler wrote no xplane here")
    profile = jax.profiler.ProfileData.from_file(files[0])
    found = {}
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("harmony/clock."):
                    found[e.name] = (e.start_ns, e.duration_ns, dict(e.stats))
    if not found:
        pytest.skip("the CPU profiler recorded no TraceMe events here")
    assert set(found) == {"harmony/clock.full", "harmony/clock.light"}
    full, light = found["harmony/clock.full"], found["harmony/clock.light"]
    assert full[0] <= light[0] and light[0] + light[1] <= full[0] + full[1]
    assert full[2].get("job_id") == "j9" and light[2].get("kind") == "COMP"


# -- the light form ----------------------------------------------------------

def test_light_form_emits_nothing_and_feeds_its_accumulator(receiver):
    got = []
    with trace_span("outer") as outer:
        with trace_span("step.dispatch", record=False, acc=got.append) as s:
            assert s is None
            assert span_mod.current_span() is outer  # context untouched
            time.sleep(0.005)
    assert [x.description for x in receiver.spans] == ["outer"]
    assert len(got) == 1 and 0.004 < got[0] < 1.0
    assert longest_spans()[0]["description"] in ("outer", "step.dispatch")
    assert {r["description"] for r in longest_spans()} == {
        "outer", "step.dispatch"}


def test_full_form_feeds_an_accumulator_too(receiver):
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    with job_stage("clock-job", "init"):
        time.sleep(0.002)
    fam = parse_exposition(get_registry().expose())[
        "harmony_job_stage_seconds_total"]
    cells = {(l["job"], l["stage"]): v for _n, l, v in fam["samples"]}
    assert cells[("clock-job", "init")] >= 0.002
    assert receiver.by_description("job.init")[0].annotations["job_id"] == "clock-job"
    assert span_mod.job_stage_seconds()["clock-job"]["init"] >= 0.002


# -- open and longest spans --------------------------------------------------

def test_flight_recorder_reports_a_sleeping_taskunit_wait():
    """A worker that waits for a grant that does not come is visible as an
    OPEN ``taskunit.wait`` — job and kind named — while it waits, and as
    the longest closed one afterwards."""
    from harmony_tpu.runtime.taskunit import (
        GlobalTaskUnitScheduler,
        LocalTaskUnitScheduler,
        TaskUnitClient,
    )

    span_mod.reset_span_watch()
    sched = GlobalTaskUnitScheduler()
    sched.on_job_start("stuck-job", ["w0", "w1"])  # quorum of two: w1 never asks
    client = TaskUnitClient("stuck-job", "w0", sched, LocalTaskUnitScheduler())
    waited = []
    give_up = threading.Event()

    def worker():
        try:
            with client.scope("COMP", abort=give_up.is_set, poll=0.01,
                              wait_acc=waited.append):
                pass
        except Exception:
            pass

    t = threading.Thread(target=worker, name="stuck-worker")
    t.start()
    try:
        deadline = time.monotonic() + 5.0
        mine = []
        while not mine and time.monotonic() < deadline:
            time.sleep(0.02)
            mine = [r for r in flight.get_recorder().span_watch()["open"]
                    if r["description"] == "taskunit.wait"
                    and r["annotations"].get("job_id") == "stuck-job"]
        assert mine, "the sleeping wait was not reported as open"
        assert mine[0]["thread"] == "stuck-worker"
        assert mine[0]["annotations"]["kind"] == "CPU"
        assert mine[0]["open_sec"] > 0.0
    finally:
        give_up.set()
        t.join(5.0)
    assert not [r for r in open_spans()
                if r["annotations"].get("job_id") == "stuck-job"]
    longest = {r["description"]: r for r in
               flight.get_recorder().span_watch()["longest"]}
    assert longest["taskunit.wait"]["duration_sec"] >= 0.02
    assert waited and waited[0] >= 0.02  # the aborted wait still counted


def test_flight_dump_carries_the_span_watch(tmp_path):
    import json

    rec = flight.FlightRecorder(out_dir=str(tmp_path))
    with trace_span("held.open", job_id="j"):
        path = rec.dump("test")
    body = json.load(open(path))
    assert any(r["description"] == "held.open" for r in body["spans"]["open"])


# -- the phase budget --------------------------------------------------------

NEW = ("grant_wait", "probe", "bookkeeping")


def _residual(store, job):
    return store.snapshot(1e9)[job]["phases"]


def test_three_phases_are_carved_out_of_residual_only():
    """Replaying one feed with and without the three measured phases: the
    wall and every old phase are unchanged, and what the three name is
    exactly what ``residual`` lost."""
    assert set(NEW) <= set(PHASES)
    old_feed = {"input_wait": 0.01, "host_dispatch": 0.02, "pull_comm": 0.1,
                "compute": 0.5, "push_comm": 0.1}
    named = {"grant_wait": 0.11, "probe": 0.03, "bookkeeping": 0.02}
    before, after = PhaseBudgetStore(), PhaseBudgetStore()
    for e in range(8):
        before.observe_epoch("j", "j", "w0", e, 1.0, dict(old_feed))
        after.observe_epoch("j", "j", "w0", e, 1.0, {**old_feed, **named},
                            device_split="modelled")
    b, a = _residual(before, "j"), _residual(after, "j")
    for p in old_feed:
        assert a[p] == pytest.approx(b[p])
    assert sum(a[p] for p in NEW) + a[RESIDUAL] == pytest.approx(b[RESIDUAL])
    row = after.snapshot(1e9)["j"]
    assert sum(row["phases"].values()) == pytest.approx(row["wall_sec"])
    assert row["device_split"] == "modelled"
    assert sum(row["fractions"].values()) == pytest.approx(1.0, abs=1e-5)


def test_budget_store_keeps_the_feeds_device_split():
    store = PhaseBudgetStore()
    store.observe_epoch("j", "j", "w0", 0, 1.0, {"compute": 0.5},
                        device_split="measured")
    assert store.snapshot(1e9)["j"]["device_split"] == "measured"


def test_worker_feeds_the_three_phases_on_a_shared_chip(tmp_path):
    """Two tenants through the jobserver on the CPU mesh: each one's budget
    carries the three measured phases, the invariant holds, and the
    contended tenant's grant wait is not zero."""
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.metrics.phases import peek_budget, reset_budget

    reset_budget()
    server = JobServer(num_executors=1)
    server.start()
    try:
        futs = [server.submit(JobConfig(
            job_id=f"clock-t{i}", app_type="dolphin",
            trainer="harmony_tpu.apps.mlr:MLRTrainer",
            params=TrainerParams(
                num_epochs=16, num_mini_batches=4, comm_probe_period=1,
                app_params={"num_classes": 4, "num_features": 16,
                            "features_per_partition": 4}),
            num_workers=1,
            user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                  "data_args": {"n": 256, "num_features": 16,
                                "num_classes": 4, "seed": i}},
        )) for i in range(2)]
        for f in futs:
            f.result(timeout=300)
        status = server._status()
    finally:
        server.shutdown()
    rows = peek_budget().snapshot(1e9)
    for i in range(2):
        row = rows[f"clock-t{i}"]
        assert set(NEW) <= set(row["phases"])
        assert sum(row["phases"].values()) == pytest.approx(
            row["wall_sec"], rel=1e-3, abs=1e-4)
        assert row["phases"]["bookkeeping"] > 0.0
        assert row["phases"]["probe"] > 0.0
        assert row["device_split"] in ("modelled", "measured")
        stages = status["job_stages"][f"clock-t{i}"]
        assert {"grant_wait", "table_create", "data_load", "init",
                "build_step", "first_window"} <= set(stages)
    assert sum(rows[f"clock-t{i}"]["phases"]["grant_wait"]
               for i in range(2)) > 0.0
    assert {"open", "longest"} == set(status["flight_spans"])
    longest = {r["description"] for r in status["flight_spans"]["longest"]}
    assert {"taskunit.wait", "step.dispatch", "window.bookkeeping",
            "drain.d2h", "drain.stack"} <= longest


# -- compile counters --------------------------------------------------------

def test_compile_counters_carry_the_job_label():
    import jax
    import jax.numpy as jnp

    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.runtime import progcache

    with trace_span("dolphin.worker", job_id="compile-job"):
        with trace_span("drain.stack"):  # a child without its own job_id
            jax.jit(lambda x: (x * 3.0 + 1.0).sum())(jnp.ones((7, 5)))
    row = progcache.compiles_by_job()["compile-job"]
    assert row["compiles"] >= 1 and row["seconds"] > 0.0
    fams = parse_exposition(get_registry().expose())
    jobs = {l["job"] for _n, l, _v in
            fams["harmony_compiles_total"]["samples"]}
    assert "compile-job" in jobs
    stages = {l["stage"] for _n, l, _v in
              fams["harmony_compile_seconds_total"]["samples"]
              if l["job"] == "compile-job"}
    assert "backend_compile_duration" in stages
