"""JobServer multi-tenancy + TaskUnit scheduling tests.

Analogues of the reference's jobserver behavior: submit over the command
channel, run-everywhere scheduling, concurrent jobs interleaved by the
global TaskUnit order, graceful shutdown.
"""
import threading
import time

import numpy as np
import pytest

from harmony_tpu.config.params import JobConfig, TrainerParams
from harmony_tpu.jobserver import (
    FifoExclusiveScheduler,
    JobServer,
    ShareAllScheduler,
    submit_job,
)
from harmony_tpu.jobserver.client import CommandSender
from harmony_tpu.parallel import DevicePool
from harmony_tpu.runtime.taskunit import (
    CPU,
    NET,
    VOID,
    GlobalTaskUnitScheduler,
    LocalTaskUnitScheduler,
    TaskUnitClient,
    TaskUnitInfo,
)


def mlr_job(job_id="mlr", n=256, epochs=3, workers=1, slack=0):
    return JobConfig(
        job_id=job_id,
        app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=epochs,
            num_mini_batches=4,
            clock_slack=slack,
            app_params={
                "num_classes": 4,
                "num_features": 16,
                "features_per_partition": 4,
                "step_size": 0.5,
            },
        ),
        num_workers=workers,
        user={
            "data_fn": "harmony_tpu.apps.mlr:make_synthetic",
            "data_args": {"n": n, "num_features": 16, "num_classes": 4, "seed": 7},
        },
    )


def addvector_job(job_id="addv", n=128, epochs=2, workers=2, slack=1):
    return JobConfig(
        job_id=job_id,
        app_type="dolphin",
        trainer="harmony_tpu.apps.addvector:AddVectorTrainer",
        params=TrainerParams(
            num_epochs=epochs,
            num_mini_batches=4,
            clock_slack=slack,
            app_params={"num_keys": 8, "vector_dim": 2, "delta": 1.0},
        ),
        num_workers=workers,
        user={
            "data_fn": "harmony_tpu.apps.addvector:make_marks",
            "data_args": {"n": n},
        },
    )


class TestTaskUnits:
    def test_weighted_fair_grants_favor_cheap_job(self):
        """Under contention the scheduler meters ONE non-VOID unit at a
        time across jobs, and when several units wait, the lowest
        DEVICE-TIME deficit wins — measured unit seconds, not unit counts
        (count-pacing starved the cheapest of three tenants 15x)."""
        g = GlobalTaskUnitScheduler()
        g.on_job_start("cheap", ["c0"])
        g.on_job_start("dear", ["d0"])
        g.report_unit_cost("cheap", 0.01)
        g.report_unit_cost("dear", 0.10)
        # one grant each: deficits are now cheap=0.01, dear=0.10 — equal
        # unit COUNTS, very different device-time charges
        u_d0 = TaskUnitInfo("dear", "d0", CPU, 0)
        assert g.wait_ready(u_d0, timeout=5)
        g.on_unit_finished(u_d0)
        # occupy the meter with cheap's unit 0...
        u_c0 = TaskUnitInfo("cheap", "c0", CPU, 0)
        assert g.wait_ready(u_c0, timeout=5)
        granted = []

        def waiter(job, eid, seq):
            u = TaskUnitInfo(job, eid, CPU, seq)
            assert g.wait_ready(u, timeout=10)
            granted.append((job, u))

        # ...then queue dear FIRST (earlier arrival), cheap second
        td = threading.Thread(target=waiter, args=("dear", "d0", 1))
        td.start()
        time.sleep(0.1)
        tc = threading.Thread(target=waiter, args=("cheap", "c0", 1))
        tc.start()
        time.sleep(0.1)
        assert granted == []  # meter: nothing granted while u_c0 runs
        g.on_unit_finished(u_c0)
        tc.join(timeout=10)
        assert [j for j, _ in granted] == ["cheap"]  # deficit beats arrival
        assert td.is_alive()  # dear still metered out
        g.on_unit_finished(granted[0][1])
        td.join(timeout=10)
        assert [j for j, _ in granted] == ["cheap", "dear"]
        g.on_job_finish("cheap")
        g.on_job_finish("dear")

    def test_quorum_grant_and_global_order(self):
        g = GlobalTaskUnitScheduler()
        g.on_job_start("j", ["e0", "e1"])
        granted = []

        def worker(eid):
            g.wait_ready(TaskUnitInfo("j", eid, CPU, 0), timeout=5)
            granted.append(eid)

        t0 = threading.Thread(target=worker, args=("e0",))
        t0.start()
        time.sleep(0.1)
        assert granted == []  # quorum incomplete: e0 must wait for e1
        t1 = threading.Thread(target=worker, args=("e1",))
        t1.start()
        t0.join(timeout=5)
        t1.join(timeout=5)
        assert sorted(granted) == ["e0", "e1"]
        assert g.grant_order() == [("j", 0, CPU)]

    def test_unregistered_job_passes_through(self):
        g = GlobalTaskUnitScheduler()
        assert g.wait_ready(TaskUnitInfo("ghost", "e", CPU, 0), timeout=1)

    def test_local_slots_bound_concurrency(self):
        local = LocalTaskUnitScheduler(cpu_slots=1, net_slots=2)
        running = {"CPU": 0, "max": 0}
        lock = threading.Lock()

        def use(kind):
            local.acquire(kind)
            with lock:
                running["CPU"] += 1
                running["max"] = max(running["max"], running["CPU"])
            time.sleep(0.05)
            with lock:
                running["CPU"] -= 1
            local.release(kind)

        ts = [threading.Thread(target=use, args=(CPU,)) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert running["max"] == 1  # one CPU slot

    def test_client_scope_sequences(self):
        g = GlobalTaskUnitScheduler()
        local = LocalTaskUnitScheduler()
        g.on_job_start("j", ["e0"])
        c = TaskUnitClient("j", "e0", g, local)
        with c.scope(CPU):
            pass
        with c.scope(NET):
            pass
        assert [k for (_, _, k) in g.grant_order()] == [CPU, NET]


class TestJobServer:
    def test_single_job_end_to_end(self, devices):
        server = JobServer(4, device_pool=DevicePool(devices[:4]))
        server.start()
        fut = server.submit(mlr_job())
        result = fut.result(timeout=120)
        assert "mlr/w0" in result["workers"]
        losses = result["workers"]["mlr/w0"]["losses"]
        assert losses[-1] < losses[0]
        server.shutdown()
        assert server.state == "CLOSED"
        # job-owned table dropped at cleanup
        assert server.master.table_ids() == []

    def test_concurrent_multitenant_jobs(self, devices):
        """MLR + AddVector concurrently on the SAME executors (ShareAll),
        TaskUnit-scheduled; both finish correct."""
        server = JobServer(4, device_pool=DevicePool(devices[:4]))
        server.start()
        f1 = server.submit(mlr_job(workers=2, slack=1, epochs=2))
        f2 = server.submit(addvector_job(workers=2, slack=1))
        r1 = f1.result(timeout=180)
        r2 = f2.result(timeout=180)
        assert len(r1["workers"]) == 2 and len(r2["workers"]) == 2
        grants = server.global_taskunit.grant_order()
        jobs_in_order = {j for (j, _, _) in grants}
        assert jobs_in_order == {"mlr", "addv"}  # both flowed through one order
        server.shutdown()

    def test_addvector_exact_with_multitenancy(self, devices):
        """Exact final table contents, validated via the shared-table path:
        pre-creating the table under the explicit id means the job reuses it
        (not owns it), so it survives job cleanup for inspection."""
        from harmony_tpu.config.params import TableConfig

        server = JobServer(4, device_pool=DevicePool(devices[:4]))
        server.start()
        n, epochs, workers = 128, 2, 2
        shared_cfg = TableConfig(
            table_id="shared-addv", capacity=8, value_shape=(2,), num_blocks=8
        )
        server.master.create_table(shared_cfg, server.master.executor_ids())
        job = addvector_job(n=n, epochs=epochs, workers=workers)
        job = job.replace(tables=[shared_cfg])
        server.submit(job).result(timeout=120)
        vals = np.asarray(server.master.get_table("shared-addv").table.pull_array())
        np.testing.assert_allclose(vals, np.full((8, 2), n * epochs))
        server.shutdown()

    def test_two_same_app_jobs_do_not_share_model(self, devices):
        """Two concurrent MLR jobs with trainer-default table ids must get
        PRIVATE (job-namespaced) model tables."""
        server = JobServer(4, device_pool=DevicePool(devices[:4]))
        server.start()
        seen_tables = set()
        f1 = server.submit(mlr_job("dup-app-a", epochs=2))
        f2 = server.submit(mlr_job("dup-app-b", epochs=2))
        deadline = time.time() + 60
        while time.time() < deadline and (not f1.done() or not f2.done()):
            seen_tables.update(server.master.table_ids())
            time.sleep(0.01)
        f1.result(timeout=60)
        f2.result(timeout=60)
        assert "dup-app-a:mlr-model" in seen_tables
        assert "dup-app-b:mlr-model" in seen_tables
        server.shutdown()

    def test_worker_crash_does_not_deadlock_taskunits(self, devices):
        """w0 dies during init; w1 must finish (quorum shrinks) and the job
        future must resolve with the error instead of hanging."""
        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        job = addvector_job("crashy", workers=2)
        job = job.replace(trainer="tests.helpers:CrashOnW0Trainer")
        fut = server.submit(job)
        with pytest.raises(RuntimeError, match="synthetic failure"):
            fut.result(timeout=60)
        server.shutdown(timeout=60)
        assert server.state == "CLOSED"

    def test_resubmit_after_completion_allowed(self, devices):
        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        server.submit(mlr_job("again", epochs=1)).result(timeout=120)
        server.submit(mlr_job("again", epochs=1)).result(timeout=120)
        server.shutdown()

    def test_fifo_scheduler_serializes(self, devices):
        server = JobServer(
            4, scheduler=FifoExclusiveScheduler(), device_pool=DevicePool(devices[:4])
        )
        server.start()
        seen = []
        orig_launch = server._launch

        def tracking_launch(cfg, execs):
            seen.append((cfg.job_id, time.perf_counter()))
            orig_launch(cfg, execs)

        server._scheduler._launch = tracking_launch
        f1 = server.submit(mlr_job("fifo-a", epochs=2))
        f2 = server.submit(mlr_job("fifo-b", epochs=1))
        f1.result(timeout=120)
        f2.result(timeout=120)
        assert [s[0] for s in seen] == ["fifo-a", "fifo-b"]
        server.shutdown()

    def test_duplicate_job_id_rejected(self, devices):
        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        f = server.submit(mlr_job("dup", epochs=1))
        with pytest.raises(ValueError):
            server.submit(mlr_job("dup"))
        f.result(timeout=120)
        server.shutdown()


class TestTcpControlPlane:
    def test_submit_status_shutdown_over_tcp(self, devices):
        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        port = server.serve_tcp()
        sender = CommandSender(port)
        reply = submit_job(mlr_job("tcp-job", epochs=1), port)
        assert reply["job_id"] == "tcp-job"
        status = sender.send_status_command()
        assert status["ok"] and status["state"] == "INIT"
        # wait for the job then shut down over the wire
        deadline = time.time() + 120
        while server.running_jobs() and time.time() < deadline:
            time.sleep(0.1)
        assert sender.send_shutdown_command()["ok"]
        deadline = time.time() + 30
        while server.state != "CLOSED" and time.time() < deadline:
            time.sleep(0.05)
        assert server.state == "CLOSED"

    def test_bad_command_gets_error_reply(self, devices):
        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        port = server.serve_tcp()
        reply = CommandSender(port)._roundtrip({"command": "NOPE"})
        assert not reply["ok"] and "unknown command" in reply["error"]
        server.shutdown()


class TestFailureIsolation:
    def test_failed_job_does_not_poison_tenants(self, devices):
        """A job that dies fails ITS future; a concurrent healthy job and a
        subsequently submitted job both complete, and the server stays
        open for business (ref stance §5.3: fail fast per job — here
        per-job, not per-server)."""
        import pytest as _pytest

        from harmony_tpu.config.params import JobConfig, TrainerParams
        from harmony_tpu.jobserver.server import JobServer

        def cfg(job_id, trainer, data_fn):
            return JobConfig(
                job_id=job_id, app_type="dolphin", trainer=trainer,
                params=TrainerParams(num_epochs=2, num_mini_batches=2,
                                     app_params={"num_keys": 4}),
                num_workers=2,
                user={"data_fn": data_fn, "data_args": {"n": 64}},
            )

        server = JobServer(num_executors=4)
        server.start()
        try:
            bad = server.submit(cfg(
                "boom", "tests.helpers:ExplodingTrainer",
                "harmony_tpu.apps.addvector:make_marks"))
            good = server.submit(cfg(
                "good", "harmony_tpu.apps.addvector:AddIntegerTrainer",
                "harmony_tpu.apps.addvector:make_marks"))
            with _pytest.raises(RuntimeError, match="injected failure"):
                bad.result(timeout=120)
            result = good.result(timeout=120)
            assert len(result["workers"]) == 2
            # the server remains healthy: a post-failure submission succeeds
            late = server.submit(cfg(
                "late", "harmony_tpu.apps.addvector:AddIntegerTrainer",
                "harmony_tpu.apps.addvector:make_marks"))
            assert late.result(timeout=120)["workers"]
            assert server.state != "CLOSED"
        finally:
            server.shutdown(timeout=60)


class TestCarveScheduler:
    def test_disjoint_slices_and_queueing(self):
        """Protocol-level (fake launch): slices are disjoint, arrivals
        without min_slice free executors queue, finish re-launches."""
        from harmony_tpu.jobserver.scheduler import CarveScheduler

        launched = {}
        sched = CarveScheduler(min_slice=2)
        sched.bind([f"e{i}" for i in range(8)],
                   lambda cfg, exs: launched.__setitem__(cfg.job_id, exs))
        sched.on_job_arrival(mlr_job("a"))
        assert len(launched["a"]) == 8  # fair share at arrival = 8 // 1
        sched.on_job_arrival(mlr_job("b"))
        assert "b" not in launched  # pool exhausted -> queued
        sched.on_job_finish("a")
        assert len(launched["b"]) >= 2  # freed slice launches the queue
        assert set(launched["b"]) <= {f"e{i}" for i in range(8)}

    def test_fair_share_carving(self):
        from harmony_tpu.jobserver.scheduler import CarveScheduler

        launched = {}
        sched = CarveScheduler(min_slice=1)
        sched.bind([f"e{i}" for i in range(8)],
                   lambda cfg, exs: launched.__setitem__(cfg.job_id, exs))
        # Drive arrivals while slices shrink: 8//1=8 for the first job, so
        # use finish/arrive interleaving to observe carving at various loads
        sched.on_job_arrival(mlr_job("a"))
        sched.on_job_finish("a")
        sched.on_job_arrival(mlr_job("b"))  # 8 free again
        launched.clear()
        sched.on_job_arrival(mlr_job("c"))  # 0 free -> queue
        assert "c" not in launched
        sched.on_job_finish("b")            # frees 8, c gets 8//1=8
        assert len(launched["c"]) == 8
        assert sorted(sched.slice_of("c")) == sorted(launched["c"])

    def test_jobserver_integration_disjoint(self, devices):
        """Two concurrent jobs under carve scheduling run on disjoint
        executor slices and both complete with exact sums."""
        from harmony_tpu.jobserver.scheduler import CarveScheduler

        sched = CarveScheduler(min_slice=4, max_share=4)
        server = JobServer(8, scheduler=sched, device_pool=DevicePool(devices))
        server.start()
        try:
            fa = server.submit(addvector_job("carve-a", workers=1, slack=0))
            fb = server.submit(addvector_job("carve-b", workers=1, slack=0))
            slices = {}
            deadline = time.time() + 30
            while time.time() < deadline and (
                not sched.slice_of("carve-a") or not sched.slice_of("carve-b")
            ):
                time.sleep(0.05)
            slices["a"] = set(sched.slice_of("carve-a"))
            slices["b"] = set(sched.slice_of("carve-b"))
            ra, rb = fa.result(timeout=120), fb.result(timeout=120)
            assert slices["a"] and slices["b"] and not (slices["a"] & slices["b"])
        finally:
            server.shutdown(timeout=60)

    def test_max_share_allows_concurrency(self):
        from harmony_tpu.jobserver.scheduler import CarveScheduler

        launched = {}
        sched = CarveScheduler(min_slice=2, max_share=4)
        sched.bind([f"e{i}" for i in range(8)],
                   lambda cfg, exs: launched.__setitem__(cfg.job_id, exs))
        sched.on_job_arrival(mlr_job("a"))
        sched.on_job_arrival(mlr_job("b"))
        assert len(launched["a"]) == 4 and len(launched["b"]) == 4
        assert not set(launched["a"]) & set(launched["b"])

    def test_resource_change_reconciles_pool(self):
        from harmony_tpu.jobserver.scheduler import CarveScheduler

        launched = {}
        sched = CarveScheduler(min_slice=2, max_share=4)
        sched.bind([f"e{i}" for i in range(8)],
                   lambda cfg, exs: launched.__setitem__(cfg.job_id, exs))
        sched.on_job_arrival(mlr_job("a"))           # takes e0..e3
        # e4..e7 depart; e8..e9 arrive
        sched.on_resource_change(launched["a"] + ["e8", "e9"])
        sched.on_job_arrival(mlr_job("b"))
        assert set(launched["b"]) == {"e8", "e9"}    # never the departed ones
        sched.on_job_finish("a")                     # a's slice still known
        sched.on_job_arrival(mlr_job("c"))
        assert set(launched["c"]) <= set(launched["a"])


class TestDeferredModelEval:
    """Deferred model evaluation at graceful shutdown (ref: JobServerDriver
    shutdown runs deferred evaluation over the ModelChkpManager chain,
    JobServerDriver.java:178-214 + DolphinMaster.evaluate())."""

    def _job(self, tmp_path, epochs=3):
        cfg = mlr_job("eval-mlr", n=256, epochs=epochs, workers=1)
        cfg.params.model_chkp_period = 1
        cfg.params.offline_model_eval = True
        return cfg

    def test_chain_and_eval_at_shutdown(self, devices, tmp_path):
        server = JobServer(2, device_pool=DevicePool(devices[:2]),
                           chkp_root=str(tmp_path))
        server.start()
        cfg = self._job(tmp_path, epochs=3)
        res = server.submit(cfg).result(timeout=300)
        assert len(res["model_chkp_ids"]) == 3  # one snapshot per epoch
        assert "eval-mlr" not in server.eval_results  # deferred, not yet run
        server.shutdown(timeout=300)
        evals = server.eval_results["eval-mlr"]
        assert isinstance(evals, list) and len(evals) == 3
        # training progress is visible across the replayed chain: the last
        # snapshot must beat the first on training-set loss
        assert evals[-1]["loss"] < evals[0]["loss"]
        assert all(np.isfinite(m["loss"]) for m in evals)
        # replay consumes the chain: the disk is reclaimed
        import os

        root = os.path.join(str(tmp_path), "eval-mlr")
        leftovers = [
            d for sub in ("temp", "commit")
            for d in os.listdir(os.path.join(root, sub))
            if os.path.isdir(os.path.join(root, sub, d))
        ]
        assert leftovers == []

    def test_no_chain_without_period(self, devices, tmp_path):
        server = JobServer(2, device_pool=DevicePool(devices[:2]),
                           chkp_root=str(tmp_path))
        server.start()
        res = server.submit(mlr_job("plain", n=128, epochs=1, workers=1)).result(
            timeout=300
        )
        assert "model_chkp_ids" not in res
        server.shutdown(timeout=300)
        assert server.eval_results == {}

    def test_eval_failure_recorded_not_raised(self, devices, tmp_path):
        server = JobServer(2, device_pool=DevicePool(devices[:2]),
                           chkp_root=str(tmp_path))
        server.start()
        cfg = self._job(tmp_path, epochs=1)
        # break the deferred eval's data source AFTER training uses it: the
        # test_data_fn path resolves lazily inside the closure
        cfg.user["test_data_fn"] = "harmony_tpu.apps.mlr:no_such_fn"
        server.submit(cfg).result(timeout=300)
        server.shutdown(timeout=300)
        assert "error" in server.eval_results["eval-mlr"]


class TestSharedTableLifetime:
    def test_creator_finishing_first_does_not_kill_tenant(self, devices):
        """Two jobs share one model table by id; the CREATOR finishes long
        before the tenant. Storage must survive until the LAST user releases
        (master refcount) — previously the creator's cleanup deleted the
        buffers under the still-training tenant."""
        from harmony_tpu.config.params import TableConfig

        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        shared = TableConfig(table_id="life-m", capacity=16,
                             value_shape=(4,), num_blocks=8)

        def job(jid, epochs):
            cfg = mlr_job(jid, n=64, epochs=epochs, workers=1)
            cfg.tables = [shared]
            return cfg

        fa = server.submit(job("life-a", epochs=1))   # creator: done fast
        fa.result(timeout=300)
        # creator already finished and released; tenant must still be able
        # to ATTACH (refcount went 1 -> 0 would have dropped it... the
        # sequential case recreates; the concurrent case is the real race)
        fb = server.submit(job("life-b", epochs=3))
        fc = server.submit(job("life-c", epochs=6))   # overlapping tenants
        rb, rc = fb.result(timeout=300), fc.result(timeout=300)
        server.shutdown(timeout=60)
        for r in (rb, rc):
            losses = next(iter(r["workers"].values()))["losses"]
            assert np.isfinite(losses).all()
        # fully released at the end: a later server could recreate the id
        assert "life-m" not in server.master.table_ids()


class TestJobLogger:
    def test_per_job_prefixed_log_lines(self, devices, caplog):
        """Operator-facing lifecycle logging carries a [JobId: x] prefix on
        every job-scoped line (ref: jobserver/JobLogger.java:34-75), so a
        multi-tenant server's interleaved log stays attributable."""
        import logging

        with caplog.at_level(logging.INFO, logger="harmony_tpu.jobserver"):
            server = JobServer(1, device_pool=DevicePool(devices[:1]))
            server.start()
            cfg = addvector_job("logged", n=32, epochs=1, workers=1, slack=0)
            server.submit(cfg).result(timeout=300)
            server.shutdown(timeout=60)
        msgs = [r.getMessage() for r in caplog.records]
        for want in ("submitted", "dispatched", "training", "finished"):
            assert any(m.startswith(f"[JobId: logged] {want}") for m in msgs), (
                want, msgs)
        assert any(m.startswith("jobserver up") for m in msgs)
        assert any(m.startswith("shutdown initiated") for m in msgs)


class TestPodFastFail:
    def test_broken_pod_fails_dispatch_fast(self, devices):
        """Once the pod is poisoned (partial broadcast / wedged follower),
        later dispatches must fail in milliseconds with a restart
        instruction — not hang in collectives that can never complete."""
        from harmony_tpu.jobserver.pod import PodJobServer

        server = PodJobServer(1, device_pool=DevicePool(devices[:1]),
                              num_followers=1)
        server.start()

        class _FakeConn:
            def close(self):
                pass

        server._followers[1] = (_FakeConn(), None)
        server._pod_broken = "simulated wedged follower"
        fut = server.submit(addvector_job("podfail", n=32, epochs=1,
                                          workers=1, slack=0))
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="pod is broken"):
            fut.result(timeout=60)
        assert time.monotonic() - t0 < 5.0
        server._followers.clear()
        server.shutdown(timeout=30)

    def test_lockstep_multiworker_exact_sums(self, devices):
        """The DispatchTurnstile schedule (what makes multi-worker SSP
        legal on a multi-process pod — the old submit-time rejection is
        gone) preserves push exactness: an AddVector job with two workers
        under force_lockstep lands every push exactly once, and twice in a
        row produces the same deterministic grant order. (The pod e2e leg
        lives in test_multihost.py; this is the in-process half.)"""
        from harmony_tpu.config.params import TableConfig

        server = JobServer(4, device_pool=DevicePool(devices[:4]))
        server.start()
        n, epochs = 64, 2
        shared_cfg = TableConfig(
            table_id="lockstep-addv", capacity=8, value_shape=(2,),
            num_blocks=8, update_fn="add",
        )
        server.master.create_table(shared_cfg, server.master.executor_ids())
        cfg = addvector_job("lockstep", n=n, epochs=epochs, workers=2,
                            slack=1).replace(tables=[shared_cfg])
        cfg.user["force_lockstep"] = True
        res = server.submit(cfg).result(timeout=120)
        assert set(res["workers"]) == {"lockstep/w0", "lockstep/w1"}
        vals = np.asarray(
            server.master.get_table("lockstep-addv").table.pull_array()
        )
        # both workers' pushes all landed, exactly once each
        np.testing.assert_allclose(vals, np.full((8, 2), n * epochs))
        server.shutdown(timeout=30)


class TestPodFollower:
    def test_follower_protocol_and_error_reporting(self, devices):
        """Drive a PodFollower with a scripted leader socket: JOIN arrives,
        a RUN_JOB naming executors the follower does not have yields a
        JOB_DONE error report (never a crash or a hang), and SHUTDOWN ends
        the loop."""
        import json as _json
        import socket as _socket
        import threading as _threading

        from harmony_tpu.jobserver.pod import PodFollower

        lsock = _socket.socket()
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(1)
        port = lsock.getsockname()[1]
        box = {}

        def leader():
            conn, _ = lsock.accept()
            f = conn.makefile("r")
            box["join"] = _json.loads(f.readline())
            cfg = mlr_job("pod-missing", n=64, epochs=1, workers=1)
            conn.sendall((_json.dumps({
                "cmd": "RUN_JOB", "conf": cfg.to_dict(),
                "executor_ids": ["executor-does-not-exist"],
            }) + "\n").encode())
            box["done"] = _json.loads(f.readline())
            conn.sendall(b'{"cmd": "SHUTDOWN"}\n')
            conn.close()

        t = _threading.Thread(target=leader, daemon=True)
        t.start()
        follower = PodFollower("127.0.0.1", port, pid=3, num_executors=1)
        follower.run()  # returns on SHUTDOWN
        t.join(timeout=30)
        assert box["join"] == {"cmd": "JOIN", "pid": 3}
        done = box["done"]
        assert done["cmd"] == "JOB_DONE" and done["pid"] == 3
        assert not done["ok"]
        assert "missing executors" in done["error"]


class TestJobOptimizerLoop:
    def test_job_reconfigures_itself_mid_training(self, devices):
        """JobConfig.optimizer wires the per-job elasticity loop (the
        reference's ETOptimizationOrchestrator run by the driver): a canned
        add-one-server optimizer forces a live migration WHILE the job
        trains under the JobServer; training stays correct and the result
        reports the reconfiguration."""
        server = JobServer(2, device_pool=DevicePool(devices[:4]))
        server.start()
        cfg = addvector_job("opt-addv", n=128, epochs=6, workers=1, slack=0)
        cfg.optimizer = "add_one_server"
        cfg.optimizer_period = 0.2
        result = server.submit(cfg).result(timeout=300)
        assert result.get("reconfigs", 0) >= 1, result
        server.shutdown(timeout=60)

    def test_homogeneous_optimizer_runs_quietly(self, devices):
        """The real cost-model optimizer (not a canned plan) runs on live
        metrics without breaking training; with a tiny balanced job it may
        or may not reconfigure, but the job must stay correct."""
        server = JobServer(2, device_pool=DevicePool(devices[:2]))
        server.start()
        cfg = mlr_job("opt-mlr", n=256, epochs=4, workers=1)
        cfg.optimizer = "homogeneous"
        cfg.optimizer_period = 0.2
        result = server.submit(cfg).result(timeout=300)
        losses = result["workers"]["opt-mlr/w0"]["losses"]
        assert losses[-1] < losses[0]
        server.shutdown(timeout=60)

    def test_lease_released_when_orchestrator_construction_fails(self, devices):
        """If optimizer resolution/construction raises AFTER the exclusive
        lease is acquired, the lease must be released — otherwise every
        resubmission of the job silently trains unoptimized."""
        from harmony_tpu.config.params import TableConfig
        from harmony_tpu.jobserver.entity import DolphinJobEntity
        from harmony_tpu.runtime.master import ETMaster

        master = ETMaster(DevicePool(devices[:1]))
        execs = master.add_executors(1)
        handle = master.create_table(
            TableConfig(table_id="leak", capacity=8, value_shape=(2,),
                        num_blocks=2),
            [execs[0].id],
        )
        cfg = JobConfig(job_id="leak-job", app_type="dolphin",
                        trainer="harmony_tpu.apps.mlr:MLRTrainer",
                        params=TrainerParams(),
                        optimizer="no.such.module:Opt")
        ent = DolphinJobEntity(cfg, metric_manager=object())
        ent._master = master
        ent._handle = handle
        with pytest.raises(ModuleNotFoundError):
            ent._make_orchestrator()
        assert master.acquire_optimizer_lease(handle.table_id)
        master.release_optimizer_lease(handle.table_id)

    def test_one_jobs_reconfig_does_not_erase_tenant_metrics(self, devices):
        """Job A's optimizer migrates A's table mid-run; job B's metrics
        (and its exact ServerMetrics accounting) must survive untouched —
        reconfiguration cleanup is scoped to the reconfiguring job."""
        server = JobServer(2, device_pool=DevicePool(devices[:4]))
        server.start()
        a = addvector_job("iso-a", n=128, epochs=6, workers=1, slack=0)
        a.optimizer = "add_one_server"
        a.optimizer_period = 0.1
        b = mlr_job("iso-b", n=256, epochs=4, workers=1)
        ra = server.submit(a)
        rb = server.submit(b)
        res_a, res_b = ra.result(timeout=300), rb.result(timeout=300)
        assert res_a.get("reconfigs", 0) >= 1, res_a
        assert "optimizer_errors" not in res_a, res_a
        # B's per-job accounting stayed exact despite A's migrations
        b_pulls = sum(m.pull_count for m in server.metrics.server_metrics(job_id="iso-b"))
        assert b_pulls == 4 * 4  # 4 epochs x 4 batches
        # and B's batch series survived the reconfig window
        assert server.metrics.worker_batch_metrics(job_id="iso-b")
        server.shutdown(timeout=60)
