"""JobServer — the long-running multi-tenant master.

Parity with the reference's jobserver (SURVEY.md §2.5):

  * lifecycle state machine NOT_INIT -> INIT -> CLOSED
    (ref: JobServerDriver.java:56-305),
  * ResourcePool: acquire N homogeneous executors from the ETMaster once at
    startup; all jobs share them (ref: ResourcePool.java:39-106),
  * submit handling: deserialize the job config, build the JobEntity, hand
    to the pluggable JobScheduler (ref: submit handling
    JobServerDriver.java:239-257),
  * JobDispatcher: per job — setup tables -> register -> TaskUnit
    on_job_start -> run -> drop tables -> deregister -> scheduler
    on_job_finish (ref: JobDispatcher.java:55-87),
  * graceful shutdown waits for running jobs (ref: shutdown 178-214),
  * a TCP command endpoint on localhost accepting SUBMIT/SHUTDOWN
    (ref: CommandSender/Listener socket protocol, client/CommandSender.java:
    49-80) — see client.py for the wire format.
"""
from __future__ import annotations

import json
import queue as _queue
import socket
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Any, Dict, List, Optional

from harmony_tpu.config.base import ConfigBase
from harmony_tpu.config.params import JobConfig
from harmony_tpu.jobserver.entity import JobEntity, build_entity
from harmony_tpu.jobserver.joblog import job_logger, server_log
from harmony_tpu.jobserver.overload import OverloadMonitor
from harmony_tpu.jobserver.scheduler import JobScheduler, ShareAllScheduler, make_scheduler
from harmony_tpu.metrics.doctor import Doctor, set_doctor
from harmony_tpu.metrics.history import HistoryScraper, HistoryStore, extra_targets
from harmony_tpu.metrics.manager import MetricManager
from harmony_tpu.parallel.mesh import DevicePool
from harmony_tpu.runtime.master import ETMaster
from harmony_tpu.runtime.taskunit import GlobalTaskUnitScheduler, LocalTaskUnitScheduler
from harmony_tpu.tracing.span import (
    SpanContext,
    current_span,
    get_tracing,
    job_stage_adder,
    job_stage_seconds,
    record_span,
    trace_span,
    wire_context,
)
from harmony_tpu.utils.statemachine import StateMachine


class NotLeader(RuntimeError):
    """Raised by submit() when the durable submission record was refused
    because this leader's lease lapsed mid-command (deposed between the
    TCP gate check and the append). The command plane converts it into
    the NOT_LEADER reply so the client retries on the successor — an
    acknowledged submission is ALWAYS in the replicated log."""


class JobResult:
    def __init__(self) -> None:
        self.future: "Future[Dict[str, Any]]" = Future()
        #: when the submission was registered (``time.monotonic_ns``):
        #: the start of ``job.grant_wait``
        self.submitted_ns = time.monotonic_ns()


def _json_sanitize(obj: Any) -> Any:
    """Best-effort JSON projection of a job result for the wire: plain
    scalars/containers pass through, numpy scalars coerce, anything else
    (device arrays, closures) becomes its repr — the WAIT/chief-report
    paths must never fail on an exotic result value."""
    if isinstance(obj, dict):
        return {str(k): _json_sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_sanitize(v) for v in obj]
    if isinstance(obj, (bool, int, float, str)) or obj is None:
        return obj
    try:
        import numpy as np

        if isinstance(obj, np.generic):
            return obj.item()
    except Exception:
        pass
    return repr(obj)


class JobServer:
    def __init__(
        self,
        num_executors: int,
        scheduler: Optional[JobScheduler | str] = None,
        device_pool: Optional[DevicePool] = None,
        cpu_slots: int = 1,
        net_slots: int = 2,
        chkp_root: Optional[str] = None,
        dashboard_url: Optional[str] = None,
    ) -> None:
        if isinstance(scheduler, str):
            scheduler = make_scheduler(scheduler)  # the -scheduler flag analogue
        self._state = StateMachine(
            states=["NOT_INIT", "INIT", "CLOSING", "CLOSED"],
            transitions=[
                ("NOT_INIT", "INIT"),
                ("INIT", "CLOSING"),
                ("CLOSING", "CLOSED"),
            ],
            initial="NOT_INIT",
        )
        self.master = ETMaster(device_pool)
        self.metrics = MetricManager()
        self.metrics.start_collection()
        # Live metrics to a dashboard (ref: DolphinDriver POSTing to the
        # Flask dashboard via DashboardConnector.java:30-100): every job
        # metric tees to the async connector, which drops rather than
        # blocks when the dashboard is slow or down.
        self._dashboard = None
        self._span_receiver = None
        if dashboard_url:
            from harmony_tpu.dashboard.connector import (
                DashboardConnector,
                DashboardSpanReceiver,
            )

            self._dashboard = DashboardConnector(dashboard_url)
            # finished spans tee to the dashboard's span store (async,
            # drop-don't-block like every other dashboard post) so its
            # per-job trace/timeline view renders real control-plane
            # traces, not only metric rows
            self._span_receiver = get_tracing().add_receiver(
                DashboardSpanReceiver(self._dashboard)
            )
        # the crash-correlated flight recorder starts capturing spans the
        # moment a server exists in this process (tracing/flight.py)
        from harmony_tpu.tracing import flight as _flight

        _flight.get_recorder()
        # per-process Prometheus endpoint (HARMONY_METRICS_PORT; None
        # when the knob is unset — tests and one-shots pay nothing)
        from harmony_tpu.metrics.exporter import exporter_from_env

        self.metrics_exporter = exporter_from_env()
        self.global_taskunit = GlobalTaskUnitScheduler()
        self.local_taskunit = LocalTaskUnitScheduler(cpu_slots, net_slots)
        self._scheduler = scheduler or ShareAllScheduler()
        self._num_executors = num_executors
        self._chkp_root = chkp_root
        self._jobs: Dict[str, JobResult] = {}
        self._entities: Dict[str, JobEntity] = {}
        # Deferred model evaluations, run during graceful shutdown (ref:
        # JobServerDriver.java:178-214). job_id -> closure(master).
        self._deferred_evals: Dict[str, Any] = {}
        self.eval_results: Dict[str, Any] = {}
        self._dispatch_threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        self._tcp_thread: Optional[threading.Thread] = None
        self._tcp_sock: Optional[socket.socket] = None
        self.port: Optional[int] = None
        # Bounded command plane (jobserver/overload.py): a fixed worker
        # pool drains a bounded accept queue; the monitor watches queue
        # lag + telemetry-cycle overrun and steps the degradation
        # ladder. Built unconditionally — admission questions are asked
        # even when serve_tcp never runs (direct submit() callers).
        self.overload = OverloadMonitor()
        self._cmd_queue: Optional["_queue.Queue"] = None
        self._cmd_workers: List[threading.Thread] = []
        self._cmd_queue_cap = 0
        # Embedded input-data service (harmony_tpu/inputsvc): started on
        # demand when the first opted-in job arrives — scheduled and
        # owned by the jobserver like any other tenant resource, scaled
        # by the ledger-fed autoscaler, surfaced via STATUS.
        self.input_service = None
        self._input_autoscaler = None
        # Embedded serving plane (harmony_tpu/serving): started on
        # demand by the first SERVING command — request-scale reads of
        # live training state, micro-batched onto the sparse gather and
        # admission-controlled by the same overload ladder as commands.
        self.serving = None
        # Telemetry history + root-cause doctor (metrics/history.py +
        # metrics/doctor.py): a jobserver-side scraper polls every known
        # process's /metrics (the leader's own registry in-process, pod
        # followers via their heartbeat-advertised exporter ports) and
        # the tenant ledger into a bounded time-series store; the doctor
        # evaluates its rule catalog after every poll. Diagnoses land as
        # kind="diagnosis" joblog events, ride STATUS, and tee to the
        # dashboard when one is configured.
        self.history = HistoryStore()
        self.doctor = Doctor(
            self.history,
            stragglers_fn=self.metrics.straggler_report,
            sinks=(self._post_diagnosis,),
        )
        set_doctor(self.doctor)
        self._history_scraper = HistoryScraper(
            self.history,
            targets_fn=self._scrape_targets,
            ledger_fn=self.metrics.tenant_ledger,
            on_cycle=self._on_scrape_cycle,
        )
        # Device policy engine (jobserver/policy.py): each window it
        # reads the ledger + diagnoses + critpath verdicts and replans
        # placement through the elastic fences — grow under-SLO tenants
        # onto idle executors, shrink/pack/preempt low-priority tenants
        # under contention. HARMONY_POLICY selects off/advise/act; the
        # plain server has no elastic actuator, so it advises; the pod
        # server overrides the tenants/fence hooks with real ones.
        from harmony_tpu.jobserver.policy import PolicyEngine

        self.policy = PolicyEngine(
            scheduler=self._scheduler,
            ledger_fn=self.metrics.tenant_ledger,
            tenants_fn=self._policy_tenants,
            fence_fn=self._policy_fence,
            diagnoses_fn=self.doctor.recent,
            leader_ok_fn=self._ha_leader_ok,
            sinks=(self._post_policy,),
        )
        # Incident correlation (metrics/incidents.py): folds the joblog
        # stream + flight-ring fault evidence into open→mitigating→
        # resolved incidents with causal chains and MTTD/MTTR. Runs on
        # the same scrape cycle as the doctor/policy; incidents persist
        # as kind="incident" joblog events so the HA tee makes them
        # survive a leader takeover (ha.py adopts the replayed set).
        from harmony_tpu.metrics.incidents import IncidentEngine, \
            set_incidents

        self.incidents = IncidentEngine(sinks=(self._post_incident,))
        set_incidents(self.incidents)
        # Control-plane HA (jobserver/ha.py): wired by enable_ha when
        # this server is one replica of an HA control plane. leader_epoch
        # stamps every durable log entry and pod RUN_JOB/PLAN message so
        # a deposed leader's late writes are fenced everywhere.
        self.ha_log = None
        self.ha_lease = None
        self.ha_replicator = None
        self.ha_replica_id: Optional[str] = None
        self.leader_epoch = 0
        self._ha_sink = None

    # -- control-plane HA ------------------------------------------------

    def enable_ha(self, log, lease=None, replicator=None,
                  replica_id: Optional[str] = None) -> None:
        """Wire the durable replicated job log (+ lease + replicator)
        into this server: every structured joblog event tees into the
        log, submissions/completions get first-class durable entries,
        and the leader epoch fences RUN_JOB/PLAN broadcasts. Call
        BEFORE start(); jobserver/ha.py's takeover does."""
        from harmony_tpu.jobserver import joblog

        def sink(job_id: str, ev: Dict[str, Any]) -> None:
            self._ha_append(ev.get("kind", "event"), job_id=job_id,
                            **{k: v for k, v in ev.items()
                               if k not in ("kind", "ts")})

        with self._lock:
            self.ha_log = log
            self.ha_lease = lease
            self.ha_replicator = replicator
            self.ha_replica_id = replica_id
            self.leader_epoch = (lease.epoch if lease is not None
                                 else log.fence_epoch)
            self._ha_sink = sink
        log.set_epoch(self.leader_epoch)
        joblog.add_sink(sink)
        if replicator is not None:
            replicator.start()

    def _ha_leader_ok(self) -> bool:
        """False once a held lease has lapsed — the deposed state in
        which every mutating command answers NOT_LEADER and durable
        appends are refused (split-brain fencing, local half)."""
        return self.ha_lease is None or self.ha_lease.is_valid()

    def _not_leader_reply(self) -> Dict[str, Any]:
        """The structured NOT_LEADER redirect, with the current lease
        holder's advertised address when the lease store knows one."""
        hint = None
        if self.ha_lease is not None:
            import os as _os

            from harmony_tpu.jobserver.lease import leader_hint

            hint = leader_hint(
                _os.path.dirname(self.ha_lease.path),
                own_holder_id=self.ha_lease.holder_id)
        return {"ok": False, "not_leader": True,
                "error": "NOT_LEADER: this replica's lease "
                         "lapsed (deposed)",
                "leader": hint}

    #: entry-envelope keys DurableJobLog.append owns; event fields that
    #: collide (elastic fences carry their own ``epoch``, diagnoses a
    #: ``job``) are namespaced ``ev_*`` so the tee can never clash with
    #: the envelope — or silently corrupt seq/epoch fencing
    _HA_RESERVED = ("seq", "epoch", "ts", "kind", "job")

    def _ha_append(self, kind: str, job_id: Optional[str] = None,
                   **fields: Any) -> bool:
        """Guarded durable append: never fails the serving path, drops
        (loudly) once this leader is deposed. Returns False when the
        entry did NOT land durably — the deposed drop, or an append
        error (ENOSPC/EIO on the log disk). A caller whose ack DEPENDS
        on the entry (submit()'s submission record) must refuse on
        False; the telemetry tees ignore it (best-effort as before).
        The chaos sweep's halog-ENOSPC schedule caught the old
        swallow-and-ack shape handing out acks no successor could ever
        replay."""
        if self.ha_log is None:
            return True
        if not self._ha_leader_ok():
            server_log.warning(
                "halog append %r dropped: this leader's lease lapsed "
                "(deposed)", kind)
            return False
        try:
            fields = {(f"ev_{k}" if k in self._HA_RESERVED else k): v
                      for k, v in fields.items()}
            self.ha_log.append(kind, job_id=job_id,
                               epoch=self.leader_epoch, **fields)
        except Exception as e:  # noqa: BLE001 - durability is surfaced,
            server_log.error("halog append %r failed: %s: %s",
                             kind, type(e).__name__, e)
            return False
        return True

    def _ha_record_done(self, job_id: str, fut: "Future") -> None:
        exc = fut.exception()
        if exc is None:
            self._ha_append("job_done", job_id=job_id, ok=True)
        else:
            self._ha_append(
                "job_done", job_id=job_id, ok=False,
                error=f"{type(exc).__name__}: {exc}"[:300])

    def _ha_status(self) -> Dict[str, Any]:
        from harmony_tpu.jobserver import joblog

        if self.ha_log is None:
            return {"enabled": False}
        takeovers = [ev for ev in joblog.job_events("__ha__", limit=8)
                     if ev.get("kind") == "leader_takeover"]
        return {
            "enabled": True,
            "role": ("leader" if self._ha_leader_ok() else "deposed"),
            "replica": self.ha_replica_id,
            "leader_epoch": self.leader_epoch,
            "lease": (self.ha_lease.stats()
                      if self.ha_lease is not None else None),
            "log": self.ha_log.stats(),
            "replication": (self.ha_replicator.stats()
                            if self.ha_replicator is not None else None),
            "takeovers": takeovers,
        }

    def _on_metric(self, record) -> None:
        """Every job metric lands in the manager AND (when configured)
        tees to the dashboard connector — the manager is authoritative
        (optimizer/queries); the dashboard is best-effort observability."""
        self.metrics.on_metric(record)
        if self._dashboard is not None:
            self._dashboard.metric_sink(record)
            self._maybe_post_tenants(record)

    #: minimum seconds between tenant-ledger posts to the dashboard —
    #: epoch reports can land at hundreds/sec across tenants, and the
    #: ledger snapshot is a (cheap but nonzero) whole-store walk
    _TENANT_POST_PERIOD = 2.0
    _last_tenant_post = 0.0

    def _maybe_post_tenants(self, record) -> None:
        """Rate-limited tee of the per-tenant cost vectors to the
        dashboard (kind="tenant", one row per job): epoch boundaries are
        the natural cadence — that is when the ledger's numbers move."""
        import time as _time

        from harmony_tpu.metrics.collector import EpochMetrics

        if not isinstance(record, EpochMetrics):
            return
        now = _time.monotonic()
        # under overload the dashboard tee rate-limits HARDER (the
        # ladder's cheapest fidelity shed — it was best-effort anyway)
        period = self._TENANT_POST_PERIOD * self.overload.dashboard_factor()
        if now - self._last_tenant_post < period:
            if now - self._last_tenant_post >= self._TENANT_POST_PERIOD:
                self.overload.count_shed("dashboard_skip")
            return
        self._last_tenant_post = now
        try:
            for jid, row in self.metrics.tenant_ledger().items():
                self._dashboard.post(jid, "tenant", row)
        except Exception:
            pass  # dashboard posts are best-effort by contract

    # -- lifecycle -------------------------------------------------------

    def start(self) -> None:
        """Acquire the executor pool; become ready for submissions."""
        executors = self.master.add_executors(self._num_executors)
        # execution metering is a blocking-backend concept (see
        # GlobalTaskUnitScheduler.meter_execution)
        self.global_taskunit.meter_execution = all(
            e.device.platform == "cpu" for e in executors
        )
        self._scheduler.bind([e.id for e in executors], self._launch)
        self._history_scraper.start()
        self._state.transition("INIT")
        server_log.info("jobserver up: %d executors, scheduler=%s",
                        len(executors), type(self._scheduler).__name__)

    def shutdown(self, timeout: Optional[float] = 300.0) -> None:
        """Graceful: stop accepting, drain running jobs, close (ref:
        shutdown waits for jobs then runs deferred work,
        JobServerDriver.java:178-214).

        The accept-gate flips FIRST (INIT -> CLOSING, under the registry
        lock so no mid-submit job can slip past it) — then the drain loop
        re-snapshots until no job is left. ``timeout`` bounds the WHOLE
        drain: a wedged job cannot hold shutdown hostage; the server closes
        and the stragglers stay visible through their futures."""
        with self._lock:
            initiated = self._state.compare_and_transition("INIT", "CLOSING")
        if initiated:
            server_log.info("shutdown initiated; draining %d running job(s)",
                            len(self.running_jobs()))
        if not initiated:
            self._state.wait_for("CLOSED", timeout=timeout)
            return
        self._stop_tcp()
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                pending = [r for r in self._jobs.values() if not r.future.done()]
            if not pending:
                break
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                break  # timed out: close anyway, leave stragglers observable
            try:
                pending[0].future.result(timeout=remaining)
            except Exception:
                pass  # failures/timeouts are visible via the futures
        # Join the dispatch threads themselves (not just their futures): a
        # thread still unwinding its finally-block at interpreter exit gets
        # killed mid-C++-teardown and aborts the process. Joins share the
        # same deadline (+ a small grace period when already past it).
        with self._lock:
            threads = list(self._dispatch_threads)
        grace = time.monotonic() + 5.0
        drained = True
        for t in threads:
            limit = grace if deadline is None else max(deadline, grace)
            t.join(timeout=max(0.0, limit - time.monotonic()))
            if t.is_alive():
                drained = False  # straggler still owns its executors
        self._run_deferred_evals(timeout, drained)
        try:
            self._on_closing(timeout)
        finally:
            if self._span_receiver is not None:
                get_tracing().remove_receiver(self._span_receiver)
                self._span_receiver = None
            if self._dashboard is not None:
                self._dashboard.close()  # flush the async queue, then stop
            self._history_scraper.stop()
            from harmony_tpu.metrics.doctor import peek_doctor

            if peek_doctor() is self.doctor:
                set_doctor(None)
            from harmony_tpu.metrics.incidents import peek_incidents, \
                set_incidents as _set_incidents

            if peek_incidents() is self.incidents:
                _set_incidents(None)
            if self.metrics_exporter is not None:
                self.metrics_exporter.stop()
                self.metrics_exporter = None
            self._stop_input_service()
            self._stop_serving()
            self._stop_ha()
            self._state.transition("CLOSED")

    def _stop_ha(self) -> None:
        """HA teardown on graceful shutdown: unhook the joblog tee,
        stop the replication stream, release the lease (so a standby
        takes over immediately instead of waiting out the window), and
        close the log."""
        from harmony_tpu.jobserver import joblog

        with self._lock:
            sink, self._ha_sink = self._ha_sink, None
            replicator, self.ha_replicator = self.ha_replicator, None
            lease, self.ha_lease = self.ha_lease, None
            log, self.ha_log = self.ha_log, None
        if sink is not None:
            joblog.remove_sink(sink)
        if replicator is not None:
            replicator.stop()
        if lease is not None:
            lease.release()
        if log is not None:
            log.close()

    def _on_closing(self, timeout: Optional[float]) -> None:
        """Subclass hook running after the drain + deferred evals but
        BEFORE the CLOSED transition (pod teardown must finish while
        observers still see CLOSING — anything keyed on CLOSED, like the
        worker process exit, may run the instant the state flips)."""

    def _run_deferred_evals(self, timeout: Optional[float], drained: bool) -> None:
        """The deferred-work stage of graceful shutdown (ref:
        JobServerDriver.java:178-214: after the job drain, run the model
        evaluations the Dolphin masters deferred). Failures are recorded per
        job, never raised — shutdown must complete. The stage gets its own
        ``timeout`` budget (shutdown is thus bounded by ~2x timeout): each
        eval runs on a daemon thread and a slow one is abandoned with a
        recorded error, so user eval code cannot hold shutdown hostage.
        If the job drain itself timed out, evals are SKIPPED — stragglers
        still occupy the executors the eval would restore tables onto."""
        with self._lock:
            evals = dict(self._deferred_evals)
            self._deferred_evals.clear()
        if not evals:
            return
        stage_deadline = None if timeout is None else time.monotonic() + timeout
        abandoned = False
        for job_id, fn in evals.items():
            if not drained:
                self.eval_results[job_id] = {
                    "error": "skipped: job drain timed out"
                }
                continue
            if abandoned:
                # an abandoned (timed-out) eval thread may still be
                # running; evals can be multi-process COLLECTIVES, and a
                # second one interleaving with it enqueues programs in
                # orders the followers (strictly sequential) cannot match
                # — skip the rest instead of deadlocking the pod
                self.eval_results[job_id] = {
                    "error": "skipped: a previous eval timed out and may "
                             "still be running"
                }
                continue
            box: Dict[str, Any] = {}

            def call(fn=fn, box=box) -> None:
                try:
                    box["result"] = fn(self.master)
                except Exception as e:  # noqa: BLE001 - recorded below
                    box["error"] = f"{type(e).__name__}: {e}"

            t = threading.Thread(
                target=call, daemon=True, name=f"deferred-eval-{job_id}"
            )
            t.start()
            remaining = (
                None if stage_deadline is None
                else max(0.0, stage_deadline - time.monotonic())
            )
            t.join(timeout=remaining)
            if t.is_alive():
                self.eval_results[job_id] = {"error": "timed out"}
                abandoned = True  # its thread may still be mid-collective
            elif "error" in box:
                self.eval_results[job_id] = {"error": box["error"]}
            else:
                self.eval_results[job_id] = box["result"]

    @property
    def state(self) -> str:
        return self._state.state

    # -- submission ------------------------------------------------------

    def submit(self, config: JobConfig) -> "Future[Dict[str, Any]]":
        """SUBMIT: schedule a job; returns a future for its result.

        Trace threading: the submitter's span context rides inside the
        config (``user["_trace"]`` — already set by the TCP ingest when
        the CLI sent one, else captured from the ambient span here), so
        the dispatch thread, the pod legs and the workers all re-parent
        onto ONE submission trace across threads and processes."""
        if "_trace" not in config.user:
            wire = wire_context()
            if wire is not None:
                config.user["_trace"] = wire
        from harmony_tpu import inputsvc

        if inputsvc.enabled_for(config.params):
            # before scheduling: the workers resolve the endpoint at
            # dispatch time, so the service must exist by then
            self._ensure_input_service()
        with self._lock:
            # State checked under the registry lock: shutdown's INIT->CLOSING
            # flip holds the same lock, so a submit can't interleave between
            # the check and registration and launch after the drain.
            if not self._state.is_state("INIT"):
                raise RuntimeError(f"server not accepting jobs (state={self.state})")
            existing = self._jobs.get(config.job_id)
            if existing is not None and not existing.future.done():
                raise ValueError(f"duplicate job id {config.job_id} (still running)")
            if len(self._jobs) > 1024:  # bound registry growth on long-lived servers
                for jid in [j for j, r in self._jobs.items() if r.future.done()]:
                    del self._jobs[jid]
            jr = JobResult()
            self._jobs[config.job_id] = jr
        job_logger(config.job_id).info(
            "submitted (app_type=%s, workers=%d)",
            config.app_type, config.num_workers,
        )
        if self.ha_log is not None:
            # the durable submission record carries the WHOLE config
            # (``_trace`` included): a takeover re-arms the same
            # submission from exactly this entry. A drop here means the
            # lease lapsed since the command gate — acking anyway would
            # hand the client an acked job NO successor can ever replay
            # (the acked-then-lost hole), so unwind and refuse instead.
            if not self._ha_append("submission", job_id=config.job_id,
                                   config=config.to_dict()):
                with self._lock:
                    self._jobs.pop(config.job_id, None)
                if not self._ha_leader_ok():
                    raise NotLeader(
                        f"submission {config.job_id} not durable: lease "
                        "lapsed (deposed)")
                # the log disk refused the record (ENOSPC/EIO): acking
                # anyway would be the acked-then-lost hole — refuse with
                # a retryable error; the client's bounded retry succeeds
                # once the store heals
                raise RuntimeError(
                    f"submission {config.job_id} not durable: log "
                    "append failed (sick log store); retry")
            jr.future.add_done_callback(
                lambda f, j=config.job_id: self._ha_record_done(j, f))
        self._scheduler.on_job_arrival(config)
        return jr.future

    def _launch(self, config: JobConfig, executor_ids: List[str]) -> None:
        """Scheduler-chosen launch: dispatch the job on a thread (the
        JobDispatcher.executeJob flow)."""
        t = threading.Thread(
            target=self._dispatch, args=(config, executor_ids), name=f"dispatch-{config.job_id}"
        )
        t.daemon = True
        with self._lock:
            # prune finished threads so a long-lived server doesn't retain
            # one dead Thread per job ever dispatched
            self._dispatch_threads = [x for x in self._dispatch_threads if x.is_alive()]
            self._dispatch_threads.append(t)
        t.start()

    def _trace_parent_of(self, config: JobConfig) -> Optional[SpanContext]:
        """Explicit re-parent target for a span opened on a fresh thread:
        the submission's wire context — UNLESS an ambient span already
        carries the trace (nested dispatch legs must nest, not re-root)."""
        if current_span() is not None:
            return None
        return SpanContext.from_wire(config.user.get("_trace"))

    def _dispatch(self, config: JobConfig, executor_ids: List[str]) -> None:
        with trace_span(
            "jobserver.dispatch",
            parent=self._trace_parent_of(config),
            job_id=config.job_id,
            executors=len(executor_ids),
        ):
            self._dispatch_job(config, executor_ids)

    def _dispatch_job(self, config: JobConfig, executor_ids: List[str]) -> None:
        jr = self._jobs[config.job_id]
        # queued -> mesh granted: began on the submitting thread, ends here
        record_span("job.grant_wait", jr.submitted_ns, job_id=config.job_id,
                    acc=job_stage_adder(config.job_id, "grant_wait"))
        jlog = job_logger(config.job_id)
        jlog.info("dispatched on executors %s", executor_ids)
        from harmony_tpu.jobserver import elastic as _el

        self._ha_append("dispatch", job_id=config.job_id,
                        executors=list(executor_ids),
                        attempt=_el.attempt_of(config))
        t0 = time.monotonic()
        entity = None
        try:
            # build_entity inside the try: an unknown app_type or bad config
            # must resolve the future (else callers hang) and must still run
            # scheduler.on_job_finish (else FIFO wedges permanently).
            entity = build_entity(
                config,
                global_taskunit=self.global_taskunit,
                local_taskunit=self.local_taskunit,
                metric_sink=self._on_metric,
                chkp_root=self._chkp_root,
                metric_manager=self.metrics,
                **self._entity_extras(config, executor_ids),
            )
            with self._lock:
                self._entities[config.job_id] = entity
            entity.setup(self.master, executor_ids)
            result = entity.run()
            # Register the job's deferred model evaluation BEFORE cleanup
            # drops its tables — the eval replays checkpoints from disk at
            # shutdown, so it needs only the closure, not the tables.
            deferred = entity.deferred_evaluation()
            if deferred is not None:
                with self._lock:
                    self._deferred_evals[config.job_id] = deferred
            entity.cleanup()
            jlog.info("finished in %.1fs", time.monotonic() - t0)
            jr.future.set_result(result)
        except BaseException as e:  # noqa: BLE001 - delivered via future
            jlog.error("failed after %.1fs: %s: %s",
                       time.monotonic() - t0, type(e).__name__, e)
            if entity is not None:
                try:
                    entity.cleanup()
                except Exception:
                    pass
            jr.future.set_exception(e)
        finally:
            with self._lock:
                self._entities.pop(config.job_id, None)
            self._scheduler.on_job_finish(config.job_id)
            if _el.attempt_of(config) == 0 and not config.user.get(
                    "elastic_shrink"):
                # non-elastic submissions consume no reacquire: drop any
                # policy pin so it cannot leak to a reused job id (the
                # elastic loop clears its own at submission end — a pin
                # must survive the per-attempt finish that precedes its
                # consuming reacquire)
                try:
                    self._scheduler.plan_grant(config.job_id, None)
                except Exception:
                    pass

    def _entity_extras(self, config: JobConfig,
                       executor_ids: List[str]) -> Dict[str, Any]:
        """Subclass hook: extra build_entity kwargs (the pod server wires
        its plan channel for multi-process grants here)."""
        return {}

    def _scrape_targets(self) -> Dict[str, Any]:
        """History-scraper target provider: this process's own registry
        (sampled in-process — the leader pays no HTTP for itself) plus
        any ``HARMONY_OBS_SCRAPE_TARGETS`` extras (standalone inputsvc
        workers). The pod server adds follower exporters discovered
        from the heartbeat plumbing."""
        from harmony_tpu.metrics.registry import get_registry

        targets: Dict[str, Any] = {"leader": get_registry().expose}
        targets.update(extra_targets())
        if self.overload.degraded():
            # degraded fidelity: sample a rotating subset per cycle
            # (full coverage over a few cycles) instead of missing the
            # scrape period on every cycle. The leader's own in-process
            # registry is free and never rotated out.
            keep = self.overload.plan_subset(
                list(targets), plan="scrape", keep=("leader",))
            targets = {k: v for k, v in targets.items() if k in keep}
        return targets

    def _on_scrape_cycle(self) -> None:
        """After every history-scraper poll: the doctor evaluates its
        rules, then the policy engine (throttled to its own period)
        replans off the fresh verdicts — sensor before actuator, every
        cycle, both contained (a broken one must not stop the other).

        This is also the overload detector's telemetry feed: each
        stage's wall time is compared to the scrape period, and under
        degradation the doctor/policy evaluate only the rotating tenant
        subset with fresh samples (jobserver/overload.py)."""
        ov = self.overload
        period = self._history_scraper.period
        st = self._history_scraper.stats()
        ov.note_cycle("scrape",
                      float(st.get("last_cycle_ms") or 0.0) / 1000.0,
                      period)
        jobs = None
        if ov.degraded():
            try:
                jobs = set(ov.plan_subset(
                    [str(j) for j in self.metrics.tenant_ledger()],
                    plan="tenants"))
            except Exception:
                jobs = None
        t0 = time.monotonic()
        try:
            self.doctor.diagnose(jobs=jobs)
        except Exception:
            pass
        ov.note_cycle("diagnose", time.monotonic() - t0, period)
        t0 = time.monotonic()
        try:
            if ov.shedding():
                # the planner is pure fidelity: at the bottom rung it
                # sheds whole evaluations, not just tenants
                ov.count_shed("policy_skip")
            else:
                self.policy.maybe_evaluate(jobs=jobs)
        except Exception:
            pass
        ov.note_cycle("plan", time.monotonic() - t0, period)
        t0 = time.monotonic()
        try:
            self.incidents.correlate()
        except Exception:
            pass
        ov.note_cycle("correlate", time.monotonic() - t0, period)
        ov.step()

    def _policy_tenants(self) -> Dict[str, Dict[str, Any]]:
        """Policy-engine actuator view: the running tenants whose
        placement CAN be replanned (elastic attempts with a fence
        channel). The plain server has none — the pod server overrides
        with its elastic-active bookkeeping."""
        return {}

    def _policy_fence(self, job_id: str, kind: str) -> Optional[int]:
        """Policy-engine actuator: schedule a lockstep elastic fence on
        a running attempt. No fence channel on the plain server —
        actions stay advisory here."""
        return None

    def _post_policy(self, action: Dict[str, Any]) -> None:
        """Policy sink: tee every recorded action to the dashboard as a
        kind="policy" row (same best-effort contract as metric posts)."""
        if self._dashboard is not None:
            try:
                self._dashboard.post(str(action.get("job")), "policy",
                                     dict(action))
            except Exception:
                pass  # dashboard posts are best-effort by contract

    def _post_diagnosis(self, diag) -> None:
        """Doctor sink: tee every fresh diagnosis to the dashboard as a
        kind="diagnosis" row (same best-effort contract as metric
        posts) so the history panel can overlay verdicts on series."""
        if self._dashboard is not None:
            try:
                self._dashboard.post(diag.subject, "diagnosis",
                                     diag.to_dict())
            except Exception:
                pass  # dashboard posts are best-effort by contract

    def _post_incident(self, incident: Dict[str, Any]) -> None:
        """Incident-engine sink: tee every lifecycle transition to the
        dashboard as a kind="incident" row (same best-effort contract
        as metric posts) so the /incidents panel can render timelines."""
        if self._dashboard is not None:
            try:
                self._dashboard.post(str(incident.get("subject")),
                                     "incident", dict(incident))
            except Exception:
                pass  # dashboard posts are best-effort by contract

    def _ensure_input_service(self) -> None:
        """Start the embedded input service + its autoscaler once. A
        configured HARMONY_INPUT_SERVICE_ADDR means a standalone service
        process owns the role — workers will use it directly and the
        jobserver starts nothing."""
        import os

        from harmony_tpu import inputsvc

        if os.environ.get("HARMONY_INPUT_SERVICE_ADDR"):
            return
        with self._lock:
            if self.input_service is not None:
                return
            svc = inputsvc.InputService()
            port = svc.start()
            inputsvc.set_default_endpoint(("127.0.0.1", port))
            metrics = self.metrics

            def wait_frac() -> "float | None":
                rows = metrics.tenant_ledger()
                fr = [r.get("input_wait_frac") for r in rows.values()
                      if r.get("input_wait_frac") is not None]
                return sum(fr) / len(fr) if fr else None

            def straggler() -> "float | None":
                reps = metrics.straggler_report()
                ratios = [r["ratio"] for r in reps.values()]
                return max(ratios) if ratios else None

            # the autoscaler shares the POLICY engine's rate-limit gate:
            # input-worker scaling and device packing both key off the
            # input-wait signal, and a shared cooldown on that signal is
            # what keeps them from fighting over it
            scaler = inputsvc.InputAutoscaler(svc, wait_frac, straggler,
                                              gate=self.policy.gate)
            scaler.start()
            self.input_service = svc
            self._input_autoscaler = scaler
        server_log.info("input service up on port %d (%d workers)",
                        port, svc.workers)

    def _stop_input_service(self) -> None:
        with self._lock:
            svc, self.input_service = self.input_service, None
            scaler, self._input_autoscaler = self._input_autoscaler, None
        if scaler is not None:
            scaler.stop()
        if svc is not None:
            from harmony_tpu import inputsvc

            inputsvc.set_default_endpoint(None)
            svc.stop()

    def _ensure_serving(self):
        """Start the embedded serving endpoint once (first SERVING
        command) and return it. Live lookups resolve through
        ``_entities`` — the same handle the trainers update — and
        pinned lookups through this server's checkpoint root; admission
        rides the shared overload monitor."""
        with self._lock:
            if self.serving is not None:
                return self.serving
            from harmony_tpu.serving import ServingEndpoint

            def live_table(job_id: str):
                with self._lock:
                    entity = self._entities.get(job_id)
                handle = (getattr(entity, "table_handle", None)
                          if entity is not None else None)
                return handle.table if handle is not None else None

            svc = ServingEndpoint(
                table_fn=live_table,
                chkp_root=self._chkp_root,
                overload=self.overload,
            )
            port = svc.start()
            self.serving = svc
        server_log.info("serving endpoint up on port %d", port)
        return svc

    def _stop_serving(self) -> None:
        with self._lock:
            svc, self.serving = self.serving, None
        if svc is not None:
            svc.stop()

    def running_jobs(self) -> List[str]:
        with self._lock:
            return [j for j, r in self._jobs.items() if not r.future.done()]

    def _status(self) -> Dict[str, Any]:
        """STATUS reply body (subclasses extend, e.g. pod health)."""
        from harmony_tpu.jobserver import joblog
        from harmony_tpu.runtime import progcache
        from harmony_tpu.tracing import flight

        # ONE straggler walk per STATUS: the report, the ledger join
        # and the phase-budget analysis all consume the same figures
        stragglers = self.metrics.straggler_report()
        return {
            "ok": True,
            "state": self.state,
            "running": self.running_jobs(),
            "evaluated": sorted(self.eval_results),
            # recovery observability: fault-injection fires + transport/
            # checkpoint retry counters + isolated-worker respawns for
            # THIS process, and the structured per-job recovery events
            # (shrink/re-grow/confinement/rehabilitation)
            "fault_counters": self.metrics.fault_counters(),
            "job_events": joblog.job_events(),
            # telemetry plane: per-job straggler attribution from the
            # step-time records, this process's flight-recorder dumps
            # (path + correlated trace ids), and where /metrics lives
            "stragglers": stragglers,
            # per-tenant device cost accounting (metrics/accounting.py):
            # MFU, device-seconds, resident HBM, input-wait, SLO
            # attainment per job@attempt — what `obs top` renders
            "tenants": self.metrics.tenant_ledger(stragglers=stragglers),
            # step-phase time budget + critical-path attribution
            # (metrics/phases.py + critpath.py): per-tenant phase
            # seconds/fractions, bound classification, and per-epoch
            # gating worker+phase — what `obs critpath` renders
            "phase_budget": self.metrics.phase_budget(
                stragglers=stragglers),
            # newest sampled device-profile capture on THIS process's
            # disk (HARMONY_PROFILE_DIR), if the sampler ever ran —
            # until now xplane dumps landed and nothing referenced them
            "profile_capture": flight.profile_capture_path(),
            "flight_records": flight.get_recorder().records(),
            # every span open in this process right now and the longest
            # closed span per description (tracing/span.py): a stall
            # names itself here while it lasts, and afterwards
            "flight_spans": flight.get_recorder().span_watch(),
            # JAX's own compiles by the job whose span they ran under
            # (runtime/progcache.py): "which job recompiled, and when"
            "compiles": progcache.compiles_by_job(),
            # the tiles each traced Pallas kernel chose and the grid steps
            # a call takes under them (ops/attention.py tile_plan)
            "kernel_plans": progcache.kernel_plans(),
            # what each job's rematerialised blocks keep by name, a step
            # (ops/residuals.py): the kernels' forwards run once a layer
            "remat_saved": progcache.remat_saved(),
            # seconds of each job's start by stage (the job.<stage> spans)
            "job_stages": job_stage_seconds(),
            "metrics_port": (self.metrics_exporter.port
                             if self.metrics_exporter is not None else None),
            # telemetry history + doctor (metrics/history.py + doctor.py):
            # store/scraper shape and the newest structured diagnoses —
            # what `harmony-tpu obs doctor` renders
            "history": {**self.history.stats(),
                        "scraper": self._history_scraper.stats()},
            "diagnoses": self.doctor.recent(),
            # disaggregated input service (harmony_tpu/inputsvc): port,
            # worker slots, per-tenant queue traffic, cache hit/byte
            # stats and autoscaler events — None when not running
            "input_service": (self.input_service.stats()
                              if self.input_service is not None else None),
            # serving plane (harmony_tpu/serving): port, per-tenant
            # qps/latency, batch occupancy and cache hit/byte stats —
            # None until the first SERVING command starts it
            "serving": (self.serving.stats()
                        if self.serving is not None else None),
            # control-plane HA (jobserver/ha.py): role, leader epoch,
            # durable-log/lease/replication shape and recent takeovers —
            # {"enabled": False} outside an HA deployment
            "ha": self._ha_status(),
            # device policy engine (jobserver/policy.py): mode, the last
            # computed plan (candidates + why each was or wasn't acted
            # on), recent actions, and the rate-limit gate's state —
            # what `harmony-tpu obs plan` renders
            "policy": self.policy.status(),
            # control-plane overload (jobserver/overload.py): ladder
            # level, queue fill/lag, shed counters and the recovery
            # gate — the operator's "is fidelity degraded, and why"
            "overload": self.overload.status(),
            # incident correlation (metrics/incidents.py): open/
            # mitigating/resolved counts, MTTR, and the newest causal
            # chains — what `harmony-tpu obs incidents` renders
            "incidents": self.incidents.status(),
        }

    # -- TCP command endpoint (ref: CommandListener) ---------------------

    #: byte cap on ONE command message — the same fix class as the
    #: scraper's bounded read (metrics/history.py _read_bounded): a
    #: client streaming forever must cost a bounded buffer, not RSS
    _MAX_CMD_BYTES = 16 << 20

    def serve_tcp(self, port: int = 0, host: str = "127.0.0.1") -> int:
        """Listen on ``host`` (default localhost — the single-machine
        contract; an HA control plane whose clients live on other hosts
        binds its advertised interface, cli --ha-bind); returns the
        bound port. Wire format: one JSON object per connection:
        {"command": "SUBMIT", "conf": <JobConfig>} or
        {"command": "SHUTDOWN"}; reply is one JSON object.

        Bounded command plane (jobserver/overload.py): the accept loop
        feeds a bounded queue drained by a FIXED worker pool — never a
        thread per connection (that was the wedge under submit storms:
        thousands of connections, thousands of threads, then the GIL
        and RSS fall over together). A full queue answers BUSY
        {retry_after_ms} right at accept; admission for SUBMIT is
        checked again, against dispatch in-flight, before anything
        durable happens."""
        from harmony_tpu import faults
        from harmony_tpu.jobserver import overload as _ov

        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((host, port))
        sock.listen(64)
        cap = _ov.cmd_queue_cap()
        q: "_queue.Queue" = _queue.Queue(maxsize=cap)
        workers: List[threading.Thread] = []
        for i in range(_ov.cmd_workers()):
            t = threading.Thread(target=self._cmd_worker, args=(q, cap),
                                 daemon=True, name=f"jobserver-cmd-{i}")
            t.start()
            workers.append(t)
        with self._lock:
            self._tcp_sock = sock
            self._cmd_queue = q
            self._cmd_workers = workers
            self._cmd_queue_cap = cap
        self.port = sock.getsockname()[1]

        def loop() -> None:
            while True:
                try:
                    conn, _ = sock.accept()
                except OSError:
                    return  # socket closed
                if faults.armed():
                    try:
                        faults.site("server.accept", depth=q.qsize())
                    except Exception:
                        # an injected accept fault drops THIS connection
                        # (a flaky NIC/kernel accept path); the loop and
                        # the queued work are untouched
                        try:
                            conn.close()
                        except OSError:
                            pass
                        continue
                try:
                    q.put_nowait((conn, time.monotonic()))
                except _queue.Full:
                    # shed at the door, loudly: a structured BUSY beats
                    # an accepted-then-starved connection every time
                    self.overload.note_queue(q.qsize(), cap)
                    self.overload.count_shed("accept_shed")
                    self._send_busy(conn, self.overload.retry_after_ms())
                    self.overload.step()

        self._tcp_thread = threading.Thread(target=loop, daemon=True, name="jobserver-tcp")
        self._tcp_thread.start()
        return self.port

    def _send_busy(self, conn: socket.socket, retry_after_ms: int) -> None:
        """Best-effort BUSY reply on a connection being shed (bounded —
        the accept loop must never block on a slow shed client)."""
        reply = {"ok": False, "busy": True,
                 "retry_after_ms": int(retry_after_ms),
                 "error": "BUSY: control plane overloaded"}
        try:
            conn.settimeout(1.0)
            conn.sendall((json.dumps(reply) + "\n").encode())
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _cmd_worker(self, q: "_queue.Queue", cap: int) -> None:
        """One fixed-pool worker: drain the accept queue forever (a
        None sentinel stops it). Queue lag — how long the connection
        waited for a worker — is the overload detector's primary
        command-plane signal."""
        while True:
            item = q.get()
            if item is None:
                return
            conn, enq_t = item
            lag = time.monotonic() - enq_t
            self.overload.note_queue(q.qsize(), cap, lag_sec=lag)
            self.overload.step()
            try:
                self._handle_conn(conn)
            except Exception:  # noqa: BLE001 - a handler bug must not
                pass           # kill the pool worker

    def _read_command(self, conn: socket.socket,
                      deadline: float) -> bytes:
        """Bounded read of one newline-terminated command: capped in
        BYTES and WALL CLOCK (not per-recv — a trickling client used to
        reset a 30s timeout on every byte and hold its thread forever;
        same fix class as the PR-11 scraper hardening)."""
        data = b""
        while not data.endswith(b"\n"):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self.overload.count_shed("slowloris_evict")
                raise TimeoutError(
                    "command read exceeded its wall-clock deadline "
                    "(slow client evicted)")
            conn.settimeout(min(5.0, remaining))
            try:
                chunk = conn.recv(65536)
            except socket.timeout:
                continue  # loop re-checks the WALL deadline
            if not chunk:
                break
            data += chunk
            if len(data) > self._MAX_CMD_BYTES:
                self.overload.count_shed("oversize_evict")
                raise ValueError(
                    f"command exceeds {self._MAX_CMD_BYTES} byte cap")
        return data

    def _handle_conn(self, conn: socket.socket) -> None:
        from harmony_tpu import faults

        from harmony_tpu.jobserver import overload as _ov

        deadline = time.monotonic() + _ov.cmd_deadline_sec()
        # The error reply MUST go out before `with conn` closes the socket —
        # sending after close silently drops it and the client sees bare EOF.
        with conn:
            try:
                data = self._read_command(conn, deadline)
                msg = json.loads(data.decode())
                cmd = msg.get("command")
                if faults.armed():
                    # raises = an injected command-path failure; it
                    # surfaces to the client as a structured error reply
                    faults.site("server.command", cmd=str(cmd))
                if (cmd in ("SUBMIT", "POD_RESHARD", "WAIT", "SERVING")
                        and not self._ha_leader_ok()):
                    # deposed leader: every mutating/authoritative
                    # command redirects — a client following the lease
                    # holder's advertised address lands on the successor
                    reply = self._not_leader_reply()
                elif cmd == "SUBMIT":
                    # Admission BEFORE anything durable: a rejected
                    # submission left no trace (no registry entry, no
                    # joblog append), an admitted one proceeds into
                    # submit()'s durable path — accepted-then-shed is
                    # structurally impossible.
                    with self._lock:
                        q = self._cmd_queue
                    retry_ms = self.overload.admit_submit(
                        queue_depth=(q.qsize() if q is not None else 0),
                        queue_cap=(self._cmd_queue_cap or 1),
                        inflight=len(self.running_jobs()))
                    if retry_ms is not None:
                        reply = {"ok": False, "busy": True,
                                 "retry_after_ms": retry_ms,
                                 "error": "BUSY: control plane "
                                          "overloaded; retry after "
                                          f"{retry_ms}ms"}
                    else:
                        config = ConfigBase.from_dict(msg["conf"])
                        # the client's span context (client.py sends it
                        # beside the config): ride it inside the config
                        # so the whole dispatch chain re-parents onto
                        # the CLI's trace
                        wire = msg.get("trace")
                        if wire and "_trace" not in config.user:
                            config.user["_trace"] = dict(wire)
                        try:
                            with trace_span(
                                "jobserver.submit",
                                parent=SpanContext.from_wire(
                                    config.user.get("_trace")),
                                job_id=config.job_id,
                            ):
                                self.submit(config)
                            reply = {"ok": True, "job_id": config.job_id}
                        except NotLeader:
                            # deposed BETWEEN the gate check and the
                            # durable append: the submission was unwound,
                            # so redirect instead of acking a job no
                            # successor can replay
                            reply = self._not_leader_reply()
                elif cmd == "STATUS":
                    # walks the ledger and the budget under the GIL,
                    # beside the dispatching threads: a span, so a device
                    # gap that falls under it says so
                    with trace_span("jobserver.status"):
                        reply = self._status()
                elif cmd == "WAIT":
                    # bounded wait on a submission's result — the
                    # failover client's way to follow ONE submission
                    # across a leader change (the successor re-arms it
                    # under the same job id and resolves a fresh future)
                    job_id = str(msg.get("job_id"))
                    # the future poll is also capped by the command
                    # deadline: a WAIT occupies one fixed-pool worker,
                    # and clients poll in a loop anyway (wait_result)
                    timeout = min(float(msg.get("timeout", 30.0)), 300.0,
                                  max(0.5, deadline - time.monotonic()))
                    with self._lock:
                        jr = self._jobs.get(job_id)
                    if jr is None:
                        reply = {"ok": False, "known": False,
                                 "error": f"unknown job {job_id!r}"}
                    else:
                        try:
                            result = jr.future.result(timeout=timeout)
                            reply = {"ok": True, "done": True,
                                     "result": _json_sanitize(result)}
                        except (TimeoutError, FuturesTimeoutError):
                            reply = {"ok": True, "done": False,
                                     "running": job_id in
                                     self.running_jobs()}
                        except BaseException as e:  # noqa: BLE001
                            reply = {"ok": False, "known": True,
                                     "done": True,
                                     "error": f"{type(e).__name__}: {e}"}
                elif cmd == "POD_RESHARD":
                    # operator-initiated live migration of a running pod
                    # job (PodJobServer.schedule_pod_reshard; plain
                    # servers reject — the attribute is pod-only)
                    fn = getattr(self, "schedule_pod_reshard", None)
                    if fn is None:
                        reply = {"ok": False,
                                 "error": "not a pod server"}
                    else:
                        fn(job_id=str(msg["job_id"]), src=str(msg["src"]),
                           dst=str(msg["dst"]),
                           num_blocks=int(msg["num_blocks"]),
                           epoch=int(msg["epoch"]))
                        reply = {"ok": True}
                elif cmd == "SERVING":
                    # serving-endpoint discovery (harmony_tpu/serving):
                    # starts the data plane on demand and answers its
                    # address. Leader-gated above: only the replica that
                    # owns live tables (and re-arms the checkpoint
                    # chains) may advertise itself to readers, so a
                    # takeover re-routes every ServingClient through
                    # the same NOT_LEADER walk as submissions.
                    svc = self._ensure_serving()
                    reply = {"ok": True, "port": svc.port,
                             "host": svc.address[0]}
                elif cmd == "SHUTDOWN":
                    threading.Thread(target=self.shutdown, daemon=True).start()
                    reply = {"ok": True}
                else:
                    reply = {"ok": False, "error": f"unknown command {cmd!r}"}
            except Exception as e:  # noqa: BLE001 - reported to the client
                reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            try:
                conn.sendall((json.dumps(reply) + "\n").encode())
            except OSError:
                pass  # client went away; nothing to tell it

    def _stop_tcp(self) -> None:
        # under the lock: shutdown() can be invoked from a TCP handler
        # thread, and two concurrent SHUTDOWNs racing this check-close-
        # clear sequence could close-then-read a None socket
        with self._lock:
            sock, self._tcp_sock = self._tcp_sock, None
            q, self._cmd_queue = self._cmd_queue, None
            workers, self._cmd_workers = self._cmd_workers, []
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        if q is not None:
            # drain queued (never-served) connections so their clients
            # see EOF now, then stop the pool with one sentinel each
            while True:
                try:
                    item = q.get_nowait()
                except _queue.Empty:
                    break
                if item is not None:
                    try:
                        item[0].close()
                    except OSError:
                        pass
            for _ in workers:
                try:
                    q.put(None, timeout=1.0)
                except _queue.Full:
                    break  # workers are daemons; leak rather than hang
            # a worker mid-WAIT legitimately holds its slot up to the
            # command deadline — don't stall shutdown on it
            for t in workers:
                if t is not threading.current_thread():
                    t.join(timeout=0.5)
