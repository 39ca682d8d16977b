"""TaskUnit scheduling — Harmony's core multi-tenancy mechanism, rebuilt.

The reference interleaves concurrent jobs on shared executors by slicing
tasklet work into TaskUnits typed by the resource they saturate:

  * local side: per-executor semaphores — 1 CPU slot, 2 NET slots; a tasklet
    declares each phase (PULL=NET, COMP=CPU, PUSH=NET, SYNC=VOID) and blocks
    until granted (ref: LocalTaskUnitScheduler.java:33-145; slot counts at
    36-37),
  * global side: the driver collects TaskUnitWaitMsg from every executor of
    a job and, once ALL of them wait, broadcasts TaskUnitReadyMsg — yielding
    one global order of TaskUnits across jobs so phases interleave
    identically on every executor (ref: GlobalTaskUnitScheduler.java:29-92).

TPU mapping: an "executor" is a worker thread driving jitted steps over the
job's mesh slice; CPU slots gate device-compute-heavy units (fused steps),
NET slots gate collective/transfer-heavy units (host-driven pulls/pushes,
resharding). The wait/ready protocol is method calls on the in-process
global scheduler; the API mirrors the message vocabulary so a multi-host
control plane can sit behind it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

from harmony_tpu.tracing.span import trace_span

# Unit kinds and which slot pool they consume (VOID consumes nothing —
# barrier/sync phases, ref TaskUnitInfo ResourceType VOID).
CPU = "CPU"
NET = "NET"
VOID = "VOID"

# Phase -> resource typing (ref: WorkerTasklet declares PULL=NET, COMP=CPU,
# PUSH=NET, SYNC=VOID when wrapping each phase in a TaskUnit).
PHASE_RESOURCE = {
    "PULL": NET,
    "COMP": CPU,
    "PUSH": NET,
    "SYNC": VOID,
    CPU: CPU,
    NET: NET,
    VOID: VOID,
}


class TaskUnitAborted(RuntimeError):
    """An interruptible admission wait (scope(abort=...)) was withdrawn —
    the caller's work is being torn down and the grant is no longer
    wanted. Never raised for ordinary scheduling waits."""


@dataclasses.dataclass(frozen=True)
class TaskUnitInfo:
    """Identity of one schedulable unit (ref: evaluator/impl/TaskUnitInfo)."""

    job_id: str
    executor_id: str
    kind: str
    seq: int  # per-(job, executor) monotonically increasing phase counter


class GlobalTaskUnitScheduler:
    """Driver-side: one global grant order across concurrent jobs.

    Fairness: grants are DEFICIT-ORDERED and, under contention, METERED.
    The reference's pure quorum broadcast produces *an* order, not a fair
    one — measured with three tenants, the cheapest job's units queued
    behind the other tenants' device backlogs for a 15x slowdown. Here,
    when more than one job is waiting, each job may
    hold at most one un-finished granted unit per resource kind (the
    TaskUnitClient reports scope exit — the reference's
    onTaskUnitFinished), and ready units are granted lowest-deficit-first
    (deficit = units granted so far), so tenants alternate enqueues
    instead of flooding. A lone job keeps the zero-overhead
    grant-everything path."""

    def __init__(self) -> None:
        # Meter EXECUTION only where scope-exit means execution finished
        # (blocking backends — CPU's in-process collectives): there the
        # single global slot IS the device schedule. On async backends
        # (real TPU) scope exit is just enqueue-complete; serializing
        # enqueues across tenants would tax throughput without governing
        # device time —
        # fairness there comes from the deficit-ordered grants plus the
        # contended in-flight cap bounding every tenant's queue depth.
        # The JobServer flips this from its device pool at start.
        self.meter_execution = True
        self._cond = threading.Condition()
        self._job_executors: Dict[str, Set[str]] = {}
        # (job_id, seq, kind) -> executors currently waiting
        self._waiting: Dict[Tuple[str, int, str], Set[str]] = {}
        self._granted: Set[Tuple[str, int, str]] = set()
        # arrival order of wait keys (deficit ties break by arrival)
        self._arrival: Dict[Tuple[str, int, str], int] = {}
        self._arrival_counter = 0
        # fairness metering (see class doc). Deficit is DEVICE-TIME
        # weighted: charging grants by unit count would pace every tenant
        # 1:1 — exactly what makes a cheap job finish with the most
        # expensive one (the 15x). Jobs report their measured per-unit
        # seconds (report_unit_cost); until a job has a measurement its
        # units charge the mean known cost (neutral).
        self._deficit: Dict[str, float] = {}
        self._unit_cost: Dict[str, float] = {}
        self._outstanding: Dict[Tuple[str, str], int] = {}  # (job, kind)
        # last grant/finish per job — the anticipatory-hold recency signal
        self._last_activity: Dict[str, float] = {}
        # granted key -> executors that have NOT yet finished it (a SET,
        # not a count: an executor may both finish a unit and then leave
        # the job — counting would double-decrement and release the
        # contention meter while a peer is still inside the scope)
        self._finishes: Dict[Tuple[str, int, str], Set[str]] = {}
        # Bounded: a long-lived server grants one entry per phase per batch
        # forever; keep a recent window for tests/metrics, not full history.
        self._grant_log: deque = deque(maxlen=100_000)

    def on_job_start(self, job_id: str, executor_ids: List[str]) -> None:
        with self._cond:
            self._job_executors[job_id] = set(executor_ids)
            # WFQ virtual-time start: a late arrival begins at the lowest
            # active deficit, not zero — zero would let it monopolize
            # grants until it "caught up" with long-running tenants.
            active = [self._deficit[j] for j in self._job_executors
                      if j != job_id and j in self._deficit]
            self._deficit.setdefault(job_id, min(active) if active else 0.0)

    def on_job_finish(self, job_id: str) -> None:
        with self._cond:
            self._job_executors.pop(job_id, None)
            self._deficit.pop(job_id, None)
            self._last_activity.pop(job_id, None)
            for key in [k for k in self._waiting if k[0] == job_id]:
                del self._waiting[key]
                self._arrival.pop(key, None)
            for key in [k for k in self._granted if k[0] == job_id]:
                self._granted.discard(key)
            for key in [k for k in self._finishes if k[0] == job_id]:
                del self._finishes[key]
            for jk in [k for k in self._outstanding if k[0] == job_id]:
                del self._outstanding[jk]
            self._maybe_grant_locked()  # departed meter may unblock peers
            self._cond.notify_all()

    def num_jobs(self) -> int:
        """Registered jobs — workers use >1 as the contention signal to
        shrink their in-flight dispatch windows."""
        with self._cond:
            return len(self._job_executors)

    def peer_unit_cost(self, job_id: str) -> float:
        """Largest measured per-unit cost among OTHER registered jobs
        (0.0 when unknown) — workers size their batch groups toward it: a
        cheap tenant pays ~one residual peer-unit wait per OWN unit, so
        matching its unit span to the peers' cuts its unit count (and
        with it the dominant term of its slowdown) without lengthening
        anyone's residual beyond what the big tenants already impose."""
        with self._cond:
            return max(
                (self._unit_cost.get(j, 0.0) for j in self._job_executors
                 if j != job_id), default=0.0,
            )

    def report_unit_cost(self, job_id: str, seconds: float) -> None:
        """Measured per-unit device seconds for a job (workers report the
        smeared per-batch time at each metric drain); EWMA-smoothed."""
        if seconds <= 0:
            return
        with self._cond:
            prev = self._unit_cost.get(job_id)
            self._unit_cost[job_id] = (
                seconds if prev is None else 0.5 * prev + 0.5 * seconds
            )
            while len(self._unit_cost) > 4096:  # long-lived server bound
                self._unit_cost.pop(next(iter(self._unit_cost)))

    def _charge_locked(self, job: str) -> float:
        cost = self._unit_cost.get(job)
        if cost is None:
            known = [self._unit_cost[j] for j in self._job_executors
                     if j in self._unit_cost]
            cost = sum(known) / len(known) if known else 1.0
        return cost

    def _release_meter_locked(self, job_id: str, kind: str) -> None:
        jk = (job_id, kind)
        n = self._outstanding.get(jk, 0)
        if n <= 1:
            self._outstanding.pop(jk, None)
        else:
            self._outstanding[jk] = n - 1

    def on_unit_finished(self, unit: "TaskUnitInfo") -> None:
        """Scope exit (the reference's onTaskUnitFinished): releases this
        job's meter for the unit's kind so the next lowest-deficit tenant
        can be granted."""
        key = (unit.job_id, unit.seq, unit.kind)
        with self._cond:
            pending = self._finishes.get(key)
            if pending is None:
                return
            pending.discard(unit.executor_id)
            if not pending:
                del self._finishes[key]
                self._release_meter_locked(unit.job_id, unit.kind)
                self._last_activity[unit.job_id] = time.monotonic()
                self._maybe_grant_locked()
                self._cond.notify_all()

    def update_job_executors(self, job_id: str, executor_ids: List[str]) -> None:
        """Reconfiguration adjusts the wait quorum."""
        with self._cond:
            self._job_executors[job_id] = set(executor_ids)
            self._maybe_grant_locked()

    def on_executor_done(self, job_id: str, executor_id: str) -> None:
        """A worker that stopped (finished, early-stopped, or crashed) must
        leave the quorum, or every surviving worker of the job deadlocks in
        wait_ready forever (the analogue of the reference keeping barrier
        counts consistent when executors leave). Its pending finishes are
        force-released so its job's meter never sticks."""
        with self._cond:
            quorum = self._job_executors.get(job_id)
            if quorum is not None:
                quorum.discard(executor_id)
            for waiters in self._waiting.values():
                waiters.discard(executor_id)
            # a departed executor can never report on_unit_finished:
            # remove it from every pending finish set it appears in
            # (idempotent with its own earlier on_unit_finished calls)
            for key in [k for k in self._finishes if k[0] == job_id]:
                pending = self._finishes[key]
                pending.discard(executor_id)
                if not pending:
                    del self._finishes[key]
                    self._release_meter_locked(job_id, key[2])
            self._maybe_grant_locked()

    def wait_ready(self, unit: TaskUnitInfo, timeout: Optional[float] = None) -> bool:
        """TaskUnitWaitMsg: block until the whole job's quorum waits on this
        seq and the grant is broadcast (TaskUnitReadyMsg). The wait wakes
        periodically to re-evaluate grants — an anticipatory hold (see
        _maybe_grant_locked) lapses by TIME, and no event fires when it
        does."""
        key = (unit.job_id, unit.seq, unit.kind)
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            if unit.job_id not in self._job_executors:
                return True  # job not registered: scheduling disabled for it
            if key in self._granted:
                # an abortable wait re-entering after its poll timeout,
                # whose grant landed in the unlocked gap: re-registering
                # the key in _waiting would leave a stale quorum-complete
                # entry that a later grant pass hands to NOBODY — pinning
                # the per-kind meter and wedging every tenant's admission
                return True
            if key not in self._waiting:
                self._arrival_counter += 1
                self._arrival[key] = self._arrival_counter
            self._waiting.setdefault(key, set()).add(unit.executor_id)
            self._maybe_grant_locked()
            while key not in self._granted:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    return False
                # periodic re-evaluation only where an anticipatory hold
                # can exist (contended + metered): elsewhere grants are
                # purely notify-driven and polling is pure overhead
                holds_possible = (self.meter_execution
                                  and len(self._job_executors) > 1)
                step = remaining
                if holds_possible:
                    step = (self.RESERVE_WINDOW if remaining is None
                            else min(remaining, self.RESERVE_WINDOW))
                if not self._cond.wait_for(
                        lambda: key in self._granted, timeout=step):
                    if holds_possible:
                        self._maybe_grant_locked()  # a hold may have lapsed
            return True

    # Anticipatory-hold window (seconds): how long after the least-served
    # tenant's last grant/finish the slot is held for its RETURN before
    # peers may take it. Covers the microscopic host gaps between a
    # streaming tenant's consecutive units (loop bookkeeping, sub-ms) and
    # short drains — far below any real unit span.
    RESERVE_WINDOW = 0.05

    def _maybe_grant_locked(self) -> None:
        ready = []
        for key, waiters in self._waiting.items():
            quorum = self._job_executors.get(key[0])
            if quorum is not None and waiters and quorum <= waiters:
                ready.append(key)
        if not ready:
            return
        # contention = more than one job REGISTERED (not "currently
        # waiting": grants are near-instant, so the wait set rarely holds
        # two jobs at once and a wait-set test would never engage the
        # meter)
        contended = len(self._job_executors) > 1
        # Anticipatory hold (the disk-scheduler trick, applied to tenant
        # fairness): the least-served tenant streams its units through
        # microscopic host gaps; a work-conserving grant into such a gap
        # would charge it one full peer-unit residual per OWN unit — the
        # measured ~4x cheapest-tenant slowdown. If the least-served job
        # was active within RESERVE_WINDOW and a candidate's deficit is
        # comfortably ahead of it, the slot is held for its return (the
        # hold lapses by time; wait_ready re-evaluates periodically).
        fav = fav_d = None
        fav_hold = False
        if contended and self.meter_execution and self._job_executors:
            fav = min(self._job_executors,
                      key=lambda j: self._deficit.get(j, 0.0))
            fav_d = self._deficit.get(fav, 0.0)
            fav_hold = (
                time.monotonic() - self._last_activity.get(fav, 0.0)
                < self.RESERVE_WINDOW
            )
        # lowest-deficit job first; arrival order breaks ties (and is the
        # whole order for a lone job — the legacy behavior)
        ready.sort(key=lambda k: (self._deficit.get(k[0], 0),
                                  self._arrival.get(k, 0)))
        granted_any = False
        for key in ready:
            job, _seq, kind = key
            if contended and kind != VOID and self.meter_execution:
                if any(jk[1] == kind for jk in self._outstanding):
                    # Metered PER KIND: the device is one CPU resource —
                    # under contention at most one un-finished CPU unit
                    # is outstanding ACROSS jobs, so the deficit-ordered
                    # grant sequence IS the device schedule. NET units
                    # are host-driven transfers: gating them behind an
                    # outstanding COMP unit would collapse the
                    # 1-CPU/2-NET compute/transfer overlap, so each kind
                    # meters only against itself.
                    continue
                if (fav_hold and job != fav
                        and fav_d + 2 * self._charge_locked(fav)
                        < self._deficit.get(job, 0.0)):
                    continue  # hold the slot for the least-served tenant
            waiters = self._waiting.pop(key)
            self._arrival.pop(key, None)
            self._granted.add(key)
            self._grant_log.append(key)
            self._deficit[job] = (
                self._deficit.get(job, 0.0) + self._charge_locked(job)
            )
            self._last_activity[job] = time.monotonic()
            if kind != VOID:
                self._outstanding[(job, kind)] = (
                    self._outstanding.get((job, kind), 0) + 1
                )
                self._finishes[key] = set(waiters)
            granted_any = True
        if granted_any:
            self._cond.notify_all()

    def cancel_wait(self, unit: TaskUnitInfo) -> bool:
        """Withdraw a pending wait (the abort path of an interruptible
        scope). Returns True when the unit was ALREADY granted — the
        caller then owns the grant and must balance the meter (finish it,
        empty or not). A withdrawn wait must not linger in ``_waiting``:
        for a single-executor quorum a stale complete entry would be
        granted to nobody and pin the job's per-kind meter forever."""
        key = (unit.job_id, unit.seq, unit.kind)
        with self._cond:
            if key in self._granted:
                return True
            waiters = self._waiting.get(key)
            if waiters is not None:
                waiters.discard(unit.executor_id)
                if not waiters:
                    del self._waiting[key]
                    self._arrival.pop(key, None)
            return False

    def grant_order(self) -> List[Tuple[str, int, str]]:
        """The single global TaskUnit order (for tests/metrics)."""
        with self._cond:
            return list(self._grant_log)


class LocalTaskUnitScheduler:
    """Executor-side slot gate (1 CPU / 2 NET by default)."""

    def __init__(self, cpu_slots: int = 1, net_slots: int = 2) -> None:
        self.cpu_slots = cpu_slots
        self.net_slots = net_slots
        self._sems = {
            CPU: threading.BoundedSemaphore(cpu_slots),
            NET: threading.BoundedSemaphore(net_slots),
        }

    def acquire(self, kind: str) -> None:
        if kind != VOID:
            self._sems[kind].acquire()

    def release(self, kind: str) -> None:
        if kind != VOID:
            self._sems[kind].release()


class TaskUnitClient:
    """Per-(job, executor) handle workers use to wrap phases.

    ``scope(kind)`` = waitSchedule: ask the global scheduler (quorum +
    broadcast), then take the local slot; exit releases it
    (ref: LocalTaskUnitScheduler.waitSchedule 83-102 + onTaskUnitFinished).
    Plugs into WorkerTasklet(taskunit=...).
    """

    def __init__(
        self,
        job_id: str,
        executor_id: str,
        global_sched: GlobalTaskUnitScheduler,
        local_sched: LocalTaskUnitScheduler,
    ) -> None:
        self.job_id = job_id
        self.executor_id = executor_id
        self._global = global_sched
        self._local = local_sched
        self._seq = itertools.count()

    @contextlib.contextmanager
    def scope(self, phase: str, abort=None, poll: float = 0.25,
              wait_acc=None):
        """Accepts a phase name (PULL/COMP/PUSH/SYNC) or a raw resource
        kind. ``abort`` (optional callable) makes the admission wait
        interruptible: polled every ``poll`` seconds; when it returns True
        the wait is withdrawn and :class:`TaskUnitAborted` raised (a grant
        that raced the abort is finished empty so the meter stays
        balanced). Background producers use it so their teardown never
        hangs on a grant that can no longer arrive (e.g. the job's
        executor already left the quorum).

        The admission wait — from the ask to the local slot taken — is the
        light span ``taskunit.wait`` (tracing/span.py): an event on the
        profiler's clock, visible as OPEN while it lasts, its seconds
        handed to ``wait_acc`` (the worker's ``grant_wait`` phase)."""
        kind = PHASE_RESOURCE[phase]
        unit = TaskUnitInfo(self.job_id, self.executor_id, kind, next(self._seq))
        with trace_span("taskunit.wait", record=False, acc=wait_acc,
                        job_id=self.job_id, kind=kind):
            if abort is None:
                self._global.wait_ready(unit)
            else:
                while not self._global.wait_ready(unit, timeout=poll):
                    if abort():
                        if self._global.cancel_wait(unit):
                            self._global.on_unit_finished(unit)  # raced grant
                        raise TaskUnitAborted(
                            f"{self.job_id}/{self.executor_id} {kind} "
                            "admission wait aborted"
                        )
            self._local.acquire(kind)
        try:
            yield
        finally:
            self._local.release(kind)
            # onTaskUnitFinished: releases the fairness meter (see
            # GlobalTaskUnitScheduler.on_unit_finished)
            self._global.on_unit_finished(unit)

    def contended(self) -> bool:
        """More than one tenant registered — workers shrink their
        in-flight dispatch windows so no tenant's units queue behind a
        deep single-job device backlog."""
        return self._global.num_jobs() > 1

    def report_unit_cost(self, seconds: float) -> None:
        """Forward this job's measured per-unit seconds to the fair-queue
        deficit accounting."""
        self._global.report_unit_cost(self.job_id, seconds)

    def peer_unit_cost(self) -> float:
        """Largest peer unit cost (see GlobalTaskUnitScheduler) — the
        group-sizing hint for cheap tenants."""
        return self._global.peer_unit_cost(self.job_id)
