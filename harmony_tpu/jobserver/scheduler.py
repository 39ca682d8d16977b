"""Pluggable global job scheduling.

Parity with the reference's JobScheduler SPI (jobserver/driver/
JobScheduler.java: onJobArrival / onJobFinish / onResourceChange, pluggable
via the -scheduler flag, bin/start_jobserver.sh:21) and its default
SchedulerImpl, which runs every job immediately on ALL executors —
multi-tenant overlap on the shared pool (SchedulerImpl.java:28-66).

Also ships a FIFO-exclusive policy (jobs get the whole pool one at a time)
as the second built-in, mirroring how the reference's pluggability was
actually used.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

from harmony_tpu.config.params import JobConfig

# Callback the server provides: actually launch the job on these executors.
LaunchFn = Callable[[JobConfig, List[str]], None]


class JobScheduler:
    """SPI. Implementations decide when a job runs and on which executors."""

    def bind(self, executor_ids: List[str], launch: LaunchFn) -> None:
        self._executors = list(executor_ids)
        self._launch = launch
        # eager policy-target init: plan_grant (the policy thread) and
        # reacquire (dispatch threads) both touch the map, and the base
        # class is lockless — creating it HERE, before any job exists,
        # removes the lazy-init race that could silently drop a pin
        if getattr(self, "_policy_target_map", None) is None:
            self._policy_target_map: Dict[str, Tuple[List[str], bool]] = {}

    # -- policy-engine SPI (jobserver/policy.py) -------------------------

    def _policy_targets(self) -> Dict[str, Tuple[List[str], bool]]:
        """The ``job_id -> (executors, shared)`` map of policy-planned
        grants, created in :meth:`bind` (lazy fallback for direct-
        constructed test doubles that never bind)."""
        t = getattr(self, "_policy_target_map", None)
        if t is None:
            t = self._policy_target_map = {}
        return t

    def plan_grant(self, job_id: str, executors: Optional[List[str]],
                   shared: bool = False) -> None:
        """Pin the NEXT :meth:`reacquire` grant for ``job_id`` to this
        executor set (the policy engine's actuator: the grant lands when
        the elastic fence ends the running attempt). ``shared=True``
        allows the grant to OVERLAP other tenants' slices (pack/preempt
        — ShareAll-style sharing arbitrated by the TaskUnit fair
        queue). ``executors=None`` clears the pin. One-shot: consumed by
        whichever reacquire runs next for the job."""
        if executors is None:
            self._policy_targets().pop(job_id, None)
        else:
            self._policy_targets()[job_id] = (list(executors), bool(shared))

    def planned_grant(self, job_id: str
                      ) -> Optional[Tuple[List[str], bool]]:
        return self._policy_targets().get(job_id)

    def idle_executors(self) -> List[str]:
        """Executors no running job holds — the policy engine's grow
        fodder. Overlap schedulers (share-all) have no idle notion and
        report none."""
        return []

    def idle_units(self) -> List[List[str]]:
        """Idle capacity in GRANT units: the indivisible executor
        groups a policy grow may take (one executor each by default;
        whole host processes on a process-carved pod — the planner must
        never split a process between exclusive tenants)."""
        return [[e] for e in self.idle_executors()]

    def queued_jobs(self) -> List[JobConfig]:
        """Arrivals waiting for capacity (the policy engine's contention
        signal). Non-queueing schedulers report none."""
        return []

    def on_job_arrival(self, config: JobConfig) -> None:
        raise NotImplementedError

    def on_job_finish(self, job_id: str) -> None:
        raise NotImplementedError

    def on_resource_change(self, executor_ids: List[str]) -> None:
        self._executors = list(executor_ids)

    def retire(self, executor_ids: List[str]) -> None:
        """Remove executors from future grants (a pod follower died or
        went silent; its devices cannot serve while it is gone). Running
        grants are untouched — their jobs fail through their own paths.
        No longer permanent: :meth:`restore` reverses it when a silenced
        follower's heartbeats resume or a replacement process JOINs."""
        gone = set(executor_ids)
        self._executors = [e for e in self._executors if e not in gone]

    def restore(self, executor_ids: List[str]) -> None:
        """Re-admit previously retired executors (elastic rehabilitation:
        a confined follower proved itself alive again, or a replacement
        JOINed with the same executor allocation order)."""
        known = set(self._executors)
        self._executors.extend(e for e in executor_ids if e not in known)

    def reacquire(self, job_id: str, preferred: List[str]) -> List[str]:
        """Elastic in-place recovery grant: the SAME submission needs
        executors for its next attempt, preferring the previous grant's
        survivors (minimal data movement). Returns the granted executor
        ids ([] = nothing available; recovery fails over to a plain job
        failure). A policy-planned grant (:meth:`plan_grant`) wins when
        one is pinned — that is how the policy engine's fenced actions
        land. Default (share-all semantics): the surviving preferred
        set, else every live executor."""
        tgt = self._policy_targets().pop(job_id, None)
        if tgt is not None:
            execs = [e for e in tgt[0] if e in self._executors]
            if execs:
                return execs
        alive = [e for e in preferred if e in self._executors]
        return alive or list(self._executors)


class ShareAllScheduler(JobScheduler):
    """Default: every job starts immediately on ALL executors (the
    reference's SchedulerImpl multi-tenant overlap)."""

    def on_job_arrival(self, config: JobConfig) -> None:
        self._launch(config, list(self._executors))

    def on_job_finish(self, job_id: str) -> None:
        pass


class FifoExclusiveScheduler(JobScheduler):
    """One job at a time on the whole pool; arrivals queue."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._queue: Deque[JobConfig] = deque()
        self._running: Optional[str] = None

    def on_job_arrival(self, config: JobConfig) -> None:
        with self._lock:
            if self._running is not None:
                self._queue.append(config)
                return
            self._running = config.job_id
        self._launch(config, list(self._executors))

    def on_job_finish(self, job_id: str) -> None:
        nxt = None
        with self._lock:
            if self._running == job_id:
                self._running = None
                if self._queue:
                    nxt = self._queue.popleft()
                    self._running = nxt.job_id
        if nxt is not None:
            self._launch(nxt, list(self._executors))


class CarveScheduler(JobScheduler):
    """Mesh carving: every job gets a DISJOINT slice of the executor pool
    (the BASELINE north-star sharing mode — jobs share the pod by slicing
    the mesh, not by overlapping on every chip like ShareAll). Fair share
    at arrival = pool // (running jobs + 1), floored at ``min_slice``;
    arrivals that cannot get ``min_slice`` free executors queue FIFO, and
    a finishing job returns its slice (launching queued jobs first)."""

    def __init__(self, min_slice: int = 1, max_share: Optional[int] = None) -> None:
        """``max_share`` caps any one job's slice — without it the FIRST
        arrival's fair share is the whole idle pool and later jobs queue
        behind it; set e.g. pool//2 to leave room for concurrent tenants."""
        if min_slice < 1:
            raise ValueError("min_slice must be >= 1")
        if max_share is not None and max_share < min_slice:
            raise ValueError("max_share must be >= min_slice")
        self.min_slice = min_slice
        self.max_share = max_share
        self._lock = threading.Lock()
        self._free: List[str] = []
        self._slices: Dict[str, List[str]] = {}
        self._queue: Deque[JobConfig] = deque()

    def bind(self, executor_ids: List[str], launch: LaunchFn) -> None:
        super().bind(executor_ids, launch)
        self._free = list(executor_ids)

    def retire(self, executor_ids: List[str]) -> None:
        """Dead executors must leave the FREE pool too (under the lock,
        against concurrent slice grants), or the next _take_slice hands
        them to a job that can only fail pod admission."""
        gone = set(executor_ids)
        with self._lock:
            super().retire(executor_ids)
            self._free = [e for e in self._free if e not in gone]

    def restore(self, executor_ids: List[str]) -> None:
        """Rehabilitated executors rejoin the free pool (and may unblock
        queued arrivals) unless some job's live slice already claims
        them."""
        with self._lock:
            super().restore(executor_ids)
            sliced = {e for sl in self._slices.values() for e in sl}
            self._free.extend(
                e for e in executor_ids
                if e not in sliced and e not in self._free
            )
            launches = self._drain_queue_locked()
        for cfg, sl in launches:
            self._launch(cfg, sl)

    def _claim_target_locked(self, job_id: str,
                             tgt: "Tuple[List[str], bool]") -> List[str]:
        """Under the lock: land a policy-planned grant. Exclusive
        targets take only still-free executors (a concurrent arrival
        may have claimed some since the plan); shared targets overlap
        live slices by design (pack/preempt). [] = plan no longer
        satisfiable — the caller falls back to the normal grant."""
        execs, shared = tgt
        known = set(self._executors)
        execs = [e for e in execs if e in known]
        if not shared:
            free = set(self._free)
            execs = [e for e in execs if e in free]
        if not execs:
            return []
        taken = set(execs)
        self._free = [e for e in self._free if e not in taken]
        self._slices[job_id] = execs
        return execs

    def idle_executors(self) -> List[str]:
        with self._lock:
            return list(self._free)

    def queued_jobs(self) -> List[JobConfig]:
        with self._lock:
            return list(self._queue)

    def reacquire(self, job_id: str, preferred: List[str]) -> List[str]:
        """In-place recovery grant: a policy-planned target wins when
        still satisfiable; else take the still-free survivors of the
        previous grant; if none survive, carve a fresh slice. The grant
        registers under ``job_id`` so the attempt's on_job_finish returns
        it like any slice (each attempt pairs one reacquire with one
        finish)."""
        with self._lock:
            tgt = self._policy_targets().pop(job_id, None)
            if tgt is not None:
                take = self._claim_target_locked(job_id, tgt)
                if take:
                    return take
            free = set(self._free)
            take = [e for e in preferred if e in free]
            if not take:
                take = self._take_slice() or []
            else:
                taken = set(take)
                self._free = [e for e in self._free if e not in taken]
            if take:
                self._slices[job_id] = take
        return take

    def _take_slice(self) -> Optional[List[str]]:
        """Under the lock: carve the next job's slice or None to queue."""
        share = max(
            self.min_slice, len(self._executors) // (len(self._slices) + 1)
        )
        if self.max_share is not None:
            share = min(share, self.max_share)
        if len(self._free) < self.min_slice:
            return None
        take = self._free[: min(share, len(self._free))]
        del self._free[: len(take)]
        return take

    def on_job_arrival(self, config: JobConfig) -> None:
        with self._lock:
            sl = self._take_slice()
            if sl is None:
                self._queue.append(config)
                return
            self._slices[config.job_id] = sl
        self._launch(config, sl)

    def on_job_finish(self, job_id: str) -> None:
        launches = []
        with self._lock:
            known = set(self._executors)
            mine = self._slices.pop(job_id, [])
            # only still-provisioned executors return to the pool (some
            # may have departed via on_resource_change while the job
            # ran), and never ones another live slice still holds — a
            # shared (packed) grant overlaps slices, so the LAST tenant
            # off an executor frees it
            held = {e for sl in self._slices.values() for e in sl}
            self._free.extend(
                e for e in mine
                if e in known and e not in held and e not in self._free
            )
            launches = self._drain_queue_locked()
        for cfg, sl in launches:
            self._launch(cfg, sl)

    def _drain_queue_locked(self):
        """Under the lock: carve slices for queued jobs while any fit;
        returns the (config, slice) launches to fire outside the lock."""
        launches = []
        while self._queue:
            sl = self._take_slice()
            if sl is None:
                break
            cfg = self._queue.popleft()
            self._slices[cfg.job_id] = sl
            launches.append((cfg, sl))
        return launches

    def on_resource_change(self, executor_ids: List[str]) -> None:
        """Reconcile the free pool with the new executor set: departed
        executors leave _free immediately (running jobs keep their slices
        until they finish — a live re-carve is plan-engine territory), and
        arrivals join _free, possibly unblocking the queue."""
        launches = []
        with self._lock:
            super().on_resource_change(executor_ids)
            known = set(executor_ids)
            sliced = {e for sl in self._slices.values() for e in sl}
            self._free = [e for e in self._free if e in known]
            self._free.extend(
                e for e in executor_ids
                if e not in sliced and e not in self._free
            )
            launches = self._drain_queue_locked()
        for cfg, sl in launches:
            self._launch(cfg, sl)

    def slice_of(self, job_id: str) -> List[str]:
        with self._lock:
            return list(self._slices.get(job_id, []))


class ProcessCarveScheduler(CarveScheduler):
    """Mesh carving in whole-HOST-PROCESS units, for multi-host pods.

    On a pod, two concurrent jobs are hazard-free only when their XLA
    programs never share a process: disjoint process sets cannot form a
    cross-process enqueue-order cycle (see jobserver/pod.py's admission
    rule). This scheduler guarantees that shape by construction — every
    slice is a set of COMPLETE processes, so the PodJobServer dispatches
    all carved jobs concurrently. Fair share at arrival = total processes
    // (running jobs + 1), floored at ``min_procs``.

    The executor->process map is injected by the server after allocation
    (``set_process_map``); until then the scheduler treats the pool as one
    process (degenerating to FIFO-exclusive, which is safe)."""

    def __init__(self, min_procs: int = 1, max_procs: Optional[int] = None) -> None:
        super().__init__(min_slice=1, max_share=None)
        if min_procs < 1:
            raise ValueError("min_procs must be >= 1")
        if max_procs is not None and max_procs < min_procs:
            raise ValueError("max_procs must be >= min_procs")
        self.min_procs = min_procs
        self.max_procs = max_procs
        self._proc_of: Dict[str, int] = {}

    def set_process_map(self, proc_of: Dict[str, int]) -> None:
        """executor id -> process index (from Executor.device.process_index)."""
        with self._lock:
            self._proc_of = dict(proc_of)

    def reacquire(self, job_id: str, preferred: List[str]) -> List[str]:
        """Whole-process recovery grant: survivors are kept only as
        COMPLETE free processes (a partial process in a recovery grant
        would break the disjoint-process concurrency guarantee every
        carved tenant relies on); otherwise a fresh whole-process slice
        is carved. A policy-planned grant wins when satisfiable — the
        planner composes pod targets from :meth:`idle_units` (whole
        processes), and :meth:`_claim_target_locked` re-validates the
        shape as the backstop."""
        with self._lock:
            tgt = self._policy_targets().pop(job_id, None)
            if tgt is not None:
                take = self._claim_target_locked(job_id, tgt)
                if take:
                    return take
            free = set(self._free)
            wanted = set(preferred)
            members: Dict[int, List[str]] = {}
            for e in self._executors:
                members.setdefault(self._proc_of.get(e, 0), []).append(e)
            take = [
                e for p, mem in sorted(members.items())
                # the WHOLE process must be both preferred and free — a
                # half-claimed process is exactly the shape the carve
                # exists to forbid
                if mem and wanted >= set(mem) and free >= set(mem)
                for e in mem
            ]
            if not take:
                take = self._take_slice() or []
            else:
                taken = set(take)
                self._free = [e for e in self._free if e not in taken]
            if take:
                self._slices[job_id] = take
        return take

    def _claim_target_locked(self, job_id: str,
                             tgt: "Tuple[List[str], bool]") -> List[str]:
        """Whole-process backstop for policy grants: an EXCLUSIVE
        target that splits any process is rejected outright (the
        normal reacquire path then grants) — a half-claimed process is
        exactly the shape this scheduler exists to forbid. Shared
        (pack/preempt) targets overlap by design and pass through."""
        execs, shared = tgt
        if not shared:
            members: Dict[int, List[str]] = {}
            for e in self._executors:
                members.setdefault(self._proc_of.get(e, 0), []).append(e)
            want = set(execs) & set(self._executors)
            for p, mem in members.items():
                if want & set(mem) and not want >= set(mem):
                    return []
        return super()._claim_target_locked(job_id, tgt)

    def idle_units(self) -> List[List[str]]:
        """Idle capacity in whole-process units — the only grant shape
        a policy grow may take here."""
        with self._lock:
            members: Dict[int, List[str]] = {}
            for e in self._executors:
                members.setdefault(self._proc_of.get(e, 0), []).append(e)
            free = set(self._free)
            return [list(mem) for _p, mem in sorted(members.items())
                    if mem and free >= set(mem)]

    def _take_slice(self) -> Optional[List[str]]:
        """Under the lock: carve whole free processes or None to queue."""
        proc_members: Dict[int, List[str]] = {}
        for e in self._executors:
            proc_members.setdefault(self._proc_of.get(e, 0), []).append(e)
        free = set(self._free)
        free_procs = sorted(
            p for p, members in proc_members.items()
            if all(e in free for e in members)
        )
        share = max(
            self.min_procs, len(proc_members) // (len(self._slices) + 1)
        )
        if self.max_procs is not None:
            share = min(share, self.max_procs)
        if len(free_procs) < self.min_procs:
            return None
        take_procs = free_procs[: min(share, len(free_procs))]
        take = [e for p in take_procs for e in proc_members[p]]
        self._free = [e for e in self._free if e not in set(take)]
        return take


_SCHEDULERS: Dict[str, type] = {
    "share_all": ShareAllScheduler,
    "fifo": FifoExclusiveScheduler,
    "carve": CarveScheduler,
    "pod_carve": ProcessCarveScheduler,
}


def make_scheduler(name: str) -> JobScheduler:
    """Scheduler-by-name (the -scheduler flag analogue)."""
    try:
        return _SCHEDULERS[name]()
    except KeyError:
        raise KeyError(f"unknown scheduler {name!r}; have {sorted(_SCHEDULERS)}") from None
