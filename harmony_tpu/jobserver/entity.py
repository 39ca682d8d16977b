"""JobEntity — app-type abstraction between the JobServer and frameworks.

Parity with the reference's JobEntity/JobMaster pair (jobserver/driver/
JobEntity.java, JobMaster.java): each app type implements table/executor
setup plus a run loop. DolphinJobEntity mirrors the reference's
(dolphin/jobserver/DolphinJobEntity.java:40-168): model table created on the
job's executors ("servers"), input provisioned to workers, PS-collocation
only (servers == workers == all granted executors), and input-table reuse
across jobs when the table id matches.

The trainer and its data come from the serializable JobConfig: dotted-path
symbols (config.base.resolve_symbol) stand in for Tang's
bind-implementation-by-class-name.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import numpy as np

from harmony_tpu.config.base import resolve_symbol
from harmony_tpu.config.params import JobConfig, TrainerParams
from harmony_tpu.data.devcache import host_data as _HOST_DATA_CACHE
from harmony_tpu.dolphin.data import TrainingDataProvider
from harmony_tpu.dolphin.master import (
    BatchProgressTracker,
    MiniBatchController,
    WorkerStateManager,
)
from harmony_tpu.dolphin.trainer import TrainerContext
from harmony_tpu.dolphin.worker import WorkerTasklet
from harmony_tpu.metrics.collector import MetricCollector
from harmony_tpu.runtime.master import ETMaster, TableHandle
from harmony_tpu.tracing.span import job_stage
from harmony_tpu.runtime.taskunit import (
    GlobalTaskUnitScheduler,
    LocalTaskUnitScheduler,
    TaskUnitClient,
)


class JobEntity:
    """SPI: one instance per submitted job. ``chkp_root`` is where an app
    type may durably stage model checkpoints (unused by apps that have no
    model table to chain)."""

    def __init__(self, config: JobConfig, chkp_root: Optional[str] = None) -> None:
        self.config = config
        self.chkp_root = chkp_root

    def setup(self, master: ETMaster, executor_ids: List[str]) -> None:
        raise NotImplementedError

    def run(self) -> Dict[str, Any]:
        raise NotImplementedError

    def cleanup(self) -> None:
        raise NotImplementedError

    def deferred_evaluation(self):
        """Optional: return a closure(master) the JobServer should run at
        graceful shutdown (ref: deferred model evaluation,
        JobServerDriver.java:178-214). Default: nothing deferred."""
        return None


class DolphinJobEntity(JobEntity):
    def __init__(
        self,
        config: JobConfig,
        global_taskunit: Optional[GlobalTaskUnitScheduler] = None,
        local_taskunit: Optional[LocalTaskUnitScheduler] = None,
        metric_sink=None,
        chkp_root: Optional[str] = None,
        metric_manager=None,
        pod_plan_sink=None,
        pod_eval_channel=None,
        pod_unit_scope=None,
        pod_unit_contended=None,
    ) -> None:
        super().__init__(config, chkp_root)
        self._global_tu = global_taskunit
        self._local_tu = local_taskunit
        self._metric_sink = metric_sink
        self._metric_manager = metric_manager
        # Leader-side pod channels (present only on the pod leader for
        # single-dispatch-thread jobs): the plan channel lets the
        # optimizer loop run on multi-process grants; the eval channel
        # turns the shutdown-stage deferred model eval into a pod
        # collective (followers replay the same restores/evaluations in
        # lockstep).
        self._pod_plan_sink = pod_plan_sink
        self._pod_eval_channel = pod_eval_channel
        # Cross-job unit protocol (EVERY participating process of a
        # multi-process pod job — runtime/podunits.py): all of this job's
        # global-dispatch regions run inside leader-granted units so
        # overlapping tenants enqueue in one pod-wide order.
        self._pod_unit_scope = pod_unit_scope
        self._pod_unit_contended = pod_unit_contended
        self._chkp_mgr = None
        self._chkp_chain = None
        self._chkp_dir: Optional[str] = None
        self._master: Optional[ETMaster] = None
        self._handle: Optional[TableHandle] = None
        self._local_handle: Optional[TableHandle] = None
        self._workers: List[WorkerTasklet] = []
        self._ctrl: Optional[MiniBatchController] = None
        self.progress: Optional[BatchProgressTracker] = None
        self._applied_plans: List[Dict[str, Any]] = []  # pod reshard log
        # resume_from_chain: epoch to resume at + the restored chain's
        # global counter (so the continued chain keeps monotonic ids)
        self._starting_epoch = 0
        self._chkp_counter_base = 0
        #: elastic recovery accounting (restore stats + shrink plan) —
        #: set by _restore_elastic, surfaced in the job result
        self._elastic_restore: Optional[Dict[str, Any]] = None
        #: the manifest of the chain entry the model table was restored
        #: from (``_restore_chain`` / ``_restore_elastic``); it names the
        #: layout the entry holds (``_in_trainer_layout``)
        self._restored_entry = None

    # -- setup -----------------------------------------------------------

    def _make_trainer(self):
        if not self.config.trainer:
            raise ValueError(f"job {self.config.job_id}: no trainer configured")
        cls = resolve_symbol(self.config.trainer)
        return cls(**self.config.params.app_params)

    def _data_source_key(self) -> "tuple | None":
        """Identity of this job's data source: the generator/loader dotted
        path + canonicalized args. Jobs sharing it reuse device-resident
        batches (data/devcache) — the analogue of the reference's same-id
        input-table reuse (DolphinJobEntity.java:76-121). None when args
        aren't canonicalizable (unhashable values)."""
        user = self.config.user

        def tag(v):
            # type-tagged recursively (see Trainer.jit_signature: True == 1
            # == 1.0 must not collide — a data_fn can behave differently per
            # type, and (1,) == (1.0,) collides the same way)
            if isinstance(v, (list, tuple)):
                return (type(v).__name__, tuple(tag(x) for x in v))
            return (type(v).__name__, v)

        try:
            args = tuple(sorted(
                (k, tag(v)) for k, v in user.get("data_args", {}).items()
            ))
            hash(args)
        except TypeError:
            return None
        return (user.get("data_fn"), args)

    def _make_data(self) -> List[np.ndarray]:
        """Materialize the job's dataset. Jobs with the SAME (data_fn,
        data_args) are defined to see the same dataset — the host arrays are
        cached under the source key (and the per-batch device copies under
        the same key in data/devcache), mirroring the reference's same-id
        input-table sharing. Non-deterministic sources that must differ per
        job should vary their args (e.g. a seed) to opt out."""
        user = self.config.user
        if "data_fn" not in user:
            raise ValueError(f"job {self.config.job_id}: user.data_fn missing")
        key = self._data_source_key()
        if key is not None:
            cached = _HOST_DATA_CACHE.get(key)
            if cached is not None:
                return cached
        fn = resolve_symbol(user["data_fn"])
        out = fn(**user.get("data_args", {}))
        arrays = [
            np.asarray(a)
            for a in (out if isinstance(out, (tuple, list)) else (out,))
        ]
        if key is not None:
            _HOST_DATA_CACHE.put(key, arrays)
        return arrays

    def _make_input_feed(self, provider, lo: int, hi: int, nb: int):
        """Input-service feed for one worker's slice — or None, which
        keeps in-process assembly. None whenever the job did not opt in
        (``TrainerParams.input_service`` / HARMONY_INPUT_SERVICE), the
        dataset identity cannot cross the wire, or no service endpoint
        is known (embedded service not running and no
        HARMONY_INPUT_SERVICE_ADDR) — the service is an optimization,
        never a dependency."""
        from harmony_tpu import inputsvc

        if not inputsvc.enabled_for(self.config.params):
            return None
        user = self.config.user
        if "data_fn" not in user:
            return None
        if inputsvc.default_endpoint() is None:
            return None
        try:
            spec = inputsvc.DatasetSpec.build(
                user["data_fn"], user.get("data_args", {}),
                lo=lo, hi=hi, num_mini_batches=nb,
                shuffle=provider.is_shuffling,
                seed=provider.seed,
            )
        except TypeError:
            return None  # non-canonical data_args: no wire identity
        return inputsvc.TrainerInputFeed(
            spec, provider, tenant=self.config.job_id,
        )

    def setup(self, master: ETMaster, executor_ids: List[str]) -> None:
        # Table creation dispatches multi-device init programs — under
        # cross-job pod tenancy that region must hold a dispatch unit like
        # any other (a concurrent tenant's enqueue interleaving with it
        # would diverge across processes).
        import contextlib

        scope = (self._pod_unit_scope() if self._pod_unit_scope is not None
                 else contextlib.nullcontext())
        with scope:
            self._setup_inner(master, executor_ids)

    def _setup_inner(self, master: ETMaster, executor_ids: List[str]) -> None:
        with job_stage(self.config.job_id, "table_create"):
            self._create_tables(master, executor_ids)
        self._executor_ids = list(executor_ids)
        with job_stage(self.config.job_id, "data_load"):
            self._data_arrays = self._make_data()

    def _create_tables(self, master: ETMaster,
                       executor_ids: List[str]) -> None:
        self._master = master
        cfg = self.config
        data_axis = max(1, cfg.user.get("data_axis", 1))
        probe = self._make_trainer()  # one probe serves all schema queries
        #: the name of the layout the trainer keeps its model in, which
        #: every chain entry of this job records beside its epoch and a
        #: restore compares (``PyTreeTrainer.table_layout``; None: the
        #: table's rows are keys, there is nothing to lay out)
        self._table_layout = getattr(probe, "table_layout", None)
        self._restored_entry = None
        if cfg.tables:
            # Explicit table id => shared-table semantics: reuse if it exists
            # (the reference reuses same-id tables across jobs,
            # DolphinJobEntity.java:76-121 — deliberately shared state).
            self._handle, _ = master.get_or_create_table(
                cfg.tables[0], executor_ids, data_axis
            )
        elif cfg.user.get("elastic_recovery"):
            # Elastic in-place recovery (jobserver/elastic.py): the SAME
            # submission continues on a changed executor set — partial
            # restore reads only the blocks this process cannot source
            # from its recovery cache (O(lost bytes), the shrink
            # contract), at the epoch floor of the last committed chain
            # entry.
            if getattr(probe, "uses_local_table", False):
                raise ValueError(
                    f"job {cfg.job_id}: elastic recovery does not cover "
                    "worker-local tables (their state is not chained)"
                )
            self._handle, self._starting_epoch, self._chkp_counter_base = (
                self._restore_elastic(master, executor_ids, data_axis)
            )
        elif cfg.user.get("resume_from_chain"):
            # Auto-resume: rebuild the model table from the job's LAST
            # committed chain checkpoint (restore-by-state, ref:
            # ETMaster.createTable(chkpId, associators)) and continue from
            # the epoch it covers. The restore is cross-topology, so the
            # grant may be a different executor set than the one that
            # wrote the chain (a shrunk pod after a follower death).
            if getattr(probe, "uses_local_table", False):
                raise ValueError(
                    f"job {cfg.job_id}: resume_from_chain does not cover "
                    "worker-local tables (their state is not chained)"
                )
            self._handle, self._starting_epoch, self._chkp_counter_base = (
                self._restore_chain(master, executor_ids, data_axis)
            )
        else:
            # Trainer-default schema => PRIVATE model table: namespace by job
            # id so two concurrent jobs of the same app never collide on the
            # trainer's fixed default id (e.g. two MLR jobs both saying
            # "mlr-model").
            table_cfg = probe.model_table_config()
            table_cfg = table_cfg.replace(
                table_id=f"{cfg.job_id}:{table_cfg.table_id}"
            )
            self._handle = master.create_table(table_cfg, executor_ids, data_axis)
        if self._restored_entry is not None:  # either restore above
            self._handle = self._in_trainer_layout(
                master, self._handle, probe, executor_ids, data_axis)
        self._note_table_layout(probe)
        self._trainer_factory = lambda: (
            resolve_symbol(cfg.trainer)(**cfg.params.app_params)
        )
        # Worker-local model table (ref: DolphinJobEntity's optional
        # local-model table, created on workers alongside the input table).
        self._local_handle = None
        if getattr(probe, "uses_local_table", False):
            local_cfg = probe.local_table_config()
            local_cfg = local_cfg.replace(table_id=f"{cfg.job_id}:{local_cfg.table_id}")
            self._local_handle = master.create_table(local_cfg, executor_ids, data_axis)

    def _note_table_layout(self, probe) -> None:
        """STATUS ``tenants.<job>.table_layout`` / the gauge
        ``harmony_table_tile_exact`` (metrics/table_layout.py), made once
        here, where the table is created or restored. A trainer whose
        sections do not fit a restored table's row count refuses it here,
        by name (PyTreeTrainer.section_stride)."""
        from harmony_tpu.metrics import table_layout
        from harmony_tpu.table import TableSpec

        spec = getattr(self._handle.table, "spec", None)
        if isinstance(spec, TableSpec):  # hash tables have no block rows
            stride_of = getattr(probe, "section_stride", None)
            leaf_rows = getattr(probe, "leaf_rows", None)
            table_layout.note(
                self.config.job_id, spec,
                stride_of(spec.config.capacity) if stride_of else None,
                leaf_rows.record() if leaf_rows is not None else None)

    # -- run (the DolphinMaster.start analogue) --------------------------

    def _in_trainer_layout(self, master: ETMaster, handle: TableHandle,
                           probe, executor_ids: List[str],
                           data_axis: int) -> TableHandle:
        """The table just restored from a chain entry
        (``self._restored_entry``, its manifest), in the layout ``probe``
        trains on. An entry records its layout's name beside its
        epoch (``app_meta["layout"]``); one written before the leaves were
        row ranges records none and holds them raveled end to end — row
        counts can coincide while offsets differ, so the name decides,
        never the capacity. Such a table is converted once, here, on the
        host (``PyTreeTrainer.rows_from_flat_chain``: p, m and v alike)
        into a fresh table of the trainer's own schema; a name the
        trainer does not know is refused with both named."""
        info, mine = self._restored_entry, self._table_layout
        theirs = (info.app_meta or {}).get("layout")
        if mine is None or theirs == mine:
            return handle
        from harmony_tpu.models.pytree_trainer import FLAT
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        why = None
        if theirs not in (None, FLAT):
            why = "the trainer does not know that layout"
        elif mesh_spans_processes(handle.table.mesh):
            why = ("the conversion from " + repr(FLAT) + " runs on one "
                   "host: resume the chain on a single process once")
        if why:
            raise ValueError(
                f"job {self.config.job_id}: chain entry {info.chkp_id} holds "
                f"its model table in layout {theirs or FLAT!r}, the trainer "
                f"{type(probe).__name__} reads {mine!r}: {why}")
        from harmony_tpu.jobserver.joblog import job_logger

        rows = probe.rows_from_flat_chain(
            np.asarray(handle.table.pull_array()))
        cfg = probe.model_table_config(table_id=handle.table_id)
        handle.drop()
        handle = master.create_table(cfg, executor_ids, data_axis)
        stride = probe.section_stride(cfg.capacity)
        for first in range(0, rows.shape[0], stride):  # a section a put:
            # the put holds the table twice and its rows once
            part = rows[first:first + stride]
            handle.table.multi_put(
                list(range(first, first + part.shape[0])), part)
        job_logger(self.config.job_id).info(
            "chain entry %s converted from layout %r to %r (%d -> %d rows)",
            info.chkp_id, FLAT, mine, info.table_config.capacity,
            cfg.capacity)
        return handle

    def _restore_chain(self, master: ETMaster, executor_ids: List[str],
                       data_axis: int):
        """Rebuild the model table from the MOST RECENTLY WRITTEN chain
        checkpoint (by the monotonic epoch tag; created_at tie-breaks —
        id counters are NOT a reliable epoch clock: the pod id scan skips
        past a stale run's ids, and a resubmitted single-process chain
        restarts its counter) and resume at the EPOCH the manifest
        records (chain entries carry app_meta={"epoch": e}; the snapshot
        covers epoch e, so training resumes at e+1).

        Exactness: single-worker resume is numerically identical to an
        uninterrupted run (the snapshot is a clean epoch cut). For
        multi-worker SSP jobs the snapshot is a CONSISTENT table state at
        the chief's hook slot that may already contain sibling pushes
        from their in-flight epoch; resuming replays those — approximate,
        exactly like the reference's StartingEpochIdx resume (workers
        restart from global MIN progress and re-apply beyond it), and
        acceptable under bounded-staleness semantics.

        Returns (handle, starting_epoch, counter_base)."""
        mgr, ordered, base = self._chain_scan("resume_from_chain")
        cfg = self.config
        from harmony_tpu.checkpoint.manager import CheckpointCorruptError
        from harmony_tpu.jobserver.joblog import job_logger

        failures = []
        for info in ordered:
            try:
                handle = mgr.restore(master, info.chkp_id, executor_ids,
                                     data_axis)
            except (CheckpointCorruptError, FileNotFoundError) as e:
                job_logger(cfg.job_id).warning(
                    "chain entry %s is corrupt/torn (%s: %s); quarantining "
                    "and falling back to the previous committed entry",
                    info.chkp_id, type(e).__name__, e,
                )
                failures.append((info.chkp_id, f"{type(e).__name__}: {e}"))
                mgr.quarantine(info.chkp_id)
                continue
            self._restored_entry = info
            return handle, int(info.app_meta["epoch"]) + 1, base
        raise ValueError(
            f"job {cfg.job_id}: every chain checkpoint failed integrity "
            f"on restore (all quarantined): {failures}"
        )

    def _chain_scan(self, why: str):
        """Shared chain discovery for resume_from_chain AND elastic
        recovery: epoch-tagged entries under this job's chkp root,
        newest-first by the MONOTONIC epoch tag (wall clock can regress
        across hosts/NTP steps and must never discard newer progress;
        created_at only tie-breaks entries claiming the same epoch —
        a resubmitted-from-scratch chain re-covering old ones), plus the
        continuation counter base (ids stay unique/ordered past EVERY
        existing entry; the epoch clock is the manifest tag, never the
        counter). Torn-manifest entries are quarantined during the scan.
        Returns (manager, ordered_infos, counter_base)."""
        from harmony_tpu.checkpoint.manager import (
            CheckpointCorruptError,
            CheckpointManager,
        )
        from harmony_tpu.jobserver.joblog import job_logger

        cfg = self.config
        if self.chkp_root is None:
            raise ValueError(
                f"job {cfg.job_id}: {why} needs the server's chkp_root "
                "(the chain lives there)"
            )
        mgr = CheckpointManager.for_job(self.chkp_root, cfg.job_id)
        prefix = f"{cfg.job_id}:"
        infos = []
        for cid in mgr.list_checkpoints():
            if not cid.startswith(prefix):
                continue
            try:
                info = mgr.info(cid)
            except CheckpointCorruptError as e:
                # torn manifest: this entry can never restore — quarantine
                # it NOW so no later scan trips on it either
                job_logger(cfg.job_id).warning(
                    "chain entry %s has a torn manifest (%s); quarantined",
                    cid, e,
                )
                mgr.quarantine(cid)
                continue
            if info.app_meta is None or "epoch" not in info.app_meta:
                continue  # not a chain entry (no epoch tag)
            infos.append(info)
        if not infos:
            raise ValueError(
                f"job {cfg.job_id}: {why} found no epoch-tagged chain "
                f"checkpoints under {self.chkp_root}"
            )

        def counter_of(cid: str) -> int:
            try:
                return int(cid.rsplit("-", 2)[1])
            except (ValueError, IndexError):
                return 0

        base = max(counter_of(i.chkp_id) for i in infos)
        # Newest-first with CORRUPTION FALLBACK (callers quarantine a
        # failing entry and try the previous committed one — losing one
        # epoch of progress beats failing the resume outright; anything
        # non-corruption aborts immediately: it would fail identically
        # on every entry).
        ordered = sorted(
            infos,
            key=lambda i: (int(i.app_meta["epoch"]), i.created_at),
            reverse=True,
        )
        return mgr, ordered, base

    def _restore_elastic(self, master: ETMaster, executor_ids: List[str],
                         data_axis: int):
        """The shrink/re-grow restore: newest committed chain entry,
        partial-read (recovery cache first, checkpoint storage only for
        what this process genuinely lost — manager.restore_partial), with
        the same newest->oldest corruption fallback as _restore_chain.
        Records the restore accounting (the O(lost-bytes) evidence) in
        ``self._elastic_restore`` for the job result. Returns
        (handle, starting_epoch, counter_base)."""
        from harmony_tpu import faults
        from harmony_tpu.checkpoint.manager import CheckpointCorruptError
        from harmony_tpu.jobserver.joblog import job_logger
        from harmony_tpu.table import ownership as _ownership

        cfg = self.config
        rec = cfg.user.get("elastic_recovery") or {}
        mgr, ordered, base = self._chain_scan("elastic recovery")
        failures = []
        for info in ordered:
            if faults.armed():
                faults.site("elastic.restore", chkp_id=info.chkp_id,
                            attempt=int(rec.get("attempt", 0)))
            try:
                handle, stats = mgr.restore_partial(
                    master, info.chkp_id, executor_ids, data_axis
                )
            except (CheckpointCorruptError, FileNotFoundError) as e:
                job_logger(cfg.job_id).warning(
                    "elastic recovery: chain entry %s is corrupt/torn "
                    "(%s: %s); quarantining and falling back",
                    info.chkp_id, type(e).__name__, e,
                )
                failures.append((info.chkp_id, f"{type(e).__name__}: {e}"))
                mgr.quarantine(info.chkp_id)
                continue
            lost_execs = [e for e in rec.get("lost_executors", [])
                          if e in info.executors]
            plan = None
            if lost_execs:
                try:
                    plan = _ownership.shrink_plan(
                        info.ownership, info.executors, lost_execs,
                        executor_ids,
                    )
                except ValueError:
                    plan = None
            self._elastic_restore = {
                "attempt": int(rec.get("attempt", 0)),
                "kind": rec.get("kind", "shrink"),
                "chkp_id": info.chkp_id,
                "resumed_epoch": int(info.app_meta["epoch"]) + 1,
                "executors": list(executor_ids),
                "lost_executors": list(lost_execs),
                "lost_block_count": (len(plan["lost"]) if plan else 0),
                **stats,
            }
            job_logger(cfg.job_id).event(
                "elastic_restore",
                recovery=self._elastic_restore["kind"],
                **{k: v for k, v in self._elastic_restore.items()
                   if k not in ("executors", "kind")})
            self._restored_entry = info
            return handle, int(info.app_meta["epoch"]) + 1, base
        raise ValueError(
            f"job {cfg.job_id}: every chain checkpoint failed integrity "
            f"during elastic recovery (all quarantined): {failures}"
        )

    def run(self) -> Dict[str, Any]:
        cfg = self.config
        params: TrainerParams = cfg.params
        # num_workers == 0 means "one worker per granted executor" (the
        # documented 'all executors' default, ref SchedulerImpl runs on all).
        num_workers = cfg.num_workers or len(self._executor_ids)
        nb = params.num_mini_batches
        from harmony_tpu.jobserver.joblog import job_logger

        job_logger(cfg.job_id).info(
            "training: %d worker(s), %d epoch(s) x %d mini-batch(es)",
            num_workers, params.num_epochs, nb,
        )
        # floor_batch: a RESUMED continuation (auto-resume / elastic
        # recovery) must never report an epoch floor below its resume
        # point — the pod plan/fence horizon check reads this
        self.progress = BatchProgressTracker(
            nb, floor_batch=self._starting_epoch * nb
        )
        # Model-checkpoint chaining (ref: ModelChkpManager wired by
        # DolphinMaster.start:186-189): snapshots run off the CHIEF worker's
        # epoch hook — one snapshot per job epoch, async writers.
        epoch_hook = None
        if params.model_chkp_period > 0:
            from harmony_tpu.parallel.mesh import mesh_spans_processes

            spans = mesh_spans_processes(self._handle.table.mesh)
            if spans:
                # Pod checkpoint chains ride the synchronous collective
                # path (ModelChkpManager.on_epoch -> CheckpointManager
                # pod branch). Legal for ANY worker count: the epoch hook
                # runs INSIDE the chief's turnstile turn (_finish_epoch),
                # the same deterministic cycle slot on every process —
                # the same argument that admits pod reshard plans. Needs
                # a SHARED chkp root (each process stages its own blocks
                # into one checkpoint directory).
                if self.chkp_root is None:
                    raise ValueError(
                        f"job {cfg.job_id}: pod checkpoint chains need a "
                        "SHARED chkp_root (per-process temp dirs would "
                        "each hold only a fragment of every checkpoint)"
                    )
                if params.offline_model_eval:
                    # Guards must be SYMMETRIC across processes — one
                    # process raising while its peers proceed into the
                    # job's collectives wedges the pod. Every process can
                    # evaluate the structural support condition itself:
                    # the grant must include the pod leader (process 0 —
                    # the only holder of the eval channel). Followers of
                    # a supported grant legitimately lack the channel
                    # (they replay on the EVAL_COLLECTIVE broadcast).
                    import jax as _jax

                    procs = {
                        d.process_index
                        for d in self._handle.table.mesh.devices.flat
                    }
                    if 0 not in procs:
                        raise ValueError(
                            f"job {cfg.job_id}: offline_model_eval needs "
                            "the grant to include the pod leader "
                            "(process 0), which runs the collective eval"
                        )
                    if (_jax.process_index() == 0
                            and self._pod_eval_channel is None):
                        raise ValueError(
                            f"job {cfg.job_id}: offline_model_eval on a "
                            "multi-process grant needs the pod eval "
                            "channel (running outside a PodJobServer?)"
                        )
            import os
            import tempfile

            from harmony_tpu.checkpoint.manager import CheckpointManager
            from harmony_tpu.dolphin.evaluator import ModelChkpManager

            root = self.chkp_root or tempfile.mkdtemp(
                prefix=f"harmony-chkp-{cfg.job_id}-"
            )
            self._chkp_dir = root
            self._chkp_mgr = CheckpointManager.for_job(root, cfg.job_id)
            if cfg.user.get("elastic_shrink"):
                # elastic jobs keep a host copy of THIS process's staged
                # blocks per chain entry (the recovery cache): a shrink
                # restore then reads only genuinely lost blocks from
                # storage — the O(lost-bytes) contract
                from harmony_tpu.jobserver import elastic as _elastic

                self._chkp_mgr.recovery_retain = _elastic.cache_enabled()
            if self._chkp_counter_base:
                # a RESUMED job continues its chain: counters (and the
                # epoch mapping a future resume derives from them) stay
                # monotonic across the restart
                self._chkp_mgr.advance_counter(self._chkp_counter_base)
            self._chkp_chain = ModelChkpManager(
                self._chkp_mgr, self._handle, period=params.model_chkp_period,
                layout=self._table_layout,
            )
            epoch_hook = self._chkp_chain.on_epoch
        tm_hook = self._make_table_metrics_hook()
        # Single-worker jobs have no MiniBatchController to feed the
        # progress tracker; feed it from the epoch hook so the pod plan
        # horizon check (schedule_pod_reshard) has a REAL observed floor
        # instead of a vacuous 0. Deferrable (host accounting only): under
        # multi-epoch windows the replay feeds it post-drain in order, so
        # the floor lags at most one window — conservative, never ahead.
        tracker_hook = None
        if num_workers == 1:
            _tracker, _wid0 = self.progress, f"{cfg.job_id}/w0"

            def tracker_hook(e: int) -> None:
                _tracker.on_batch(_wid0, (e + 1) * nb - 1)

        epoch_hook = self._compose_epoch_hooks(
            tracker_hook, epoch_hook, tm_hook, self._make_pod_plan_hook()
        )
        from harmony_tpu.jobserver import podplan

        plan_epoch_fn = (lambda: podplan.next_epoch(cfg.job_id))
        orchestrator = self._make_orchestrator()
        # Pod lockstep: a multi-worker job whose grant spans host processes
        # needs a deterministic dispatch schedule — every process runs the
        # same worker threads, and their global SPMD programs must enqueue
        # in the same order everywhere (dolphin/master.DispatchTurnstile).
        # The SSP slack is clamped to >=1 so the gate never blocks INSIDE a
        # turn (turnstile divergence is bounded by one turn anyway, which
        # is stricter than any slack); TaskUnit announcement is dropped —
        # the pod admission rule gives multi-process jobs exclusive
        # processes, so there are no tenants to interleave with.
        # user.force_lockstep opts a single-process job into the same
        # deterministic schedule — the reproducible-baseline switch pod
        # tests compare against (same schedule => identical numerics).
        # NOTE: lockstep jobs drop TaskUnit admission (a quorum wait
        # inside a turn deadlocks the cycle); on a pod the admission rule
        # gives multi-process jobs exclusive processes so nothing is lost,
        # but a force_lockstep job on a SHARED single-process server opts
        # out of the 1-CPU/2-NET interleaving contract with co-tenants —
        # it is a determinism knob, not a production scheduling mode.
        pod_lockstep = num_workers > 1 and (
            len({
                self._master.executor(e).device.process_index
                for e in self._executor_ids
            }) > 1
            or bool(cfg.user.get("force_lockstep"))
        )
        turnstile = None
        if pod_lockstep:
            from harmony_tpu.dolphin.master import DispatchTurnstile

            turnstile = DispatchTurnstile(
                [f"{cfg.job_id}/w{i}" for i in range(num_workers)]
            )
        self._ctrl = (
            MiniBatchController(
                max(params.clock_slack, 1) if pod_lockstep
                else params.clock_slack,
                params.num_epochs * nb,
                tracker=self.progress,
            )
            if num_workers > 1
            else None
        )
        wsm = WorkerStateManager([f"{cfg.job_id}/w{i}" for i in range(num_workers)])
        # Chief-only global init: others wait here until it has run
        # (see WorkerTasklet.global_init).
        init_barrier = threading.Barrier(num_workers)
        if self._global_tu is not None:
            self._global_tu.on_job_start(
                cfg.job_id, [f"{cfg.job_id}/w{i}" for i in range(num_workers)]
            )
        n = self._data_arrays[0].shape[0]
        if n < num_workers * nb:
            raise ValueError(
                f"job {cfg.job_id}: {n} examples cannot feed {num_workers} "
                f"workers x {nb} mini-batches"
            )
        per = n // num_workers
        results: Dict[str, Any] = {}
        errors: List[BaseException] = []
        # Trace threading: worker threads cannot inherit the dispatch
        # span's contextvar, so capture its wire context HERE (the
        # dispatch thread) and hand it down; the elastic attempt index
        # labels every worker span/histogram with the job@aN key.
        from harmony_tpu.jobserver import elastic as _elastic
        from harmony_tpu.tracing.span import wire_context

        trace_parent = wire_context()
        attempt = _elastic.attempt_of(cfg)

        def run_worker(idx: int) -> None:
            wid = f"{cfg.job_id}/w{idx}"
            try:
                wsm.await_barrier(wid, "INIT")
                # Last worker takes the remainder so no example is dropped.
                hi = (idx + 1) * per if idx < num_workers - 1 else n
                sl = slice(idx * per, hi)
                src = self._data_source_key()
                data = TrainingDataProvider(
                    [a[sl] for a in self._data_arrays], nb,
                    dataset_key=(
                        None if src is None else (src, sl.start, hi, nb)
                    ),
                )
                input_feed = self._make_input_feed(data, sl.start, hi, nb)
                ctx = TrainerContext(
                    params=params,
                    model_table=self._handle.table,
                    local_table=(
                        self._local_handle.table
                        if self._local_handle is not None
                        else None
                    ),
                    worker_id=wid,
                    num_workers=num_workers,
                )
                # Pod-unit jobs drop local TaskUnit admission: ordering
                # AND cross-tenant fairness come from the pod arbiter (a
                # local quorum wait inside a granted unit would deadlock
                # the grant discipline the same way it would a turnstile
                # turn).
                taskunit = (
                    TaskUnitClient(cfg.job_id, wid, self._global_tu, self._local_tu)
                    if self._global_tu is not None
                    and self._local_tu is not None
                    and not pod_lockstep
                    and self._pod_unit_scope is None
                    else None
                )
                worker = WorkerTasklet(
                    cfg.job_id,
                    ctx,
                    self._trainer_factory(),
                    data,
                    self._handle.table.mesh,
                    collector=MetricCollector(sink=self._metric_sink,
                                              job_id=cfg.job_id,
                                              worker_id=wid),
                    batch_barrier=(
                        self._ctrl.make_barrier(wid) if self._ctrl is not None else None
                    ),
                    taskunit=taskunit,
                    epoch_callback=(epoch_hook if idx == 0 else None),
                    starting_epoch=self._starting_epoch,
                    # resumed jobs must NOT re-run global init: the
                    # restored table already holds trained state, and an
                    # additive init would corrupt it
                    global_init=(idx == 0 and self._starting_epoch == 0),
                    post_init_barrier=init_barrier.wait,
                    dispatch_turn=self._make_dispatch_turn(turnstile, wid),
                    pod_contended=self._pod_unit_contended,
                    pending_plan_epoch=(plan_epoch_fn if idx == 0 else None),
                    # the metrics hook only reads already-drained counters,
                    # so fused multi-epoch windows may defer it; checkpoint
                    # chains snapshot state AT their epoch and disable them
                    defer_epoch_callback=(params.model_chkp_period <= 0),
                    trace_parent=trace_parent,
                    attempt=attempt,
                    input_feed=input_feed,
                )
                self._workers.append(worker)
                results[wid] = worker.run()
            except BaseException as e:  # noqa: BLE001 - reported to dispatcher
                errors.append(e)
                # A worker that dies before the init barrier must break it,
                # or every other worker waits forever (fail-fast, like the
                # reference's driver-kill on evaluator failure).
                init_barrier.abort()
            finally:
                if turnstile is not None:
                    # a finished (or dead) worker must not stall the cycle
                    turnstile.leave(wid)
                if self._ctrl is not None:
                    self._ctrl.deregister_worker(wid)
                if self._global_tu is not None:
                    # Shrink the TaskUnit quorum, or surviving workers
                    # deadlock waiting for this one's phase announcements.
                    self._global_tu.on_executor_done(cfg.job_id, wid)
                wsm.await_barrier(wid, "CLEANUP", timeout=60)

        threads = [
            threading.Thread(target=run_worker, args=(i,), name=f"{cfg.job_id}-w{i}")
            for i in range(num_workers)
        ]
        if orchestrator is not None:
            orchestrator.start()
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            if orchestrator is not None:
                orchestrator.stop()
                self._master.release_optimizer_lease(self._handle.table_id)
        if self._global_tu is not None:
            self._global_tu.on_job_finish(cfg.job_id)
        if errors:
            fence = next(
                (e for e in errors
                 if getattr(e, "elastic_fence", None) is not None), None,
            )
            if fence is not None and self._chkp_chain is not None:
                # an elastic fence ends the attempt ON PURPOSE right
                # after the fence epoch's chain hook — join the async
                # writers so the recovery point is COMMITTED before the
                # leader plans the next attempt (otherwise the restore
                # falls back an epoch and re-runs it)
                try:
                    self._chkp_chain.drain()
                except BaseException:  # noqa: BLE001 - fence still stands
                    pass
            if fence is not None:
                # the fence outranks sibling errors: a worker released by
                # the fence's stop broadcast may error while unwinding,
                # and raising THAT would strip the marker the elastic
                # loop classifies on — permanently failing a submission
                # that was mid-planned-reconfiguration
                raise fence
            raise errors[0]
        if tm_hook is not None:
            # final report AFTER all workers joined: the chief's last epoch
            # hook fires while SSP-lagging peers may still be dispatching;
            # their tail ops land in this closing window
            tm_hook(params.num_epochs)
        out: Dict[str, Any] = {"job_id": cfg.job_id, "workers": results}
        if self._elastic_restore is not None:
            # the recovery attempt's restore accounting (the O(lost-bytes)
            # evidence the elastic chaos tests assert against)
            out["elastic_restore"] = dict(self._elastic_restore)
        if self._applied_plans:
            out["applied_plans"] = list(self._applied_plans)
        if orchestrator is not None:
            out["reconfigs"] = len(orchestrator.reconfig_log)
            if orchestrator.errors:
                # failed rounds must be visible in the job result, not just
                # in a list that dies with the orchestrator
                out["optimizer_errors"] = [
                    f"{type(e).__name__}: {e}" for e in orchestrator.errors
                ]
        if self._chkp_chain is not None:
            # Join the async snapshot writers before the dispatcher drops the
            # table; the surviving ids are the replayable chain. A checkpoint
            # problem must NOT fail a job whose training succeeded — record
            # it as a warning and return the ids still considered live.
            try:
                out["model_chkp_ids"] = self._chkp_chain.drain()
            except BaseException as e:  # noqa: BLE001 - demoted to warning
                out["model_chkp_ids"] = list(self._chkp_chain.chkp_ids)
                out["model_chkp_warning"] = f"{type(e).__name__}: {e}"
            # The chain is a durable artifact (like the reference's
            # HDFS-committed checkpoints): surface where it lives so callers
            # can replay or delete it.
            out["model_chkp_root"] = self._chkp_dir
        return out

    def _make_dispatch_turn(self, turnstile, wid: str):
        """The worker's per-dispatch admission context: the job-internal
        turnstile turn (multi-worker determinism), the cross-job pod unit
        (share-all ordering), their COMPOSITION (turn outside, unit
        inside — the turnstile serializes this process's threads so unit
        sequence numbers stay deterministic), or None (single-process
        single-thread jobs need neither)."""
        import contextlib

        scope = self._pod_unit_scope
        if turnstile is None and scope is None:
            return None
        if turnstile is None:
            return scope
        if scope is None:
            return lambda: turnstile.turn(wid)

        @contextlib.contextmanager
        def composed():
            with turnstile.turn(wid):
                with scope():
                    yield

        return composed

    _OPTIMIZERS = {
        "homogeneous": "harmony_tpu.optimizer:HomogeneousOptimizer",
        "heterogeneous": "harmony_tpu.optimizer:HeterogeneousOptimizer",
        "add_one_server": "harmony_tpu.optimizer:AddOneServerOptimizer",
        "delete_one_server": "harmony_tpu.optimizer:DeleteOneServerOptimizer",
        "empty": "harmony_tpu.optimizer:EmptyPlanOptimizer",
    }

    def _make_orchestrator(self):
        """Per-job elasticity loop (ref: ETOptimizationOrchestrator run by
        the driver for each Dolphin job): metrics -> Optimizer -> plan ->
        live migration of THIS job's model table while it trains. Enabled
        by JobConfig.optimizer (a registry name or dotted path)."""
        name = self.config.optimizer
        if not name:
            return None
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        plan_sink = None
        if mesh_spans_processes(self._handle.table.mesh):
            # Multi-process grant: ONLY the leader runs the optimization
            # loop, and its plans are HANDED to the pod control plane for
            # epoch-aligned lockstep application (followers return None —
            # they apply plans, never produce them). Rejections here must
            # be SYMMETRIC across processes (one process raising while its
            # peers proceed into the job's collectives wedges the pod), so
            # the support condition is derived purely from config + mesh:
            # the grant must include the pod leader (process 0), the only
            # holder of the plan channel. Every participant evaluates the
            # same predicate and raises together.
            import jax as _jax

            procs = {
                d.process_index
                for d in self._handle.table.mesh.devices.flat
            }
            if 0 not in procs:
                raise ValueError(
                    f"job {self.config.job_id}: optimizer={name!r} on a "
                    "multi-process grant needs the grant to include the "
                    "pod leader (process 0), which runs the optimization "
                    "loop and owns the plan channel"
                )
            if _jax.process_index() != 0:
                return None
            if self._pod_plan_sink is None:
                # Only reachable OUTSIDE a PodJobServer (which wires the
                # sink for every multi-process grant): there are no pod
                # followers to desynchronize from in that case, so a
                # one-sided raise is safe.
                raise ValueError(
                    f"job {self.config.job_id}: optimizer={name!r} on a "
                    "multi-process grant has no pod plan channel "
                    "(running outside a PodJobServer?)"
                )
            plan_sink = self._make_pod_plan_adapter()
        if self._metric_manager is None:
            raise ValueError(
                f"job {self.config.job_id}: optimizer={name!r} needs the "
                "jobserver's MetricManager (running outside a JobServer?)"
            )
        # One optimization loop per table: a tenant attaching to a shared
        # table whose creator already optimizes it trains unoptimized
        # rather than racing competing migration plans.
        if not self._master.acquire_optimizer_lease(self._handle.table_id):
            return None
        try:
            from harmony_tpu.optimizer import OptimizationOrchestrator

            cls = resolve_symbol(self._OPTIMIZERS.get(name, name))
            return OptimizationOrchestrator(
                self._master,
                self._handle,
                cls(),
                self._metric_manager,
                period_sec=self.config.optimizer_period,
                job_id=self.config.job_id,
                plan_sink=plan_sink,
            )
        except BaseException:
            # run()'s finally only releases through the orchestrator; a
            # construction failure here would otherwise hold the lease
            # forever and make every resubmission train unoptimized
            self._master.release_optimizer_lease(self._handle.table_id)
            raise

    def _make_pod_plan_adapter(self):
        """Adapt a DolphinPlan to the pod plan channel: move-only plans
        (the pod's reconfiguration unit) are scheduled at the earliest
        epoch clearing the window-horizon lead past this leader's observed
        progress; executor add/delete plans are declined (pod topology
        changes are a process-lifecycle operation, not a table move)."""
        from harmony_tpu.dolphin.worker import WorkerTasklet

        job_id = self.config.job_id
        sink = self._pod_plan_sink
        metrics = self._metric_manager
        # Monotonic high-water mark of observed epochs: run_once clears
        # job metrics after an accepted plan, and a later round reading
        # EMPTY metrics must not regress its epoch estimate to 0 and
        # schedule a plan BEHIND the job's real progress (the divergent-
        # application hazard; the pod-side progress-tracker check is
        # vacuous for single-worker jobs).
        seen = {"hi": 0}

        def apply(dplan) -> bool:
            if dplan.evaluators_to_add or dplan.evaluators_to_delete:
                from harmony_tpu.jobserver.joblog import job_logger

                job_logger(job_id).warning(
                    "pod optimization declined a plan with executor "
                    "add/delete (move-only plans are supported on pods)"
                )
                return False
            wm = metrics.worker_batch_metrics(job_id=job_id)
            cur = max((m.epoch_idx for m in wm), default=0)
            cur = seen["hi"] = max(cur, seen["hi"])
            epoch = cur + WorkerTasklet.EPOCH_WINDOW + 2
            if epoch >= self.config.params.num_epochs:
                from harmony_tpu.jobserver.joblog import job_logger

                job_logger(job_id).warning(
                    "pod optimization declined: earliest safe apply epoch "
                    "%d is past the job's end (%d epochs) — too few "
                    "epochs remain for a lockstep migration",
                    epoch, self.config.params.num_epochs,
                )
                return False
            for step in dplan.transfer_steps:
                sink(job_id, step.src, step.dst, step.num_blocks, epoch)
            return bool(dplan.transfer_steps)

        return apply

    def _make_pod_plan_hook(self):
        """Apply pod-scheduled reshard plans at the chief's epoch hook —
        the deterministic lockstep point every process reaches at the same
        logical epoch (see jobserver/podplan.py and
        PodJobServer.schedule_pod_reshard). Deferrable: under multi-epoch
        windows the hook replays post-drain in epoch order, identically on
        every process, so the move still lands at one consistent point.
        Single-process servers never schedule plans; the hook is a dict
        lookup per epoch there."""
        from harmony_tpu.jobserver import podplan

        job_id = self.config.job_id

        def hook(epoch_idx: int) -> None:
            for p in podplan.take(job_id, epoch_idx):
                if p.get("elastic_fence"):
                    # Elastic fence: this attempt ends HERE — at the one
                    # point lockstep guarantees every process reaches at
                    # the same logical epoch, right AFTER the chain hook
                    # snapshotted this epoch (hook composition order in
                    # run()), so the re-dispatch resumes at epoch+1 with
                    # nothing lost. Sibling workers are released through
                    # the SSP stop broadcast; the fence error carries the
                    # marker the elastic dispatch loop classifies on.
                    from harmony_tpu.jobserver.elastic import ElasticFence

                    if self._ctrl is not None:
                        self._ctrl.request_stop()
                    raise ElasticFence(str(p["elastic_fence"]), epoch_idx)
                # clamp to what src actually owns (deterministic: every
                # process sees the same block map) so "drain" plans can
                # just pass a large count
                counts = self._handle.block_manager.block_counts()
                owned = counts.get(p["src"], 0)
                n = min(int(p["num_blocks"]), owned)
                skipped = None
                if n:
                    # Process-set guard: a plan that would change WHICH
                    # PROCESSES own blocks mid-training is skipped (every
                    # process computes the same decision from the shared
                    # block map). A worker whose process left the table
                    # mesh would keep dispatching programs over devices
                    # it no longer shares — on multi-controller runtimes
                    # that wedges collective-context setup. Executor-level
                    # moves (including cross-process grows while the
                    # process still owns other blocks) are unrestricted;
                    # table-level process grow/shrink outside a training
                    # loop is fully supported (cross_set_reshard).
                    def owner_procs(cmap):
                        return {
                            self._master.executor(e).device.process_index
                            for e, c in cmap.items() if c > 0
                        }

                    after = dict(counts)
                    after[p["src"]] = owned - n
                    after[p["dst"]] = after.get(p["dst"], 0) + n
                    if owner_procs(after) != owner_procs(counts):
                        from harmony_tpu.jobserver.joblog import job_logger

                        skipped = "process-set change mid-training"
                        job_logger(job_id).warning(
                            "pod plan %s->%s (%d blocks) skipped: it "
                            "would change the owning PROCESS set of a "
                            "running job", p["src"], p["dst"], n,
                        )
                        n = 0
                if n:
                    self._handle.move_blocks(p["src"], p["dst"], n)
                entry = {
                    "epoch": epoch_idx, "src": p["src"], "dst": p["dst"],
                    "moved": n,
                    "owners_after": len(self._handle.owning_executors()),
                }
                if skipped:
                    entry["skipped"] = skipped
                self._applied_plans.append(entry)

        return hook

    def cleanup(self) -> None:
        """Table teardown (_cleanup_tables) + drop any unapplied pod
        reshard plans (a resubmitted job id must not inherit them)."""
        from harmony_tpu.jobserver import podplan

        podplan.clear(self.config.job_id)
        self._cleanup_tables()

    @staticmethod
    def _compose_epoch_hooks(*hooks):
        hooks = [h for h in hooks if h is not None]
        if not hooks:
            return None
        if len(hooks) == 1:
            return hooks[0]

        def composed(epoch_idx: int) -> None:
            for h in hooks:
                h(epoch_idx)

        return composed

    def _make_table_metrics_hook(self):
        """Per-epoch ServerMetrics emission (ref: the ET MetricReportMsg
        built-ins every executor reports — per-table block counts, pull
        request counts, pulled bytes — feeding MetricManager and through it
        the optimizer's cost models). Single-controller attribution: each
        owning executor reports its block count and a block-proportional
        share of THIS JOB's op-counter deltas since the last report — the
        deltas come from the job's own workers, not the table's cumulative
        counters, so jobs sharing one table never claim each other's
        traffic."""
        if self._metric_sink is None:
            return None
        from harmony_tpu.metrics.collector import ServerMetrics

        last = {"pulls": 0, "pushes": 0, "pull_bytes": 0}
        job_id = self.config.job_id
        handle = self._handle

        # largest-remainder split: shares sum EXACTLY to the total (plain
        # flooring leaks remainder ops every window)
        from harmony_tpu.optimizer.hetero import _largest_remainder as apportion

        def report(epoch_idx: int) -> None:
            stats = {k: 0 for k in last}
            for w in list(self._workers):
                for k in stats:
                    stats[k] += w.op_stats[k]
            delta = {k: stats[k] - last[k] for k in last}
            last.update(stats)
            counts = handle.block_manager.block_counts()
            owners = [(ex, n) for ex, n in counts.items() if n > 0]
            weights = [n for _, n in owners]
            pulls = apportion(delta["pulls"], weights)
            pushes = apportion(delta["pushes"], weights)
            pbytes = apportion(delta["pull_bytes"], weights)
            for i, (ex, nblocks) in enumerate(owners):
                self._metric_sink(ServerMetrics(
                    job_id=job_id,
                    executor_id=ex,
                    window_idx=epoch_idx,
                    num_blocks=nblocks,
                    pull_count=pulls[i],
                    push_count=pushes[i],
                    pull_bytes=pbytes[i],
                ))

        return report

    def deferred_evaluation(self):
        """Return a closure replaying this job's checkpoint chain, or None.

        Registered with the JobServer after a successful run; executed during
        graceful shutdown (ref: JobServerDriver.java:178-214 — shutdown waits
        for jobs, then runs the deferred model evaluation that
        DolphinMaster.evaluate() performs over the ModelChkpManager chain).
        Test data resolves lazily inside the closure (user.test_data_fn,
        falling back to the training data) so nothing large is pinned between
        job end and shutdown. Replayed checkpoints are deleted after
        evaluation — the eval is the chain's consumer — so a long-lived
        server doesn't accrete one model copy per epoch per job."""
        if self._chkp_chain is None or not self.config.params.offline_model_eval:
            return None
        chkp_ids = list(self._chkp_chain.chkp_ids)
        if not chkp_ids:
            return None
        cfg = self.config
        mgr = self._chkp_mgr
        executor_ids = list(self._executor_ids)
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        eval_channel = (
            self._pod_eval_channel
            if mesh_spans_processes(self._handle.table.mesh)
            else None
        )

        def run_eval(master: ETMaster) -> List[Dict[str, float]]:
            from harmony_tpu.dolphin.evaluator import (
                ModelEvaluator,
                resolve_eval_inputs,
            )

            # the SHARED resolution (leader and pod followers must issue
            # byte-identical collectives — see resolve_eval_inputs)
            trainer, batch = resolve_eval_inputs(cfg)
            if eval_channel is None:
                metrics = ModelEvaluator(master, mgr).evaluate_checkpoints(
                    chkp_ids, trainer, batch, executor_ids
                )
            else:
                # pod collective: followers must enter the SAME restore +
                # evaluate collectives — broadcast first, evaluate
                # together, then await their acks. A leader-side failure
                # AFTER the broadcast leaves followers inside collectives
                # nothing will complete: the finally still collects what
                # it can (bounded) and the channel poisons the pod on a
                # missing/failed ack.
                eval_channel("start", cfg.job_id, {"chkp_ids": chkp_ids})
                try:
                    metrics = ModelEvaluator(master, mgr).evaluate_checkpoints(
                        chkp_ids, trainer, batch, executor_ids
                    )
                finally:
                    eval_channel("finish", cfg.job_id)
            for cid in chkp_ids:  # consumed: reclaim the disk (the
                # LEADER owns shared-root cleanup; followers never delete)
                mgr.delete(cid)
            return metrics

        return run_eval

    # -- teardown --------------------------------------------------------

    def _cleanup_tables(self) -> None:
        """Release job tables (ref: JobDispatcher drops tables at job end;
        shared/reused tables survive). The master refcounts shared tables:
        every tenant releases its reference and storage is freed only when
        the LAST one does — a creator finishing first must not delete
        buffers under a tenant still training."""
        # Idempotent: the dispatcher calls cleanup() again on exceptions —
        # each handle reference is nulled BEFORE dropping so a second pass
        # (or a drop that raises midway) can never decrement the shared
        # refcount twice and steal another tenant's reference.
        h, self._handle = self._handle, None
        lh, self._local_handle = self._local_handle, None
        if h is not None:
            h.drop()
        if lh is not None:
            lh.drop()

    @property
    def table_handle(self) -> Optional[TableHandle]:
        return self._handle


class PregelJobEntity(JobEntity):
    """Vertex-centric BSP job under the JobServer (ref: the pregel side of
    the app-type switch — pregel/jobserver/PregelJobEntity.java: vertex +
    swapped message tables on the job's executors, PregelMaster run loop).

    Config mapping: ``config.trainer`` names the Computation class;
    ``user.graph_fn``/``user.graph_args`` build the Graph (the analogue of
    the reference's vertex-file bulk load); ``user.max_supersteps`` bounds
    the run. Computation classes that take the graph (PageRank's out-degree
    normalization) receive it as a ``graph=`` kwarg."""

    def __init__(
        self,
        config: JobConfig,
        global_taskunit: Optional[GlobalTaskUnitScheduler] = None,
        local_taskunit: Optional[LocalTaskUnitScheduler] = None,
        metric_sink=None,
        chkp_root: Optional[str] = None,
        metric_manager=None,  # no per-table optimizer loop for graphs
        pod_plan_sink=None,   # accepted for interface parity; graphs have
        pod_eval_channel=None,  # no model table to migrate/evaluate by plan
        pod_unit_scope=None,
        pod_unit_contended=None,  # supersteps have no window to shrink
    ) -> None:
        super().__init__(config, chkp_root)  # no model table: root unused
        self._global_tu = global_taskunit
        self._local_tu = local_taskunit
        # Cross-job pod units (share-all tenancy): the master wraps every
        # superstep dispatch — and setup wraps table creation — in
        # leader-granted units, exactly like dolphin entities.
        self._pod_unit_scope = pod_unit_scope
        self._pregel_master = None
        self._registered = False

    def setup(self, master: ETMaster, executor_ids: List[str]) -> None:
        import contextlib
        import inspect

        from harmony_tpu.parallel.mesh import build_mesh
        from harmony_tpu.pregel.master import PregelMaster

        cfg = self.config
        user = cfg.user
        if "graph_fn" not in user:
            raise ValueError(f"job {cfg.job_id}: user.graph_fn missing")
        graph = resolve_symbol(user["graph_fn"])(**user.get("graph_args", {}))
        comp_cls = resolve_symbol(cfg.trainer)
        app_params = dict(cfg.params.app_params)
        if "graph" in inspect.signature(comp_cls.__init__).parameters:
            app_params["graph"] = graph
        computation = comp_cls(**app_params)
        devices = [master.executor(e).device for e in executor_ids]
        mesh = build_mesh(devices, data=1)
        taskunit = None
        if (self._global_tu is not None and self._local_tu is not None
                and self._pod_unit_scope is None):
            # local TaskUnit admission, like dolphin: dropped under pod
            # units (ordering + fairness come from the arbiter)
            wid = f"{cfg.job_id}/w0"
            self._global_tu.on_job_start(cfg.job_id, [wid])
            self._registered = True
            taskunit = TaskUnitClient(cfg.job_id, wid, self._global_tu, self._local_tu)
        scope = (self._pod_unit_scope() if self._pod_unit_scope is not None
                 else contextlib.nullcontext())
        try:
            with scope:  # table creation + seeds dispatch global programs
                self._pregel_master = PregelMaster(
                    graph,
                    computation,
                    mesh,
                    max_supersteps=int(user.get("max_supersteps", 100)),
                    taskunit=taskunit,
                    job_id=cfg.job_id,
                    dispatch_turn=self._pod_unit_scope,
                )
        except BaseException:
            self._deregister()  # a failed setup must not leave a stale quorum
            raise

    def _deregister(self) -> None:
        if self._registered and self._global_tu is not None:
            self._global_tu.on_executor_done(self.config.job_id,
                                             f"{self.config.job_id}/w0")
            self._global_tu.on_job_finish(self.config.job_id)
            self._registered = False

    def run(self) -> Dict[str, Any]:
        # Deregister in finally: a job that dies mid-superstep must not leave
        # its quorum entry in the global TaskUnit scheduler (stale quorums
        # deadlock other jobs' wait_ready on the long-running server).
        try:
            return self._pregel_master.run()
        finally:
            self._deregister()

    def cleanup(self) -> None:
        if self._pregel_master is not None:
            self._pregel_master.close()


def build_entity(config: JobConfig, **kwargs) -> JobEntity:
    """App-type dispatch (ref: JobEntity.getJobEntity app-type switch)."""
    if config.app_type == "dolphin":
        return DolphinJobEntity(config, **kwargs)
    if config.app_type == "pregel":
        return PregelJobEntity(config, **kwargs)
    raise ValueError(f"unknown app_type {config.app_type!r}")
