"""WorkerTasklet — the training hot loop.

Parity with the reference's WorkerTasklet (dolphin/core/worker/
WorkerTasklet.java:96-168): per epoch, per mini-batch the phases

    SYNC  (mini-batch barrier, SSP gate)     -> barrier object
    PULL  (model pull)                        \
    COMP  (trainer local compute)              > ONE fused jitted SPMD step
    PUSH  (push updates)                      /

with per-batch and per-epoch metrics (WorkerTasklet.java:194-229).

TPU-first: the three data phases compile into a single XLA program over the
job's mesh — pull is the all-gather of the model-axis-sharded table, compute
is MXU math over the data-axis-sharded batch, push is the delta fold whose
batch-axis contraction XLA lowers to a cross-chip reduction. When no host
decision is needed between batches, the WHOLE epoch further fuses into one
``lax.scan`` dispatch (removes per-step host round-trips).

Steps are dispatched through ``DenseTable.apply_step`` so buffer donation
stays invisible to concurrent host accessors, and hyper-parameters enter the
step as arguments so per-epoch decay reaches the compiled program.

Phase boundaries still exist for scheduling: each batch announces its
TaskUnits to the (optional) TaskUnit scheduler so concurrent jobs interleave
compute-heavy and network-heavy spans (ref: LocalTaskUnitScheduler.java:
83-102) — the whole step is announced as COMP.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harmony_tpu import faults
from harmony_tpu.data import devcache
from harmony_tpu.data.loader import StageRing
from harmony_tpu.dolphin.data import TrainingDataProvider
from harmony_tpu.dolphin.prefetch import PrefetchPipeline, StagedBatch
from harmony_tpu.dolphin.trainer import Trainer, TrainerContext
from harmony_tpu.metrics.collector import (
    BatchMetrics,
    EpochMetrics,
    InputPipelineMetrics,
    MetricCollector,
)
from harmony_tpu.parallel.dispatch import dispatch_scope
from harmony_tpu.parallel.mesh import DATA_AXIS
from harmony_tpu.runtime import progcache
from harmony_tpu.tracing import SpanContext, job_stage, trace_span
from harmony_tpu.tracing.span import job_stage_adder
from harmony_tpu.tracing.profiler import maybe_profile_epoch
from harmony_tpu.tracing.stepscopes import in_cache_key, step_scope
from harmony_tpu.utils.platform import on_mesh, traced_on


def _phase_boundary(tree, replicate_on: "Optional[Mesh]" = None):
    """Materialization point between the step's PULL/COMP/PUSH stages
    (``lax.optimization_barrier``): XLA does not fuse across it, so each
    stage computes what its standalone program would (cross-phase fusion
    re-associates matmul accumulations — measured ~1e-7 loss drift; the
    parity tests in tests/test_sparse_step.py hold the step to a
    per-phase accessor loop bit for bit).
    ``replicate_on`` additionally pins the boundary value replicated on
    that mesh — the PULL stage's documented contract (pull IS the
    all-gather of the model-axis-sharded table), without which GSPMD
    partitions the downstream compute from whatever sharding propagates
    backward and reduction orders drift with the layout. On TPU the
    keyed stages already end at Pallas kernel calls (ops/sparse.py),
    which are materialization boundaries anyway. What a stage holds is
    the step's to say: the pull-all step of a two-part trainer puts the
    COMP fence after the GRADIENT and runs the elementwise update rule
    in PUSH (:func:`pull_all_step`) — no matmul crosses a fence it did
    not cross before."""
    if replicate_on is not None:
        tree = _replicated_tree(tree, replicate_on)
    return jax.lax.optimization_barrier(tree)


def _replicated_tree(tree, mesh: Mesh):
    """Constrain every array leaf replicated on ``mesh`` — the boundary
    sharding of _phase_boundary: GSPMD propagates shardings backward
    through unconstrained values, so a phase-crossing value left natural
    partitions its producing reduction by the consumer's layout, and
    float accumulation orders drift."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, rep), tree
    )


def update_lowering(spec, trainer, mesh: Mesh) -> str:
    """How a ``pull_mode == "all"`` step applies its update.
    ``"row_ranges"``: the update rule runs in the PUSH stage on the stored
    rows — the trainer states its step in two parts for this table
    (``Trainer.row_update_parts``), the table folds row ranges
    (``TableSpec.takes_row_ranges``: range-partitioned, the additive fold,
    no ``post`` hook) and every device of ``mesh`` holds it whole. (On a
    model-sharded table a parameter's row and its optimizer state's rows
    lie on different shards, so an elementwise rule over them is no local
    fold: that table keeps today's program.) ``"whole_delta"``:
    ``compute``'s ``[capacity, ...]`` delta through ``push_all``. Decided
    at step build from the table's schema, the trainer and the mesh."""
    from harmony_tpu.table.table import row_shards

    if (spec.takes_row_ranges and row_shards(mesh, spec.num_blocks) == 1
            and trainer.row_update_parts(spec.config.capacity) is not None):
        return "row_ranges"
    return "whole_delta"


def pull_all_step(spec, trainer, mesh: Mesh):
    """The ``pull_mode == "all"`` step body ``(arr, batch, hyper) ->
    (new_arr, metrics)`` on a dense model table — what ``_step_core``
    runs, for callers that compile the shipped step without a worker.

    ``whole_delta``: PULL the table | COMP ``trainer.compute`` | PUSH
    ``push_all`` of its delta. ``row_ranges`` (:func:`update_lowering`):
    PULL the table | COMP the gradient, fenced and pinned replicated as
    the delta is — one section, not three, and where the trainer hands it
    over in row pieces a tuple of them, not one array | PUSH the update
    rule on the stored rows and its fold, section by section where the
    rows lie: no delta is concatenated and none is added to the whole
    table. Per element the arithmetic is the same in both: ``stored +
    (new - stored)``."""
    if update_lowering(spec, trainer, mesh) == "whole_delta":

        def _step(arr, batch, hyper):
            with step_scope("table.pull"):
                model = _phase_boundary(spec.pull_all(arr),
                                        replicate_on=mesh)         # PULL
            with step_scope("compute"):
                delta, metrics = _phase_boundary(
                    trainer.compute(model, batch, hyper),
                    replicate_on=mesh)                             # COMP
            with step_scope("table.push"):
                return spec.push_all(arr, delta), metrics          # PUSH

        return _step
    _, _, gradient, push_update = trainer.row_update_parts(
        spec.config.capacity)

    def _step(arr, batch, hyper):
        with step_scope("table.pull"):
            model = _phase_boundary(spec.pull_all(arr),
                                    replicate_on=mesh)             # PULL
        with step_scope("compute"):
            g, metrics = _phase_boundary(gradient(model, batch),
                                         replicate_on=mesh)        # COMP
        with step_scope("table.push"):
            return push_update(spec, arr, model, g, hyper), metrics  # PUSH

    return _step


class _TimedAdmission:
    """A dispatch turn whose admission — entering it — is a
    ``taskunit.wait`` light span (kind TURN) feeding ``acc``; the turn
    itself is held and released as before."""

    __slots__ = ("_turn", "_acc", "_job_id")

    def __init__(self, turn, acc, job_id: str) -> None:
        self._turn, self._acc, self._job_id = turn, acc, job_id

    def __enter__(self):
        with trace_span("taskunit.wait", record=False, acc=self._acc,
                        job_id=self._job_id, kind="TURN"):
            return self._turn.__enter__()

    def __exit__(self, *exc):
        return self._turn.__exit__(*exc)


class WorkerTasklet:
    """Drives the training loop for one job over its mesh slice."""

    def __init__(
        self,
        job_id: str,
        ctx: TrainerContext,
        trainer: Trainer,
        data: TrainingDataProvider,
        mesh: Mesh,
        collector: Optional[MetricCollector] = None,
        batch_barrier: Optional[Callable[[int], bool]] = None,
        taskunit: Optional[Any] = None,
        epoch_callback: Optional[Callable[[int], None]] = None,
        starting_epoch: int = 0,
        global_init: bool = True,
        post_init_barrier: Optional[Callable[[], None]] = None,
        defer_epoch_callback: bool = False,
        dispatch_turn: Optional[Callable[[], Any]] = None,
        pending_plan_epoch: Optional[Callable[[], Optional[int]]] = None,
        pod_contended: Optional[Callable[[], bool]] = None,
        trace_parent: Optional[Dict[str, str]] = None,
        attempt: int = 0,
        input_feed: Optional[Any] = None,
    ) -> None:
        self.job_id = job_id
        # Disaggregated input service (harmony_tpu/inputsvc): a
        # TrainerInputFeed streaming assembled host batches off the
        # shared input workers, with built-in bounded retry and
        # in-process fallback. None = local assembly (the default).
        # The feed replaces WHERE host batches come from; staging,
        # devcache bypass, reshard invalidation and sharding checks are
        # untouched, and losses stay bit-identical for a fixed seed.
        self._input_feed = input_feed
        # Trace threading (tracing/span.py): the worker runs on its own
        # thread, so the entity hands the dispatch span's wire context
        # down explicitly — contextvars do not cross Thread starts. The
        # elastic attempt index keys the `attempt` label/annotation as
        # `job@aN` (jobserver/elastic.attempt_key's scheme).
        self.trace_parent = trace_parent
        self.attempt = int(attempt or 0)
        self.attempt_key = (job_id if self.attempt <= 0
                            else f"{job_id}@a{self.attempt}")
        self.ctx = ctx
        self.trainer = trainer
        self.data = data
        self.mesh = mesh
        self.collector = collector or MetricCollector()
        # batch_barrier(batch_idx) -> stop_flag (ref: MiniBatchBarrier.await
        # returning the master's stop decision, MiniBatchBarrier.java:28-60).
        self.batch_barrier = batch_barrier
        self.taskunit = taskunit
        self.epoch_callback = epoch_callback
        # True = the callback only does host accounting off already-drained
        # values (metric emission) and may run AFTER a multi-epoch fused
        # window drains, once per epoch in order. False = the callback
        # observes table state at its epoch boundary (checkpoint chains),
        # which a window would skip past — windows stay off.
        self.defer_epoch_callback = defer_epoch_callback
        self.starting_epoch = starting_epoch  # resume (ref: StartingEpochIdx)
        # Multi-worker jobs: exactly ONE worker (the chief) may run the
        # trainer's global init — it writes shared tables, and N identical
        # additive inits would accumulate N-fold (ref: initGlobalSettings is
        # a per-JOB setup). post_init_barrier makes the others wait for it.
        self.global_init = global_init
        self.post_init_barrier = post_init_barrier
        # Pod-lockstep multi-worker: a callable yielding this worker's
        # admission-turn context manager (dolphin/master.DispatchTurnstile).
        # Every multi-device dispatch this worker makes — batch steps,
        # metric drains, probes — happens inside a turn, so concurrent
        # worker threads enqueue in the SAME deterministic order on every
        # process of the pod.
        self.dispatch_turn = dispatch_turn
        # Cross-job pod tenancy: returns the contended flag of this job's
        # last COMPLETED dispatch unit (runtime/podunits.py) — a value
        # every process reads at the same logical point, so dispatch-
        # window decisions branched on it stay deterministic pod-wide.
        self.pod_contended = pod_contended
        # Pod reshard plans: callable returning the next scheduled plan
        # epoch (or None). Multi-epoch windows must END at a plan epoch so
        # its application (via the deferred epoch-hook replay) lands right
        # after that epoch's dispatches, not after the whole window.
        # Deterministic across pod processes by the scheduling contract:
        # plans carry multi-epoch lead, so by the time any process makes
        # the window decision covering the plan epoch, the plan has
        # arrived everywhere (jobserver/podplan.py).
        self.pending_plan_epoch = pending_plan_epoch
        self._pending_probe = None  # probe deferred into the 1st batch turn
        self._step = None
        self._epoch_fn = None
        self._eval_fn = None
        self._program_cache_key = None  # set by _build_step
        self._built_once = False
        # Comm/comp split probe (see _probe_comm): period in epochs; 0 = off.
        # Cadence: the split is a property of the (layout, shapes) pair, so
        # the probe runs on FIRST use and again after any rebuild/reshard
        # (which clears the programs), plus a slow drift refresh every
        # 8x period epochs — NOT every period epochs (an every-epoch probe
        # both blocked multi-epoch dispatch windows for default jobs and,
        # under multi-tenancy, serialized ~8 dispatches per epoch behind
        # other tenants' steps, dominating cheap jobs' wall time).
        self.comm_probe_every = getattr(ctx.params, "comm_probe_period", 1)
        self._next_probe = 0  # epochs-since-start of the next drift refresh
        # EWMA of own dispatch seconds per batch. None = unseeded — a
        # legitimately measured 0.0 must count as a measurement (0.0 is
        # reachable on sub-resolution timers), so seeding tests use the
        # sentinel, never truthiness.
        self._own_batch_cost: Optional[float] = None
        self._prewarmed_stacked = None  # (sharding, stacked) from prewarm
        self._probe_pull = None
        self._probe_pp = None
        self._comm_probe_times = (0.0, 0.0)
        self._probe_failures = 0  # in a row: backs the next attempt off
        self._step_sharding = None
        self._local_sharding = None
        self._batch_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self._batch_sig = progcache.sharding_signature(self._batch_sharding)
        # Keep device-resident copies of batches across epochs (kills the
        # per-epoch H2D re-transfer; only valid when batches are stable).
        self.cache_device_batches = not data.is_shuffling
        self._batch_cache: Dict[int, Any] = {}
        self._stacked_cache = None
        # Async input pipeline (dolphin/prefetch.py): batch assembly + H2D
        # staging on a producer thread, overlapping device compute. Config
        # default ON; _prefetch_usable() gates it off where a background
        # device_put would break pod-deterministic dispatch order.
        self._prefetch_on = bool(getattr(ctx.params, "input_prefetch", True))
        self._active_pipeline: Optional[PrefetchPipeline] = None
        # (epoch, pipeline) spawned ahead of its epoch (see
        # _spawn_next_pipeline) — consumed by _epoch_batch_stream
        self._next_pipeline: Optional[Tuple[int, PrefetchPipeline]] = None
        # set by _on_layout_announcement when the ANNOUNCED target mesh
        # spans processes — self.mesh lags the flip, so this is what stops
        # new staging producers from spawning in the announce->flip window
        self._staging_unsafe = False
        self._prefetch_hits = 0
        self._prefetch_misses = 0
        # This worker's own op counters (single-threaded; per-job metric
        # attribution sums these across the job's workers).
        self.op_stats: Dict[str, int] = {"pulls": 0, "pushes": 0, "pull_bytes": 0}
        # Per-job throughput SLO (metrics/accounting.py): the env knob
        # overrides the per-job param for every tenant (operator floor).
        # Breach detection is chief-only and windowed: SLO_WINDOW_EPOCHS
        # consecutive epochs under 90% of target fire ONE structured
        # joblog event (kind="slo"); recovery re-arms it.
        from harmony_tpu.metrics import accounting as _acct

        target = _acct.slo_target_from_env()
        if target is None:
            p = float(getattr(ctx.params, "target_samples_per_sec", 0.0)
                      or 0.0)
            target = p if p > 0 else None
        self._slo_target: Optional[float] = target
        self._slo_below = 0
        self._slo_fired = False
        # FLOPs of one step of the CURRENT compiled program (progcache
        # cost table); resolved lazily after the first compile, reset on
        # rebuild. None = backend exposes no cost model (ledger keeps
        # the None — 0.0 is reserved for real zeros).
        self._flops_per_step: Optional[float] = None
        # Step-phase time budget (metrics/phases.py): host-dispatch
        # seconds accumulate on the training thread between "batch
        # ready" and the device dispatch call; per-epoch phase splits
        # stage in _phase_pending (keyed by epoch) between the metric
        # drain — where the work split is computed — and _finish_epoch,
        # where the epoch WALL is finally known and the budget feeds.
        self._phase_dispatch_acc = 0.0
        self._phase_pending: Dict[int, Dict[str, float]] = {}
        self._phase_input_wait: Dict[int, float] = {}
        # the three control phases, measured by their spans' own clock
        # reads (``acc`` of trace_span) on the training thread since the
        # last budget feed: ``taskunit.wait`` + turn admission, the comm
        # probe, the post-drain bookkeeping. _budget_mark is where the
        # last fed stretch of wall ended: a feed's wall runs from there,
        # so probe and bookkeeping — which sit BETWEEN the epochs' own
        # timers — are inside the wall they are phases of.
        self._phase_ctl = {"grant_wait": 0.0, "probe": 0.0,
                           "bookkeeping": 0.0}
        self._budget_mark: Optional[int] = None  # time.monotonic_ns()
        # the window ledger (metrics/phases.py): the training thread's
        # SELF seconds under each light span since the last feed, by the
        # spans' own clock reads (_span_acc); _span_total is every second
        # a tracked span has covered so far, which is how a span that
        # closes knows what closed inside it. _window counts the drained
        # windows of this attempt, _compile_mark is the job's JAX compile
        # seconds at the last feed.
        self._win_spans: Dict[str, float] = {}
        self._span_total = 0.0
        self._window = 0
        self._compile_mark = 0.0
        # drained step VECTORS (tokens per expert ...) waiting for the
        # trainer's counters: see _observe_vector_backlog
        self._vector_backlog: List[Dict[str, np.ndarray]] = []
        # the step program whose first dispatch (trace + lower + compile
        # or cache load, all lazy) has been made: job.build_step covers
        # the first dispatch of every NEW program
        self._step_dispatched: Any = None

    # -- step construction ----------------------------------------------

    @staticmethod
    def _with_sync(metrics, arr):
        """Guarantee at least one step-output-dependent metric: the async
        loop's in-flight throttle blocks on metrics, so an empty dict would
        make the bound a no-op. ``_sync`` is one element of the pushed
        array (data-dependent, so XLA cannot fold it away); host-side
        consumers strip underscore-keys."""
        if metrics:
            return metrics
        return {"_sync": jnp.ravel(arr)[0]}

    def _step_core(self, mesh: Mesh):
        """The fused PULL/COMP/PUSH body shared by per-batch and per-epoch
        compilation. ``hyper`` is a dict of scalars (lr etc.) passed fresh
        each dispatch so host-side decay is honored. ``mesh`` is the LAYOUT
        SNAPSHOT's mesh, threaded from the caller so the traced program is
        fully determined by its program-cache key (reading the live
        table's mesh here let a prewarm cache a target-key program whose
        sharding constraints pinned the OLD mesh)."""
        from harmony_tpu.table.hashtable import DeviceHashTable

        spec = self.ctx.model_table.spec
        trainer = self.trainer
        sync = self._with_sync
        is_hash = isinstance(self.ctx.model_table, DeviceHashTable)

        def _hash_pull_push(state, batch, compute):
            """Shared keyed core for hash-backed model tables: getOrInit
            pull -> compute(rows) -> token push, in one fused program.

            Keys MUST be replicated before they index the table: a
            data-sharded key vector of uneven per-shard length (batch ids +
            replicated reserved rows) makes XLA's SPMD partitioner pad its
            operands, and padded lanes flow through the elementwise chain
            as phantom key-0 entries (key 0 is reserved as a second
            defense). Returns (state, compute's aux, metrics with the
            mandatory _dropped count — drops are drained into
            table.overflow_count at epoch end, never silent)."""
            replicated = NamedSharding(mesh, P())
            with step_scope("table.pull"):
                keys = jax.lax.with_sharding_constraint(
                    trainer.pull_keys(batch), replicated
                )
                state, rows, token = spec.pull(state, keys)        # PULL
                rows = _phase_boundary(rows, replicate_on=mesh)
            with step_scope("compute"):
                delta, aux, metrics = _phase_boundary(
                    compute(rows), replicate_on=mesh)              # COMP
            # SPI hook (identity by default): trainers maintaining cross-row
            # invariants (e.g. LDA's summary row = sum of word rows)
            # reconcile the delta with the admission mask so a dropped
            # row's contribution drops EVERYWHERE, not just at its own slot
            with step_scope("table.push"):
                delta = trainer.mask_delta(delta, token[2])
                state = spec.push(state, token, delta)             # PUSH
            metrics = dict(metrics)
            metrics["_dropped"] = jnp.sum(~token[2]).astype(jnp.float32)
            return state, aux, metrics

        if is_hash and trainer.pull_mode != "keys":
            raise ValueError(
                "hash-backed model tables need pull_mode='keys' "
                "(pull_all over an unbounded key domain is undefined)"
            )
        if trainer.uses_local_table:
            local_spec = self.ctx.local_table.spec
            if is_hash:
                # Sparse model table beside a dense worker-local table (the
                # sparse-LDA shape: hash-backed topic-word counts, dense
                # per-doc assignments).

                def _step(state, local, batch, hyper):
                    # the local pull belongs to the PULL stage even though
                    # it is traced inside the compute closure — barrier it
                    # like the model pull
                    with step_scope("table.pull"):
                        lmodel = _phase_boundary(local_spec.pull_all(local),
                                                 replicate_on=mesh)
                    state, new_l, metrics = _hash_pull_push(
                        state,
                        batch,
                        lambda rows: trainer.compute_with_local(
                            rows, lmodel, batch, hyper
                        ),
                    )
                    with step_scope("table.push"):
                        new_local = local_spec.write_all(local, new_l)
                    return (state, new_local), sync(metrics, state[1])

                return _step

            def _step(arr, local, batch, hyper):
                with step_scope("table.pull"):
                    model, lmodel = _phase_boundary(
                        (spec.pull_all(arr), local_spec.pull_all(local)),
                        replicate_on=mesh,
                    )                                              # PULL
                with step_scope("compute"):
                    delta, new_l, metrics = _phase_boundary(
                        trainer.compute_with_local(model, lmodel, batch,
                                                   hyper),
                        replicate_on=mesh,
                    )                                              # COMP
                with step_scope("table.push"):
                    new_arr = spec.push_all(arr, delta)            # PUSH
                    new_local = local_spec.write_all(local, new_l)
                return (new_arr, new_local), sync(metrics, new_arr)

            return _step

        if is_hash:

            def _step(state, batch, hyper):
                def compute(rows):
                    delta, metrics = trainer.compute(rows, batch, hyper)
                    return delta, None, metrics

                state, _, metrics = _hash_pull_push(state, batch, compute)
                return state, sync(metrics, state[1])

            return _step
        if trainer.pull_mode == "all":
            body = pull_all_step(spec, trainer, mesh)

            def _step(arr, batch, hyper):
                new_arr, metrics = body(arr, batch, hyper)
                return new_arr, sync(metrics, new_arr)

        else:
            def _step(arr, batch, hyper):
                with step_scope("table.pull"):
                    keys = trainer.pull_keys(batch)
                    model = _phase_boundary(spec.pull(arr, keys),
                                            replicate_on=mesh)     # PULL
                with step_scope("compute"):
                    delta, metrics = _phase_boundary(
                        trainer.compute(model, batch, hyper),
                        replicate_on=mesh)                         # COMP
                with step_scope("table.push"):
                    new_arr = spec.push(arr, keys, delta)          # PUSH
                return new_arr, sync(metrics, new_arr)

        return _step

    def _note_push_lowering(self, mesh: Mesh) -> None:
        """STATUS ``tenants.<job>.table_layout.push_lowering`` / the gauge
        ``harmony_table_push_pallas_rows``: what the step just built does
        for its keyed push (metrics/table_layout.py)."""
        from harmony_tpu.metrics import table_layout
        from harmony_tpu.table import TableSpec

        spec = getattr(self.ctx.model_table, "spec", None)
        if not isinstance(spec, TableSpec):  # hash tables: no block rows
            return
        with on_mesh(mesh):
            lowering = spec.push_lowering(self._pull_rows)
        table_layout.note_push(self.job_id, spec.table_id, lowering)

    def _note_update_lowering(self, mesh: Mesh) -> None:
        """STATUS ``tenants.<job>.table_layout.update_lowering`` /
        ``fold_lowering`` and their gauges: how the pull-all step just
        built applies its update (:func:`update_lowering`), what the fold
        of its row sections lowers to (``TableSpec.fold_lowering``) and how
        much of the gradient that fold reads where the leaves' relayouts
        left it (``harmony_table_fold_direct_row_share``)."""
        from harmony_tpu.metrics import table_layout

        spec, trainer = self.ctx.model_table.spec, self.trainer
        lowering = update_lowering(spec, trainer, mesh)
        table_layout.note_update(self.job_id, spec.table_id, lowering)
        if lowering == "row_ranges":
            rows, sections = trainer.row_update_parts(
                spec.config.capacity)[:2]
            with on_mesh(mesh):
                fold = spec.fold_lowering(rows, sections)
            leaf_rows = getattr(trainer, "leaf_rows", None)
            table_layout.note_fold(
                self.job_id, spec.table_id, fold,
                leaf_rows.record() if leaf_rows is not None else None)

    def _program_key(self, table_sharding, local_sharding) -> "tuple | None":
        """Structural signature of everything the jitted step traces, for the
        process-level program cache (runtime/progcache) — None opts out.
        Components: trainer behavior, table schema + layout SNAPSHOT (the
        same snapshot the jit out_shardings use — reading the live sharding
        twice would let a concurrent reshard poison the cache with a
        key/executable layout mismatch), batch shapes, hyper keys, and the
        dispatch shape."""
        tsig = self.trainer.jit_signature()
        if tsig is None:
            return None
        table_sig = progcache.table_signature(
            self.ctx.model_table, sharding=table_sharding
        )
        if table_sig is None:
            return None
        if self.trainer.uses_local_table:
            local_sig = progcache.table_signature(
                self.ctx.local_table, sharding=local_sharding
            )
            if local_sig is None:
                return None
        else:
            local_sig = None
        batch_sig = tuple(
            (self.data.batch_size, *tail, str(dt))
            for tail, dt in self.data.array_specs()
        )
        hyper_sig = tuple(sorted(self.trainer.hyperparams().keys()))
        return (tsig, table_sig, local_sig, batch_sig, hyper_sig,
                self.data.num_mini_batches if self._use_fused_epoch() else None)

    def _program_builders(self, tsh, lsh):
        """The step/epoch jit-wrapper constructors for a GIVEN layout
        snapshot — shared by _build_step (live layout) and _prewarm_layout
        (announced target layout)."""
        mesh = (tsh[0] if isinstance(tsh, tuple) else tsh).mesh

        def build_step():
            step = in_cache_key(
                traced_on(mesh, self._step_core(mesh)))
            if self.trainer.uses_local_table:
                return jax.jit(step, out_shardings=((tsh, lsh), None),
                               donate_argnums=(0, 1))
            return jax.jit(step, out_shardings=(tsh, None), donate_argnums=0)

        def build_epoch():
            step = traced_on(mesh, self._step_core(mesh))
            if self.trainer.uses_local_table:

                def _epoch2(arr, larr, stacked, hyper):
                    def body(carry, b):
                        (new_pair, metrics) = step(carry[0], carry[1], b, hyper)
                        return new_pair, metrics

                    (fa, fl), ms = jax.lax.scan(body, (arr, larr), stacked)
                    return (fa, fl), ms

                return jax.jit(in_cache_key(_epoch2),
                               out_shardings=((tsh, lsh), None),
                               donate_argnums=(0, 1))

            def _epoch(arr, stacked, hyper):
                return jax.lax.scan(lambda a, b: step(a, b, hyper), arr, stacked)

            return jax.jit(in_cache_key(_epoch), out_shardings=(tsh, None),
                           donate_argnums=0)

        return build_step, build_epoch

    def _prewarm_layout(self, new_mesh: Mesh) -> None:
        """Layout-announcement listener (TableHandle._reshard_to_owners
        announces the TARGET mesh before flipping ownership): build the
        step/epoch programs for the target layout under their progcache
        key and run ONE zero-input dispatch so XLA compiles NOW, while
        training still runs on the old layout — the post-flip rebuild then
        finds warm wrappers and the migrated epoch costs ~the move instead
        of a recompile (ref: the access-latch-only stall of
        MigrationExecutor.java:163-253). Best-effort: any failure falls
        back to the ordinary rebuild."""
        try:
            from harmony_tpu.table.hashtable import DeviceHashTable

            table = self.ctx.model_table
            is_hash = isinstance(table, DeviceHashTable)
            if self.trainer.uses_local_table:
                return  # the (model, local) pair reshards independently
            if (self.dispatch_turn is not None
                    or self._mesh_spans_processes(table.mesh)
                    or self._mesh_spans_processes(new_mesh)):
                # Multi-process / turnstiled: the prewarm would dispatch a
                # global program from ONE process outside the deterministic
                # schedule — the other processes never join its collectives
                # and the move wedges (same hazard class as _probe_comm's
                # guard). Pod reshard pre-warming needs a collective
                # protocol; fall back to the ordinary rebuild.
                return
            tsh_new = (tuple(table._make_shardings(new_mesh)) if is_hash
                       else table._make_sharding(new_mesh))
            if tsh_new == self._step_sharding:
                return  # announced layout == live layout: nothing to warm
            key = self._program_key(tsh_new, None)
            if key is None:
                return  # uncacheable trainer: a throwaway warm helps nobody
            fused = self._use_fused_epoch()
            stacked = None
            if fused:
                # EVERY worker pre-uploads its own stacked slice to the
                # target layout (pure H2D, no collectives) — the re-upload
                # is part of the relayout stall
                batches = list(self.data.epoch_batches())
                st_sh = NamedSharding(new_mesh, P(None, DATA_AXIS))
                stacked = tuple(
                    jax.device_put(np.stack([b[i] for b in batches]), st_sh)
                    for i in range(len(batches[0]))
                )
                self._prewarmed_stacked = (tsh_new, stacked)
                gkey = self._devcache_key_for_sig(
                    "stacked", progcache.sharding_signature(
                        NamedSharding(new_mesh, P(DATA_AXIS))
                    )
                )
                if gkey is not None:
                    devcache.put(gkey, stacked)
            if not self.global_init:
                return  # program warm is chief-only: progcache is shared,
                # so one worker's warm serves the whole job (N duplicate
                # zero-table epochs would tax the very devices training on)
            build_step, build_epoch = self._program_builders(tsh_new, None)
            step = progcache.get_or_build((key, "step"), build_step)
            epoch_fn = (progcache.get_or_build((key, "epoch"), build_epoch)
                        if fused else None)
            spec = table.spec
            if is_hash:
                # an all-EMPTY hash state (slot_keys == 0) is a valid
                # table; the dummy step's inserts are discarded
                arr0 = (
                    jax.device_put(
                        np.zeros(spec.keys_shape, np.int32), tsh_new[0]),
                    jax.device_put(
                        np.zeros(spec.values_shape, spec.dtype), tsh_new[1]),
                )
            else:
                arr0 = jax.device_put(
                    np.zeros(spec.storage_shape, spec.dtype), tsh_new
                )
            hyper = self._hyper()
            if fused:
                with dispatch_scope(new_mesh) as fin:
                    out = fin(epoch_fn(arr0, stacked, hyper))
            else:
                batch_sh = NamedSharding(new_mesh, P(DATA_AXIS))
                dummy = tuple(
                    jax.device_put(
                        np.zeros((self.data.batch_size, *tail), dt),
                        batch_sh)
                    for tail, dt in self.data.array_specs()
                )
                with dispatch_scope(new_mesh) as fin:
                    out = fin(step(arr0, dummy, hyper))
            jax.block_until_ready(out)  # compile fully done BEFORE the flip
        except Exception:
            return

    def _build_step(self) -> None:
        table = self.ctx.model_table
        data_ax = table.mesh.shape.get(DATA_AXIS, 1)
        if self.data.batch_size % max(data_ax, 1):
            raise ValueError(
                f"mini-batch size {self.data.batch_size} not divisible by the "
                f"mesh data axis ({data_ax}); pick num_mini_batches so that "
                "each batch splits evenly across data-parallel shards"
            )
        # ONE locked read of each table's layout, used for BOTH the cache
        # key and the compiled out_shardings (see _program_key docstring).
        tsh = table.sharding
        lsh = self.ctx.local_table.sharding if self.trainer.uses_local_table else None
        prev_key = self._program_cache_key if self._built_once else None
        self._program_cache_key = self._program_key(tsh, lsh)
        key = self._program_cache_key

        build_step, build_epoch = self._program_builders(tsh, lsh)
        self._step = progcache.get_or_build(
            None if key is None else (key, "step"), build_step
        )
        if self._use_fused_epoch():
            self._epoch_fn = progcache.get_or_build(
                None if key is None else (key, "epoch"), build_epoch
            )
        mesh_now = (tsh[0] if isinstance(tsh, tuple) else tsh).mesh
        self._eval_fn = progcache.get_or_build(
            None if key is None else (key, "eval"),
            lambda: jax.jit(traced_on(mesh_now, self.trainer.evaluate)),
        )
        # Per-batch pull size for op accounting (ref: RemoteAccessOpStat
        # counters behind MetricReportMsg): keys-mode row count comes from
        # an eval_shape of pull_keys (no compute), all-mode pulls capacity.
        if self.trainer.pull_mode == "keys":
            sample = tuple(
                jax.ShapeDtypeStruct((self.data.batch_size, *tail), dt)
                for tail, dt in self.data.array_specs()
            )
            self._pull_rows = int(
                jax.eval_shape(self.trainer.pull_keys, sample).shape[0]
            )
            self._note_push_lowering(mesh_now)
        else:
            self._pull_rows = int(table.spec.config.capacity)
            self._note_update_lowering(mesh_now)
        self._step_sharding = tsh
        self._local_sharding = lsh
        prev_batch_sig = self._batch_sig if self._built_once else None
        # keep the worker's mesh view current: the probe/drain dispatch
        # scopes key their global-order decision on it, and a stale 1-device
        # mesh would skip the lock for now-multi-device programs
        self.mesh = mesh_now
        self._batch_sharding = NamedSharding(mesh_now, P(DATA_AXIS))
        self._batch_cache.clear()   # cached batches live on the old mesh
        self._stacked_cache = None
        pw = self._prewarmed_stacked
        self._prewarmed_stacked = None
        if pw is not None and pw[0] == tsh:
            # the announcement listener already uploaded the dataset to
            # this exact layout — skip the re-upload half of the stall
            self._stacked_cache = pw[1]
        self._probe_pull = None     # probe programs target the old layout
        # memoized: _devcache_key needs it per batch, and the signature
        # enumerates every mesh device
        self._batch_sig = progcache.sharding_signature(self._batch_sharding)
        cur_batch_sig = self._batch_sig
        if (self.data.dataset_key is not None
                and prev_batch_sig is not None
                and prev_batch_sig != cur_batch_sig):
            # An ACTUAL layout transition: release the global device buffers
            # THIS worker cached under the departed layout — otherwise up to
            # the cache budget of HBM stays pinned on devices the job may
            # have just released. Only the departed signature is dropped
            # (never "everything unlike mine"): another tenant's buffers
            # under a different live layout must survive, and a dropped
            # entry in concurrent use stays valid anyway (drops only forget
            # the cache reference; device buffers are immutable).
            devcache.drop(
                lambda k: k[0] == self.data.dataset_key
                and k[2] == prev_batch_sig
            )
        if (prev_key is not None and key != prev_key):
            # Same for compiled programs: the departed layout's executables
            # (out_shardings bound to possibly-released devices) can never
            # hit again under the old key.
            progcache.drop(lambda k: k[0] == prev_key)
        self._built_once = True
        # tenant cost accounting: (re)bind this job's tables for byte
        # attribution, refresh the resident-table gauge, and invalidate
        # the cached per-step FLOP figure (the new build may trace a
        # different program). Guarded: accounting never fails a build.
        self._flops_per_step = None
        try:
            from harmony_tpu.metrics.accounting import ledger

            acct = ledger()
            acct.bind_table(table.spec.table_id, self.job_id,
                            self.attempt_key)
            if self.trainer.uses_local_table:
                acct.bind_table(self.ctx.local_table.spec.table_id,
                                self.job_id, self.attempt_key)
            acct.set_resident(self.job_id, self.attempt_key, "table",
                              self._table_resident_bytes())
            if self._slo_target is not None:
                acct.set_slo_target(self.job_id, self.attempt_key,
                                    self._slo_target)
        except Exception:
            pass

    def _build_comm_probe(self) -> None:
        """Standalone PULL and PULL+PUSH(zero-delta) programs mirroring the
        step's table traffic.

        The fused step folds pull/push into one XLA program, so their time
        is unobservable from outside — and the elasticity optimizer's cost
        model degenerates without a comm/comp split (more shards always
        looks free). These probes make the split measurable: dispatching
        PULL alone times the model-axis all-gather; PULL+PUSH adds the
        delta fold's scatter/reduction; the step time minus both is comp.
        The reference fed its optimizer per-op pull/push timers
        (dolphin/core/worker/ModelAccessor.java:33-49); one probe per
        epoch is the fused-mode equivalent. Non-donating (the live table
        buffer must survive), so a probe transiently holds one extra copy
        of the table array."""
        from harmony_tpu.table.hashtable import DeviceHashTable

        spec = self.ctx.model_table.spec
        trainer = self.trainer
        if isinstance(self.ctx.model_table, DeviceHashTable):
            replicated = NamedSharding(self.ctx.model_table.mesh, P())

            def pull_fn(state, batch):
                keys = jax.lax.with_sharding_constraint(
                    trainer.pull_keys(batch), replicated
                )
                _, rows, _ = spec.pull(state, keys)
                return rows

            def pp_fn(state, batch):
                keys = jax.lax.with_sharding_constraint(
                    trainer.pull_keys(batch), replicated
                )
                new_state, rows, token = spec.pull(state, keys)
                return spec.push(new_state, token, jnp.zeros_like(rows))

        elif trainer.pull_mode == "all":

            def pull_fn(arr, batch):
                return spec.pull_all(arr)

            def pp_fn(arr, batch):
                model = spec.pull_all(arr)
                return spec.push_all(arr, jnp.zeros_like(model))

        else:
            def pull_fn(arr, batch):
                return spec.pull(arr, trainer.pull_keys(batch))

            def pp_fn(arr, batch):
                keys = trainer.pull_keys(batch)
                rows = spec.pull(arr, keys)
                return spec.push(arr, keys, jnp.zeros_like(rows))

        key = self._program_cache_key
        mesh = self.mesh  # the built step's layout snapshot (_build_step)
        self._probe_pull = progcache.get_or_build(
            None if key is None else (key, "probe_pull"),
            lambda: jax.jit(traced_on(mesh, pull_fn)),
        )
        self._probe_pp = progcache.get_or_build(
            None if key is None else (key, "probe_pp"),
            lambda: jax.jit(traced_on(mesh, pp_fn)),
        )

    @staticmethod
    def _mesh_spans_processes(mesh: Mesh) -> bool:
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        return mesh_spans_processes(mesh)

    def _probe_comm(self, batch: Tuple[np.ndarray, ...]) -> None:
        """Time the probe programs on one batch (warmup dispatch first so
        compile never lands in the measurement); stores (pull_s, push_s)
        for _emit_batch_metrics — on the SHARED table, so every worker of
        the job reads the chief's measurement instead of re-measuring the
        same table's cost (the probe blocks the table lock for several
        device round-trips; once per job per epoch is enough). A failed
        probe skips this measurement — the previous split stays in
        effect — and backs off (see the handler below)."""
        spans = self._mesh_spans_processes(self.ctx.model_table.mesh)
        if spans and self.dispatch_turn is None and self.ctx.num_workers != 1:
            # Multi-process mesh with multiple dispatch threads and no
            # turnstile: probe programs are global collectives and a
            # divergent dispatch order would wedge the pod. (Unreachable
            # when the entity wires the turnstile; kept as a guard for
            # direct WorkerTasklet users.)
            return
        if self._probe_pull is None:
            self._build_comm_probe()

        # min-of-3 after a warmup/compile dispatch: these programs run
        # sub-millisecond on small tables and the split comes from a
        # SUBTRACTION, so single-shot jitter would routinely invert it.
        # Under multi-tenant contention each dispatch waits behind other
        # tenants' steps at the dispatch lock, so the sample count drops
        # to 1 — a noisier split beats stalling a cheap tenant for eight
        # serialized waits.
        samples = (1 if self.taskunit is not None and self.taskunit.contended()
                   else 3)

        def timed(fn, *args) -> float:
            # The global dispatch scope wraps each DISPATCH, not the whole
            # loop — on async backends the wait happens outside the lock, so
            # other tenants never stall behind a probe's round-trips.
            def once() -> float:
                t0 = time.perf_counter()
                # model-pull wire-time fault site INSIDE the timed
                # region: a "delay" rule injects deterministic comm
                # latency the probe then honestly MEASURES into the
                # split — the phase-budget acceptance's injection point
                # (the blockmove.send delay-rule precedent)
                if faults.armed():
                    faults.site("worker.pull", job=self.job_id,
                                worker=self.ctx.worker_id, probe=1)
                with dispatch_scope(self.mesh) as fin:
                    out = fin(fn(*args))
                jax.block_until_ready(out)
                return time.perf_counter() - t0

            once()  # warmup/compile
            return min(once() for _ in range(samples))

        try:
            # Under the table lock: another worker's DONATING step must not
            # invalidate the state buffer mid-probe (same rule as every
            # host accessor — see DenseTable.array). The lock is held for
            # the few-ms probe dispatches, once per epoch.
            with self.ctx.model_table._lock:
                state = self.ctx.model_table._step_state
                batch_dev = self._shard_batch(batch)
                t_pull = timed(self._probe_pull, state, batch_dev)
                t_pp = timed(self._probe_pp, state, batch_dev)
        except Exception as e:
            if spans:
                # A one-sided probe failure on a multi-process mesh has
                # already desynchronized the pod's dispatch order (this
                # process dispatched fewer global programs than its
                # peers). Failing the job fast beats wedging the pod in a
                # collective that can never complete.
                raise
            # A probe failure (a table too large for the probe's
            # non-donating copies, a layout race, a transient backend
            # error) must never kill training, and must not slow it
            # either: the programs stay built (no compile, and
            # _epoch_window_len keeps its windows), this measurement is
            # skipped, and the next attempt waits twice as long after
            # each failure in a row — the caller has just set
            # _next_probe one refresh period ahead.
            self._probe_failures += 1
            self._next_probe += 8 * self.comm_probe_every * (
                2 ** min(self._probe_failures, 8) - 1)
            if self._probe_failures == 1:
                logging.getLogger(__name__).warning(
                    "%s: comm probe failed (%s: %s); no new comm/comp "
                    "split, the next attempts back off", self.job_id,
                    type(e).__name__, str(e)[:300])
            return
        self._probe_failures = 0
        self._comm_probe_times = (t_pull, max(t_pp - t_pull, 0.0))
        # publish for sibling workers sharing this table (read at emit
        # time) through the table's typed accessor — a lock-fenced
        # publication, not a private-attr poke
        self.ctx.model_table.set_comm_split(self._comm_probe_times)

    def _use_fused_epoch(self) -> bool:
        """Whole-epoch compilation is only correct with no between-batch host
        decisions: no SSP gate, no TaskUnit scheduling, stable batches.
        Under a TaskUnit scheduler the per-batch path is kept so concurrent
        tenants interleave at BATCH granularity (one fused epoch would hand
        one tenant the device for a whole epoch per grant)."""
        return (
            self.batch_barrier is None
            and self.taskunit is None
            and not self.data.is_shuffling
        )

    # Max fused epochs per drain. Each drained window costs one full
    # host<->device round trip; on small PS jobs those round trips, not
    # compute, can dominate the epoch loop. Bounded so donated-buffer
    # chains and metric latency stay short.
    EPOCH_WINDOW = 8

    def _epoch_window_cap(self) -> int:
        """``EPOCH_WINDOW``, or the operator's ``HARMONY_EPOCH_WINDOW`` where
        that is smaller: a job whose step runs for most of a second feeds the
        ledger (STATUS, the metrics) only every ``window x step`` seconds, and
        a drain costs it a host turnaround — the operator's trade. A
        setting, not a figure taken from the epochs' measured time: every
        process of a pod must cut its windows alike, and their clocks do
        not agree. Process-wide, like every ``HARMONY_*`` knob."""
        raw = os.environ.get("HARMONY_EPOCH_WINDOW")
        if not raw:
            return self.EPOCH_WINDOW
        return max(1, min(self.EPOCH_WINDOW, int(raw)))

    def _epoch_window_len(self, epoch: int, num_epochs: int) -> int:
        """How many consecutive epochs may dispatch before the next drain.

        >1 only when nothing on the host needs to OBSERVE state between
        epochs: no SSP barrier (its stop decisions are per batch), a
        windowable trainer hook (see Trainer.epoch_hook_windowable), and
        an epoch callback that is either absent or declared deferrable
        (metrics-only). Works over both the fused-epoch and the
        async-batched dispatch paths (the latter keeps per-batch TaskUnit
        admission, so multi-tenant interleaving is unchanged). The window
        never crosses a comm-probe epoch — the probe measures the live
        table between dispatches."""
        if self.batch_barrier is not None:
            return 1
        if self.pod_contended is not None and self.pod_contended():
            # Cross-job pod tenancy: a multi-epoch window is one dispatch
            # UNIT, and co-tenants wait out whole units — contended jobs
            # interleave at single-epoch granularity instead. The flag is
            # read at the last completed unit's exit (deterministic
            # pod-wide), so every process shrinks at the same epoch.
            return 1
        # un-overridden hooks are no-ops (windowable by construction);
        # overriders must OPT IN at the class that defines the hook
        if not Trainer._epoch_hook_windowable(self.trainer):
            return 1
        if self.epoch_callback is not None and not self.defer_epoch_callback:
            return 1
        w = min(self._epoch_window_cap(), num_epochs - epoch)
        if self.pending_plan_epoch is not None:
            due = self.pending_plan_epoch()
            if due is not None and due >= epoch:
                w = min(w, due - epoch + 1)  # window ends AT the plan epoch
        if self.comm_probe_every and self.global_init:
            if self._probe_pull is None:
                # a probe (re)build is due at this epoch boundary — keep
                # per-epoch until it has run (first epoch / after reshard)
                w = min(w, 1)
            else:
                until = self._next_probe - (epoch - self.starting_epoch)
                if until > 0:
                    w = min(w, until)
        return max(1, w)

    def _maybe_rebuild(self) -> None:
        """Live re-sharding: if EITHER table's layout changed since compile
        (plan-driven migration), rebuild so out_shardings/donation target the
        new mesh instead of pinning results to released devices."""
        if self.ctx.model_table.sharding != self._step_sharding:
            self._build_step()
        elif (
            self.trainer.uses_local_table
            and self.ctx.local_table.sharding != self._local_sharding
        ):
            self._build_step()

    def _shard_batch(self, batch: Tuple[np.ndarray, ...]):
        return tuple(jax.device_put(a, self._batch_sharding) for a in batch)

    def _host_batch(self, batch_idx: int, batch):
        """The host arrays for ``batch_idx`` — ``batch`` when the caller
        carried them, else re-materialized from the provider (only reached
        on stable-batch paths: a devcache-bypass epoch whose cache a live
        reshard just cleared)."""
        if batch is not None:
            return batch
        return self.data.batch_at(batch_idx)

    def _prefetch_usable(self) -> bool:
        """Background staging is safe only where this worker's device_puts
        may interleave freely with dispatches: pod-lockstep turnstiles
        need every multi-device operation inside an admission turn, and on
        multi-process meshes a device_put that replicates across processes
        is itself collective-backed — both would wedge under a producer
        thread. The TaskUnit fair queue is fine (staging rides it as NET
        units when single-worker — see _epoch_batch_stream)."""
        return (
            self._prefetch_on
            and self.dispatch_turn is None
            and not self._staging_unsafe  # announced spanning target
            and not self._mesh_spans_processes(self.mesh)
        )

    def _devcache_epoch_ready(self) -> bool:
        """True when EVERY batch of the (stable) epoch already has a
        device-resident copy — the epoch then bypasses host assembly and
        staging entirely (the devcache-hit fast path)."""
        if not self.cache_device_batches:
            return False
        nb = self.data.num_mini_batches
        if len(self._batch_cache) == nb:
            return True
        return all(
            i in self._batch_cache or devcache.contains(self._devcache_key(i))
            for i in range(nb)
        )

    def _epoch_batch_stream(self, epoch: int):
        """One epoch's input as (batch_idx, host_batch | None, StagedBatch
        | None) triples — the three input regimes behind one iterator:

          * devcache-hit epoch: every batch is device-resident already;
            host assembly is bypassed entirely (host_batch is None);
          * prefetched epoch: a PrefetchPipeline producer assembles and
            stages batches ahead of the compute loop;
          * synchronous fallback (config off / pod lockstep /
            multi-process mesh): the pre-pipeline behavior, unchanged.

        Callers MUST close() the returned generator (the dispatch loop's
        finally does) so an early stop tears the producer down."""
        # ONE ready evaluation for both the handoff decision and the
        # branch below: a sibling worker devcache.put-ing the last batch
        # between two evaluations could flip it and strand the handoff
        # unclosed (leaked producer thread + staged device buffers)
        ready = self._devcache_epoch_ready()
        handoff, self._next_pipeline = self._next_pipeline, None
        if handoff is not None and (handoff[0] != epoch or ready):
            # wrong-epoch (defensive; epochs stream in order) or the cache
            # filled (stable batches only — no RNG was drawn): tear the
            # pre-spawn down before any fallback path
            handoff[1].close()
            handoff = None
        if ready:
            for i in range(self.data.num_mini_batches):
                yield i, None, None
            return
        if not self._prefetch_usable():
            if handoff is not None:
                # usability flipped AFTER the spawn (reshard onto a
                # spanning mesh): the producer already drew this epoch's
                # shuffle, so abandoning it would double-advance the RNG
                # and break seeded parity — consume it in host-only mode
                # (no background device_puts) instead
                handoff[1].stop_staging()
            else:
                # synchronous fallback: the feed (when present) must
                # still be the source — its epoch replay never advanced
                # the provider's sequential RNG, so epoch_batches() here
                # would replay epoch 0's draw
                src = (self._input_feed.epoch_iter(epoch)
                       if self._input_feed is not None
                       else self.data.epoch_batches())
                for i, b in enumerate(src):
                    yield i, b, None
                return
        if handoff is not None:
            # pre-spawned during the previous epoch's drain: batch 0 is
            # (usually) already staged — no epoch-start input stall
            pipeline = handoff[1]
        else:
            pipeline = self._make_pipeline(epoch)
        self._active_pipeline = pipeline
        if self._staging_unsafe:
            # an announcement may have raced pipeline construction (the
            # listener demotes only pipelines it can SEE); recheck after
            # the assignment so one side always lands — idempotent
            pipeline.stop_staging()
        self._prefetch_hits = 0
        self._prefetch_misses = 0
        try:
            for staged in pipeline:
                yield staged.index, staged.host, staged
        finally:
            self._active_pipeline = None
            pipeline.close()
            self._emit_prefetch_metrics(epoch, pipeline)

    def _make_pipeline(self, epoch: int) -> PrefetchPipeline:
        net_scope = None
        if self.taskunit is not None and self.ctx.num_workers == 1:
            # staging transfers ride the fair queue as NET units (the
            # reference's PULL/PUSH resource class) with an interruptible
            # admission wait (teardown must not hang on a grant that can
            # no longer arrive) — but only for single-worker jobs:
            # TaskUnit quorum matches per-worker seq streams, and
            # producer-timed units would misalign them across a
            # multi-worker job's executors
            net_scope = lambda abort: self.taskunit.scope(  # noqa: E731
                "NET", abort=abort)
        skip_staged = None
        if self.cache_device_batches:
            # partial-cache epochs (one LRU-evicted batch) re-stage only
            # what is actually missing; resident batches flow host-only
            skip_staged = lambda i: (  # noqa: E731
                i in self._batch_cache
                or devcache.contains(self._devcache_key(i))
            )
        epoch_source = None
        if self._input_feed is not None:
            feed = self._input_feed
            # bound per pipeline: each pipeline owns ONE epoch's stream
            epoch_source = lambda: feed.epoch_iter(epoch)  # noqa: E731
        return PrefetchPipeline(
            self.data,
            lambda: self._batch_sharding,
            self._inflight_cap,
            epoch=epoch,
            job_id=self.job_id,
            net_scope=net_scope,
            skip_stage_fn=skip_staged,
            epoch_source=epoch_source,
        )

    def _spawn_next_pipeline(self, next_epoch: int) -> None:
        """Cross-epoch overlap: spawned right BEFORE this epoch's metric
        drain (its blocking device round-trips are the one host-idle
        window of the batched loop), so the next epoch's gather and
        staging run during the drain and batch 0 is ready when the next
        stream opens. Only called after the current epoch's stream fully
        drained, so the provider's per-epoch RNG draws stay in epoch
        order — seeded shuffles match the synchronous path exactly."""
        if self._next_pipeline is not None:
            return
        if next_epoch >= self.ctx.params.num_epochs:
            return
        if not self._prefetch_usable() or self._devcache_epoch_ready():
            return
        pipeline = self._make_pipeline(next_epoch)
        self._next_pipeline = (next_epoch, pipeline)
        if self._staging_unsafe:
            # announcement raced the spawn (see _epoch_batch_stream)
            pipeline.stop_staging()

    def _close_next_pipeline(self) -> None:
        if self._next_pipeline is not None:
            self._next_pipeline[1].close()
            self._next_pipeline = None

    def _emit_prefetch_metrics(self, epoch: int, pipeline: PrefetchPipeline) -> None:
        s = pipeline.stats()
        svc = fb = 0
        if self._input_feed is not None:
            # EXACT per-epoch attribution from the feed (a cumulative
            # delta would misattribute when the pre-spawned next-epoch
            # pump lands batches before this epoch's emit)
            es = self._input_feed.epoch_stats(epoch)
            svc = es["service"]
            fb = es["fallbacks"]
        self.collector.add(
            InputPipelineMetrics(
                job_id=self.job_id,
                worker_id=self.ctx.worker_id,
                epoch_idx=epoch,
                staged_batches=s["staged"],
                prefetch_hits=self._prefetch_hits,
                prefetch_misses=self._prefetch_misses,
                max_depth=s["max_depth"],
                produce_sec=s["produce_sec"],
                stage_sec=s["stage_sec"],
                producer_idle_sec=s["producer_idle_sec"],
                consumer_stall_sec=s["consumer_stall_sec"],
                dropped_batches=s["dropped_batches"],
                service_batches=svc,
                service_fallbacks=fb,
            )
        )
        # input_wait phase (metrics/phases.py): staged per epoch here —
        # the stream closes inside the dispatch loop, before the epoch
        # wall is known at _finish_epoch, where the budget feeds
        self._phase_input_wait[epoch] = float(s["consumer_stall_sec"])
        try:  # tenant ledger: input-wait seconds feed the wait fraction
            from harmony_tpu.metrics.accounting import ledger

            ledger().record_input_wait(self.job_id, self.attempt_key,
                                       s["consumer_stall_sec"])
        except Exception:
            pass

    def _on_layout_announcement(self, new_mesh: Mesh) -> None:
        """Reshard announcement listener: staged input batches target the
        departing layout — drop their device copies (the consumer
        re-places the retained host arrays on the live mesh), THEN prewarm
        the target layout's programs. A target mesh that SPANS processes
        makes background device_puts collective-backed, so there the
        producers are demoted to host-only assembly (they keep the epoch
        RNG draw; the consumer places on the live mesh) instead of merely
        invalidated."""
        unsafe = self._mesh_spans_processes(new_mesh)
        # sticky until a later announcement says otherwise: the worker's
        # own mesh view (self.mesh) only updates at the post-flip rebuild,
        # so _prefetch_usable would otherwise green-light one more staging
        # producer in the announcement->flip window
        self._staging_unsafe = unsafe
        # snapshot both attributes: the training thread concurrently
        # hands off / nulls them (this listener runs on the master thread)
        nxt = self._next_pipeline
        for pipeline in (
            self._active_pipeline,
            nxt[1] if nxt is not None else None,
        ):
            if pipeline is None:
                continue
            if unsafe:
                pipeline.stop_staging()
            else:
                pipeline.invalidate()
        self._prewarm_layout(new_mesh)

    def _devcache_key_for_sig(self, tag, sig) -> "tuple | None":
        """devcache key under an EXPLICIT layout signature (the prewarm
        path registers uploads for a layout that is not live yet)."""
        if self.data.dataset_key is None:
            return None
        return (self.data.dataset_key, tag, sig)

    def _devcache_key(self, tag) -> "tuple | None":
        """Key into the process-level device data cache (data/devcache) —
        None unless the provider carries a data-source identity."""
        return self._devcache_key_for_sig(tag, self._batch_sig)

    def _cached_batch(self, batch_idx: int, batch):
        """Device copy of one batch. The global cache (when the dataset has
        an identity) lets resubmitted jobs reuse device buffers; the
        per-worker cache is ALWAYS kept as well, so a dataset that blows the
        global byte budget (LRU thrash, 0% hit rate) still uploads at most
        once per worker — never worse than the cache-free behavior."""
        batch_dev = self._batch_cache.get(batch_idx)
        if batch_dev is not None:
            return batch_dev
        gkey = self._devcache_key(batch_idx)
        batch_dev = devcache.get(gkey) if gkey is not None else None
        if batch_dev is None:
            batch_dev = self._shard_batch(self._host_batch(batch_idx, batch))
            if gkey is not None:
                devcache.put(gkey, batch_dev)
        self._batch_cache[batch_idx] = batch_dev
        return batch_dev

    # Bounded retries when a live reshard lands BETWEEN the rebuild check
    # and the dispatch (a step compiled for the old layout then receives the
    # new-layout array — XLA raises a device-mismatch at dispatch time, the
    # step does not execute). Reshards are rare; one retry usually wins.
    MAX_RESHARD_RETRIES = 4

    @staticmethod
    def _is_layout_race(e: ValueError) -> bool:
        return "incompatible devices" in str(e)

    def _dispatch_batch(self, batch_idx: int, batch, hyper,
                        staged: "Optional[StagedBatch]" = None):
        """Rebuild-check + batch placement + dispatch, retried across
        concurrent reshards (the batch cache re-populates on the new mesh
        after a rebuild clears it). ``staged`` is a prefetched device copy;
        it is used only while its sharding still matches the live step's
        (a reshard invalidates it and the host copy is re-placed)."""
        # step-boundary fault site (armed()-guarded: disarmed cost is one
        # global read — no ctx dict, no site dispatch). A "crash" rule
        # here kills THIS process mid-epoch like a SIGKILL'd follower —
        # the deterministic trigger the pod recovery tests arm via the
        # env-serialized plan (match on proc to pick the victim).
        if faults.armed():
            faults.site(
                "worker.step", job=self.job_id, worker=self.ctx.worker_id,
                batch=batch_idx, proc=jax.process_index(),
            )
        for _ in range(self.MAX_RESHARD_RETRIES):
            self._maybe_rebuild()
            # host-dispatch phase (metrics/phases.py): the host seconds
            # between batch-ready and the device dispatch call —
            # placement, cache lookups, staging takes. Timed so the
            # budget can subtract it from the smeared step wall; the
            # fault site INSIDE the region lets a "delay" rule inject a
            # deterministic host stall the budget then measures (the
            # dispatch-bound acceptance scenario).
            t_place = time.perf_counter()
            if faults.armed():
                faults.site("worker.dispatch", job=self.job_id,
                            worker=self.ctx.worker_id, batch=batch_idx)
            batch_dev = staged.take(self._batch_sharding) if staged is not None else None
            if batch_dev is not None:
                self._prefetch_hits += 1
                if self.cache_device_batches and batch_idx not in self._batch_cache:
                    # seed the caches with the prefetched copy so later
                    # epochs (and resubmissions) bypass host work entirely
                    self._batch_cache[batch_idx] = batch_dev
                    gkey = self._devcache_key(batch_idx)
                    if gkey is not None:
                        devcache.put(gkey, batch_dev)
            elif self.cache_device_batches:
                if staged is not None:
                    self._prefetch_misses += 1
                batch_dev = self._cached_batch(batch_idx, batch)
            else:
                if staged is not None:
                    self._prefetch_misses += 1
                batch_dev = self._shard_batch(self._host_batch(batch_idx, batch))
            self._phase_dispatch_acc += time.perf_counter() - t_place
            # model-pull wire-time site on the step path proper (the
            # probe carries its twin): a "delay" rule makes each step
            # pay the injected comm latency the probe measured, so the
            # budget's pull_comm attribution matches the wall it splits.
            if faults.armed():
                faults.site("worker.pull", job=self.job_id,
                            worker=self.ctx.worker_id, batch=batch_idx)
            try:
                if self._step is not self._step_dispatched:
                    # the first dispatch of a new step program is where it
                    # is traced, lowered and compiled (or loaded): lazy in
                    # jit and in the program cache's wrapper alike
                    self._step_dispatched = self._step
                    with job_stage(self.job_id, "build_step", first=True):
                        return self._dispatch_step(self._step, batch_dev,
                                                   hyper)
                return self._dispatch_step(self._step, batch_dev, hyper)
            except ValueError as e:
                if not self._is_layout_race(e):
                    raise
                # FORCE a rebuild: the race proves something layout-derived
                # is stale even if the cheap sharding compare above missed
                # it (every cache repopulates on the current mesh). The
                # staged copy targets the departed layout — drop it.
                staged = None
                self._build_step()
        raise RuntimeError(
            f"table resharded {self.MAX_RESHARD_RETRIES}x during one batch "
            "dispatch; reconfiguration is outpacing training"
        )

    def _hyper(self) -> Dict[str, jnp.ndarray]:
        return {k: jnp.asarray(v) for k, v in self.trainer.hyperparams().items()}

    def _dispatch_step(self, fn, batch_like, hyper=None):
        """Route the dispatch through the owning table lock(s)."""
        from harmony_tpu.table.table import DenseTable

        if hyper is None:
            hyper = self._hyper()
        if self.trainer.uses_local_table:
            return DenseTable.apply_step_multi(
                [self.ctx.model_table, self.ctx.local_table],
                fn,
                batch_like,
                hyper,
            )
        return self.ctx.model_table.apply_step(fn, batch_like, hyper)

    # -- the loop --------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        """One span covers the worker's whole run — re-parented onto the
        dispatch/submit trace when the entity handed a wire context down
        (the job's epochs/steps/checkpoints/moves then share the
        submission's trace_id end to end), a fresh root otherwise."""
        with trace_span(
            "dolphin.worker",
            parent=SpanContext.from_wire(self.trace_parent),
            job_id=self.job_id,
            worker_id=self.ctx.worker_id,
            attempt=self.attempt_key,
        ):
            return self._run_inner()

    def _run_inner(self) -> Dict[str, Any]:
        ctx, params = self.ctx, self.ctx.params
        # Global init writes shared tables (multi-device programs): under
        # pod tenancy that region holds a dispatch turn/unit like any
        # batch (siblings are parked at the init barrier, but OTHER jobs'
        # units must not interleave mid-init). Turn BALANCE: the cyclic
        # turnstile admits workers in strict rotation, so chief-only
        # turns would skew the alternation and walk the SSP gate past its
        # slack INSIDE a turn (deadlock) — every worker takes the turn,
        # no-op for non-chiefs.
        if self.global_init:
            # also a TaskUnit under tenancy: un-gated init dispatches
            # collide FIFO at the raw dispatch lock behind peers' units —
            # both delaying this job's start and jittering the peers
            with self._turn(), self._taskunit_scope("CPU"):
                with job_stage(self.job_id, "init"):
                    self.trainer.init_global_settings(ctx)
        elif self._balanced_turns() or self.taskunit is not None:
            # siblings announce the SAME init unit (empty region): the
            # TaskUnit quorum needs every worker to wait on each (seq,
            # kind), and the cyclic turnstile needs matching turn counts —
            # a chief-only unit would misalign both for the whole job
            with self._turn(), self._taskunit_scope("CPU"):
                pass
        if self.post_init_barrier is not None:
            self.post_init_barrier()
        self.trainer.on_training_start(ctx, self.starting_epoch)
        # subscribe to reshard announcements: staged input batches drop
        # their device copies and the target layout's programs compile
        # WHILE training still runs on the old one (_on_layout_announcement)
        add_listener = getattr(ctx.model_table, "add_layout_listener", None)
        if add_listener is not None:
            add_listener(self._on_layout_announcement)
        try:
            return self._run_epoch_loop(params)
        finally:
            # a pre-spawned next-epoch producer must not outlive the run
            # (early stop / exception): join it before reporting back
            self._close_next_pipeline()
            remove = getattr(ctx.model_table, "remove_layout_listener", None)
            if remove is not None:
                remove(self._on_layout_announcement)

    def _run_epoch_loop(self, params) -> Dict[str, Any]:
        ctx = self.ctx
        with job_stage(self.job_id, "build_step"):
            self._build_step()
        self._budget_mark = time.monotonic_ns()
        self._compile_mark = self._compile_seconds()
        # job.first_window: from here to the end of the first drained
        # window's bookkeeping — when the job's counters first move
        first_window = contextlib.ExitStack()
        first_window.enter_context(job_stage(self.job_id, "first_window"))
        try:
            return self._epoch_loop_body(params, first_window)
        finally:
            first_window.close()

    def _epoch_loop_body(self, params, first_window) -> Dict[str, Any]:
        ctx = self.ctx
        stop = False
        global_batch_idx = 0
        epoch_losses: List[float] = []
        epoch = self.starting_epoch
        while epoch < params.num_epochs and not stop:
            # chief-only (the split is a property of the shared table, not
            # the worker; siblings read the published value). Probe batch
            # is a plain prefix slice — the provider's epoch_batches()
            # would consume a shuffle from its RNG and change seeded batch
            # order relative to a probe-free run.
            since = epoch - self.starting_epoch
            if self.comm_probe_every and self.global_init and (
                self._probe_pull is None or since >= self._next_probe
            ):
                self._next_probe = since + 8 * self.comm_probe_every
                first = self.data.first_rows(self.data.batch_size)
                if first and len(first[0]):
                    if (self.dispatch_turn is not None
                            and not self._use_fused_epoch()):
                        # turnstiled/batched: defer into the first batch
                        # turn so the probe's dispatches happen inside
                        # this worker's admission slot (a separate CYCLIC
                        # turn would skew the turnstile unboundedly)
                        self._pending_probe = first
                    else:
                        # fused path (pod units are request/grant, not a
                        # cycle — an extra unit is harmless) or no turns;
                        # a TaskUnit under tenancy for the same raw-lock
                        # reason as global init — but ONLY single-worker
                        # jobs: the probe is chief-only, and a chief-only
                        # unit would misalign the multi-worker quorum's
                        # per-worker seq streams
                        with trace_span(
                                "dolphin.comm_probe", job_id=self.job_id,
                                epoch=epoch,
                                acc=self._span_acc("dolphin.comm_probe")):
                            with self._turn(), (
                                    self._taskunit_scope("CPU")
                                    if self.ctx.num_workers == 1
                                    else contextlib.nullcontext()):
                                self._timed_probe(first)
            window = self._epoch_window_len(epoch, params.num_epochs)
            batch_idx0 = global_batch_idx
            if window > 1:
                # Multi-epoch window: dispatches chain on the table state
                # with trainer hooks run between them (declared windowable
                # = epoch-indexed only), ONE drain at the end, then the
                # per-epoch host bookkeeping replays in order.
                # sampled continuous device capture (chief-only: the
                # profiler is process-wide; N workers double-starting
                # would fight over one session)
                with maybe_profile_epoch(
                    epoch, self.job_id, span=window,
                    enabled=self.global_init,
                ), trace_span(
                    "dolphin.epoch_window",
                    job_id=self.job_id,
                    worker_id=self.ctx.worker_id,
                    epoch=epoch,
                    epochs=window,
                    window=self._window,
                    fused=self._use_fused_epoch(),
                ):
                    if self._use_fused_epoch():
                        results, per_epoch_sec = self._run_fused_epochs(
                            epoch, window
                        )
                        global_batch_idx += (
                            window * self.data.num_mini_batches
                        )
                    else:
                        results, global_batch_idx, per_epoch_sec = (
                            self._run_batched_epochs_window(
                                epoch, window, global_batch_idx
                            )
                        )
                # an epoch's wall is the window's / k, so the window's
                # control seconds are fed / k too (as drain_t / k is)
                wall, ctl = self._take_budget_feed(
                    window, epoch, global_batch_idx - batch_idx0)
                with trace_span("window.bookkeeping", job_id=self.job_id,
                                epoch=epoch, epochs=window,
                                acc=self._span_acc("window.bookkeeping",
                                                   self._add_bookkeeping)):
                    for j, (epoch_examples, last_metrics, nb) in enumerate(
                            results):
                        # account THIS epoch's ops just before its callback
                        # replays, so the callback's ServerMetrics delta
                        # covers exactly one epoch
                        self._account_ops(nb)
                        self._finish_epoch(
                            epoch + j,
                            time.perf_counter() - per_epoch_sec,
                            epoch_examples,
                            last_metrics,
                            epoch_losses,
                            # all but the window's LAST hook ran between
                            # dispatches; the last runs here, post-drain
                            call_trainer_hook=(j == len(results) - 1),
                            budget_wall=wall, budget_ctl=ctl,
                        )
                first_window.close()
                epoch += window
                continue
            epoch_t0 = time.perf_counter()
            with maybe_profile_epoch(
                epoch, self.job_id, enabled=self.global_init,
            ), trace_span(
                "dolphin.epoch",
                job_id=self.job_id,
                worker_id=self.ctx.worker_id,
                epoch=epoch,
                window=self._window,
                fused=self._use_fused_epoch(),
            ) as span:
                if self._use_fused_epoch():
                    results, _ = self._run_fused_epochs(epoch, 1)
                    epoch_examples, last_metrics, nb1 = results[0]
                    self._account_ops(nb1)
                    global_batch_idx += self.data.num_mini_batches
                else:
                    epoch_examples, last_metrics, global_batch_idx, stop = (
                        self._run_batched_epoch(epoch, global_batch_idx)
                    )
                if epoch_examples == 0 and stop and span is not None:
                    # stopped before any batch: "not an epoch at all" below,
                    # so the span must not inflate per-epoch aggregates
                    span.discard()
            if epoch_examples == 0 and stop:
                break  # stopped before any batch: not an epoch at all
            wall, ctl = self._take_budget_feed(
                1, epoch, global_batch_idx - batch_idx0)
            with trace_span("window.bookkeeping", job_id=self.job_id,
                            epoch=epoch, epochs=1,
                            acc=self._span_acc("window.bookkeeping",
                                               self._add_bookkeeping)):
                self._finish_epoch(epoch, epoch_t0, epoch_examples,
                                   last_metrics, epoch_losses,
                                   budget_wall=wall, budget_ctl=ctl)
            first_window.close()
            epoch += 1
        self._observe_vector_backlog()  # the last window's
        self.trainer.cleanup(ctx)
        return {
            "job_id": self.job_id,
            # (starting_epoch, epochs_run) is the exactly-once evidence
            # the elastic tests stitch across recovery attempts: each
            # attempt's half-open epoch range [starting_epoch,
            # starting_epoch + epochs_run) must tile [0, num_epochs)
            "starting_epoch": self.starting_epoch,
            "epochs_run": len(epoch_losses),
            "losses": epoch_losses,
            "stopped_early": stop,
        }

    # Bound on steps enqueued without a device sync (keeps the dispatch
    # queue and donated-buffer chain short on long epochs).
    MAX_INFLIGHT = 32
    # Under multi-tenant contention the deep window becomes the UNFAIRNESS:
    # another tenant's next unit waits behind this job's whole enqueued
    # backlog (a 15x slowdown for the cheapest of three tenants when measured)
    # — so contended jobs keep the device queue shallow.
    CONTENDED_INFLIGHT = 2

    def _inflight_cap(self) -> int:
        if self.taskunit is not None and self.taskunit.contended():
            return self.CONTENDED_INFLIGHT
        return self.MAX_INFLIGHT

    def _run_batched_epoch(
        self, epoch: int, global_batch_idx: int
    ) -> Tuple[int, Dict[str, float], int, bool]:
        """Per-batch dispatch with SYNC gate + TaskUnit announcement.

        Dispatch is ASYNC: steps enqueue without blocking, metrics stay on
        device, and ONE stacked transfer per metric key at epoch end fetches
        them all — a per-batch scalar read would stall the host on the
        device once per step. Blocking on the step's own outputs
        (never a table snapshot a donating step could invalidate) is
        preserved; it just happens once per epoch / in-flight window.

        TaskUnit semantics under async dispatch: the COMP scope gates
        ADMISSION, not occupancy. The device executes one XLA program at a
        time, so the globally-coordinated grant order becomes the device
        queue order — which is the interleaving the reference's occupancy
        slots produced on CPU executors (and, multi-host, identical
        enqueue order across hosts is what keeps collectives
        deadlock-free). Holding the slot through device execution would
        add a host wait per batch without changing the device-side
        serialization.
        """
        pending, batch_sizes, epoch_examples, global_batch_idx, stop, work_t = (
            self._dispatch_epoch_batches(epoch, global_batch_idx)
        )
        dispatch_sec = self._take_dispatch_sec()
        if not stop:
            # next epoch's host assembly runs while the drain below blocks
            # (under TaskUnit contention its STAGING still queues behind
            # the drain's NET unit — per-kind metering admits one NET unit
            # at a time across tenants, by design; the gather/shuffle work
            # overlaps regardless)
            self._spawn_next_pipeline(epoch + 1)
        self._observe_vector_backlog()  # the device has this epoch's steps
        last_metrics: Dict[str, float] = {}
        if pending:
            with trace_span("dolphin.metric_drain", job_id=self.job_id,
                            epoch=epoch, batches=len(pending)):
                # the drain's stack programs are multi-device dispatches:
                # under pod lockstep they take a turn like any batch, and
                # under TaskUnit tenancy they are a NET unit (a transfer
                # phase, like the reference's PULL/PUSH typing) so they
                # ride the fair queue instead of colliding FIFO at the
                # raw dispatch lock behind peers' compute units. The
                # timer starts INSIDE — admission wait is scheduling, not
                # work, and must not inflate the per-batch times feeding
                # the optimizer's cost model.
                with self._turn(), self._taskunit_scope("NET"):
                    t0 = time.perf_counter()
                    host = self._drain_pending(pending)
            work_t += time.perf_counter() - t0
            # Async dispatch makes true per-batch device time unobservable
            # without per-step syncs; smear the epoch's work time (barrier
            # waits excluded) evenly — averages feeding the optimizer stay
            # right, per-batch variance is deliberately given up.
            with trace_span("drain.emit", acc=self._span_acc(
                    "drain.emit", self._add_bookkeeping)):
                last_metrics = self._emit_batch_metrics(
                    epoch, host, batch_sizes, work_t / len(pending),
                    dispatch_sec=dispatch_sec,
                )
            self._account_ops(len(pending))
        return epoch_examples, last_metrics, global_batch_idx, stop

    # Target span of one admitted TaskUnit under contention: a cheap job
    # pays ~one residual big-unit wait per OWN unit (non-preemptive slot),
    # so per-batch units make its slowdown scale with the PEERS' batch
    # time. Grouping consecutive batches until a unit spans ~this many
    # seconds normalizes unit granularity in TIME across tenants. 60ms:
    # the residual a cheap tenant eats per grant scales with THIS number
    # (FAIRNESS max-slowdown was the cheapest job at 0.1), while grants
    # themselves are in-process condition-variable ops — near free.
    UNIT_SPAN_TARGET = 0.06

    def _units_per_scope(self) -> int:
        if self.batch_barrier is not None:
            return 1  # the SSP gate is per batch; never hold a slot on it
        if self.taskunit is not None:
            if not self.taskunit.contended():
                return 1
            c = self._own_batch_cost
            if c is None:
                return 1
            # A tenant pays ~one residual PEER-unit wait per own unit
            # (non-preemptive slot), so the dominant slowdown term for a
            # cheap job is its UNIT COUNT, not its unit size: stretch the
            # span target toward the largest peer unit (bounded — never
            # hold the slot longer than half a second) so a cheap job
            # crosses the schedule few times instead of once per batch.
            target = self.UNIT_SPAN_TARGET
            peer = self.taskunit.peer_unit_cost()
            if peer:
                target = max(target, min(peer, 0.5))
            return max(1, min(8, int(target / max(c, 1e-6))))
        if self.pod_contended is not None and self.dispatch_turn is not None:
            # Pod units on the batched path: group a FIXED batch count per
            # unit so an uncontended job does not pay a leader round trip
            # per mini-batch. Fixed, not UNIT_SPAN_TARGET-measured — the
            # group size must be identical on every process (a local
            # timing would diverge the unit sequence and wedge the pod);
            # the contended flag is deterministic (read at unit exit).
            return 1 if self.pod_contended() else 8
        return 1

    def _dispatch_epoch_batches(self, epoch: int, global_batch_idx: int):
        """The per-batch dispatch loop of one epoch — async, TaskUnit
        admission per batch group (see _units_per_scope), NO drain.
        Returns (pending device metrics, batch_sizes, examples,
        global_batch_idx, stop, dispatch_seconds)."""
        epoch_examples = 0
        stop = False
        pending: List[Dict[str, jnp.ndarray]] = []
        batch_sizes: List[int] = []
        work_t = 0.0  # dispatch time, EXCLUDING admission/barrier waits
        # between two epochs' dispatches the host puts the hyperparameters
        # on the device and opens the next batch stream: a light span, so a
        # device that runs dry here says so
        with trace_span("epoch.turnover", record=False,
                        acc=self._span_acc("epoch.turnover")):
            hyper = self._hyper()
            it = self._epoch_batch_stream(epoch)
            nxt = next(it, None)
        try:
            while nxt is not None and not stop:
                with self._turn():
                    if self._pending_probe is not None:
                        # turnstiled pods probe inside the chief's first batch
                        # turn (a separate probe turn would skew the cycle by
                        # one turn per probe epoch, unboundedly across epochs)
                        first, self._pending_probe = self._pending_probe, None
                        with trace_span(
                                "dolphin.comm_probe", job_id=self.job_id,
                                epoch=epoch,
                                acc=self._span_acc("dolphin.comm_probe")):
                            self._timed_probe(first)
                    if self.batch_barrier is not None:  # SYNC TaskUnit
                        stop = self.batch_barrier(global_batch_idx)
                        if stop:
                            break
                    group = self._units_per_scope()
                    with self._taskunit_scope("COMP"):
                        # timer starts AFTER admission: the grant wait is
                        # scheduling, not work — counting it would both skew
                        # the optimizer's comm/comp split and feed an
                        # inflated unit cost back into the fair-queue deficit
                        # (a starved cheap job would look expensive and be
                        # starved harder)
                        t_scope = time.perf_counter()
                        done = 0
                        while nxt is not None and done < group:
                            batch_idx, batch, staged = nxt
                            t0 = time.perf_counter()
                            with trace_span(
                                    "step.dispatch", record=False,
                                    acc=self._span_acc("step.dispatch")):
                                metrics = self._dispatch_batch(
                                    batch_idx, batch, hyper, staged
                                )
                            pending.append(metrics)
                            cap = self._inflight_cap()
                            if len(pending) >= cap:
                                # Sliding window: block on the OLDEST
                                # outstanding step so the device queue stays
                                # full.
                                with trace_span(
                                        "step.backpressure", record=False,
                                        acc=self._span_acc(
                                            "step.backpressure")):
                                    jax.block_until_ready(
                                        pending[len(pending) - cap])
                            # dt spans dispatch AND the backpressure sync: on
                            # async backends the sync absorbs real device time
                            # that would otherwise land in neither work_t nor
                            # the drain (those steps are complete by then)
                            dt = time.perf_counter() - t0
                            # own per-batch EWMA sizes future groups (None =
                            # unseeded; a measured 0.0 is a real sample)
                            self._own_batch_cost = (
                                dt if self._own_batch_cost is None
                                else 0.5 * self._own_batch_cost + 0.5 * dt
                            )
                            work_t += dt
                            # bypass epochs carry no host arrays; the
                            # provider's equal split fixes the batch size
                            n_ex = (batch[0].shape[0] if batch is not None
                                    else self.data.batch_size)
                            batch_sizes.append(n_ex)
                            epoch_examples += n_ex
                            global_batch_idx += 1
                            done += 1
                            if done < group:
                                nxt = next(it, None)
                            else:
                                nxt = None  # refetched below
                        if self.taskunit is not None:
                            # live per-UNIT cost for the weighted-fair queue:
                            # the drain-time report (authoritative on async
                            # backends) can be a whole multi-epoch window
                            # away, and a blind WFQ degenerates to 1:1
                            # pacing. Under the metered global slot the
                            # in-scope elapsed is ~this unit's own execution
                            # (blocking backends) or its enqueue cost
                            # (async) — either way job-relative.
                            self.taskunit.report_unit_cost(
                                time.perf_counter() - t_scope
                            )
                if not stop:
                    nxt = next(it, None)
        finally:
            # an early stop (SSP gate) or a raising dispatch must tear the
            # prefetch producer down NOW, not at GC time
            it.close()
        return pending, batch_sizes, epoch_examples, global_batch_idx, stop, work_t

    def _drain_pending(
        self, pending: "List[Dict[str, jnp.ndarray]]"
    ) -> Dict[str, np.ndarray]:
        """Bring a run of per-step device metrics to host: one stack-op +
        one transfer per metric key (per dtype when possible) for the WHOLE
        list (each transfer is a host<->device round trip). A mid-run
        reshard leaves metrics on different device
        sets, so stacking is per run of same-sharded values (still
        O(reshards) ops, not O(steps))."""
        runs: List[List[Dict[str, jnp.ndarray]]] = [[pending[0]]]
        probe = next(iter(pending[0]))
        for m in pending[1:]:
            if m[probe].sharding == runs[-1][-1][probe].sharding:
                runs[-1].append(m)
            else:
                runs.append([m])
        # The eager stacks DISPATCH under the table lock AND the
        # process-wide dispatch scope: they are multi-device
        # programs (and can carry an implicit transfer when a metric
        # landed with a different placement), and a dispatch racing
        # ANY other job's dispatches enqueues per-device work in
        # divergent orders — on backends with in-process collectives
        # that inverts a rendezvous and aborts the process
        # (parallel/dispatch.py). The D2H copies below stay outside.
        combined = None
        with trace_span("drain.stack", acc=self._span_acc("drain.stack")), \
                self.ctx.model_table._lock:
            with dispatch_scope(self.mesh) as finish:
                stacked = finish({
                    k: [jnp.stack([m[k] for m in r]) for r in runs]
                    for k in pending[0]
                })
                if len(runs) == 1:
                    # Fold ALL same-dtype keys into one array so the
                    # drain is ONE device->host transfer per dtype, not
                    # one per key. (Multi-run drains — a mid-run reshard
                    # — keep the per-key path.)
                    keys = sorted(stacked)
                    groups: Dict[Any, List[str]] = {}
                    for k in keys:
                        # sharding in the key: sibling metrics may
                        # land on different device sets, and one
                        # eager stack over non-colocated arrays
                        # raises at dispatch
                        sig = (stacked[k][0].dtype,
                               stacked[k][0].shape,
                               stacked[k][0].sharding)
                        groups.setdefault(sig, []).append(k)
                    combined = {
                        dt: (ks, finish(jnp.stack(
                            [stacked[k][0] for k in ks])))
                        for dt, ks in groups.items()
                    }
        # the copies wait for every step still in flight: this is where
        # the host stands while the device finishes the window. Every
        # group's transfer is asked for NOW, so each starts as its data
        # lands: asked for one after another once the window is done, three
        # groups were three round trips of device idle (4.5 ms a drain)
        with trace_span("drain.d2h", acc=self._span_acc("drain.d2h")):
            for _, arr in (combined or {}).values():
                arr.copy_to_host_async()
            if combined is not None:
                host = {}
                for ks, arr in combined.values():
                    mat = np.asarray(arr)          # one D2H per dtype
                    for i, k in enumerate(ks):
                        host[k] = np.atleast_1d(mat[i])
            else:
                host = {
                    k: np.concatenate(
                        [np.atleast_1d(np.asarray(s)) for s in v])
                    for k, v in stacked.items()
                }
        return host

    def _run_batched_epochs_window(
        self, first_epoch: int, k: int, global_batch_idx: int
    ):
        """``k`` epochs of async per-batch dispatches (TaskUnit admission
        per batch is preserved — concurrent tenants still interleave at
        batch granularity) with ONE metric drain for the whole window.
        Windowable trainer hooks run between epochs, exactly as in
        :meth:`_run_fused_epochs`. Returns ([(examples, last_metrics)] per
        epoch, global_batch_idx, seconds_per_epoch)."""
        per_epoch = []
        t_start = time.perf_counter()
        for j in range(k):
            pending, sizes, examples, global_batch_idx, _stop, work_t = (
                self._dispatch_epoch_batches(first_epoch + j, global_batch_idx)
            )
            per_epoch.append((pending, sizes, examples, work_t,
                              self._take_dispatch_sec()))
            with trace_span("epoch.turnover", record=False,
                            acc=self._span_acc("epoch.turnover")):
                # next epoch's producer overlaps either the next dispatch
                # run (j+1 < k) or the window drain below
                self._spawn_next_pipeline(first_epoch + j + 1)
                if j + 1 < k:
                    self.trainer.on_epoch_finished(self.ctx, first_epoch + j)
        self._observe_vector_backlog()  # the device has this window's steps
        all_pending = [m for p, _, _, _, _ in per_epoch for m in p]
        drain_t = 0.0
        host: Dict[str, np.ndarray] = {}
        if all_pending:
            with trace_span("dolphin.metric_drain", job_id=self.job_id,
                            epoch=first_epoch, batches=len(all_pending),
                            epochs=k):
                # the drain's stacks are dispatches; timer starts INSIDE
                # the turn (admission wait is scheduling, not work); NET
                # unit under tenancy — see _run_batched_epoch's drain
                with self._turn(), self._taskunit_scope("NET"):
                    t0 = time.perf_counter()
                    host = self._drain_pending(all_pending)
            drain_t = time.perf_counter() - t0
        out = []
        off = 0
        # per-batch records, the ledger and the work split of k epochs:
        # host bookkeeping the device does not wait for — unless both
        # tenants do it at once
        with trace_span("drain.emit", acc=self._span_acc(
                "drain.emit", self._add_bookkeeping)):
            for pending, sizes, examples, work_t, disp_t in per_epoch:
                nb = len(pending)
                last: Dict[str, float] = {}
                if nb:
                    epoch_host = {key: v[off:off + nb]
                                  for key, v in host.items()}
                    last = self._emit_batch_metrics(
                        first_epoch + len(out), epoch_host, sizes,
                        (work_t + drain_t / k) / nb,
                        dispatch_sec=disp_t,
                    )
                off += nb
                # accounting deferred to run()'s replay loop (see
                # _run_fused_epochs) so ServerMetrics deltas stay per-epoch
                out.append((examples, last, nb))
        per_epoch_sec = (time.perf_counter() - t_start) / k
        return out, global_batch_idx, per_epoch_sec

    def _add_grant_wait(self, sec: float) -> None:
        self._phase_ctl["grant_wait"] += sec

    def _add_bookkeeping(self, sec: float) -> None:
        self._phase_ctl["bookkeeping"] += sec

    def _timed_probe(self, first) -> None:
        """The comm probe, its seconds (admission excluded: the caller
        holds the turn/unit already) added to the ``probe`` phase."""
        t0 = time.perf_counter()
        try:
            self._probe_comm(first)
        finally:
            self._phase_ctl["probe"] += time.perf_counter() - t0

    def _span_acc(self, name: str, also=None):
        """The ``acc`` of a span of the window ledger's vocabulary, made
        where the span OPENS: when it closes, its seconds less those of
        the tracked spans that closed meanwhile — its self time — are
        added under ``name``, and ``also`` (a phase accumulator that was
        the span's ``acc`` before) gets the whole of them. No clock read
        beside the span's own."""
        return functools.partial(self._add_span, name, self._span_total,
                                 also)

    def _add_span(self, name: str, opened_at: float, also, sec: float) -> None:
        own = sec - (self._span_total - opened_at)
        self._span_total = opened_at + sec
        self._win_spans[name] = self._win_spans.get(name, 0.0) + own
        if also is not None:
            also(sec)

    def _compile_seconds(self) -> float:
        """JAX's compile seconds so far under this job's spans
        (``harmony_compile_seconds_total``, all stages)."""
        return float(progcache.compiles_by_job().get(
            self.job_id, {}).get("seconds", 0.0))

    def _take_budget_feed(self, k: int, epoch: int, steps: int
                          ) -> Tuple[float, Dict[str, float]]:
        """``(wall per epoch, control phases per epoch)`` of the ``k``
        epochs from ``epoch`` that just ran: the wall since the last
        feed's end and the control seconds accumulated since, both / k;
        resets both. The same stretch, whole, is the window ledger's
        record (metrics/phases.py): closed here, judged by the store, and
        counted by the chief."""
        now = time.monotonic_ns()
        mark = self._budget_mark if self._budget_mark is not None else now
        self._budget_mark = now
        k = max(int(k), 1)
        ctl = {p: v / k for p, v in self._phase_ctl.items()}
        for p in self._phase_ctl:
            self._phase_ctl[p] = 0.0
        wall = (now - mark) * 1e-9
        spans, self._win_spans = self._win_spans, {}
        try:  # the ledger must never fail the epoch boundary
            from harmony_tpu.metrics.phases import budget, count_window

            compiled = self._compile_seconds()
            record = {
                "window": self._window, "epoch": int(epoch), "epochs": k,
                "steps": int(steps), "start_ns": mark, "end_ns": now,
                "wall_s": wall, "spans": spans,
                "compile_s": compiled - self._compile_mark,
                "first": self._window == 0,
            }
            self._compile_mark = compiled
            stall = budget().observe_window(
                self.job_id, self.attempt_key, self.ctx.worker_id, record)
            if self.global_init:  # per JOB, as _check_slo is
                count_window(self.job_id, record, stall)
        except Exception:
            pass
        self._window += 1
        return wall / k, ctl

    def _take_dispatch_sec(self) -> float:
        """Drain the host-dispatch accumulator (one epoch's placement
        seconds; single-threaded — only the training thread feeds it)."""
        v, self._phase_dispatch_acc = self._phase_dispatch_acc, 0.0
        return v

    def _observe_vector_backlog(self) -> None:
        """Hand the drained steps' vectors to the trainer's counters. They
        decide nothing, and a 512-expert router's grid is a millisecond of
        counter updates an epoch — so not inside the turnover, where the
        device stands idle until the next dispatch: the dispatch paths call
        this once the NEXT window is enqueued (before its drain), and the
        epoch loop once more when it ends. Still the ``bookkeeping``
        phase."""
        backlog, self._vector_backlog = self._vector_backlog, []
        for vectors in backlog:
            with trace_span("drain.vectors", acc=self._span_acc(
                    "drain.vectors", self._add_bookkeeping)):
                self.trainer.observe_step_vectors(self.job_id, vectors)

    def _emit_batch_metrics(
        self,
        epoch: int,
        host: Dict[str, np.ndarray],
        batch_sizes: List[int],
        per_batch_time: float,
        dispatch_sec: float = 0.0,
        dispatch_in_work: bool = True,
    ) -> Dict[str, float]:
        """Shared epoch-end drain: strip internal underscore-keys (_sync),
        emit one BatchMetrics per batch with the smeared time, and return
        the final batch's metrics as floats."""
        if "_dropped" in host:
            # keys the sparse table refused mid-training: fold into the
            # table's cumulative overflow counter (never silent). "_dropped"
            # is only emitted by the hash-table step, so the concrete type
            # is known — no defensive getattr that could silently detach
            # the counter.
            n = int(np.sum(host["_dropped"]))
            if n:
                self.ctx.model_table.count_dropped(n)
        host = {k: v for k, v in host.items() if not k.startswith("_")}
        # a step's vectors ([steps, ...]: tokens per expert) feed the
        # trainer's counters; the series below are per-step scalars
        vectors = {k: v for k, v in host.items() if v.ndim > 1}
        if vectors:
            self._vector_backlog.append(vectors)
            host = {k: v for k, v in host.items() if k not in vectors}
        # one shared fallback rule (_primary_key) for the per-batch series
        lkey = self._primary_key(host)
        losses = host[lkey] if lkey is not None else np.zeros(len(batch_sizes))
        # honest comm/comp split from the last probe (see _probe_comm):
        # comp = measured step time minus the probed pull/push device time.
        # With the probe off both are 0 and comp degenerates to the whole
        # batch time — the conservative default.
        t_pull, t_push = (self.ctx.model_table.comm_split()
                          or self._comm_probe_times)
        comp = max(per_batch_time - t_pull - t_push, 0.0)
        # NOTE: the weighted-fair-queue unit cost is reported from the
        # dispatch scope only (per granted UNIT) — reporting the drain's
        # per-BATCH smear here would mix scales differing by the group
        # factor and undercharge grouped jobs.
        for b, n in enumerate(batch_sizes):
            self.collector.add(
                BatchMetrics(
                    job_id=self.job_id,
                    worker_id=self.ctx.worker_id,
                    epoch_idx=epoch,
                    batch_idx=b,
                    num_examples=n,
                    batch_time_sec=per_batch_time,
                    pull_time_sec=t_pull,
                    comp_time_sec=comp,
                    push_time_sec=t_push,
                    loss=float(losses[b]),
                )
            )
        # per-tenant step-time histogram (/metrics exposition + the
        # straggler report's raw material): one observation per batch at
        # the smeared per-batch time — async dispatch makes true
        # per-batch device time unobservable (see the drain docstrings)
        hist = self._step_histogram()
        if hist is not None:
            for _ in batch_sizes:
                hist.observe(per_batch_time)
        # tenant cost ledger (metrics/accounting.py): one feed per epoch
        # drain — device seconds, steps, examples, the compiled step's
        # FLOP figure, and the current resident-HBM components. Guarded:
        # accounting must never fail (or slow) the drain.
        try:
            from harmony_tpu.metrics.accounting import ledger

            steps = len(batch_sizes)
            acct = ledger()
            acct.observe_steps(
                self.job_id, self.attempt_key, self.ctx.worker_id,
                steps=steps, device_sec=per_batch_time * steps,
                examples=int(sum(batch_sizes)),
                flops_per_step=self._program_flops_per_step(),
                devices=int(self.mesh.devices.size),
            )
            acct.set_resident(self.job_id, self.attempt_key, "input",
                              self._input_resident_bytes())
            acct.set_resident(self.job_id, self.attempt_key, "program",
                              self._program_resident_bytes())
        except Exception:
            pass
        # Step-phase time budget (metrics/phases.py): split this epoch's
        # measured work into pull/compute/push — the probe's seconds
        # applied to the step wall (a model), refined by the compiled
        # program's FLOP seconds — and stage it (with the
        # host-dispatch seconds) for _finish_epoch, where the epoch WALL
        # is known and the budget feeds. Guarded: the budget must never
        # fail (or slow) the drain.
        try:
            from harmony_tpu.metrics.accounting import _peak_flops
            from harmony_tpu.metrics.phases import split_device_phases

            steps = len(batch_sizes)
            work = per_batch_time * steps
            split = split_device_phases(
                work, steps,
                # batched paths time placement INSIDE the per-batch dt
                # (subtract it from the work split); the fused-epoch
                # path's stacked upload happens OUTSIDE work_t
                dispatch_sec=dispatch_sec if dispatch_in_work else 0.0,
                probe_split=(t_pull, t_push),
                flops_per_step=self._program_flops_per_step(),
                peak_flops=_peak_flops(),
                devices=int(self.mesh.devices.size),
            )
            self._phase_pending[epoch] = {
                "host_dispatch": float(dispatch_sec), **split}
        except Exception:
            pass
        return {k: float(v[-1]) for k, v in host.items()}

    # -- tenant cost accounting helpers ----------------------------------

    def _program_flops_per_step(self) -> Optional[float]:
        """XLA cost-analysis FLOPs of ONE step of the current program
        (runtime/progcache's compile telemetry), resolved lazily — the
        cost row exists only after the first dispatch compiled. The
        fused-epoch program's figure covers the whole scan, so it is
        divided back down to per-step. None (never 0.0) when the
        backend exposes no cost model or the trainer opted out of
        caching."""
        if self._flops_per_step is not None:
            return self._flops_per_step
        key = self._program_cache_key
        if key is None:
            return None
        if self._use_fused_epoch():
            cost = progcache.program_cost((key, "epoch"))
            if cost is None or cost.flops is None:
                return None
            self._flops_per_step = cost.flops / max(
                self.data.num_mini_batches, 1)
        else:
            cost = progcache.program_cost((key, "step"))
            if cost is None or cost.flops is None:
                return None
            self._flops_per_step = cost.flops
        return self._flops_per_step

    def _table_resident_bytes(self) -> int:
        """Device bytes pinned by this job's table storage (dense
        array, or hash keys+values) — the dominant HBM term for table
        workloads."""
        def one(table) -> int:
            if table is None:
                return 0
            spec = table.spec
            itemsize = np.dtype(spec.dtype).itemsize
            kshape = getattr(spec, "keys_shape", None)
            if kshape is not None:  # hash table: int32 keys + values
                return (int(np.prod(kshape)) * 4
                        + int(np.prod(spec.values_shape)) * itemsize)
            return int(np.prod(spec.storage_shape)) * itemsize

        total = one(self.ctx.model_table)
        if self.trainer.uses_local_table:
            total += one(self.ctx.local_table)
        return total

    def _input_resident_bytes(self) -> int:
        """Device bytes of this worker's resident input copies (its
        stacked-epoch upload + per-batch caches — the worker's share of
        devcache occupancy)."""
        total = 0
        if self._stacked_cache is not None:
            total += sum(int(getattr(a, "nbytes", 0))
                         for a in self._stacked_cache)
        for b in self._batch_cache.values():
            leaves = b if isinstance(b, (tuple, list)) else (b,)
            total += sum(int(getattr(a, "nbytes", 0)) for a in leaves)
        return total

    def _program_resident_bytes(self) -> int:
        """Temp + generated-code bytes of this job's compiled programs
        (memory_analysis via progcache) — the constants/workspace HBM a
        compiled executable pins beyond its arguments."""
        key = self._program_cache_key
        if key is None:
            return 0
        total = 0
        for tag in ("step", "epoch", "eval"):
            cost = progcache.program_cost((key, tag))
            if cost is not None:
                total += ((cost.temp_bytes or 0)
                          + (cost.generated_code_bytes or 0))
        return total

    def _step_histogram(self):
        """Cached child of harmony_step_time_seconds for this worker's
        (job, attempt, worker) labelset; None when the registry is
        unusable (metrics must never fail the hot loop)."""
        hist = getattr(self, "_step_hist", None)
        if hist is None:
            try:
                from harmony_tpu.metrics.registry import (
                    STEP_TIME_BUCKETS,
                    get_registry,
                )

                hist = get_registry().histogram(
                    "harmony_step_time_seconds",
                    "Per-mini-batch dispatch+device seconds per worker",
                    ("job", "attempt", "worker"),
                    buckets=STEP_TIME_BUCKETS,
                ).labels(job=self.job_id, attempt=self.attempt_key,
                         worker=self.ctx.worker_id)
            except Exception:
                return None
            self._step_hist = hist
        return hist

    def _ensure_stacked_cache(self) -> None:
        """Device-resident whole-epoch dataset ([num_batches, batch, ...]
        per array), rebuilt after any reshard cleared it (the stack must
        live on the table's CURRENT mesh)."""
        if self._stacked_cache is not None:
            return
        table = self.ctx.model_table
        gkey = self._devcache_key("stacked")
        hit = devcache.get(gkey) if gkey is not None else None
        if hit is not None:
            self._stacked_cache = hit
            return
        # a part of the job's data load: its seconds join that stage
        with trace_span("dolphin.dataset_upload", job_id=self.job_id,
                        acc=job_stage_adder(self.job_id, "data_load")):
            batches = list(self.data.epoch_batches())
            stacked_sharding = NamedSharding(table.mesh, P(None, DATA_AXIS))
            self._stacked_cache = tuple(
                jax.device_put(np.stack([b[i] for b in batches]),
                               stacked_sharding)
                for i in range(len(batches[0]))
            )
        devcache.put(gkey, self._stacked_cache)

    def _dispatch_epoch_fn(self):
        """One whole-epoch dispatch (see _build_step), retried across
        concurrent reshards. Returns the epoch's stacked device metrics."""
        for _ in range(self.MAX_RESHARD_RETRIES):
            self._maybe_rebuild()
            self._ensure_stacked_cache()
            try:
                return self._dispatch_step(self._epoch_fn, self._stacked_cache)
            except ValueError as e:
                if not self._is_layout_race(e):
                    raise
                self._build_step()  # force-rebuild (see _dispatch_batch)
        raise RuntimeError(
            f"table resharded {self.MAX_RESHARD_RETRIES}x during one "
            "epoch dispatch; reconfiguration is outpacing training"
        )

    def _run_fused_epochs(
        self, first_epoch: int, k: int
    ) -> "Tuple[List[Tuple[int, Dict[str, float]]], float]":
        """``k`` whole-epoch dispatches chained on the table state with ONE
        drain at the end (k=1 = the plain fused epoch). Windowable trainer
        hooks run BETWEEN dispatches so epoch-indexed hyperparams (decay,
        PRNG folds) feed each dispatch exactly as in the per-batch loop.
        Returns ([(examples, last_metrics)] per epoch, seconds_per_epoch)."""
        # cache build BEFORE the timer starts: the one-time dataset
        # stacking/transfer must not inflate per-batch times fed to the
        # optimizer (a mid-window reshard rebuilds it inside the retry
        # loop and does count — it IS reconfiguration cost). Inside a
        # TURN: on multi-process backends a device_put onto a sharding
        # that replicates across processes is itself collective-backed
        # (gloo pairs the transfers), so two tenants' uploads
        # interleaving with steps produce a cross-process collective
        # mismatch — any global placement must hold the dispatch unit.
        with self._turn():
            # the one-time stacked upload is this path's host-dispatch
            # phase: the host work between batches-ready and device
            # dispatch (zero on warm-cache windows)
            t_place = time.perf_counter()
            self._ensure_stacked_cache()
            self._phase_dispatch_acc += time.perf_counter() - t_place
        dispatch_sec = self._take_dispatch_sec()
        work_t = 0.0  # dispatch+device seconds, EXCLUDING admission waits
        window_metrics = []
        for j in range(k):
            # each whole-epoch dispatch is one admission turn / pod unit:
            # its enqueues must not interleave with another tenant's. The
            # timer starts INSIDE the turn — a co-tenant's unit wait is
            # scheduling, not work, and must not inflate the per-batch
            # times feeding the optimizer's cost model (same rule as the
            # batched path's scopes).
            with self._turn():
                t0 = time.perf_counter()
                window_metrics.append(self._dispatch_epoch_fn())
                work_t += time.perf_counter() - t0
            if j + 1 < k:
                # windowable by declaration: depends only on the epoch
                # index, so it may run before the epoch's results drain
                self.trainer.on_epoch_finished(self.ctx, first_epoch + j)
        self._observe_vector_backlog()  # the device has this window's steps
        # ONE drain for the whole window, counted as work: the per-batch
        # times fed to the optimizer must include device execution
        t_sync = time.perf_counter()
        jax.block_until_ready(window_metrics)
        work_t += time.perf_counter() - t_sync
        per_epoch_sec = work_t / k
        nb = self.data.num_mini_batches
        out = []
        for j, stacked_metrics in enumerate(window_metrics):
            host_metrics = {
                key: np.atleast_1d(np.asarray(v))
                for key, v in stacked_metrics.items()
            }
            last = self._emit_batch_metrics(
                first_epoch + j, host_metrics,
                [self.data.batch_size] * nb, per_epoch_sec / nb,
                dispatch_sec=dispatch_sec / k, dispatch_in_work=False,
            )
            # op accounting happens in run()'s replay loop, interleaved
            # with the deferred epoch callbacks, so per-epoch ServerMetrics
            # deltas stay per-epoch instead of lumping onto the window's
            # first report
            out.append((self.data.num_examples, last, nb))
        return out, per_epoch_sec

    def _primary_key(self, metrics) -> Optional[str]:
        """The ONE key that is this job's progress scalar: 'loss', else the
        trainer's declared objective_metric (e.g. LDA's log_likelihood).
        Other metric keys are counters — never relabeled as a loss."""
        if "loss" in metrics:
            return "loss"
        om = self.trainer.objective_metric
        return om if om and om in metrics else None

    def _primary_metric(self, metrics: Dict[str, float]) -> float:
        k = self._primary_key(metrics)
        return float(metrics[k]) if k is not None else 0.0

    def _finish_epoch(self, epoch, epoch_t0, epoch_examples, last_metrics,
                      epoch_losses, call_trainer_hook: bool = True,
                      budget_wall: Optional[float] = None,
                      budget_ctl: Optional[Dict[str, float]] = None):
        # epoch-boundary fault site: the fused/windowed paths dispatch
        # whole epochs without per-batch host steps, so this is the
        # boundary every path crosses (checkpoint hooks fire right after)
        if faults.armed():
            faults.site(
                "worker.epoch", job=self.job_id, worker=self.ctx.worker_id,
                epoch=epoch, proc=jax.process_index(),
            )
        progress = self._primary_metric(last_metrics)
        epoch_sec = time.perf_counter() - epoch_t0
        self.collector.add(
            EpochMetrics(
                job_id=self.job_id,
                worker_id=self.ctx.worker_id,
                epoch_idx=epoch,
                num_examples=epoch_examples,
                epoch_time_sec=epoch_sec,
                loss=progress,
            )
        )
        # Step-phase budget feed: the epoch wall is finally known here —
        # join the staged work split + host-dispatch with this epoch's
        # input-wait and hand the row to the process budget store
        # (metrics/phases.py). Whatever the measured phases do not cover
        # (admission waits, drains' host share, this bookkeeping) stays
        # an explicit residual there. Guarded: the budget must never
        # fail the epoch boundary.
        # popped UNCONDITIONALLY, outside the guard: the stream close
        # stages an input-wait entry per epoch, and a failing split or
        # budget path must not grow these dicts by one orphan per
        # epoch for the life of the tasklet
        ph = self._phase_pending.pop(epoch, None)
        input_wait = self._phase_input_wait.pop(epoch, 0.0)
        if ph is not None:
            try:
                from harmony_tpu.metrics.phases import budget

                ph["input_wait"] = input_wait
                # grant_wait / probe / bookkeeping, measured by their
                # spans; the wall is the one that holds them (see
                # _take_budget_feed), not the epoch's own timer
                ph.update(budget_ctl or {})
                budget().observe_epoch(
                    self.job_id, self.attempt_key, self.ctx.worker_id,
                    epoch,
                    epoch_sec if budget_wall is None else budget_wall,
                    ph)
            except Exception:
                pass
        self._check_slo(epoch, epoch_examples, epoch_sec)
        epoch_losses.append(progress)
        if call_trainer_hook:
            self.trainer.on_epoch_finished(self.ctx, epoch)
        # The callback may dispatch global programs (pod checkpoint
        # chains, plan-driven block moves) — under pod tenancy it holds a
        # turn/unit. Turn balance: non-chief workers take a matching
        # no-op turn so the strict rotation stays aligned (see run()).
        if self.epoch_callback is not None:
            with self._turn():
                self.epoch_callback(epoch)
        elif self._balanced_turns():
            with self._turn():
                pass
        self.collector.flush()

    #: consecutive under-target epochs before the SLO event fires — one
    #: slow epoch (a reshard, a checkpoint, a co-tenant's burst) is
    #: noise; a sustained run is the scheduler-actionable signal
    SLO_WINDOW_EPOCHS = 3
    #: attainment floor: below this fraction of target counts as a breach
    SLO_ATTAINMENT_FLOOR = 0.9

    def _check_slo(self, epoch: int, epoch_examples: int,
                   epoch_sec: float) -> None:
        """Windowed SLO attainment check at the epoch boundary (chief
        only — the target is per JOB, so sibling workers checking their
        own shares would multiply-fire). The job-level rate is estimated
        as this worker's rate × num_workers (the data provider splits
        the epoch evenly); exact for single-worker jobs. A sustained
        breach records ONE structured joblog event (kind="slo") and
        counts in the tenant ledger; recovery above the floor re-arms."""
        if self._slo_target is None or not self.global_init:
            return
        own_sps = epoch_examples / epoch_sec if epoch_sec > 0 else 0.0
        job_sps = own_sps * max(self.ctx.num_workers, 1)
        if job_sps >= self.SLO_ATTAINMENT_FLOOR * self._slo_target:
            self._slo_below = 0
            self._slo_fired = False
            return
        self._slo_below += 1
        if self._slo_below < self.SLO_WINDOW_EPOCHS or self._slo_fired:
            return
        self._slo_fired = True
        try:
            from harmony_tpu.jobserver import joblog
            from harmony_tpu.metrics.accounting import ledger

            joblog.record_event(
                self.job_id, kind="slo",
                attempt=self.attempt_key,
                epoch=epoch,
                target_sps=self._slo_target,
                achieved_sps=round(job_sps, 3),
                attainment=round(job_sps / self._slo_target, 4),
                window_epochs=self.SLO_WINDOW_EPOCHS,
            )
            ledger().record_slo_event(self.job_id)
        except Exception:
            pass  # SLO observability never fails the epoch boundary

    def _account_ops(self, num_steps: int) -> None:
        """Fold this dispatch window's pull/push counts (one pull + one push
        per fused step) into this worker's own counters — per-job metric
        attribution sums the job's workers, so jobs sharing one table never
        double-count each other's traffic."""
        spec = self.ctx.model_table.spec
        row_bytes = int(np.prod(spec.value_shape)) * spec.dtype.itemsize if spec.value_shape else spec.dtype.itemsize
        self.op_stats["pulls"] += num_steps
        self.op_stats["pushes"] += num_steps
        self.op_stats["pull_bytes"] += num_steps * self._pull_rows * row_bytes

    def _taskunit_scope(self, kind: str):
        if self.taskunit is None:
            return contextlib.nullcontext()
        return self.taskunit.scope(kind, wait_acc=self._span_acc(
            "taskunit.wait", self._add_grant_wait))

    def _turn(self):
        """This worker's turnstile admission (pod lockstep), else a no-op.
        Entering the turn is the wait: a ``taskunit.wait`` light span
        whose seconds join the ``grant_wait`` phase."""
        if self.dispatch_turn is None:
            return contextlib.nullcontext()
        return _TimedAdmission(
            self.dispatch_turn(),
            self._span_acc("taskunit.wait", self._add_grant_wait),
            self.job_id)

    def _balanced_turns(self) -> bool:
        """True when this worker must take no-op turns to keep the cyclic
        turnstile's strict rotation aligned with its siblings' chief-only
        turns (multi-worker turnstiled jobs; single-thread jobs have no
        rotation to balance)."""
        return self.dispatch_turn is not None and self.ctx.num_workers > 1

    # -- evaluation (ref: ModelEvaluator over checkpointed models) -------

    def evaluate(self, batch: Tuple[np.ndarray, ...]) -> Dict[str, float]:
        from harmony_tpu.table.hashtable import DeviceHashTable

        table = self.ctx.model_table
        if isinstance(table, DeviceHashTable):
            raise NotImplementedError(
                "full-model evaluate is undefined over an unbounded key "
                "domain; evaluate a sparse model through its keyed pull "
                "(trainer.compute-style) or train with a dense table"
            )
        if self._eval_fn is None:
            self._eval_fn = jax.jit(traced_on(self.mesh, self.trainer.evaluate))
        model = table.pull_array()
        metrics = self._eval_fn(model, self._shard_batch(batch))
        return {k: float(v) for k, v in metrics.items()}


class FusedSparseStep:
    """ONE compiled program for a host-driven sparse pull→compute→push.

    The host path (ModelAccessor users: benchmarks, serving-style readers,
    apps driving a table outside WorkerTasklet) historically crossed
    Python per phase — ``pull`` gathers to numpy, the caller computes, and
    ``push`` scatters the delta back, three dispatches and two full host
    round-trips per batch. This wraps the cycle the way the dense SPMD
    fast path does (WorkerTasklet._program_builders): the table array
    enters as a DONATED argument, the keyed gather / compute / keyed
    scatter trace into one XLA program, and dispatch+commit ride
    ``DenseTable.apply_step`` so donation stays invisible to concurrent
    host accessors. Underneath, the keyed gather/scatter lower through
    ops/sparse.py (Pallas on TPU, jnp fallback elsewhere).

    Phase accounting matches the accessor's documented fused contract:
    the WHOLE step is charged to COMP (``comp_tracer`` feeds the
    ``harmony_phase_seconds{phase="accessor.comp"}`` histogram); the
    pull/push tracers genuinely have no separable phases to report.

    Donation rules: ONLY the table buffer (argument 0) is donated. Keys
    and extra operands — including device arrays staged by
    :meth:`run_batches` or held in the process devcache — are read-only
    by construction, preserving the devcache contract
    (data/devcache.py: cached buffers are never invalidated by a step).

    ``signature`` (hashable) names the compute_fn's traced behavior for
    the process program cache (runtime/progcache) — same contract as
    ``Trainer.jit_signature``: equal signatures MUST mean an identical
    traced program, and the default ``None`` opts out of caching.
    """

    #: steps in flight before the driver blocks on the oldest aux (keeps
    #: the donated-buffer chain and dispatch queue bounded)
    MAX_INFLIGHT = 8

    def __init__(
        self,
        table,
        compute_fn: Callable,
        *,
        signature: Optional[Any] = None,
        donate: bool = True,
    ) -> None:
        from harmony_tpu.metrics.tracer import Tracer
        from harmony_tpu.table.hashtable import DeviceHashTable
        from harmony_tpu.table.table import DenseTable

        if isinstance(table, DeviceHashTable):
            raise TypeError(
                "FusedSparseStep drives DenseTable workloads; hash-backed "
                "tables already fuse through WorkerTasklet's keyed step"
            )
        if not isinstance(table, DenseTable):
            raise TypeError(f"need a DenseTable, got {type(table).__name__}")
        self.table = table
        spec = table.spec
        self.donate = bool(donate)

        def _step(arr, keys, *extra):
            rows = spec.pull(arr, keys)                    # PULL
            delta, aux = compute_fn(rows, *extra)          # COMP
            new_arr = spec.push(arr, keys, delta)          # PUSH
            return new_arr, aux

        dn = (0,) if donate else ()
        key = None
        if signature is not None:
            from harmony_tpu.runtime import progcache as _pc

            tsig = _pc.table_signature(table)
            if tsig is not None:
                key = (tsig, "fused_sparse", signature, bool(donate))
        mesh = table.mesh
        self._fn = progcache.get_or_build(
            key, lambda: jax.jit(traced_on(mesh, _step), donate_argnums=dn)
        )
        self.cache_key = key
        self.comp_tracer = Tracer(instrument="accessor.comp")

    # -- single step ------------------------------------------------------

    def step(self, keys, *extra):
        """Dispatch one fused batch and commit; returns compute_fn's aux.
        Blocks on the aux (the accessor's per-op shape) so the tracer
        charges real device time to COMP."""
        k = keys if hasattr(keys, "dtype") else jnp.asarray(keys, jnp.int32)
        self.comp_tracer.start()
        aux = self.table.apply_step(self._fn, k, *extra)
        self.comp_tracer.record(int(k.shape[0]), block_on=aux)
        return aux

    # -- batched driver with double-buffered staging ----------------------

    def _stage(self, batch: Tuple) -> Tuple:
        """H2D placement of one host batch (keys first, then compute_fn's
        extras), replicated on the table's mesh. Staged arrays are only
        ever read by the step (never donated)."""
        mesh = self.table.mesh
        sh = NamedSharding(mesh, P())
        keys, *extra = batch
        k = keys if hasattr(keys, "dtype") else np.asarray(keys, np.int32)
        return tuple(jax.device_put(a, sh) for a in (k, *extra))

    def run_batches(self, batches, *, inflight: Optional[int] = None):
        """Drive host batches ``(keys, *extra)`` through the fused step
        with batch k+1's device_put STAGED while batch k computes — the
        double-buffered gradient/index transfer (StageRing, the input
        pipeline's primitive). Returns the list of per-batch aux outputs
        (synced). Falls back to synchronous staging on multi-process
        meshes, where a background device_put is collective-backed (same
        rule as WorkerTasklet._prefetch_usable)."""
        from harmony_tpu.data.loader import StageRing
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        cap = int(inflight) if inflight else 2
        auxes: List[Any] = []
        if mesh_spans_processes(self.table.mesh):
            for b in batches:
                auxes.append(self.table.apply_step(self._fn, *self._stage(b)))
            jax.block_until_ready(auxes)
            return auxes
        ring = StageRing(lambda: cap)

        def produce() -> None:
            try:
                for b in batches:
                    if not ring.put(self._stage(b)):
                        return
                ring.finish()
            except BaseException as e:  # surfaced at the consumer's get()
                ring.set_error(e)

        t = threading.Thread(target=produce, daemon=True,
                             name="fused-sparse-stage")
        t.start()
        try:
            while True:
                item = ring.get()
                if item is StageRing.DONE:
                    break
                auxes.append(self.table.apply_step(self._fn, *item))
                if len(auxes) >= self.MAX_INFLIGHT:
                    jax.block_until_ready(auxes[len(auxes) - self.MAX_INFLIGHT])
        finally:
            ring.close()
            t.join(timeout=5.0)
        jax.block_until_ready(auxes)
        return auxes
