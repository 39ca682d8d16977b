"""Concrete config schemas: table / executor / tasklet / job.

Mirrors the reference's typed builders — TableConfiguration.java:36-214,
ExecutorConfiguration.java:26-72, RemoteAccessConfiguration, TaskletConfiguration,
and the Dolphin job parameter set (dolphin/DolphinParameters.java) — rebuilt as
serializable dataclasses (see config/base.py for the Tang analogy).
"""
from __future__ import annotations

import dataclasses
from dataclasses import field
from typing import Any, ClassVar, Dict, List, Optional, Tuple

from harmony_tpu.config.base import ConfigBase, config

# Reference default: NumTotalBlocks def 1024
# (services/et/.../configuration/parameters/NumTotalBlocks.java).
DEFAULT_NUM_BLOCKS = 1024

#: Rows of one (8, 128) float32 tile on the TPU. A dense table whose
#: ``block_size`` is a multiple of it, with no tail block, is stored as its
#: own row matrix (metrics/table_layout.py says which tables are).
TILE_ROWS = 8


@config
class TableConfig(ConfigBase):
    """Schema of one elastic table (ref: TableConfiguration.java:36-214).

    The reference stores opaque K/V pairs with codecs; on TPU values are typed
    arrays so the schema carries value shape/dtype instead of codec classes.
    ``is_ordered`` selects range vs hash partitioning exactly as the
    reference's ``IsOrderedTable`` does (TableConfiguration.java:42-45).
    """

    table_id: str
    capacity: int                      # number of addressable keys [0, capacity)
    value_shape: Tuple[int, ...] = ()  # per-key value shape; () = scalar
    dtype: str = "float32"
    num_blocks: int = DEFAULT_NUM_BLOCKS
    is_ordered: bool = True            # range partitioner; False = hash
    is_mutable: bool = True
    update_fn: str = "add"             # name in table.update registry
    # Sparse key domain: back the table with a capacity-bounded device hash
    # table (DeviceHashTable) — getOrInit admits ANY non-negative int32 key,
    # ``capacity`` bounds SLOTS, not the key domain. Dense tables
    # (sparse=False) preallocate exactly [0, capacity).
    sparse: bool = False
    # Optional bulk-load source (ref: FilePath / BulkDataLoader binding).
    input_path: Optional[str] = None
    parser: Optional[str] = None       # dotted path of DataParser

    def __post_init__(self) -> None:
        if self.capacity <= 0:
            raise ValueError("capacity must be positive")
        if self.num_blocks <= 0:
            raise ValueError("num_blocks must be positive")
        if self.num_blocks > self.capacity:
            # Clamp HERE (not in the partitioner) so the config is the single
            # source of truth for block count — BlockManager, checkpoints and
            # storage must all agree on block ids.
            object.__setattr__(self, "num_blocks", self.capacity)
        if isinstance(self.value_shape, list):
            object.__setattr__(self, "value_shape", tuple(self.value_shape))


@config
class RetryPolicy(ConfigBase):
    """Bounded retry with exponential backoff + jitter for transient
    infrastructure faults (block-migration transport legs, checkpoint
    block I/O, the isolated orbax worker's pipe ops — see
    harmony_tpu.faults.retry.call_with_retry).

    The schedule: attempt, sleep ``base_delay_sec``, attempt, sleep
    ``base_delay_sec * multiplier`` ... capped at ``max_delay_sec``, for
    at most ``max_attempts`` attempts; each sleep is stretched by up to
    ``jitter`` (fraction) of itself so retrying peers don't stampede a
    recovering endpoint in sync. Exhaustion raises RetryError, which
    carries the ``infra_suspect`` marker the pod's auto-resume keys on.
    """

    max_attempts: int = 4
    base_delay_sec: float = 0.05
    max_delay_sec: float = 2.0
    multiplier: float = 2.0
    jitter: float = 0.25

    def __post_init__(self) -> None:
        if self.max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if self.base_delay_sec < 0 or self.max_delay_sec < 0:
            raise ValueError("delays must be non-negative")
        if self.multiplier < 1.0:
            raise ValueError("multiplier must be >= 1 (backoff, not decay)")
        if not (0.0 <= self.jitter <= 1.0):
            raise ValueError("jitter is a fraction in [0, 1]")

    _ENV_FIELDS = (
        ("max_attempts", "HARMONY_RETRY_MAX_ATTEMPTS", int),
        ("base_delay_sec", "HARMONY_RETRY_BASE_DELAY", float),
        ("max_delay_sec", "HARMONY_RETRY_MAX_DELAY", float),
        ("multiplier", "HARMONY_RETRY_MULTIPLIER", float),
        ("jitter", "HARMONY_RETRY_JITTER", float),
    )

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        """Defaults overridden by ``HARMONY_RETRY_*`` env vars — an env
        knob (like HARMONY_CHKP_BACKEND) precisely so every pod process
        inherits the same policy without per-layer plumbing."""
        import os

        kv = {}
        for field_name, var, cast in cls._ENV_FIELDS:
            raw = os.environ.get(var)
            if raw is not None:
                kv[field_name] = cast(raw)
        return cls(**kv)


@config
class RemoteAccessConfig(ConfigBase):
    """Host-side op-queue knobs (ref: RemoteAccessConfiguration: CommQueueSize,
    NumCommThreads). On TPU the data plane is XLA collectives, but the host
    control plane still runs queued ops for sparse/irregular access."""

    num_comm_threads: int = 4
    queue_size: int = 1024


@config
class ExecutorConfig(ConfigBase):
    """Per-executor resources (ref: ExecutorConfiguration.java:26-72 and the
    README operating point: 5 executors x 128 MB x 1 core). An "executor" here
    is one device (chip) slot of the pod mesh plus its host-side runtime."""

    num_devices: int = 1
    remote_access: RemoteAccessConfig = field(default_factory=RemoteAccessConfig)
    # TaskUnit slots per executor (ref: LocalTaskUnitScheduler.java:36-37).
    cpu_slots: int = 1
    net_slots: int = 2
    # Heterogeneous resource specs (ref: HeterogeneousEvalManager.java:40-70
    # matching allocations to per-request node names/sizes): restrict this
    # request to devices of a kind (case-insensitive substring, e.g.
    # "v5 lite") and/or one host process of a multi-host pod. None = any.
    device_kind: Optional[str] = None
    process_index: Optional[int] = None


@config
class TaskletConfig(ConfigBase):
    """One unit of computation submitted to an executor
    (ref: TaskletConfiguration; Tasklet.java:24-36)."""

    tasklet_id: str
    tasklet_class: str            # dotted path, resolved at start
    user_params: Dict[str, Any] = field(default_factory=dict)


@config
class TrainerParams(ConfigBase):
    """Dolphin hyper-parameter block (ref: DolphinParameters.java:26-195).

    ``num_mini_batches`` plays the role of NumWorkerBlocks: an epoch is
    partitioned into exactly this many batches (= input-table blocks per
    worker in the reference, ETTrainingDataProvider.java:38-75).
    """

    num_epochs: int = 1
    num_mini_batches: int = 10
    clock_slack: int = 0              # SSP staleness bound; 0 = BSP
    step_size: float = 0.1
    decay_rate: float = 0.9
    decay_period: int = 5
    num_trainer_threads: int = 1
    model_cache_enabled: bool = False
    # Model-checkpoint chaining during training (ref: ModelChkpManager,
    # dolphin/core/master/ModelChkpManager.java:40-80). 0 = disabled;
    # N = snapshot the model table every N epochs.
    model_chkp_period: int = 0
    # Defer offline evaluation of the chained checkpoints to JobServer
    # shutdown (ref: JobServerDriver graceful shutdown runs deferred model
    # evaluation, JobServerDriver.java:178-214).
    offline_model_eval: bool = False
    # Comm/comp split probe period in epochs (WorkerTasklet._probe_comm —
    # the fused-mode analogue of the reference's per-op pull/push timers,
    # ModelAccessor.java:33-49). Each probe costs several BLOCKING device
    # round-trips, which is real wall time: jobs
    # that feed an elasticity optimizer want 1; latency-sensitive jobs can
    # raise the period or disable with 0 (the last split stays in effect).
    comm_probe_period: int = 1
    # Asynchronous host->device input pipeline (dolphin/prefetch.py): a
    # producer thread assembles batches and stages their device transfers
    # ahead of the compute loop, overlapping host input work with device
    # compute. Ring depth follows the worker's in-flight cap (shallow
    # under TaskUnit contention). Default ON; disable for A/B parity runs
    # — losses are bit-identical either way for a fixed seed — or on
    # hosts where the extra thread is unwelcome. Ignored (synchronous
    # path kept) under pod lockstep / multi-process meshes, where a
    # background thread's device_puts would break the deterministic
    # pod-wide dispatch order.
    input_prefetch: bool = True
    # Disaggregated input-data service (harmony_tpu/inputsvc): pull
    # assembled, shard-ready batches from the shared input workers
    # instead of assembling them in-process, so same-dataset tenants
    # share ONE epoch assembly through the cross-tenant batch cache.
    # Default OFF (opt-in rollout); the process-wide
    # HARMONY_INPUT_SERVICE env knob (0/1) overrides for every job, and
    # HARMONY_INPUT_SERVICE_ADDR points trainers at a standalone service
    # process. Requires a wire-safe dataset identity (user.data_fn /
    # data_args); jobs without one keep in-process assembly. Losses are
    # bit-identical either way for a fixed seed — the service replays
    # the same epoch permutation the local provider draws — and every
    # service failure degrades to in-process assembly after bounded
    # retry (docs/INPUT_PIPELINE.md §"Input service").
    input_service: bool = False
    # Scheduling priority (jobserver/policy.py): under device contention
    # the policy engine shrinks, packs or preempts strictly LOWER-
    # priority tenants to satisfy higher-priority claimants (queued
    # arrivals, under-SLO growers). Equal priority never preempts.
    # Higher = more important; 0 = best-effort (the default).
    priority: int = 0
    # Per-job throughput SLO (metrics/accounting.py): the samples/sec
    # this job is expected to sustain. 0 = no target. When a worker
    # sustains < 90% of the target across a window of epochs it records
    # a structured joblog event (kind="slo") and the tenant ledger's
    # attainment gauge (harmony_tenant_slo_attainment) carries the
    # achieved/target ratio — the signal the ROADMAP-item-4 policy loop
    # scales on. The process-wide HARMONY_SLO_SPS env knob overrides
    # for every job (operator floor enforcement).
    target_samples_per_sec: float = 0.0
    app_params: Dict[str, Any] = field(default_factory=dict)

    #: Fields this class had until PR 27 deleted the host-driven step
    #: modes: name -> (the default every older ``to_dict`` wrote, what
    #: went). A record from an HA log or an older client's SUBMIT carries
    #: all three: at that default a field is dropped on decode, at any
    #: other value the record asks for a step mode that no longer exists
    #: and is refused by name (config/base.py ``_decode``).
    RETIRED_FIELDS: ClassVar[Dict[str, Tuple[Any, str]]] = {
        "fused_step": (True, "PR 27 deleted the unfused step mode"),
        "async_step": (False, "PR 27 deleted the async step mode"),
        "staleness_bound": (0, "PR 27 deleted the async step mode"),
    }


@config
class JobConfig(ConfigBase):
    """A full job submission (ref: the serialized conf DolphinJobLauncher
    assembles and ships over TCP; jobserver/DolphinJobLauncher.java)."""

    job_id: str
    app_type: str                      # "dolphin" | "pregel"
    trainer: Optional[str] = None      # dotted path of Trainer subclass
    # Metric-driven elasticity for this job (ref: the per-job Optimizer
    # binding behind ETOptimizationOrchestrator, and the -optimizer flag):
    # "homogeneous" | "add_one_server" | "delete_one_server" | a dotted
    # path resolving to an Optimizer class/factory. None = static sharding.
    optimizer: Optional[str] = None
    optimizer_period: float = 5.0      # seconds between optimization rounds
    update_fn: str = "add"
    tables: List[TableConfig] = field(default_factory=list)
    params: TrainerParams = field(default_factory=TrainerParams)
    num_workers: int = 0               # 0 = all executors (ref SchedulerImpl: all)
    user: Dict[str, Any] = field(default_factory=dict)
