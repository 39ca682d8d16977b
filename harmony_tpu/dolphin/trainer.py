"""The Trainer SPI — the user-facing training contract.

Capability parity with the reference's 4-phase Trainer API
(dolphin/core/worker/Trainer.java:44-92):

  reference                      harmony_tpu
  ---------                      -----------
  initGlobalSettings()           init_global_settings(ctx)
  setMiniBatchData(data)         (framework passes the batch to compute)
  pullModel(data)                pull mode: "all" or pull_keys(batch)
  localCompute(data)             compute(model, batch) -> (delta, metrics)
  pushUpdate()                   (framework pushes compute's delta)
  onEpochFinished(epoch)         on_epoch_finished(ctx, epoch)
  evaluateModel(in, test, table) evaluate(model, batch) -> metrics
  cleanup()                      cleanup(ctx)

TPU-first difference, and why the shape is not a translation: the reference
runs pull/compute/push as three host-driven RPC phases. Here ``compute`` is a
*pure jax function* so the framework can fuse PULL (gather/all-gather), COMP
(MXU math), and PUSH (scatter / reduction) into ONE jitted, SPMD-sharded
step — XLA inserts the cross-chip collectives that replace the reference's
per-key RPCs. Phase identities survive (they are still announced to the
TaskUnit scheduler for multi-job interleaving) but the hot loop is a single
compiled program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax.numpy as jnp
import numpy as np

from harmony_tpu.config.params import TrainerParams


@dataclasses.dataclass
class TrainerContext:
    """What a trainer sees of the framework: its tables and hyper-params.

    ``model_table`` is the PS table (the reference's model table on server
    executors); ``local_table`` the optional worker-local table (ref:
    DolphinJobEntity local-model table, e.g. NMF's L-matrix rows)."""

    params: TrainerParams
    model_table: Any = None          # DenseTable
    local_table: Any = None          # DenseTable or None
    worker_id: str = "worker-0"
    num_workers: int = 1


class Trainer:
    """Base class; apps override the pure parts.

    ``pull_mode`` selects the PULL realization:
      * "all"  — the whole model is pulled each batch (MLR/Lasso/NMF-R style
        whole-table pull; realized as all-gather of the sharded table).
        ``compute`` receives ``model`` of shape [capacity, *value_shape].
      * "keys" — ``pull_keys(batch)`` names the rows needed (sparse apps);
        ``compute`` receives the gathered rows.
    """

    pull_mode: str = "all"
    # True: the job also carries a worker-local table (ref: DolphinJobEntity
    # optional local-model table, e.g. NMF's L-matrix rows); the fused step
    # then threads BOTH table arrays functionally and ``compute_with_local``
    # is used instead of ``compute``.
    uses_local_table: bool = False
    # Name of the trainer's objective in its compute() metrics when it is
    # NOT called "loss" (e.g. LDA's "log_likelihood"): per-batch/epoch
    # progress series fall back to it. None = only "loss" counts; other
    # metric keys are counters, never relabeled as a loss.
    objective_metric: "str | None" = None

    # -- lifecycle (host side) ------------------------------------------

    def init_global_settings(self, ctx: TrainerContext) -> None:
        """One-time setup before the first epoch (may push initial model
        values into the table)."""

    def on_training_start(self, ctx: TrainerContext, starting_epoch: int) -> None:
        """Called by the worker just before the epoch loop with the resume
        epoch (ref: StartingEpochIdx reaching the worker conf) — trainers
        with epoch-dependent state (LDA's PRNG fold, decay schedules) must
        seed from here, not assume epoch 0."""

    #: OPT-IN: set True on your subclass when :meth:`on_epoch_finished`
    #: depends only on ``epoch_idx`` and the trainer's OWN attributes
    #: (decay schedules, PRNG epoch counters) — never on trained values,
    #: pulled models, or tables. The worker then may invoke it between the
    #: dispatches of a multi-epoch fused window, BEFORE that epoch's
    #: device results have drained (collapsing one host<->device round
    #: trip per epoch into one per window). Trainers that don't override
    #: the hook at all are windowable regardless (the no-op reads
    #: nothing); the flag matters only for overriders.
    epoch_hook_windowable = False

    def on_epoch_finished(self, ctx: TrainerContext, epoch_idx: int) -> None:
        """Per-epoch hook (host side; may adjust step size etc. — see
        ``epoch_hook_windowable`` if it reads trained state)."""

    def cleanup(self, ctx: TrainerContext) -> None:
        """Final hook after the last epoch."""

    def observe_step_vectors(self, job_id: str, vectors) -> None:
        """Host side, at the metric drain: the step metrics that are not
        scalars (``{name: array [steps, ...]}``, e.g. tokens per expert) —
        they feed counters, never the per-batch loss series."""

    @classmethod
    def _epoch_hook_windowable(cls, trainer: "Trainer") -> bool:
        """Whether ``trainer``'s on_epoch_finished may run between the
        dispatches of a multi-epoch window (before results drain).

        True for the base no-op. For overriders, the ``epoch_hook_
        windowable`` opt-in must be declared AT OR BELOW the class that
        defines the effective hook — a flag inherited from above describes
        a different (ancestor) hook, and a subclass replacing the hook
        must re-opt-in for its own. Instance-level assignment wins."""
        if "epoch_hook_windowable" in trainer.__dict__:
            return bool(trainer.__dict__["epoch_hook_windowable"])
        mro = type(trainer).__mro__
        hook_owner = next(c for c in mro if "on_epoch_finished" in vars(c))
        if hook_owner is Trainer:
            return True  # un-overridden no-op reads nothing
        flag_owner = next(
            (c for c in mro if "epoch_hook_windowable" in vars(c)), None
        )
        if flag_owner is None or not vars(flag_owner)["epoch_hook_windowable"]:
            return False
        return mro.index(flag_owner) <= mro.index(hook_owner)

    # -- pure parts (traced into the fused step) ------------------------

    def hyperparams(self) -> Dict[str, float]:
        """Host-side hyper-parameters passed INTO the jitted step each epoch
        (learning rate etc.). Values reach ``compute`` as traced scalars, so
        per-epoch changes (decay in on_epoch_finished) take effect without
        recompiling — a baked-in Python float would be a trace-time constant
        and silently never decay."""
        return {}

    def jit_signature(self) -> "tuple | None":
        """Structural identity of this trainer's TRACED behavior, or None.

        Jobs whose trainers report equal signatures (together with equal
        table/mesh/batch signatures) reuse each other's compiled step
        programs across submissions (runtime/progcache) — the long-running
        JobServer's resubmit-the-same-app pattern stops paying a recompile
        per job, which can dominate short jobs.

        Contract: the signature must determine everything the trainer's
        traced functions — ``compute``/``compute_with_local``,
        ``pull_keys``, ``evaluate``, and the ``hyperparams`` key set —
        would trace (the worker caches its eval program under the same
        key). The default derives it
        from the instance ``__dict__`` when every attribute is a plain
        scalar (int/float/str/bool/None, or flat tuples thereof) and opts
        out (None) otherwise — a trainer holding arrays, callables or other
        objects cannot be structurally named, and silently sharing programs
        would be worse than recompiling. Note scalars that compute() bakes
        into the trace are frozen at first dispatch ANYWAY (mutating them
        mid-job never retraces), so keying on their at-build values adds no
        new staleness hazard; per-epoch knobs belong in hyperparams().
        """
        items = []
        for k, v in sorted(self.__dict__.items()):
            # Type-tag every scalar: Python's cross-type equality
            # (True == 1 == 1.0) would otherwise collide keys whose traced
            # programs differ (an int baked into a trace doesn't promote
            # like a float would).
            if isinstance(v, (int, float, str, bool, type(None))):
                items.append((k, type(v).__name__, v))
            elif isinstance(v, tuple) and all(
                isinstance(x, (int, float, str, bool)) for x in v
            ):
                items.append((k, tuple((type(x).__name__, x) for x in v)))
            else:
                return None
        return (type(self).__module__, type(self).__qualname__, tuple(items))

    def pull_keys(self, batch: Any) -> jnp.ndarray:
        """keys to pull for this batch (pull_mode == "keys" only)."""
        raise NotImplementedError

    def mask_delta(self, delta: jnp.ndarray, ok: jnp.ndarray) -> jnp.ndarray:
        """Hash-backed tables only: reconcile the push delta with the
        admission mask (``ok`` per pulled key) BEFORE the push. Override
        when rows carry cross-row invariants that a dropped row must leave
        consistent (e.g. LDA's summary row = sum of word rows). Default:
        identity — the table itself already drops ok=False rows."""
        return delta

    def compute(
        self, model: jnp.ndarray, batch: Any, hyper: Dict[str, jnp.ndarray]
    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """The mini-batch computation. Returns ``(delta, metrics)`` where
        ``delta`` matches ``model``'s shape and is folded into the table via
        the table's update function (push). Must be jax-traceable.
        ``hyper`` carries the values from :meth:`hyperparams`."""
        raise NotImplementedError

    def row_update_parts(self, capacity: int):
        """``pull_mode == "all"`` trainers whose ``compute`` is an
        elementwise update rule over a gradient may state it in two parts
        for a model table of ``capacity`` rows: ``(rows, sections,
        gradient, push_update)``. The model's first ``sections * rows``
        rows are ``sections`` sections of state that update together, row
        by row, the parameters first. ``gradient(model, batch) -> (g,
        metrics)`` is the step's COMP: of the pulled table it reads the
        parameter section alone, and ``g`` is ``rows`` rows — one array,
        or a pytree of arrays that ``push_update`` knows how to read (the
        fence carries either).
        ``push_update(spec, arr, model, g, hyper) -> new_arr`` is
        the step's PUSH: the rule folded into the table ``arr`` where the
        rows lie (``TableSpec.fold_row_sections`` / ``push_row_ranges``;
        ``model`` is the pulled table). ``compute`` stays, the same
        arithmetic as one whole delta. Default: None — the step pushes
        ``compute``'s whole delta."""
        return None

    def compute_with_local(
        self,
        model: jnp.ndarray,
        local: jnp.ndarray,
        batch: Any,
        hyper: Dict[str, jnp.ndarray],
    ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Variant for uses_local_table trainers: returns
        ``(model_delta, new_local_array, metrics)`` — the model delta folds
        through the PS table's update fn; the local array is replaced
        wholesale (worker-private state needs no update-fn semantics)."""
        raise NotImplementedError

    def local_table_config(self):
        """Schema of the worker-local table (uses_local_table only)."""
        raise NotImplementedError

    def evaluate(
        self, model: jnp.ndarray, batch: Any
    ) -> Dict[str, jnp.ndarray]:
        """Model evaluation on held-out data (ref: evaluateModel)."""
        raise NotImplementedError
