"""Model checkpoint chaining + offline evaluation replay.

Parity with the reference's ModelChkpManager (dolphin/core/master/
ModelChkpManager.java:40-80: chain model-table checkpoints during training,
restore them between evaluation rounds) and ModelEvaluator /
ModelEvaluationTasklet (dolphin/core/worker/ModelEvaluator.java: offline
evaluation over checkpointed model tables + test data, run at job end or
deferred to server shutdown — DolphinMaster.evaluate()).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from harmony_tpu.checkpoint.manager import (
    CheckpointManager,
    CheckpointStillWriting,
    PendingCheckpoint,
)
from harmony_tpu.dolphin.trainer import Trainer
from harmony_tpu.runtime.master import ETMaster, TableHandle


class ModelChkpManager:
    """Chains per-epoch snapshots of the model table during training.

    Snapshots are ASYNC: the epoch hook runs on the worker's thread, so a
    blocking checkpoint (device->host transfer + file IO) would stall
    training for the write duration every period. The device-side snapshot
    is atomic at the hook; the bytes drain in the background and
    ``drain()`` (called before evaluation / at job end) joins the writers.
    """

    # Cap on concurrent background writers: each in-flight checkpoint pins
    # one device-side table copy, so unbounded pendings could OOM a chip
    # when the hook outpaces the disk.
    MAX_PENDING = 2

    def __init__(
        self,
        chkp_manager: CheckpointManager,
        handle: TableHandle,
        period: int = 1,
        commit: bool = True,
        layout: Optional[str] = None,
    ) -> None:
        self._mgr = chkp_manager
        self._handle = handle
        self._period = max(1, period)
        self._commit = commit
        #: how the trainer lays its model out in the table's rows, by name
        #: (``PyTreeTrainer.table_layout``): every entry records it, and a
        #: restore converts or refuses a chain that names another
        self._layout = layout
        self.chkp_ids: List[str] = []
        self._pending: List[PendingCheckpoint] = []

    def on_epoch(self, epoch_idx: int) -> Optional[str]:
        """Epoch hook: snapshot every ``period`` epochs. Plugs into
        WorkerTasklet(epoch_callback=...)."""
        if (epoch_idx + 1) % self._period:
            return None
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        meta = {"epoch": float(epoch_idx)}  # the resume path's restart key
        if self._layout is not None:
            meta["layout"] = self._layout
        if mesh_spans_processes(self._handle.table.mesh):
            # Pod: the checkpoint is a synchronous mesh collective (every
            # process's chief worker reaches this hook at the same point in
            # its deterministic schedule; checkpoint_async's background
            # barriers would race the lockstep dispatch order).
            cid = self._mgr.checkpoint(self._handle, commit=self._commit,
                                       app_meta=meta)
            self.chkp_ids.append(cid)
            return cid
        while len(self._pending) >= self.MAX_PENDING:
            oldest = self._pending.pop(0)  # backpressure: join the oldest
            try:
                oldest.wait()
            except BaseException:
                # keep the chain consistent even on the backpressure path:
                # a failed writer's id must not survive as a replayable id
                if oldest.chkp_id in self.chkp_ids:
                    self.chkp_ids.remove(oldest.chkp_id)
                raise
        p = self._mgr.checkpoint_async(self._handle, commit=self._commit,
                                       app_meta=meta)
        self._pending.append(p)
        self.chkp_ids.append(p.chkp_id)
        return p.chkp_id

    def drain(self, timeout: float = 300.0) -> List[str]:
        """Join ALL background writers; failed ids are removed from the
        chain so the survivors stay replayable, then the first failure is
        re-raised. A TIMED-OUT writer is different from a failed one: its
        checkpoint may still complete, so its id stays in the chain and
        its handle stays pending — call drain() again to re-join it.
        Call before evaluating the chain / dropping the table."""
        errors: List[BaseException] = []
        still_pending: List[PendingCheckpoint] = []
        for p in self._pending:
            try:
                p.wait(timeout=timeout)
            except CheckpointStillWriting as e:
                still_pending.append(p)  # in flight, not dead
                errors.append(e)
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)
                if p.chkp_id in self.chkp_ids:
                    self.chkp_ids.remove(p.chkp_id)
        self._pending = still_pending
        if errors:
            # A real writer failure outranks a timeout: the timeout's
            # pending survives for a retry, the failure would be lost.
            for e in errors:
                if not isinstance(e, CheckpointStillWriting):
                    raise e
            raise errors[0]
        return list(self.chkp_ids)


def resolve_eval_inputs(config):
    """(trainer, batch) for a job's offline model evaluation, resolved
    from the serializable JobConfig — THE one resolution shared by the
    leader's deferred-eval closure and the pod follower's collective leg
    (they must issue byte-identical restore/evaluate collectives; two
    hand-copied resolutions would silently desynchronize them). fn and
    args fall back TOGETHER: pairing a custom test_data_fn with the
    training data_args would call it with foreign kwargs."""
    import numpy as np

    from harmony_tpu.config.base import resolve_symbol

    user = config.user
    if "test_data_fn" in user:
        fn = resolve_symbol(user["test_data_fn"])
        args = user.get("test_data_args", {})
    else:
        fn = resolve_symbol(user["data_fn"])
        args = user.get("test_data_args", user.get("data_args", {}))
    out = fn(**args)
    batch = tuple(
        np.asarray(a)
        for a in (out if isinstance(out, (tuple, list)) else (out,))
    )
    trainer = resolve_symbol(config.trainer)(**config.params.app_params)
    return trainer, batch


class ModelEvaluator:
    """Replays checkpoints against a trainer's evaluate() on test data.

    The reference restores each chained checkpoint into a fresh table and
    runs ModelEvaluationTasklet over it; here each checkpoint restores into
    a temporary table on the given executors, evaluates, and drops.
    """

    def __init__(self, master: ETMaster, chkp_manager: CheckpointManager) -> None:
        self._master = master
        self._mgr = chkp_manager

    def evaluate_checkpoints(
        self,
        chkp_ids: List[str],
        trainer: Trainer,
        test_batch: Tuple[np.ndarray, ...],
        executor_ids: List[str],
    ) -> List[Dict[str, float]]:
        eval_fn = jax.jit(trainer.evaluate)
        out: List[Dict[str, float]] = []
        for i, cid in enumerate(chkp_ids):
            handle = self._mgr.restore(
                self._master, cid, executor_ids, table_id=f"__eval__:{cid}"
            )
            try:
                if handle.table.spec.config.sparse:
                    # no full-model array exists over an unbounded key
                    # domain: trainers provide a keyed-lookup evaluation
                    sparse_eval = getattr(trainer, "evaluate_sparse", None)
                    if sparse_eval is None:
                        raise NotImplementedError(
                            f"{type(trainer).__name__} has no "
                            "evaluate_sparse(table, batch); required to "
                            "evaluate a sparse (hash-backed) checkpoint"
                        )
                    metrics = sparse_eval(
                        handle.table, tuple(map(np.asarray, test_batch))
                    )
                else:
                    model = handle.table.pull_array()
                    metrics = eval_fn(model, tuple(map(np.asarray, test_batch)))
                out.append({k: float(v) for k, v in metrics.items()})
            finally:
                handle.drop()
        return out
