"""ModelAccessor — push/pull facade with phase timing.

Parity with the reference's ModelAccessor / ETModelAccessor (dolphin/core/
worker/ModelAccessor.java:29-77, ETModelAccessor.java:43-157): pull =
getOrInit against the model table, push = update, with pull/push tracers
feeding metrics (totalPullTimeSec/totalPushTimeSec, the numbers BASELINE.md
says become all-gather / reduce-scatter time on TPU).

Used by the host-driven (irregular/sparse) path. The dense SPMD fast path
fuses pull+push into the jitted step (see worker.py) and charges the whole
step to COMP — the accessor still reports zeros for pull/push then, matching
how a fused step genuinely has no separable phases.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from harmony_tpu.metrics.tracer import Tracer
from harmony_tpu.table.table import DenseTable


class ModelAccessor:
    def __init__(self, table: DenseTable) -> None:
        self._table = table
        self.pull_tracer = Tracer(instrument="accessor.pull")
        self.push_tracer = Tracer(instrument="accessor.push")

    def pull(self, keys: Sequence[int]) -> np.ndarray:
        self.pull_tracer.start()
        vals = self._table.multi_get_or_init(keys)
        self.pull_tracer.record(len(keys), block_on=None)
        return vals

    def pull_all(self) -> np.ndarray:
        self.pull_tracer.start()
        arr = self._table.pull_array()
        out = np.asarray(arr)
        self.pull_tracer.record(out.shape[0], block_on=None)
        return out

    def push(self, keys: Sequence[int], deltas: np.ndarray) -> None:
        self.push_tracer.start()
        self._table.multi_update(keys, deltas)
        self.push_tracer.record(len(keys))

    def fused_step(self, compute_fn, **kw) -> "Any":
        """Compile this accessor's pull→compute→push cycle into ONE
        donated-buffer program (dolphin.worker.FusedSparseStep). Phase
        charging follows the module docstring's fused contract: the whole
        step is COMP (the step's own ``comp_tracer``); this accessor's
        pull/push tracers keep reporting zero — a fused step genuinely
        has no separable phases. Keyword args pass through
        (``signature=`` opts into the process program cache)."""
        from harmony_tpu.dolphin.worker import FusedSparseStep

        return FusedSparseStep(self._table, compute_fn, **kw)

    def get_and_reset_times(self) -> tuple:
        pull, push = self.pull_tracer.total_sec, self.push_tracer.total_sec
        self.pull_tracer.reset()
        self.push_tracer.reset()
        return pull, push


class CachedModelAccessor(ModelAccessor):
    """Worker-side model cache with background refresh.

    Parity with the reference's CachedModelAccessor (dolphin/core/worker/
    CachedModelAccessor.java:40-75): a loading cache over the model table —
    pull hits the cache (loading misses from the table), push applies the
    update to the cache locally AND to the table remotely, and a background
    refresher re-pulls every cached key each ``refresh_period_sec`` so cached
    values track other workers' pushes. Selected by ModelCacheEnabled
    (ETDolphinLauncher.java picks the accessor class; here
    ``TrainerParams.model_cache_enabled`` via :func:`make_accessor`).

    The cache trades staleness for latency exactly like the reference: reads
    between refreshes can miss other workers' pushes, which is the same
    bounded-staleness contract SSP already admits.
    """

    def __init__(self, table: DenseTable, refresh_period_sec: float = 0.5) -> None:
        super().__init__(table)
        import threading

        self._cache: dict[int, np.ndarray] = {}
        # Per-key write version: refresh_now only installs a fetched value if
        # no local push landed between its (unlocked) table read and its
        # install — otherwise a pre-push table snapshot would overwrite the
        # just-pushed cache entry and break read-your-own-push.
        self._versions: dict[int, int] = {}
        self._cache_lock = threading.Lock()
        self._refresh_period = refresh_period_sec
        self._stop = threading.Event()
        self._refresher: threading.Thread | None = None
        if refresh_period_sec > 0:
            self._refresher = threading.Thread(
                target=self._refresh_loop, name="model-cache-refresh", daemon=True
            )
            self._refresher.start()

    # -- cache plumbing --------------------------------------------------

    def _refresh_loop(self) -> None:
        while not self._stop.wait(self._refresh_period):
            self.refresh_now()

    def refresh_now(self) -> None:
        """Re-pull every cached key (ref: the background refresh executor
        pulling all cached keys each period). Also callable directly by
        tests/apps that want deterministic refresh points."""
        with self._cache_lock:
            keys = sorted(self._cache)
            versions = {k: self._versions.get(k, 0) for k in keys}
        if not keys:
            return
        fresh = self._table.multi_get_or_init(keys)
        with self._cache_lock:
            for k, v in zip(keys, fresh):
                if self._versions.get(k, 0) == versions[k]:
                    self._cache[k] = v
                # else: a push raced this refresh; keep the newer local value
                # (the NEXT refresh re-pulls it, post-push, from the table).

    def close(self) -> None:
        self._stop.set()
        if self._refresher is not None:
            self._refresher.join(timeout=2.0)

    # -- accessor surface ------------------------------------------------

    def pull(self, keys: Sequence[int]) -> np.ndarray:
        self.pull_tracer.start()
        if not len(keys):  # np.stack rejects empty; match base-class shape
            out = np.asarray(self._table.multi_get_or_init([]))
            self.pull_tracer.record(0, block_on=None)
            return out
        with self._cache_lock:
            missing = [k for k in keys if k not in self._cache]
            versions = {k: self._versions.get(k, 0) for k in missing}
        overlay = {}
        if missing:
            loaded = self._table.multi_get_or_init(missing)
            with self._cache_lock:
                for k, v in zip(missing, loaded):
                    # Same version guard as refresh_now: if a push raced the
                    # load, the table snapshot may predate that push, and
                    # caching it would hide the pusher's write from later
                    # pulls. Serve it for THIS call only (overlay) and leave
                    # the key uncached so the next pull re-reads post-push
                    # table state.
                    if self._versions.get(k, 0) == versions[k]:
                        self._cache[k] = v
                    else:
                        overlay[k] = v
        with self._cache_lock:
            out = np.stack([
                self._cache.get(k, overlay.get(k)) if k in overlay else self._cache[k]
                for k in keys
            ])
        self.pull_tracer.record(len(keys), block_on=None)
        return out

    def push(self, keys: Sequence[int], deltas: np.ndarray) -> None:
        self.push_tracer.start()
        # Local apply first (cache sees own push immediately)…
        apply = self._table.spec.update_fn.apply
        with self._cache_lock:
            for k, d in zip(keys, np.asarray(deltas)):
                self._versions[k] = self._versions.get(k, 0) + 1
                if k in self._cache:
                    self._cache[k] = np.asarray(apply(self._cache[k], d))
        # …then the remote apply through the table (the authoritative copy).
        self._table.multi_update(keys, deltas)
        self.push_tracer.record(len(keys))


def make_accessor(table: DenseTable, model_cache_enabled: bool = False,
                  refresh_period_sec: float = 0.5) -> ModelAccessor:
    """Accessor factory keyed by ModelCacheEnabled (ref: ETDolphinLauncher
    binding CachedModelAccessor vs ETModelAccessor)."""
    if model_cache_enabled:
        return CachedModelAccessor(table, refresh_period_sec)
    return ModelAccessor(table)
