"""Process-level caches of materialized input data.

The reference deliberately SHARES input tables across jobs with the same
table id (DolphinJobEntity.java:76-121: "reuses existing input table across
jobs if id matches") — loading the training set once and letting every
subsequent job of the same app read it. In this framework input data is not
a table (it feeds jitted steps directly), so the analogue is two caches
keyed by the DATA SOURCE identity (generator/loader dotted path + args):

  * a host-array cache (the job entity's ``_make_data``), so resubmitting
    a job does not regenerate/reload 100s of MB, and so every job with the
    same source sees the SAME dataset by definition;
  * this module's byte-bounded device cache of per-batch/stacked device
    arrays, so the host->device transfer happens once, not once per
    submission.

Cached device arrays are read-only by contract: training steps never donate
batch arguments (only the table state), so a cached buffer is never
invalidated by a step.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Hashable, Optional, Tuple


class ByteLRU:
    """Thread-safe LRU bounded by the total byte size of its values."""

    def __init__(self, max_bytes: int) -> None:
        self._lock = threading.Lock()
        self._cache: "OrderedDict[Hashable, Tuple[Any, int]]" = OrderedDict()
        self._bytes = 0
        self.max_bytes = max_bytes
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _nbytes(value: Any) -> int:
        leaves = value if isinstance(value, (tuple, list)) else (value,)
        return sum(int(getattr(a, "nbytes", 0)) for a in leaves)

    def get(self, key: Optional[Hashable]):
        if key is None:
            return None
        with self._lock:
            hit = self._cache.get(key)
            if hit is None:
                self.misses += 1
                return None
            self._cache.move_to_end(key)
            self.hits += 1
            return hit[0]

    def contains(self, key: Optional[Hashable]) -> bool:
        """Presence probe that perturbs NEITHER the LRU order nor the
        hit/miss counters — planning queries (e.g. "can this epoch bypass
        host work?") must not masquerade as cache traffic."""
        if key is None:
            return False
        with self._lock:
            return key in self._cache

    def put(self, key: Optional[Hashable], value: Any) -> None:
        if key is None:
            return
        nb = self._nbytes(value)
        if nb > self.max_bytes:
            return  # larger than the whole budget: never cacheable
        with self._lock:
            old = self._cache.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._cache[key] = (value, nb)
            self._bytes += nb
            while self._bytes > self.max_bytes and self._cache:
                _, (_, evicted) = self._cache.popitem(last=False)
                self._bytes -= evicted

    def drop(self, predicate) -> int:
        """Remove every entry whose key matches; returns the count. Used to
        release device buffers made unreachable by a live reshard (their
        keys embed the old sharding signature and can never hit again)."""
        with self._lock:
            stale = [k for k in self._cache if predicate(k)]
            for k in stale:
                _, nb = self._cache.pop(k)
                self._bytes -= nb
            return len(stale)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "bytes": self._bytes, "entries": len(self._cache)}

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self._bytes = 0
            self.hits = self.misses = 0


# Device-resident batches: bounded well below any chip's HBM; raise via
# set_max_bytes for hosts that want more residency.
_device = ByteLRU(2 << 30)
# Host arrays (the entity's dataset cache): host RAM is cheaper.
host_data = ByteLRU(4 << 30)


def get(key: Optional[Hashable]):
    return _device.get(key)


def contains(key: Optional[Hashable]) -> bool:
    return _device.contains(key)


def put(key: Optional[Hashable], value: Any) -> None:
    _device.put(key, value)


def set_max_bytes(n: int) -> None:
    _device.max_bytes = int(n)


def drop(predicate) -> int:
    return _device.drop(predicate)


def stats() -> dict:
    return _device.stats()


def clear() -> None:
    _device.clear()
