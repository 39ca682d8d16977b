"""Two-stage distributed checkpointing.

Parity with the reference's checkpoint protocol (SURVEY.md §3.5):

  * stage 1 (temp): each executor writes ITS blocks to executor-local
    storage under ``chkpTempPath/appId/chkpId/blockIdx``
    (ref: ChkpManagerSlave.java:50-63 path scheme + class doc),
  * stage 2 (commit): blocks move to durable storage (HDFS there, a durable
    directory / GCS-style path here), recorded per-block
    (ref: commit semantics + ChkpCommitMsg),
  * sampling ratio: checkpoint only a prefix fraction of each block's keys
    (ref: samplingRatio in ChkpStartMsg — used for offline eval on samples),
  * restore into a DIFFERENT topology: ``restore()`` creates the table on
    any associator set; data re-enters through normal table writes
    (ref: ChkpManagerMaster.java:49-61, restore path picking loaders by
    commit state).

Format: one block file per block plus a JSON manifest carrying the table
config, ownership at checkpoint time, commit state, and sampling ratio —
enough to rebuild the table (and its BlockManager) from scratch. Block
files use the native CRC32-checked ``.blk`` codec (harmony_tpu.native,
C++) when available — restore then fails loudly on torn/corrupt blocks —
and fall back to ``.npy``; restore reads either, so checkpoints travel
between environments with and without the native library.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from harmony_tpu import faults, native
from harmony_tpu.config.base import ConfigBase
from harmony_tpu.config.params import RetryPolicy, TableConfig
from harmony_tpu.faults.retry import call_with_retry
from harmony_tpu.runtime.master import ETMaster, TableHandle
from harmony_tpu.tracing.span import SpanContext, trace_span, wire_context


#: Process-wide checkpoint READ accounting (blocks/bytes materialized
#: from checkpoint storage by _read_block). The elastic-shrink tests
#: assert the O(lost-bytes) restore contract against these; reset with
#: :func:`reset_read_stats`.
read_stats: Dict[str, int] = {"blocks_read": 0, "bytes_read": 0}
_READ_STATS_LOCK = threading.Lock()


def reset_read_stats() -> None:
    with _READ_STATS_LOCK:
        read_stats["blocks_read"] = 0
        read_stats["bytes_read"] = 0


# -- parallel block I/O (HARMONY_CHKP_IO_THREADS) -------------------------
#
# Every block of a checkpoint is an independent file with an independent
# checksum, so write/read legs parallelize freely: the file I/O and the
# CRC both run outside the GIL (native codec / zlib over a memoryview),
# and one block's CRC overlaps the next block's disk I/O on the pool.
# Serial (threads == 1) takes the exact pre-parallel code path — the
# bit-identical fallback. In-flight bytes on the WRITE side are bounded
# (backpressure against the D2H producer) so a slow disk never turns
# into an unbounded host-memory spike of staged blocks.

#: write-side in-flight budget per worker thread (bytes)
_INFLIGHT_PER_THREAD = 256 << 20


def _chkp_io_threads() -> int:
    """Worker count for checkpoint block I/O (HARMONY_CHKP_IO_THREADS;
    1 = the serial, bit-identical fallback)."""
    try:
        return max(1, int(os.environ.get("HARMONY_CHKP_IO_THREADS", "4")))
    except ValueError:
        return 4


def _observe_io(op: str, seconds: float) -> None:
    """harmony_chkp_io_seconds{op}: per-block checkpoint I/O latency
    (op = write | read | partial_read). Best-effort — observability
    must never fail a checkpoint."""
    try:
        from harmony_tpu.metrics.registry import get_registry

        get_registry().histogram(
            "harmony_chkp_io_seconds",
            "Per-block checkpoint I/O latency",
            ("op",),
        ).labels(op=op).observe(seconds)
    except Exception:
        pass


def _set_inflight_gauge(nbytes: int) -> None:
    try:
        from harmony_tpu.metrics.registry import get_registry

        get_registry().gauge(
            "harmony_chkp_inflight_bytes",
            "Bytes of checkpoint blocks staged in host memory awaiting "
            "their write leg (write-side backpressure budget)",
        ).set(float(nbytes))
    except Exception:
        pass


class _InflightBudget:
    """Write-side backpressure: ``acquire(nbytes)`` blocks until the
    in-flight total fits under the cap, so the D2H producer stalls
    instead of buffering the whole table ahead of a slow disk. A single
    block larger than the cap is admitted alone (never deadlocks)."""

    def __init__(self, cap_bytes: int) -> None:
        self._cap = max(1, int(cap_bytes))
        self._inflight = 0
        self._cv = threading.Condition()

    def acquire(self, n: int) -> None:
        with self._cv:
            while self._inflight > 0 and self._inflight + n > self._cap:
                self._cv.wait()
            self._inflight += n
            _set_inflight_gauge(self._inflight)

    def release(self, n: int) -> None:
        with self._cv:
            self._inflight -= n
            _set_inflight_gauge(self._inflight)
            self._cv.notify_all()


def _stage_blocks(staging: str, arrs, policy: RetryPolicy) -> Dict[str, int]:
    """Write an ORDERED iterable of ``(bid, host block)`` pairs under
    ``staging`` and return the manifest checksum map ``{str(bid): crc}``.

    Serial when HARMONY_CHKP_IO_THREADS == 1. Otherwise the caller's
    iterator keeps producing (device D2H + packing) on THIS thread while
    block write + CRC legs run on the pool — producing stalls only when
    the in-flight budget is exhausted. Per-block retry (chkp.block_write
    site + RetryPolicy) runs inside each leg, unchanged."""
    threads = _chkp_io_threads()
    if threads == 1:
        out: Dict[str, int] = {}
        for bid, arr in arrs:
            t0 = time.monotonic()
            out[str(bid)] = _write_block(staging, bid, arr, policy)
            _observe_io("write", time.monotonic() - t0)
        return out
    from concurrent.futures import Future, ThreadPoolExecutor

    budget = _InflightBudget(threads * _INFLIGHT_PER_THREAD)
    failed = threading.Event()
    futures: Dict[int, "Future"] = {}

    def write_one(bid: int, arr: np.ndarray) -> int:
        try:
            t0 = time.monotonic()
            crc = _write_block(staging, bid, arr, policy)
            _observe_io("write", time.monotonic() - t0)
            return crc
        except BaseException:
            failed.set()  # stop the producer: no point staging more D2H
            raise
        finally:
            budget.release(arr.nbytes)

    with ThreadPoolExecutor(max_workers=threads,
                            thread_name_prefix="chkp-io") as pool:
        for bid, arr in arrs:
            if failed.is_set():
                break
            budget.acquire(arr.nbytes)
            futures[bid] = pool.submit(write_one, bid, arr)
    out = {}
    first_err: Optional[BaseException] = None
    for bid in sorted(futures):
        try:
            out[str(bid)] = futures[bid].result()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            if first_err is None:
                first_err = e
    if first_err is not None:
        raise first_err
    return out


#: blocks per incremental import_blocks call on the pipelined restore
#: path — small enough that device staging overlaps the tail of the
#: reads, large enough that the jitted scatter amortizes
_RESTORE_CHUNK_BLOCKS = 16


def _fetch_blocks(d: str, bids, crcs: Dict[str, int],
                  policy: RetryPolicy, op: str = "read") -> Dict[int, np.ndarray]:
    """Read many blocks from checkpoint dir ``d`` (parallel when
    HARMONY_CHKP_IO_THREADS > 1; read order is irrelevant — every block
    is independently CRC-verified against the manifest). Returns
    ``{bid: arr}``; the first failing block's error is raised after
    outstanding reads are cancelled or drained."""

    def read_one(bid: int):
        t0 = time.monotonic()
        arr = _read_block(d, bid, expected_crc=crcs.get(str(bid)),
                          policy=policy)
        _observe_io(op, time.monotonic() - t0)
        return bid, arr

    bids = list(bids)
    threads = min(_chkp_io_threads(), max(1, len(bids)))
    if threads == 1:
        return dict(read_one(b) for b in bids)
    from concurrent.futures import ThreadPoolExecutor, as_completed

    pool = ThreadPoolExecutor(max_workers=threads,
                              thread_name_prefix="chkp-io")
    try:
        futs = [pool.submit(read_one, b) for b in bids]
        out: Dict[int, np.ndarray] = {}
        for f in as_completed(futs):
            bid, arr = f.result()  # first corrupt/lost block raises here
            out[bid] = arr
        return out
    finally:
        pool.shutdown(wait=True, cancel_futures=True)


def _account_read(arr: np.ndarray) -> None:
    with _READ_STATS_LOCK:
        read_stats["blocks_read"] += 1
        read_stats["bytes_read"] += int(arr.nbytes)
    # mirrored onto the process instrument registry so the O(lost-bytes)
    # restore behavior is scrapeable, not only assertable in tests
    try:
        from harmony_tpu.metrics.registry import get_registry

        reg = get_registry()
        reg.counter(
            "harmony_checkpoint_blocks_read_total",
            "Blocks materialized from checkpoint storage",
        ).inc()
        reg.counter(
            "harmony_checkpoint_read_bytes_total",
            "Bytes materialized from checkpoint storage",
        ).inc(int(arr.nbytes))
    except Exception:
        pass


# -- per-process recovery cache (elastic shrink) --------------------------
#
# One entry per table id: the host-side copies of the blocks THIS
# process staged for its most recent chain checkpoint, kept only while a
# job opted in (CheckpointManager.recovery_retain). On elastic recovery
# the partial restore takes every locally-cached block from here and
# reads ONLY the genuinely lost ones from checkpoint storage — the
# O(lost-bytes) half of the recovery contract. Module-global (not
# per-manager) on purpose: each recovery attempt constructs a fresh
# CheckpointManager, and the cache must survive that.

_RECOVERY_CACHE: Dict[str, Tuple[str, Dict[int, np.ndarray]]] = {}
_RECOVERY_LOCK = threading.Lock()
_RECOVERY_MAX_TABLES = 8


def _recovery_put(table_id: str, chkp_id: str,
                  blocks: Dict[int, np.ndarray]) -> None:
    with _RECOVERY_LOCK:
        _RECOVERY_CACHE.pop(table_id, None)
        _RECOVERY_CACHE[table_id] = (chkp_id, blocks)
        while len(_RECOVERY_CACHE) > _RECOVERY_MAX_TABLES:
            _RECOVERY_CACHE.pop(next(iter(_RECOVERY_CACHE)))


def recovery_blocks(chkp_id: str) -> Optional[Dict[int, np.ndarray]]:
    """This process's cached block copies for EXACTLY ``chkp_id``, or
    None. A stale entry (a different, older checkpoint of the same
    table) is never returned — mixing epochs would silently break the
    recovery's consistent-cut guarantee."""
    with _RECOVERY_LOCK:
        for cid, blocks in _RECOVERY_CACHE.values():
            if cid == chkp_id:
                return dict(blocks)
    return None


def drop_recovery_cache(table_id: Optional[str] = None,
                        prefix: Optional[str] = None) -> None:
    """Release retained block copies: one table, every table whose id
    starts with ``prefix`` (private model tables are namespaced
    ``<job_id>:...``, so the pod leader drops a finished elastic
    submission's retention by job-id prefix), or everything. Follower
    processes rely on the LRU cap instead — they cannot tell an attempt
    ending from the submission ending."""
    with _RECOVERY_LOCK:
        if table_id is None and prefix is None:
            _RECOVERY_CACHE.clear()
            return
        if table_id is not None:
            _RECOVERY_CACHE.pop(table_id, None)
        if prefix is not None:
            for tid in [t for t in _RECOVERY_CACHE if t.startswith(prefix)]:
                _RECOVERY_CACHE.pop(tid, None)


class CheckpointCorruptError(native.BlockCorruptError):
    """A checkpoint failed an integrity check on restore: a block's bytes
    don't match the manifest checksum, a block file is torn (codec CRC),
    or the manifest itself is unreadable. Subclasses the native codec's
    BlockCorruptError so existing corrupt-block handlers keep matching.
    NOT retryable — re-reading corrupt bytes cannot help — but
    RECOVERABLE: the chain-resume path quarantines the damaged checkpoint
    and falls back to the previous committed entry
    (jobserver/entity._restore_chain)."""


def _block_crc(arr: np.ndarray) -> int:
    """Integrity checksum of a block's LOGICAL bytes (dtype-ordered array
    content, not the container file) — the same digest whether the block
    was staged as .blk or .npy, by this process or a pod peer. Zero-copy:
    zlib.crc32 over a memoryview (identical polynomial/result to the
    native codec's CRC) — materializing tobytes() would add a full copy
    of every multi-hundred-MB block on both save and restore."""
    import zlib

    a = np.ascontiguousarray(arr)
    try:
        buf = memoryview(a).cast("B")
    except (TypeError, ValueError):
        buf = a.tobytes()  # extension dtypes lack the buffer protocol
    return zlib.crc32(buf) & 0xFFFFFFFF


def _write_block(d: str, bid: int, arr: np.ndarray,
                 policy: Optional[RetryPolicy] = None) -> int:
    """Write one block (CRC-trailed .blk when the native codec is up,
    .npy otherwise), retrying transient IO errors under ``policy``
    (callers writing many blocks hoist RetryPolicy.from_env() once).
    Returns the block's content checksum for the manifest."""

    def attempt() -> None:
        if faults.armed():
            faults.site("chkp.block_write", block=bid)
            # disk fault class: ENOSPC/EIO raise; "corrupt" is a torn
            # block — a truncated container lands on disk (the CRC
            # trailer / manifest checksum must catch it at read time)
            act = faults.site("disk.write", kind="chkp.block", block=bid)
            if act == "corrupt":
                torn = os.path.join(
                    d, f"{bid}.blk" if native.available() else f"{bid}.npy")
                with open(torn, "wb") as f:
                    f.write(b"\x93NUMPY-TORN")
                return
        if native.available():
            native.blk_write(os.path.join(d, f"{bid}.blk"), arr)
        else:
            np.save(os.path.join(d, f"{bid}.npy"), arr)

    call_with_retry(attempt, policy or RetryPolicy.from_env(),
                    op="chkp.block_write")
    return _block_crc(arr)


def _read_block(d: str, bid: int,
                expected_crc: Optional[int] = None,
                policy: Optional[RetryPolicy] = None) -> np.ndarray:
    """Read a block in either format, retrying transient IO. Corruption is
    FATAL to the read, never retried: the native codec's CRC trailer
    catches torn container files, and ``expected_crc`` (from the
    manifest) catches everything else — a silently truncated .npy, a
    block swapped between files, bit rot under a valid container. Both
    raise :class:`CheckpointCorruptError`."""

    def attempt() -> np.ndarray:
        if faults.armed():
            faults.site("chkp.block_read", block=bid)
            # disk fault class on the read path: "corrupt" flips bytes
            # after a clean read (bit rot under a valid container) so
            # the manifest-checksum arm below must fire; EIO raise
            # rules ride the normal retry policy
            if faults.site("disk.read", kind="chkp.block",
                           block=bid) == "corrupt":
                arr = attempt_clean()
                raw = bytearray(arr.tobytes())
                if raw:
                    raw[0] ^= 0xFF
                    return np.frombuffer(
                        bytes(raw), dtype=arr.dtype).reshape(arr.shape)
                return arr
        return attempt_clean()

    def attempt_clean() -> np.ndarray:
        blk = os.path.join(d, f"{bid}.blk")
        try:
            if os.path.exists(blk):
                return native.blk_read(blk)
            return np.load(os.path.join(d, f"{bid}.npy"))
        except native.BlockCorruptError as e:
            raise CheckpointCorruptError(str(e)) from e
        except (ValueError, EOFError) as e:
            # np.load on a torn/garbled .npy raises ValueError, and on a
            # ZERO-LENGTH file (power loss before the data flushed)
            # EOFError — same diagnosis as a CRC failure: the container
            # is corrupt, and the chain fallback must engage
            raise CheckpointCorruptError(
                f"unreadable block {bid} under {d}: {e}") from e

    arr = call_with_retry(
        attempt, policy or RetryPolicy.from_env(), op="chkp.block_read",
        fatal=(CheckpointCorruptError, FileNotFoundError),
    )
    if expected_crc is not None:
        got = _block_crc(arr)
        if got != expected_crc:
            raise CheckpointCorruptError(
                f"block {bid} under {d} fails its manifest checksum "
                f"(expected {expected_crc}, got {got})"
            )
    _account_read(arr)
    return arr


def _pack_hash_block(sk: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Hash-table block (slot_keys, values) -> ONE uint8 array for the
    block codec: [int64 n_slots, int64 value_nbytes] + keys + value bytes.
    The shapes/dtypes are reconstructed from the table config at restore."""
    head = np.asarray([sk.shape[0], v.nbytes], np.int64).tobytes()
    payload = head + np.ascontiguousarray(sk, np.int32).tobytes()
    payload += np.ascontiguousarray(v).tobytes()
    return np.frombuffer(payload, np.uint8)


def _unpack_hash_block(raw: np.ndarray, spec) -> "tuple[np.ndarray, np.ndarray]":
    buf = raw.tobytes()
    n_slots, v_nbytes = np.frombuffer(buf[:16], np.int64)
    if n_slots != spec.block_slots:
        raise IOError(
            f"hash block slot count {n_slots} != config {spec.block_slots}"
        )
    koff = 16 + int(n_slots) * 4
    sk = np.frombuffer(buf[16:koff], np.int32)
    v = np.frombuffer(buf[koff : koff + int(v_nbytes)], spec.dtype).reshape(
        spec.block_slots, *spec.value_shape
    )
    return sk, v


@dataclasses.dataclass
class CheckpointInfo:
    chkp_id: str
    table_config: TableConfig
    block_ids: List[int]
    ownership: List[int]          # block -> executor index at chkp time
    executors: List[str]
    sampling_ratio: float
    committed: bool
    created_at: float
    #: application-level tag (e.g. the chain's {"epoch": N, "layout":
    #: name}: numbers and short strings, whatever JSON holds) — optional,
    #: absent in older manifests; the resume path derives the restart
    #: epoch from it instead of guessing from id counters
    app_meta: Optional[Dict[str, Any]] = None
    #: per-block content checksums (str(block_id) -> CRC32 of the block's
    #: logical bytes — JSON keys are strings). Optional: absent in older
    #: manifests; restore verifies blocks only when present
    block_checksums: Optional[Dict[str, int]] = None

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["table_config"] = self.table_config.to_dict()
        return json.dumps(d, sort_keys=True)

    @staticmethod
    def from_json(s: str) -> "CheckpointInfo":
        # Forward compatibility, at BOTH nesting levels: a NEWER writer's
        # extra fields (on the manifest or on its embedded table config)
        # are dropped, not raised on — a TypeError here would be
        # misclassified as a torn manifest and the chain-resume scan
        # would quarantine (on object stores: delete) a perfectly valid
        # checkpoint after a version rollback. Missing REQUIRED fields
        # still raise (genuinely torn/foreign manifests).
        from harmony_tpu.config import base as _cfg_base

        d = json.loads(s)
        tc = d["table_config"]
        if isinstance(tc, dict):
            cls = _cfg_base._REGISTRY.get(tc.get("_type"))
            if cls is not None and dataclasses.is_dataclass(cls):
                keep = {f.name for f in dataclasses.fields(cls)} | {"_type"}
                tc = {k: v for k, v in tc.items() if k in keep}
        d["table_config"] = ConfigBase.from_dict(tc)
        known = {f.name for f in dataclasses.fields(CheckpointInfo)}
        return CheckpointInfo(**{k: v for k, v in d.items() if k in known})


class CheckpointStillWriting(TimeoutError):
    """wait(timeout) expired while the writer is still running — distinct
    from a writer that FAILED with a (generic) TimeoutError, so callers
    can tell 'in flight, retry later' from 'dead'."""


class PendingCheckpoint:
    """Handle for an in-flight async checkpoint (see
    CheckpointManager.checkpoint_async)."""

    def __init__(self, chkp_id: str) -> None:
        self.chkp_id = chkp_id
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> str:
        """Block until the write finishes; raises the writer's exception if
        it failed, else returns the checkpoint id."""
        if not self._done.wait(timeout):
            raise CheckpointStillWriting(
                f"checkpoint {self.chkp_id} still writing"
            )
        t = self._thread  # local capture: wait() may race with itself
        if t is not None:
            t.join()  # reap the writer thread (idempotent)
            self._thread = None
        if self._error is not None:
            raise self._error
        return self.chkp_id


class CheckpointManager:
    """Master-side coordinator (ref: ChkpManagerMaster) + the slave-side
    block IO collapsed in (single-controller: the master can reach every
    shard directly via the table's export/import)."""

    @classmethod
    def for_job(cls, chkp_root: str, job_id: str,
                backend=None) -> "CheckpointManager":
        """The per-job layout (<root>/<job>/temp, <root>/<job>/commit) —
        THE one place it is defined: the job entity and the pod
        followers' collective-eval leg must construct byte-identical
        managers or their restores diverge. ``HARMONY_CHKP_BACKEND``
        (posix|orbax) forces the commit backend when no explicit one is
        given — an env knob precisely so every pod process inherits the
        same choice (the reference's equivalent deployment switch is the
        HDFS vs local fs config, ChkpManagerSlave.java:50-63)."""
        if backend is None:
            backend = os.environ.get("HARMONY_CHKP_BACKEND") or None
        mgr = cls(os.path.join(chkp_root, job_id, "temp"),
                  os.path.join(chkp_root, job_id, "commit"),
                  backend=backend)
        # job attribution for the tenant cost ledger: a per-job manager
        # charges its checkpoint byte traffic to its job
        mgr.job_id = job_id
        return mgr

    def __init__(self, temp_root: str, commit_root: str, backend=None) -> None:
        """``commit_root`` names the durable store: a directory (posix
        backend), or an object-store URL like ``gs://bucket/chkps`` (orbax/
        tensorstore backend). ``backend`` overrides the inference — a name
        ("posix"/"orbax") or a CommitBackend instance (see backends.py)."""
        from harmony_tpu.checkpoint.backends import make_commit_backend

        self.temp_root = temp_root
        self.commit_root = commit_root
        os.makedirs(temp_root, exist_ok=True)
        self._backend = make_commit_backend(commit_root, backend)
        self._lock = threading.Lock()
        self._counter = 0
        #: set by for_job(): names the tenant this manager's checkpoint
        #: byte traffic is charged to (metrics/accounting.py); None =
        #: unattributed (table-binding fallback, or dropped)
        self.job_id: Optional[str] = None
        #: elastic-shrink jobs set this: each full-ratio checkpoint also
        #: retains this process's staged host block copies in the
        #: process-wide recovery cache (see module doc), so a later
        #: partial restore reads only genuinely LOST blocks from storage
        self.recovery_retain = False

    def _account_bytes(self, kind: str, nbytes: int, table_id: str) -> None:
        """Tenant-ledger attribution (metrics/accounting.py): a per-job
        manager (for_job) charges its job directly; others resolve
        through the ledger's table binding. Guarded — accounting must
        never fail (or slow) checkpoint I/O."""
        if nbytes <= 0:
            return
        try:
            from harmony_tpu.metrics.accounting import ledger

            if self.job_id is not None:
                ledger().record_job_bytes(self.job_id, kind, int(nbytes))
            else:
                ledger().record_table_bytes(table_id, kind, int(nbytes))
        except Exception:
            pass

    def advance_counter(self, base: int) -> None:
        """Start id counters past ``base`` — a RESUMED job's chain manager
        continues the original chain's numbering, keeping chain ids (and
        the counter->epoch mapping a later resume derives) monotonic."""
        with self._lock:
            self._counter = max(self._counter, int(base))

    # -- write path ------------------------------------------------------

    def _snapshot(self, handle: TableHandle, sampling_ratio: float,
                  app_meta: Optional[Dict[str, Any]] = None):
        """The synchronous prefix shared by sync and async checkpointing:
        id allocation + an atomic device-side snapshot (O(dispatch); the
        table lock is held for microseconds)."""
        if not (0.0 < sampling_ratio <= 1.0):
            raise ValueError(f"bad sampling_ratio {sampling_ratio}")
        if handle.table.spec.config.sparse and sampling_ratio < 1.0:
            raise ValueError(
                "sampling is undefined for sparse (hash) tables: slot order "
                "is not key order, so a prefix is not a sample"
            )
        with self._lock:
            self._counter += 1
            chkp_id = f"{handle.table_id}-{self._counter}-{int(time.time() * 1000)}"
        snap = handle.table.snapshot_blocks()
        info = CheckpointInfo(
            chkp_id=chkp_id,
            table_config=handle.table.spec.config,
            block_ids=sorted(snap),
            ownership=handle.block_manager.ownership_vector(),
            executors=handle.block_manager.executors,
            sampling_ratio=sampling_ratio,
            committed=False,
            created_at=time.time(),
            app_meta=app_meta,
        )
        return chkp_id, snap, info

    def _write(self, info, snap, block_size, commit):
        """Stage the snapshot to temp files (+ optional commit): the slow
        D2H + file IO half, runnable on any thread.

        Writes into a ``.writing`` staging dir and renames into place
        (atomic, same FS), so delete()/info()/restore()/list_checkpoints()
        NEVER observe a half-written checkpoint — an in-flight async id
        resolves to nothing until the rename."""
        tdir = os.path.join(self.temp_root, info.chkp_id)
        staging = tdir + ".writing"
        os.makedirs(staging)
        try:
            keep = None
            if info.sampling_ratio < 1.0:
                keep = max(1, int(block_size * info.sampling_ratio))
            sparse = info.table_config.sparse
            retained: Optional[Dict[int, np.ndarray]] = (
                {} if self.recovery_retain and keep is None else None
            )
            policy = RetryPolicy.from_env()

            staged_bytes = [0]

            def host_blocks():
                # pop as we go: each device block is released right after
                # its D2H transfer instead of pinning the snapshot until
                # the end. Runs on THIS thread (the producer of the
                # parallel write pool in _stage_blocks).
                for bid in sorted(snap):
                    item = snap.pop(bid)
                    if sparse:
                        sk, v = item
                        arr = _pack_hash_block(np.asarray(sk), np.asarray(v))
                    else:
                        arr = np.asarray(item)
                        arr = arr[:keep] if keep else arr
                    if retained is not None:
                        retained[bid] = arr
                    staged_bytes[0] += int(arr.nbytes)
                    yield bid, arr

            info.block_checksums = _stage_blocks(staging, host_blocks(),
                                                 policy)
            self._account_bytes("chkp_write", staged_bytes[0],
                                info.table_config.table_id)
            if retained is not None:
                _recovery_put(info.table_config.table_id, info.chkp_id,
                              retained)
            with open(os.path.join(staging, "manifest.json"), "w") as f:
                f.write(info.to_json())
            os.rename(staging, tdir)
        except BaseException:
            # never leak an unreachable partial dir (list/delete filter
            # '.writing', so nothing else could ever clean it up)
            shutil.rmtree(staging, ignore_errors=True)
            raise
        if commit:
            self.commit(info.chkp_id)

    def checkpoint(
        self,
        handle: TableHandle,
        sampling_ratio: float = 1.0,
        commit: bool = False,
        app_meta: Optional[Dict[str, Any]] = None,
    ) -> str:
        """Stage blocks to temp storage; optionally commit immediately.
        Returns the checkpoint id (``tableId-seq-timestamp``, mirroring the
        reference's tableId-timestamp scheme).

        Checkpoint and migration are mutually exclusive per table in the
        reference (AllocatedTable doc); here the per-block snapshot already
        dispatches under the table lock, so a concurrent reshard simply
        orders before or after the whole export.

        On a MULTI-PROCESS mesh this is an SPMD-collective call: every
        process of the table's mesh must call it with the same arguments
        (see _pod_checkpoint).
        """
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        with trace_span("checkpoint.write", table=handle.table_id) as sp:
            if mesh_spans_processes(handle.table.mesh):
                cid = self._pod_checkpoint(handle, sampling_ratio, commit,
                                           app_meta)
            else:
                chkp_id, snap, info = self._snapshot(
                    handle, sampling_ratio, app_meta)
                self._write(info, snap, handle.table.spec.block_size, commit)
                cid = chkp_id
            if sp is not None:
                sp.annotate("chkp_id", cid)
            return cid

    def _pod_checkpoint(
        self, handle: TableHandle, sampling_ratio: float, commit: bool,
        app_meta: Optional[Dict[str, Any]] = None,
    ) -> str:
        """Pod-mode two-stage checkpoint (ref: ChkpManagerSlave.java:50-63
        staging per-executor local files + ChkpManagerMaster.java:49-61
        coordinating the commit): each process stages ITS owned blocks from
        addressable shards — no process ever touches a non-addressable
        byte — then the mesh-lowest process writes the manifest, renames
        the staging dir into place, and runs the stage-2 commit, fenced by
        mesh barriers.

        Requirements: ``temp_root`` must be shared storage across the
        mesh's processes (the virtual-pod tests share one FS; real pods
        point temp_root at NFS/GCS-fuse — per-host-private temp dirs need
        a per-process commit protocol this round does not ship), and the
        call is SPMD-collective: every participating process calls with
        identical arguments in its deterministic call sequence (the chkp
        id is derived from the per-process counter, NOT a timestamp, so
        all processes name the same checkpoint)."""
        from harmony_tpu.parallel.multihost import mesh_sum

        if sampling_ratio != 1.0:
            raise ValueError(
                "sampling is single-process only: a sampled pod restore "
                "would need the cross-process pad path"
            )
        with self._lock:
            # Deterministic-but-unique id: no timestamps (every process
            # must derive the SAME id without talking), so bump the
            # counter past ids already present in shared storage — a
            # resubmitted job's fresh manager would otherwise reuse
            # '<table>-1-pod' and commit() would silently keep the stale
            # run's blocks. All processes scan the same shared roots at
            # the same logical point, so they agree. The scan must NOT
            # read '.writing' staging state — peers of THIS checkpoint
            # create it mid-scan, so probing it would race into divergent
            # ids; stale staging from a crashed run is handled by the
            # leader's fenced pre-clear below instead.
            while True:
                self._counter += 1
                chkp_id = f"{handle.table_id}-{self._counter}-pod"
                if not self._backend.exists(chkp_id) and not os.path.isdir(
                    os.path.join(self.temp_root, chkp_id)
                ):
                    break
        mesh = handle.table.mesh
        leader = min(d.process_index for d in mesh.devices.flat)
        import jax as _jax

        info = CheckpointInfo(
            chkp_id=chkp_id,
            table_config=handle.table.spec.config,
            block_ids=list(range(handle.table.spec.num_blocks)),
            ownership=handle.block_manager.ownership_vector(),
            executors=handle.block_manager.executors,
            sampling_ratio=1.0,
            committed=False,
            created_at=time.time(),
            app_meta=app_meta,
        )
        tdir = os.path.join(self.temp_root, chkp_id)
        staging = tdir + ".writing"
        # Failure containment: a one-sided staging error must not strand
        # peers in the fence (a psum never times out) — every process
        # reports its error flag THROUGH the fence, and all raise together
        # if anyone failed.
        # Fenced pre-clear: a crashed prior run of the same job id can
        # leave stale block files in '<id>.writing'; makedirs(exist_ok)
        # would adopt them and the leader's wholesale rename would commit
        # dead-run payloads into a fresh checkpoint. The LEADER clears the
        # staging dir before ANY process writes — behind a mesh fence so
        # no peer's write can race the clear.
        err: Optional[BaseException] = None
        try:
            if _jax.process_index() == leader:
                shutil.rmtree(staging, ignore_errors=True)
                os.makedirs(staging, exist_ok=True)
        except BaseException as e:  # noqa: BLE001 - reported via the fence
            err = e
        failures = mesh_sum(mesh, 1.0 if err else 0.0,
                            f"chkp-cleared:{chkp_id}")
        if failures:
            if err is not None:
                raise err
            raise RuntimeError(
                f"leader failed clearing the staging dir for {chkp_id}"
            )
        try:
            os.makedirs(staging, exist_ok=True)  # processes race; shared FS
            sparse = info.table_config.sparse
            mine = handle.table.addressable_blocks()
            policy = RetryPolicy.from_env()
            retained: Optional[Dict[int, np.ndarray]] = (
                {} if self.recovery_retain else None
            )

            def host_blocks():
                for bid in sorted(mine):
                    item = mine[bid]
                    if sparse:
                        arr = _pack_hash_block(
                            np.asarray(item[0]), np.asarray(item[1])
                        )
                    else:
                        arr = np.asarray(item)
                    if retained is not None:
                        retained[bid] = arr
                    yield bid, arr

            my_crcs = _stage_blocks(staging, host_blocks(), policy)
            if retained is not None:
                _recovery_put(info.table_config.table_id, chkp_id, retained)
            # Per-process checksum sidecar: only THIS process knows the
            # digests of the blocks it staged; the leader merges every
            # sidecar into the manifest's block_checksums after the
            # staged fence (which orders all sidecar writes before the
            # leader's read) and removes them before the rename.
            side_tmp = os.path.join(staging,
                                    f"_crc.{_jax.process_index()}.json.tmp")
            with open(side_tmp, "w") as f:
                json.dump(my_crcs, f, sort_keys=True)
            os.replace(side_tmp, os.path.join(
                staging, f"_crc.{_jax.process_index()}.json"))
        except BaseException as e:  # noqa: BLE001 - reported via the fence
            err = e
        failures = mesh_sum(mesh, 1.0 if err else 0.0,
                            f"chkp-staged:{chkp_id}")
        if failures:
            if _jax.process_index() == leader:
                shutil.rmtree(staging, ignore_errors=True)
            if err is not None:
                raise err
            raise RuntimeError(
                f"{int(failures)} process(es) failed staging {chkp_id}"
            )
        if _jax.process_index() == leader:
            try:
                # merge every process's checksum sidecar into the manifest
                # (duplicate block ids across sidecars — replicated blocks
                # staged by their lowest owner only — cannot conflict:
                # identical content, identical digest)
                merged: Dict[str, int] = {}
                for name in sorted(os.listdir(staging)):
                    if name.startswith("_crc.") and name.endswith(".json"):
                        p = os.path.join(staging, name)
                        with open(p) as f:
                            merged.update(json.load(f))
                        os.remove(p)
                info.block_checksums = merged or None
                with open(os.path.join(staging, "manifest.json"), "w") as f:
                    f.write(info.to_json())
                os.rename(staging, tdir)
                if commit:
                    self.commit(chkp_id)
            except BaseException as e:  # noqa: BLE001 - fenced below
                err = e
                shutil.rmtree(staging, ignore_errors=True)
        failures = mesh_sum(mesh, 1.0 if err else 0.0,
                            f"chkp-done:{chkp_id}")
        if failures:
            if err is not None:
                raise err
            raise RuntimeError(
                f"leader failed finalizing {chkp_id} (manifest/commit)"
            )
        return chkp_id

    def checkpoint_async(
        self,
        handle: TableHandle,
        sampling_ratio: float = 1.0,
        commit: bool = False,
        app_meta: Optional[Dict[str, Any]] = None,
    ) -> "PendingCheckpoint":
        """Non-blocking checkpoint: the device-side snapshot is taken NOW
        (atomic w.r.t. training steps), the D2H transfer and file IO run on
        a background thread — training continues immediately. Returns a
        :class:`PendingCheckpoint`; the checkpoint id resolves to a readable
        directory only once ``wait()`` returns (the manifest is written
        last, so an in-flight id never restores partially)."""
        from harmony_tpu.parallel.mesh import mesh_spans_processes

        if mesh_spans_processes(handle.table.mesh):
            # The pod path fences with mesh-collective barriers; running
            # those on a background thread would race the pod's lockstep
            # dispatch order. Pod checkpoints are synchronous collectives.
            raise ValueError(
                "checkpoint_async is single-process only; call "
                "checkpoint() collectively on a multi-process mesh"
            )
        chkp_id, snap, info = self._snapshot(handle, sampling_ratio, app_meta)
        pending = PendingCheckpoint(chkp_id)
        block_size = handle.table.spec.block_size
        # the writer thread has no ambient span; carry the caller's trace
        # context explicitly so the async write stays in the job's trace
        parent_wire = wire_context()

        def run():
            try:
                with trace_span("checkpoint.write_async",
                                parent=SpanContext.from_wire(parent_wire),
                                chkp_id=chkp_id):
                    self._write(info, snap, block_size, commit)
            except BaseException as e:  # surfaced by wait()
                pending._error = e
            finally:
                pending._done.set()

        t = threading.Thread(target=run, name=f"chkp-{chkp_id}", daemon=True)
        pending._thread = t
        t.start()
        return pending

    def commit(self, chkp_id: str) -> None:
        """Stage 2: move temp -> durable (ref: commit on executor close).

        Delegated to the pluggable CommitBackend (atomic per its store:
        same-FS rename for posix, orbax finalize for object stores); the
        temp copy is removed only after the durable write lands, so a crash
        mid-commit leaves the temp copy restorable. Idempotent: a retry
        after a crash between the durable write and the temp cleanup just
        finishes the cleanup."""
        with trace_span("checkpoint.commit", chkp_id=chkp_id):
            if faults.armed():
                faults.site("chkp.commit", chkp_id=chkp_id)
                # disk fault class at the durable landing: an ENOSPC
                # raise here is the mid-commit full disk — the temp
                # copy must stay restorable and a commit retry must be
                # idempotent once space returns
                faults.site("disk.fsync", kind="chkp.commit",
                            chkp_id=chkp_id)
            src = os.path.join(self.temp_root, chkp_id)
            if self._backend.exists(chkp_id):
                shutil.rmtree(src, ignore_errors=True)
                return
            if not os.path.isdir(src):
                raise FileNotFoundError(f"no temp checkpoint {chkp_id}")
            self._backend.commit(chkp_id, src)
            shutil.rmtree(src)
            # Structured commit pointer for the control plane: chain ids
            # are job-prefixed (``<job>:...``); the event rides this
            # process's joblog ring, and — when THIS process hosts an HA
            # leader (leader-local jobs) — the sink tees it into the
            # durable log. A chief-follower commit stays process-local;
            # the takeover re-arm scans shared chain storage either way.
            # Guarded lazy import: checkpointing must not hard-depend on
            # the jobserver package.
            if ":" in chkp_id:
                try:
                    from harmony_tpu.jobserver.joblog import record_event

                    record_event(chkp_id.split(":", 1)[0], "chkp_chain",
                                 chkp_id=chkp_id)
                except Exception:
                    pass

    def quarantine(self, chkp_id: str) -> None:
        """Move a DAMAGED checkpoint out of the restorable namespace
        without destroying the evidence: the temp copy is renamed to
        ``<id>.quarantined`` (filtered from every listing/scan), and the
        durable copy is quarantined by its backend (rename where the
        store supports it, delete where it doesn't). Idempotent. Called
        by the chain-resume fallback so a corrupt newest entry can never
        be picked again — by this resume or any later one."""
        from harmony_tpu.checkpoint.backends import quarantine_dir

        self._backend.quarantine(chkp_id)
        quarantine_dir(os.path.join(self.temp_root, chkp_id))

    # -- read path -------------------------------------------------------

    def _dir_of(self, chkp_id: str) -> str:
        committed = self._backend.fetch(chkp_id)
        if committed is not None:
            return committed
        temp = os.path.join(self.temp_root, chkp_id)
        if os.path.isdir(temp):
            return temp
        raise FileNotFoundError(f"checkpoint {chkp_id} not found")

    @staticmethod
    def _load_manifest(d: str) -> CheckpointInfo:
        """Torn-commit detection: a checkpoint directory whose manifest is
        missing or unparseable is a torn commit (the manifest is written
        LAST), surfaced as CheckpointCorruptError so the chain-resume
        fallback can quarantine it and try the previous entry."""
        path = os.path.join(d, "manifest.json")
        try:
            with open(path) as f:
                return CheckpointInfo.from_json(f.read())
        except FileNotFoundError as e:
            raise CheckpointCorruptError(
                f"torn checkpoint at {d}: no manifest.json") from e
        except (ValueError, KeyError, TypeError) as e:
            raise CheckpointCorruptError(
                f"torn/corrupt manifest at {path}: "
                f"{type(e).__name__}: {e}") from e

    def info(self, chkp_id: str) -> CheckpointInfo:
        """Manifest only — never materializes block data (a remote backend's
        full fetch can be GBs; metadata reads must stay cheap)."""
        text = self._backend.fetch_manifest(chkp_id)
        if text is not None:
            try:
                return CheckpointInfo.from_json(text)
            except (ValueError, KeyError, TypeError) as e:
                raise CheckpointCorruptError(
                    f"torn/corrupt manifest for {chkp_id}: "
                    f"{type(e).__name__}: {e}") from e
        temp = os.path.join(self.temp_root, chkp_id)
        if os.path.isdir(temp):
            return self._load_manifest(temp)
        raise FileNotFoundError(f"checkpoint {chkp_id} not found")

    def list_checkpoints(self) -> List[str]:
        temp = set(
            d for d in os.listdir(self.temp_root)
            if not d.endswith(".staging") and not d.endswith(".writing")
            and not d.endswith(".quarantined")
            and os.path.isdir(os.path.join(self.temp_root, d))
        )
        return sorted(temp | set(self._backend.list_ids()))

    def restore(
        self,
        master: ETMaster,
        chkp_id: str,
        associators: Sequence[str],
        data_axis: int = 1,
        table_id: Optional[str] = None,
    ) -> TableHandle:
        """Rebuild the table on ``associators`` — any topology, not just the
        one that wrote the checkpoint (ref: ETMaster.createTable(chkpId,
        associators)). Sampled checkpoints fill unsampled keys with init
        values (getOrInit semantics)."""
        with trace_span("checkpoint.restore", chkp_id=chkp_id):
            return self._restore_inner(master, chkp_id, associators,
                                       data_axis, table_id)

    def _restore_inner(
        self,
        master: ETMaster,
        chkp_id: str,
        associators: Sequence[str],
        data_axis: int = 1,
        table_id: Optional[str] = None,
    ) -> TableHandle:
        d = self._dir_of(chkp_id)
        info = self._load_manifest(d)
        cfg = info.table_config
        if table_id is not None:
            cfg = cfg.replace(table_id=table_id)
        handle = master.create_table(cfg, associators, data_axis)
        pool = None
        try:
            from harmony_tpu.parallel.mesh import mesh_spans_processes

            spec = handle.table.spec
            crcs = info.block_checksums or {}
            policy = RetryPolicy.from_env()
            threads = min(_chkp_io_threads(), max(1, len(info.block_ids)))
            # Chunked import is single-process only: import_blocks on a
            # multi-process mesh is an SPMD-collective dispatch, and the
            # chunk COUNT here derives from this process's local
            # HARMONY_CHKP_IO_THREADS — env skew across the pod would
            # diverge the collective sequence and wedge the restore.
            # Spanning meshes keep the single import call (reads still
            # parallel via _fetch_blocks below).
            pipelined = (threads > 1 and not cfg.sparse
                         and info.sampling_ratio >= 1.0
                         and not mesh_spans_processes(handle.table.mesh))
            read_bytes = 0
            raw: Dict[int, Any] = {}
            if pipelined:
                # dense full-ratio: stream reads off the I/O pool and
                # install finished chunks while later reads are still on
                # disk — restore is pipeline latency, not reads + import.
                # Chunks are formed in BLOCK-ID order (not completion
                # order) so repeated restores stay deterministic.
                from concurrent.futures import ThreadPoolExecutor

                def read_one(bid: int):
                    t0 = time.monotonic()
                    arr = _read_block(d, bid,
                                      expected_crc=crcs.get(str(bid)),
                                      policy=policy)
                    _observe_io("read", time.monotonic() - t0)
                    return arr

                pool = ThreadPoolExecutor(max_workers=threads,
                                          thread_name_prefix="chkp-io")
                raw = {bid: pool.submit(read_one, bid)
                       for bid in info.block_ids}
            else:
                # sparse / sampled need per-block post-processing against
                # table state; read everything first (still parallel)
                raw = _fetch_blocks(d, info.block_ids, crcs, policy)
            blocks: Dict[int, np.ndarray] = {}
            for bid in info.block_ids:
                arr = raw.pop(bid)
                if pipelined:
                    arr = arr.result()
                read_bytes += int(arr.nbytes)
                if cfg.sparse:
                    blocks[bid] = _unpack_hash_block(arr, spec)
                    continue
                if arr.shape[0] < spec.block_size:
                    if mesh_spans_processes(handle.table.mesh):
                        raise ValueError(
                            f"checkpoint {chkp_id} is sampled; the init-pad "
                            "path reads whole blocks host-side and is "
                            "single-process only — restore onto a "
                            "single-process mesh"
                        )
                    # sampled: pad with the block's existing init values
                    full = np.array(handle.table.export_blocks([bid])[bid])
                    full[: arr.shape[0]] = arr
                    arr = full
                blocks[bid] = arr
                if pipelined and len(blocks) >= _RESTORE_CHUNK_BLOCKS:
                    handle.table.import_blocks(blocks)
                    blocks = {}
            handle.table.import_blocks(blocks)
            self._account_bytes("chkp_read", read_bytes,
                                info.table_config.table_id)
        except BaseException:
            handle.drop()  # no half-restored orphan tables
            raise
        finally:
            if pool is not None:
                pool.shutdown(wait=True, cancel_futures=True)
        return handle

    def restore_partial(
        self,
        master: ETMaster,
        chkp_id: str,
        associators: Sequence[str],
        data_axis: int = 1,
        table_id: Optional[str] = None,
    ) -> "Tuple[TableHandle, Dict[str, int]]":
        """Elastic-recovery restore: rebuild the table on ``associators``
        reading from checkpoint storage ONLY the blocks this process does
        not already hold in its recovery cache (see module doc) — the
        O(lost-bytes) path a shrink recovery needs, vs :meth:`restore`'s
        O(model-bytes) full read. Blocks read from storage are verified
        against the manifest checksums exactly like a full restore;
        cached blocks are the very host copies whose digests the
        manifest records, staged by this process at checkpoint time.

        Topology-free like restore(): on a single-process mesh each
        needed block imports through normal table writes; on a
        multi-process mesh each process assembles only ITS addressable
        shards (``jax.make_array_from_single_device_arrays``) so no
        process ever reads — or holds — a full replica.

        Returns ``(handle, stats)`` with stats =
        {blocks_total, blocks_needed, blocks_local, blocks_read,
        bytes_read}. Sparse and sampled checkpoints fall back to the
        full restore (stats marks ``partial: 0``)."""
        with trace_span("checkpoint.restore_partial", chkp_id=chkp_id) as sp:
            handle, stats = self._restore_partial_inner(
                master, chkp_id, associators, data_axis, table_id)
            if sp is not None:
                for k, v in stats.items():
                    sp.annotate(k, v)
            # bytes_read is -1 on the sparse/sampled full-restore
            # fallback (unknown here; the inner restore accounted it)
            self._account_bytes("chkp_read", stats.get("bytes_read", 0),
                                handle.table_id)
            return handle, stats

    def _restore_partial_inner(
        self,
        master: ETMaster,
        chkp_id: str,
        associators: Sequence[str],
        data_axis: int = 1,
        table_id: Optional[str] = None,
    ) -> "Tuple[TableHandle, Dict[str, int]]":
        from harmony_tpu.parallel.mesh import mesh_spans_processes
        from harmony_tpu.table.blockmove import axis0_bounds

        d = self._dir_of(chkp_id)
        info = self._load_manifest(d)
        cfg = info.table_config
        if table_id is not None:
            cfg = cfg.replace(table_id=table_id)
        if cfg.sparse or info.sampling_ratio < 1.0:
            handle = self.restore(master, chkp_id, associators, data_axis,
                                  table_id)
            nb = len(info.block_ids)
            return handle, {"partial": 0, "blocks_total": nb,
                            "blocks_needed": nb, "blocks_local": 0,
                            "blocks_read": nb, "bytes_read": -1}
        local = recovery_blocks(chkp_id) or {}
        handle = master.create_table(cfg, associators, data_axis)
        pool = None
        try:
            arr_shape = handle.table.array.shape
            sharding = handle.table.sharding
            spans = mesh_spans_processes(handle.table.mesh)
            needed: set = set()
            for _dev, idx in sharding.addressable_devices_indices_map(
                    arr_shape).items():
                start, stop = axis0_bounds(idx, arr_shape[0])
                needed.update(range(start, stop))
            crcs = info.block_checksums or {}
            policy = RetryPolicy.from_env()
            stats = {"partial": 1, "blocks_total": len(info.block_ids),
                     "blocks_needed": len(needed), "blocks_local": 0,
                     "blocks_read": 0, "bytes_read": 0}

            def read_one(bid: int) -> np.ndarray:
                if faults.armed():
                    faults.site("chkp.partial_read", block=bid,
                                chkp_id=chkp_id)
                t0 = time.monotonic()
                arr = _read_block(d, bid, expected_crc=crcs.get(str(bid)),
                                  policy=policy)
                _observe_io("partial_read", time.monotonic() - t0)
                if arr.shape[0] < handle.table.spec.block_size:
                    raise CheckpointCorruptError(
                        f"partial restore of {chkp_id}: block {bid} is "
                        f"short ({arr.shape[0]} rows) in a full-ratio "
                        "checkpoint"
                    )
                return arr

            # lost blocks stream off the I/O pool while cached blocks —
            # and, below, per-shard device_put staging — proceed on this
            # thread: lost-block recovery is pipeline latency, not
            # sum-of-latencies
            to_read = sorted(b for b in needed if b not in local)
            futures: Dict[int, Any] = {}
            if to_read and _chkp_io_threads() > 1:
                from concurrent.futures import ThreadPoolExecutor

                pool = ThreadPoolExecutor(
                    max_workers=min(_chkp_io_threads(), len(to_read)),
                    thread_name_prefix="chkp-io")
                futures = {bid: pool.submit(read_one, bid)
                           for bid in to_read}

            resolved: Dict[int, np.ndarray] = {}

            def fetch(bid: int) -> np.ndarray:
                got = resolved.get(bid)
                if got is not None:
                    return got
                cached = local.get(bid)
                if cached is not None:
                    arr = cached
                    stats["blocks_local"] += 1
                else:
                    fut = futures.get(bid)
                    arr = fut.result() if fut is not None else read_one(bid)
                    stats["blocks_read"] += 1
                    stats["bytes_read"] += int(arr.nbytes)
                resolved[bid] = arr
                return arr

            if not spans:
                # chunked install in block-id order: device staging of a
                # finished chunk overlaps the still-outstanding reads
                chunk: Dict[int, np.ndarray] = {}
                for bid in sorted(needed):
                    chunk[bid] = fetch(bid)
                    if pool is not None and \
                            len(chunk) >= _RESTORE_CHUNK_BLOCKS:
                        handle.table.import_blocks(chunk)
                        chunk = {}
                handle.table.import_blocks(chunk)
            else:
                # per-process shard assembly: this process provides only
                # its addressable shards; peers provide theirs — the one
                # construction multi-controller jax allows without every
                # process holding (or reading) the whole table
                import jax as _jax

                dtype = handle.table.array.dtype
                shards, devs = [], []
                for dev, idx in sharding.addressable_devices_indices_map(
                        arr_shape).items():
                    start, stop = axis0_bounds(idx, arr_shape[0])
                    stacked = np.stack(
                        [np.asarray(fetch(i)) for i in range(start, stop)]
                    ).astype(dtype, copy=False)
                    shards.append(_jax.device_put(stacked, dev))
                    devs.append(dev)
                new_arr = _jax.make_array_from_single_device_arrays(
                    arr_shape, sharding, shards
                )
                handle.table.install_array(new_arr)
        except BaseException:
            handle.drop()  # no half-restored orphan tables
            raise
        finally:
            if pool is not None:
                pool.shutdown(wait=True, cancel_futures=True)
        return handle, stats

    def delete(self, chkp_id: str) -> None:
        """Remove every copy (a crashed commit can leave the checkpoint in
        both the temp and durable roots — delete both). Existence is checked
        via ``backend.exists`` — NOT ``_dir_of``, whose fetch() would
        download a remote checkpoint in full just to delete it."""
        temp = os.path.join(self.temp_root, chkp_id)
        if not self._backend.exists(chkp_id) and not os.path.isdir(temp):
            raise FileNotFoundError(f"checkpoint {chkp_id} not found")
        self._backend.delete(chkp_id)
        if os.path.isdir(temp):
            shutil.rmtree(temp)
