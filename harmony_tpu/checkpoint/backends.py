"""Pluggable durable-commit backends for two-stage checkpointing.

The reference's stage-2 commit moves per-block temp files into HDFS
(ref: services/et/.../evaluator/impl/ChkpManagerSlave.java:50-63); the
durable store is a deployment choice, not part of the protocol. Here the
commit stage is an SPI so the same CheckpointManager drives:

  * :class:`PosixCommitBackend` — durable directory on a mounted
    filesystem (local disk, NFS, a FUSE-mounted bucket). Atomic same-FS
    rename commit; the default, and the only backend tests need.
  * :class:`OrbaxCommitBackend` — the checkpoint is committed as ONE
    Orbax/tensorstore checkpoint at any path orbax can write, including
    ``gs://`` object-store URLs on TPU pods (SURVEY.md §5.9.4's
    GCS/tensorstore prescription). Fetch materializes blocks back into a
    local cache dir so the restore path stays identical.

Backends store the staged checkpoint directory (block files + a
``manifest.json`` whose ``committed`` flag they flip to True) under the
checkpoint id, and hand back a local directory on fetch.
"""
from __future__ import annotations

import json
import os
import shutil
from typing import List, Optional

import numpy as np

from harmony_tpu import faults
from harmony_tpu.faults.retry import InfraTransientError

#: sentinel prefix tagging the isolated worker's PROTOCOL lines on stdout
#: — any library the child imports may print (absl, orbax deprecation
#: notices), and an untagged line must be skipped, never parsed as a
#: response (the stale-response misattribution bug, advisor round 5)
_PROTO_PREFIX = "@harmony-chkp@ "

# Process-wide respawn counter ACROSS backend instances: each manager
# (and each elastic recovery attempt) constructs its own backend, so the
# per-instance ``iso_respawns`` alone would undercount on the metrics
# surface (MetricManager.fault_counters folds this in).
import threading as _threading  # noqa: E402 - counter lock only

_ISO_RESPAWNS = 0
_ISO_LOCK = _threading.Lock()


def _count_iso_respawn() -> None:
    global _ISO_RESPAWNS
    with _ISO_LOCK:
        _ISO_RESPAWNS += 1
    try:  # mirrored onto the /metrics registry (never fails supervision)
        from harmony_tpu.metrics.registry import get_registry

        get_registry().counter(
            "harmony_chkp_iso_respawns_total",
            "Supervision-forced isolated orbax-worker respawns",
        ).inc()
    except Exception:
        pass


def iso_respawn_total() -> int:
    """Supervision-forced isolated-worker respawns in THIS process, all
    backend instances summed (the fault-counters surface)."""
    return _ISO_RESPAWNS


class IsolatedWorkerError(InfraTransientError):
    """The isolated orbax worker died, wedged past its deadline, or
    desynchronized its protocol stream — after the in-flight op was
    already retried once on a fresh worker. ``infra_suspect``: the
    helper process failed, not the checkpoint's own content."""


def quarantine_dir(path: str) -> None:
    """Move a damaged checkpoint directory aside as ``<path>.quarantined``
    (out of every listing/scan, evidence preserved). Idempotent and
    race-tolerant: pod peers on a shared FS may quarantine concurrently."""
    if not os.path.isdir(path):
        return
    q = path + ".quarantined"
    if os.path.isdir(q):
        shutil.rmtree(q, ignore_errors=True)  # a reused id's older one
    try:
        os.rename(path, q)
    except FileNotFoundError:
        pass  # a pod peer on the shared FS quarantined it first


def _iso_deadline() -> float:
    """Bound on ONE isolated-worker exchange (request write -> response
    line) against a WARM worker. Finite on purpose: a wedged worker must
    be detected, killed, and respawned instead of hanging the pod's
    checkpoint chain forever."""
    return float(os.environ.get("HARMONY_CHKP_ISO_TIMEOUT", "120"))


def _iso_spawn_grace() -> float:
    """Extra allowance added to the exchange deadline when the worker was
    freshly spawned for it: a cold worker pays the jax+orbax import
    before it can even read the request, and that cost must not be
    misread as a wedge (it would kill/respawn in a loop forever)."""
    return float(os.environ.get("HARMONY_CHKP_ISO_SPAWN_GRACE", "60"))


def _iso_max_op() -> float:
    """HARD ceiling on one isolated-worker op, keepalives included. The
    keepalive beat proves the worker process is alive, not that the op
    inside it progresses — a save wedged on a dead NFS mount beats
    forever — so silence-extension is bounded by this cap: legitimately
    long saves get an hour by default, true op-level wedges are still
    detected, killed, and respawned."""
    return float(os.environ.get("HARMONY_CHKP_ISO_MAX_OP", "3600"))


class CommitBackend:
    """SPI: durable storage for committed checkpoints."""

    def exists(self, chkp_id: str) -> bool:
        raise NotImplementedError

    def commit(self, chkp_id: str, src_dir: str) -> None:
        """Persist ``src_dir`` (blocks + manifest.json) durably under
        ``chkp_id``, with the stored manifest's ``committed`` flag True.
        Must be atomic: a crash mid-commit must leave the id unresolvable,
        never resolvable-but-partial."""
        raise NotImplementedError

    def fetch(self, chkp_id: str) -> Optional[str]:
        """Local directory holding the committed checkpoint's files, or
        None if the id is not committed here."""
        raise NotImplementedError

    def fetch_manifest(self, chkp_id: str) -> Optional[str]:
        """The stored manifest.json text WITHOUT materializing block data
        (info()/listing must not download a multi-GB checkpoint to read
        metadata). Default falls back to a full fetch."""
        d = self.fetch(chkp_id)
        if d is None:
            return None
        with open(os.path.join(d, "manifest.json")) as f:
            return f.read()

    def delete(self, chkp_id: str) -> None:
        raise NotImplementedError

    def quarantine(self, chkp_id: str) -> None:
        """Remove a DAMAGED checkpoint from the restorable namespace.
        Stores that can rename keep the bytes for post-mortem (posix);
        the default deletes — object-store rename is a full copy, and a
        corrupt checkpoint must never stay listable either way."""
        if self.exists(chkp_id):
            self.delete(chkp_id)

    def list_ids(self) -> List[str]:
        raise NotImplementedError


class PosixCommitBackend(CommitBackend):
    """Durable directory + atomic rename (the original commit path)."""

    def __init__(self, root: str) -> None:
        self.root = root
        os.makedirs(root, exist_ok=True)

    def exists(self, chkp_id: str) -> bool:
        return os.path.isdir(os.path.join(self.root, chkp_id))

    def commit(self, chkp_id: str, src_dir: str) -> None:
        # Crash-safe across filesystems: copy into a .staging dir INSIDE
        # the durable root, then rename into place (same-FS rename =
        # atomic). A crash mid-copy leaves only a .staging orphan.
        dst = os.path.join(self.root, chkp_id)
        staging = dst + ".staging"
        if os.path.isdir(staging):
            shutil.rmtree(staging)  # leftover from a crashed commit
        shutil.copytree(src_dir, staging)
        manifest = os.path.join(staging, "manifest.json")
        with open(manifest) as f:
            info = json.load(f)
        info["committed"] = True
        with open(manifest, "w") as f:
            json.dump(info, f, sort_keys=True)
        os.rename(staging, dst)

    def fetch(self, chkp_id: str) -> Optional[str]:
        d = os.path.join(self.root, chkp_id)
        return d if os.path.isdir(d) else None

    def delete(self, chkp_id: str) -> None:
        d = os.path.join(self.root, chkp_id)
        if os.path.isdir(d):
            shutil.rmtree(d)

    def quarantine(self, chkp_id: str) -> None:
        quarantine_dir(os.path.join(self.root, chkp_id))

    def list_ids(self) -> List[str]:
        return sorted(
            d for d in os.listdir(self.root)
            if not d.endswith(".staging") and not d.endswith(".writing")
            and not d.endswith(".quarantined")
            and os.path.isdir(os.path.join(self.root, d))
        )


class OrbaxCommitBackend(CommitBackend):
    """Commit to an Orbax/tensorstore location (object stores included).

    Layout per checkpoint: one PyTree checkpoint at ``<root>/<chkp_id>``
    holding ``{"manifest": <json str>, "blocks": {"<bid>": uint8 bytes}}``
    — blocks travel as the exact bytes of their staged files, so the CRC
    trailer of ``.blk``-coded blocks survives the round trip and torn
    objects still fail loudly at restore. Orbax's own finalize step makes
    the object-store write atomic (a crashed save never lists).

    MULTI-PROCESS runtimes: orbax's save/restore run cross-process
    barriers when ``jax.process_count() > 1`` — but this backend's
    commits are LEADER-ONLY (the pod checkpoint protocol's stage-2,
    ChkpManagerSlave.java:50-63), so an in-process orbax call would
    block forever waiting for followers that never call it. In that
    case save/restore are routed through ONE persistent isolated
    single-process worker (sanitized env, CPU platform) serving ops
    over a pipe: pure host file IO either side, and the interpreter +
    jax/orbax import cost is paid once per backend instance, not per
    commit — chain checkpoints at period=1 stay cheap (a per-commit
    subprocess pushed a pod auto-resume past the jax coordination
    service's peer-death kill window in testing).
    """

    def __init__(self, root: str, cache_root: Optional[str] = None) -> None:
        import threading

        self.root = root if _is_url(root) else os.path.abspath(root)
        self.cache_root = cache_root  # local materialization dir for fetch
        self._fetched: dict = {}
        self._iso_proc = None       # persistent isolated worker (lazy)
        self._iso_lock = threading.Lock()  # serializes its pipe exchanges
        self._iso_queue = None      # stdout lines (reader thread -> ops)
        self._iso_stderr_path: Optional[str] = None
        self._iso_stderr_file = None
        #: respawns forced by supervision (deadline expiry / desync / death)
        #: — observability for tests and the fault counters
        self.iso_respawns = 0

    def _path(self, chkp_id: str) -> str:
        return (f"{self.root.rstrip('/')}/{chkp_id}" if _is_url(self.root)
                else os.path.join(self.root, chkp_id))

    @staticmethod
    def _checkpointer():
        import orbax.checkpoint as ocp

        return ocp.PyTreeCheckpointer()

    def exists(self, chkp_id: str) -> bool:
        path = self._path(chkp_id)
        if _is_url(path):
            try:
                self._checkpointer().metadata(path)
                return True
            except Exception:
                return False
        # a finalized orbax dir always carries its metadata file
        return os.path.isdir(path)

    @staticmethod
    def _in_multiprocess() -> bool:
        try:
            import jax

            return jax.process_count() > 1
        except Exception:  # pragma: no cover - jax not importable
            return False

    def _run_isolated(self, op: str, chkp_id: str, arg: str) -> None:
        """Run _commit_here/_fetch_here in the persistent isolated
        worker (see class docstring), (re)spawning it if absent/dead.
        The worker's env strips every TPU-claim and distributed-runtime
        var so its jax initializes as a plain CPU single process."""
        # one exchange at a time on the worker's pipe: concurrent commits
        # (async snapshot thread + a sync commit) would interleave writes
        # and misattribute the response lines
        with self._iso_lock:
            self._run_isolated_locked(op, chkp_id, arg)

    # -- worker supervision ----------------------------------------------
    #
    # The worker is a SUPERVISED child, not a trusted peer:
    #   * its stderr goes to a FILE, never a pipe — absl/jax/orbax logging
    #     over a long period=1 chain used to fill the 64KB pipe buffer,
    #     block the child on a write, and hang the parent's readline
    #     forever (a silent pod-wide checkpoint hang);
    #   * its stdout is drained by a dedicated reader thread into a queue,
    #     so every response wait is DEADLINE-BOUNDED (_iso_deadline);
    #   * protocol lines carry a sentinel prefix; unrecognized lines
    #     (library prints) are skipped, and a garbled TAGGED line is a
    #     protocol desync — the worker is killed, never re-read;
    #   * expiry/desync/death kill + respawn the worker and retry the
    #     in-flight op ONCE (commit/fetch are idempotent); a second
    #     failure surfaces as IsolatedWorkerError (infra_suspect), with
    #     the stderr file's tail in the message.

    def _spawn_isolated(self):
        import subprocess
        import sys
        import tempfile
        import threading

        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        # the worker is pure host file IO: pinned to the CPU backend (the
        # parent owns the accelerator) and outside the pod's distributed
        # runtime
        env = dict(os.environ)
        for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                    "JAX_PROCESS_ID"):
            env.pop(var, None)
        env["JAX_PLATFORMS"] = "cpu"
        if self._iso_stderr_path is None:
            base = self.cache_root or tempfile.gettempdir()
            os.makedirs(base, exist_ok=True)
            self._iso_stderr_path = os.path.join(
                base, f"harmony-orbax-iso-{os.getpid()}-{id(self):x}.stderr"
            )
        if self._iso_stderr_file is not None:
            try:
                self._iso_stderr_file.close()
            except OSError:
                pass
        # truncate per spawn: only the current incarnation's tail is ever
        # surfaced, and append mode would grow the file without bound on
        # a long-lived pod (period=1 chains log >64KB per chain — the
        # volume that motivated moving stderr off the pipe)
        self._iso_stderr_file = open(self._iso_stderr_path, "wb")
        code = ("import sys; sys.path.insert(0, sys.argv[1]); "
                "from harmony_tpu.checkpoint.backends import "
                "_orbax_isolated_serve; _orbax_isolated_serve()")
        proc = subprocess.Popen(
            [sys.executable, "-c", code, repo_root, self.root,
             self.cache_root or ""],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._iso_stderr_file, text=True, env=env,
        )
        self._iso_proc = proc
        import queue as _queue

        q: "_queue.Queue" = _queue.Queue()
        self._iso_queue = q

        def drain(stdout=proc.stdout, q=q):
            # EOF sentinel None tells the waiter the worker died; a fresh
            # queue per spawn means a stale thread can never feed a new
            # worker's waiter
            try:
                for line in stdout:
                    q.put(line)
            except (OSError, ValueError):
                pass
            q.put(None)

        threading.Thread(target=drain, daemon=True,
                         name="orbax-iso-stdout").start()
        return proc

    def _stderr_tail(self, n: int = 2000) -> str:
        if not self._iso_stderr_path:
            return ""
        try:
            if self._iso_stderr_file is not None:
                self._iso_stderr_file.flush()
            with open(self._iso_stderr_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def _kill_isolated(self) -> None:
        import subprocess

        proc, self._iso_proc = self._iso_proc, None
        self._iso_queue = None
        if proc is not None and proc.poll() is None:
            try:
                proc.kill()
                proc.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                # a SIGKILLed child stuck in uninterruptible IO reaps
                # later (or never); supervision must still classify this
                # as IsolatedWorkerError, not leak TimeoutExpired past
                # the retry contract
                pass
        if self._iso_stderr_file is not None:
            try:
                self._iso_stderr_file.close()
            except OSError:
                pass
            self._iso_stderr_file = None

    def _exchange_once(self, op: str, chkp_id: str, arg: str) -> dict:
        """One request/response on the live worker. Raises
        IsolatedWorkerError for every supervision failure (caller decides
        whether to retry); returns the parsed protocol response."""
        import time as _time

        proc = self._iso_proc
        fresh = proc is None or proc.poll() is not None
        if fresh:
            proc = self._spawn_isolated()
        q = self._iso_queue  # after the spawn: one queue per worker
        try:
            proc.stdin.write(json.dumps(
                {"op": op, "chkp_id": chkp_id, "arg": arg}) + "\n")
            proc.stdin.flush()
        except (OSError, ValueError) as e:
            self._kill_isolated()
            raise IsolatedWorkerError(
                f"isolated orbax worker died taking {op}: {e}\n"
                f"stderr tail:\n{self._stderr_tail()}") from e
        import queue as _queue

        start = _time.monotonic()
        deadline = (start + _iso_deadline()
                    + (_iso_spawn_grace() if fresh else 0.0))
        hard_deadline = start + _iso_max_op()
        while True:
            try:
                line = q.get(timeout=max(
                    0.0, min(deadline, hard_deadline) - _time.monotonic()))
            except _queue.Empty:
                self._kill_isolated()
                why = ("op ceiling" if _time.monotonic() >= hard_deadline
                       else "silence deadline")
                raise IsolatedWorkerError(
                    f"isolated orbax {op} exceeded its {why} "
                    f"({_iso_deadline():.0f}s silent / "
                    f"{_iso_max_op():.0f}s total); worker killed for "
                    f"respawn\nstderr tail:\n"
                    f"{self._stderr_tail()}") from None
            if line is None:  # EOF: the worker crashed mid-op
                self._kill_isolated()
                raise IsolatedWorkerError(
                    f"isolated orbax {op} crashed the worker\n"
                    f"stderr tail:\n{self._stderr_tail()}")
            if not line.startswith(_PROTO_PREFIX):
                continue  # library print on stdout: skip, never parse
            try:
                resp = json.loads(line[len(_PROTO_PREFIX):])
            except ValueError:
                # a TAGGED but unparseable line is a genuine protocol
                # desync: responses can no longer be attributed — kill
                # the worker so the next op starts from a clean stream
                self._kill_isolated()
                raise IsolatedWorkerError(
                    f"isolated orbax {op}: protocol desync "
                    f"(unparseable tagged line {line[:120]!r}); worker "
                    "killed") from None
            if resp.get("keepalive"):
                # the worker process is ALIVE inside a long op (multi-GB
                # save to slow storage): extend the SILENCE deadline —
                # but only up to the hard op ceiling, because a beat
                # proves the process lives, not that the op progresses
                # (an orbax save wedged on a dead mount beats forever).
                deadline = _time.monotonic() + _iso_deadline()
                continue
            return resp

    def _run_isolated_locked(self, op: str, chkp_id: str, arg: str) -> None:
        last: Optional[BaseException] = None
        for attempt in range(2):
            try:
                resp = self._exchange_once(op, chkp_id, arg)
            except IsolatedWorkerError as e:
                # supervision failure: the op never completed (commit and
                # fetch are idempotent) — retry ONCE on a fresh worker
                if attempt == 0:
                    self.iso_respawns += 1
                    _count_iso_respawn()
                last = e
                faults.site("chkp.iso.supervise", op=op, attempt=attempt)
                continue
            if not resp.get("ok"):
                # child-REPORTED failure: often deterministic (bad path,
                # missing id) but also how a transient storage blip (an
                # object-store 503 inside the child's save) surfaces —
                # retry ONCE (idempotent ops, cheap round-trip), then
                # raise plainly: we cannot tell the two apart, and a
                # false infra_suspect would trigger pointless auto-resume
                # churn on genuinely deterministic errors
                last = RuntimeError(
                    f"isolated orbax {op} failed: {resp.get('error')}")
                continue
            return
        raise last  # type: ignore[misc]

    def commit(self, chkp_id: str, src_dir: str) -> None:
        if self._in_multiprocess():
            self._run_isolated("commit", chkp_id, src_dir)
            return
        self._commit_here(chkp_id, src_dir)

    def _commit_here(self, chkp_id: str, src_dir: str) -> None:
        with open(os.path.join(src_dir, "manifest.json")) as f:
            info = json.load(f)
        info["committed"] = True
        blocks = {}
        for name in os.listdir(src_dir):
            if name == "manifest.json":
                continue
            with open(os.path.join(src_dir, name), "rb") as f:
                blocks[name] = np.frombuffer(f.read(), np.uint8)
        tree = {"manifest": json.dumps(info, sort_keys=True), "blocks": blocks}
        self._checkpointer().save(self._path(chkp_id), tree)
        # Manifest sidecar: a small sibling object so info()/retention scans
        # read metadata without restoring the block tree. Written AFTER the
        # finalized save — a crash in between leaves the checkpoint fully
        # usable (fetch_manifest falls back to the full fetch).
        self._write_text(self._path(chkp_id) + ".manifest.json",
                         json.dumps(info, sort_keys=True))

    @staticmethod
    def _write_text(path: str, text: str) -> None:
        if _is_url(path):  # pragma: no cover - needs a live object store
            from etils import epath

            epath.Path(path).write_text(text)  # object writes are atomic
        else:
            # temp + rename: a crash mid-write must not leave a torn
            # sidecar shadowing a fully valid checkpoint
            tmp = path + ".writing"
            with open(tmp, "w") as f:
                f.write(text)
            os.replace(tmp, path)

    def fetch_manifest(self, chkp_id: str) -> Optional[str]:
        if not self.exists(chkp_id):
            return None
        side = self._path(chkp_id) + ".manifest.json"
        text = None
        if _is_url(side):  # pragma: no cover - needs a live object store
            from etils import epath

            p = epath.Path(side)
            if p.exists():
                text = p.read_text()
        elif os.path.exists(side):
            with open(side) as f:
                text = f.read()
        if text is not None:
            try:
                json.loads(text)
                return text
            except ValueError:
                pass  # torn sidecar: fall through to the full fetch
        return super().fetch_manifest(chkp_id)  # absent/torn sidecar

    def _fetch_dir(self, chkp_id: str) -> str:
        base = self.cache_root or os.path.join(
            os.path.expanduser("~"), ".cache", "harmony_tpu", "chkp-fetch"
        )
        return os.path.join(base, chkp_id)

    def fetch(self, chkp_id: str) -> Optional[str]:
        cached = self._fetched.get(chkp_id)
        if cached and os.path.isdir(cached):
            return cached
        if not self.exists(chkp_id):
            return None
        if self._in_multiprocess():
            # the child materializes into the SAME deterministic cache dir
            # both sides compute (isolation rationale: class docstring)
            self._run_isolated("fetch", chkp_id, "")
            d = self._fetch_dir(chkp_id)
            if not os.path.isdir(d):
                raise RuntimeError(
                    f"isolated orbax fetch produced no dir at {d}")
            self._fetched[chkp_id] = d
            return d
        return self._fetch_here(chkp_id)

    def _fetch_here(self, chkp_id: str) -> Optional[str]:
        tree = self._checkpointer().restore(self._path(chkp_id))
        d = self._fetch_dir(chkp_id)
        staging = d + ".writing"
        os.makedirs(staging, exist_ok=True)
        try:
            for name, data in tree["blocks"].items():
                with open(os.path.join(staging, name), "wb") as f:
                    f.write(np.asarray(data, np.uint8).tobytes())
            with open(os.path.join(staging, "manifest.json"), "w") as f:
                f.write(tree["manifest"])
            if os.path.isdir(d):
                shutil.rmtree(d)
            os.rename(staging, d)
        except BaseException:
            shutil.rmtree(staging, ignore_errors=True)
            raise
        self._fetched[chkp_id] = d
        return d

    def delete(self, chkp_id: str) -> None:
        cached = self._fetched.pop(chkp_id, None)
        if cached and os.path.isdir(cached):
            shutil.rmtree(cached)
        path = self._path(chkp_id)
        side = path + ".manifest.json"
        if not _is_url(path):
            if os.path.isdir(path):
                shutil.rmtree(path)
            if os.path.exists(side):
                os.remove(side)
        else:  # pragma: no cover - needs a live object store
            from etils import epath

            epath.Path(path).rmtree()
            sp = epath.Path(side)
            if sp.exists():
                sp.unlink()

    def list_ids(self) -> List[str]:
        # filter orbax's in-flight temp dirs (".orbax-checkpoint-tmp"
        # siblings of a crashed/in-progress save) — same reason the posix
        # backend filters ".staging"/".writing": an unfinished commit must
        # never surface as a restorable id
        if _is_url(self.root):  # pragma: no cover - needs a live object store
            from etils import epath

            return sorted(p.name for p in epath.Path(self.root).iterdir()
                          if ".orbax-checkpoint-tmp" not in p.name
                          and not p.name.endswith(".manifest.json"))
        if not os.path.isdir(self.root):
            return []
        return sorted(
            d for d in os.listdir(self.root)
            if os.path.isdir(os.path.join(self.root, d))
            and ".orbax-checkpoint-tmp" not in d
        )


def _is_url(path: str) -> bool:
    return "://" in path


def _orbax_isolated_serve() -> None:
    """Persistent child for OrbaxCommitBackend._run_isolated: argv =
    [repo_root(consumed), root, cache_root]; serves JSON-line ops
    {"op": commit|fetch, "chkp_id", "arg"} on stdin until EOF.
    Responses are tagged with the protocol sentinel so the parent can
    tell them from library prints on stdout; stderr is a parent-owned
    FILE, so logging however verbose can never block this process on a
    full pipe. While an op is being handled a keepalive beat ticks on
    stdout, so the parent's deadline bounds SILENCE (a wedge), never the
    duration of a legitimately long save. Fault sites ("chkp.iso.serve")
    arm from the inherited HARMONY_FAULT_PLAN env, so supervision tests
    can wedge/crash/flood a REAL worker deterministically."""
    import sys
    import threading

    root, cache_root = sys.argv[2:4]
    b = OrbaxCommitBackend(root, cache_root or None)
    out_lock = threading.Lock()  # beat + response lines must not interleave

    def emit(text: str) -> None:
        with out_lock:
            sys.stdout.write(_PROTO_PREFIX + text + "\n")
            sys.stdout.flush()

    for line in sys.stdin:
        req = json.loads(line)
        stop_beat = threading.Event()

        def beat(stop=stop_beat) -> None:
            while not stop.wait(10.0):
                emit(json.dumps({"keepalive": True}))

        beat_thread = threading.Thread(target=beat, daemon=True)
        try:
            # fault site BEFORE the beat starts: an injected wedge must
            # look like a real one (silent), not a long healthy op
            action = None
            if faults.armed():
                action = faults.site("chkp.iso.serve", op=req.get("op"),
                                     chkp_id=req.get("chkp_id"))
            if action == "corrupt":
                # protocol-desync injection: a TAGGED but garbled line
                emit("not json at all")
                continue
            beat_thread.start()
            if req["op"] == "commit":
                b._commit_here(req["chkp_id"], req["arg"])
            elif req["op"] == "fetch":
                if b._fetch_here(req["chkp_id"]) is None:
                    raise RuntimeError(
                        f"no committed checkpoint {req['chkp_id']}")
            else:
                raise RuntimeError(f"unknown op {req['op']}")
            resp = {"ok": True}
        except Exception as e:  # noqa: BLE001 - reported to the parent
            resp = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        finally:
            stop_beat.set()
            if beat_thread.is_alive():
                beat_thread.join(timeout=15.0)
        emit(json.dumps(resp))


def make_commit_backend(commit_root: str, backend=None) -> CommitBackend:
    """Resolve the commit stage: an explicit CommitBackend instance, the
    names "posix"/"orbax", or by inspection of ``commit_root`` (object-store
    URLs need tensorstore, so they get the orbax backend)."""
    if isinstance(backend, CommitBackend):
        return backend
    if backend == "orbax" or (backend is None and _is_url(commit_root)):
        return OrbaxCommitBackend(commit_root)
    if backend in (None, "posix"):
        return PosixCommitBackend(commit_root)
    raise ValueError(f"unknown commit backend {backend!r}")
