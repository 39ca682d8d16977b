"""Device-side profiling hooks (the xprof / jax-profiler integration).

SURVEY.md §5.9 maps the reference's HTrace wiring to "native profiler hooks
(xprof/jax profiler) + spans" on TPU. This module is that bridge:

  * naming a HOST region in a captured profile is ``trace_span``'s own
    job (tracing/span.py opens the ``jax.profiler.TraceAnnotation``): one
    call sites both worlds, and there is no second entry point here;
  * naming what the DEVICE did inside a step is ``step_scope``'s
    (tracing/stepscopes.py): a capture carries every executed module's HLO
    with the scopes in its ``/host:metadata`` plane, and
    ``stepscopes.reduce_file`` / ``harmony-tpu obs scopes <capture>``
    read the device's time by scope from it — a sampled capture included;
  * ``profile_session(logdir)`` — capture a full device trace
    (jax.profiler.start_trace/stop_trace) around a code region; the
    resulting xplane dump is the TPU analogue of a Zipkin trace for kernels;
  * ``maybe_profile_epoch(epoch, ...)`` — SAMPLED continuous capture:
    with ``HARMONY_PROFILE_EVERY_N`` set, every Nth epoch records a
    device profile under ``HARMONY_PROFILE_DIR`` with the directory
    rotated to ``HARMONY_PROFILE_MAX_BYTES`` (oldest captures deleted
    first, the ``HARMONY_TRACE_MAX_BYTES`` shape) — so when an incident
    lands there is a recent device profile on disk WITHOUT an operator
    having attached anything (docs/DEPLOY.md §7).

Everything degrades to host-span-only when the profiler is unavailable
(CPU test runs, ancient jax) — tracing never becomes a hard dependency
of the hot path.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
from typing import Iterator, Optional

from harmony_tpu.tracing.span import trace_span

ENV_EVERY_N = "HARMONY_PROFILE_EVERY_N"
ENV_DIR = "HARMONY_PROFILE_DIR"
ENV_MAX_BYTES = "HARMONY_PROFILE_MAX_BYTES"
_DEFAULT_MAX_BYTES = 256 * 1024 * 1024


@contextlib.contextmanager
def profile_session(logdir: str) -> Iterator[None]:
    """Capture a device trace into ``logdir`` (view with xprof/tensorboard).

    Swallows double-start errors so an outer session wins — mirroring how
    the reference tolerates span-receiver re-wiring per process.
    """
    started = False
    try:
        import jax.profiler

        jax.profiler.start_trace(logdir)
        started = True
    except Exception:
        pass
    try:
        with trace_span("profile_session", logdir=logdir):
            yield
    finally:
        if started:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
            except Exception:
                pass


# -- sampled continuous capture (HARMONY_PROFILE_EVERY_N) -------------------


def profile_every_n() -> int:
    """The sampling period in epochs; 0 = continuous capture off (the
    default — a capture is real overhead and real disk)."""
    try:
        return max(0, int(os.environ.get(ENV_EVERY_N, "0") or 0))
    except ValueError:
        return 0


def _profile_dir() -> str:
    return os.environ.get(ENV_DIR) or os.path.join(
        tempfile.gettempdir(), "harmony-profiles")


def _profile_max_bytes() -> int:
    try:
        return max(1, int(os.environ.get(ENV_MAX_BYTES,
                                         str(_DEFAULT_MAX_BYTES))))
    except ValueError:
        return _DEFAULT_MAX_BYTES


def _tree_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, names in os.walk(path):
        for n in names:
            try:
                total += os.path.getsize(os.path.join(dirpath, n))
            except OSError:
                pass
    return total


def rotate_profile_dir(root: str,
                       max_bytes: Optional[int] = None) -> int:
    """Delete oldest capture entries under ``root`` until the tree fits
    ``max_bytes``; the NEWEST entry always survives (a cap smaller than
    one capture must still leave the capture an operator just paid
    for). Returns the number of entries removed. Same bounded-retention
    contract as HARMONY_TRACE_MAX_BYTES — an unattended sampler must
    never eat the disk."""
    import shutil

    cap = max_bytes if max_bytes is not None else _profile_max_bytes()
    try:
        entries = sorted(
            (os.path.join(root, n) for n in os.listdir(root)),
            key=lambda p: os.path.getmtime(p),
        )
    except OSError:
        return 0
    removed = 0
    while len(entries) > 1 and _tree_bytes(root) > cap:
        victim = entries.pop(0)
        try:
            if os.path.isdir(victim):
                shutil.rmtree(victim, ignore_errors=True)
            else:
                os.remove(victim)
            removed += 1
        except OSError:
            break  # cannot make progress; leave the rest
    return removed


def newest_capture(root: Optional[str] = None,
                   pid: Optional[int] = None) -> Optional[str]:
    """Path of the NEWEST capture entry THIS process wrote under the
    profile dir, or None when the sampler never ran (or the dir is
    unreadable). STATUS and flight-recorder dumps surface this so the
    xplane dump an incident needs is one field away instead of an
    undiscovered file on disk.

    The default dir is shared across runs and processes, so entries
    are filtered to this process's captures (``maybe_profile_epoch``
    names them ``<job>-e<epoch>-<pid>``) — a STATUS reply must not
    point an incident responder at a week-old or foreign process's
    dump. ``pid`` overrides the writer pid to match; ``pid=0`` matches
    every capture."""
    root = root or _profile_dir()
    suffix = f"-{os.getpid() if pid is None else pid}"
    try:
        names = os.listdir(root)
    except OSError:
        return None
    newest, newest_m = None, -1.0
    for n in names:
        if pid != 0 and not n.endswith(suffix):
            continue
        p = os.path.join(root, n)
        try:
            m = os.path.getmtime(p)
        except OSError:
            continue
        if m > newest_m:
            newest, newest_m = p, m
    return newest


@contextlib.contextmanager
def maybe_profile_epoch(epoch: int, job_id: str = "",
                        span: int = 1,
                        enabled: bool = True) -> Iterator[None]:
    """Capture a device profile around this epoch (or an epoch WINDOW of
    ``span`` epochs — sampled if ANY epoch in it matches the period) when
    the sampler knob says so; a plain no-op otherwise. ``enabled=False``
    lets multi-worker jobs make the capture chief-only. Capture failure
    never fails the epoch (profile_session swallows), and the logdir is
    rotated to the byte cap AFTER each capture."""
    n = profile_every_n()
    if (not enabled or n <= 0
            or not any((e % n) == 0
                       for e in range(epoch, epoch + max(span, 1)))):
        yield
        return
    safe = "".join(c if c.isalnum() or c in "-_." else "_"
                   for c in str(job_id) or "job")[:60]
    root = _profile_dir()
    logdir = os.path.join(
        root, f"{safe or 'job'}-e{epoch}-{os.getpid()}")
    try:
        os.makedirs(logdir, exist_ok=True)
    except OSError:
        yield  # unwritable profile dir: train on, capture nothing
        return
    with profile_session(logdir):
        yield
    rotate_profile_dir(root)
