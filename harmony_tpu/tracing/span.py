"""Distributed tracing spans — the HTrace-equivalent.

Parity with the reference's tracing wiring (SURVEY.md §5.1): HTrace 3.0.4
gives Harmony (a) process-wide SpanReceiver selection (utils/trace/
HTrace.java:30-56 + ReceiverConstructor: Zipkin or local-file), (b) span
creation around interesting operations, and (c) parent-span propagation
across process boundaries via avro-encoded TraceInfo
(HTraceInfoCodec/HTraceUtils, utils/src/main/avro/traceinfo.avsc).

Rebuilt here dependency-free:

  * ``Span`` — id, parent id, trace id, description, wall-clock start/stop,
    key-value annotations;
  * ``SpanReceiver`` SPI with ``InMemorySpanReceiver`` (tests/inspection)
    and ``LocalFileSpanReceiver`` (JSON-lines file — the local-file receiver
    analogue; Zipkin's wire model is the same shape, so an exporter is a
    receiver away);
  * ``trace_span`` context manager maintaining the current span in a
    contextvar (threads/asyncio safe — the analogue of HTrace's
    thread-local trace scope);
  * ``SpanContext.to_wire()/from_wire()`` — the TraceInfo codec analogue:
    a compact dict carried inside control-plane messages so master↔worker
    protocol spans keep their parents across the jobserver's TCP boundary.

One instrument, two sinks, one clock (docs/OBSERVABILITY.md §1):

  * every ``trace_span`` also opens a ``jax.profiler.TraceAnnotation`` named
    ``harmony/<description>`` — a flag test with no profiler session, and
    with one an event on the calling thread's line of the trace's
    ``/host:CPU`` plane, on the same clock as the ``/device:TPU:<n>``
    planes, so a device idle gap can be named after what the host did;
  * start/stop are taken on ``time.monotonic_ns()``; ``start_sec`` /
    ``stop_sec`` are wall seconds derived from one per-process anchor, so
    durations never depend on NTP while the wire format keeps its shape;
  * ``record=False`` is the LIGHT form for per-step regions: annotation,
    open/longest tracking and the ``acc`` callback, but no ``Span``, no ids
    and nothing emitted to receivers;
  * every open span (either form) is visible to ``open_spans()`` and the
    longest closed one per description to ``longest_spans()`` — what the
    flight recorder shows so a stall names itself.

``profile_session`` and the sampled capture are in tracing/profiler.py.
"""
from __future__ import annotations

import contextvars
import json
import os
import random
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

#: one fixed prefix on the profiler's side, so a reader of the xplane tells
#: the program's spans from PJRT's own names
ANNOTATION_PREFIX = "harmony/"

#: the per-process anchor: wall seconds are derived from the monotonic
#: clock through it, never read again
_ANCHOR_NS = time.monotonic_ns()
_ANCHOR_WALL = time.time()


def wall_sec(ns: int) -> float:
    """Wall-clock seconds of a ``time.monotonic_ns()`` reading."""
    return _ANCHOR_WALL + (ns - _ANCHOR_NS) * 1e-9


class Span:
    """One recorded region. ``trace_id`` / ``span_id`` / ``parent_id`` are
    made on first read: a span nobody receives and nobody asks the wire
    context of never pays for them."""

    __slots__ = ("_trace_id", "_span_id", "_parent_id", "_parent_span",
                 "description", "start_sec", "stop_sec", "annotations",
                 "process_id", "start_ns", "stop_ns", "job", "_discarded")

    def __init__(self, trace_id: Optional[str] = None,
                 span_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 description: str = "",
                 start_sec: Optional[float] = None,
                 stop_sec: Optional[float] = None,
                 annotations: Optional[Dict[str, Any]] = None,
                 process_id: str = "", *,
                 start_ns: Optional[int] = None,
                 parent_span: "Optional[Span]" = None) -> None:
        self._trace_id = trace_id
        self._span_id = span_id
        self._parent_id = parent_id
        self._parent_span = parent_span
        self.description = description
        self.start_ns = start_ns
        self.stop_ns: Optional[int] = None
        self.start_sec = (wall_sec(start_ns) if start_sec is None
                          and start_ns is not None else start_sec)
        self.stop_sec = stop_sec
        self.annotations = {} if annotations is None else annotations
        self.process_id = process_id
        #: the job this span belongs to — its own ``job_id`` annotation,
        #: else its parent's (what the compile counters label by)
        self.job = (self.annotations.get("job_id")
                    or (parent_span.job if parent_span is not None else None))
        self._discarded = False

    @property
    def trace_id(self) -> str:
        if self._trace_id is None:
            self._trace_id = (self._parent_span.trace_id
                              if self._parent_span is not None else _new_id())
        return self._trace_id

    @property
    def span_id(self) -> str:
        if self._span_id is None:
            self._span_id = _new_id()
        return self._span_id

    @property
    def parent_id(self) -> Optional[str]:
        if self._parent_id is None and self._parent_span is not None:
            self._parent_id = self._parent_span.span_id
        return self._parent_id

    @property
    def duration_sec(self) -> float:
        if self.start_ns is not None:
            stop = self.stop_ns if self.stop_ns is not None \
                else time.monotonic_ns()
            return (stop - self.start_ns) * 1e-9
        return (self.stop_sec or time.time()) - (self.start_sec or 0.0)

    def annotate(self, key: str, value: Any) -> None:
        self.annotations[key] = value

    def discard(self) -> None:
        """Mark the span to be dropped at context exit (e.g. the work it
        covers turned out not to have happened — an aborted epoch)."""
        self._discarded = True

    def _close(self, stop_ns: int) -> None:
        self.stop_ns = stop_ns
        self.stop_sec = wall_sec(stop_ns)

    def to_dict(self) -> Dict[str, Any]:
        """The wire / receiver shape (unchanged since the dataclass)."""
        out = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "description": self.description,
            "start_sec": self.start_sec,
            "stop_sec": self.stop_sec,
            "annotations": dict(self.annotations),
            "process_id": self.process_id,
        }
        self._parent_span = None  # ids are resolved; let the parent go
        return out


class SpanContext:
    """What crosses a process/message boundary (ref: TraceInfo avro record:
    traceId + spanId are enough to re-parent remote child spans)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str) -> None:
        self.trace_id = trace_id
        self.span_id = span_id

    def to_wire(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @staticmethod
    def from_wire(wire: Optional[Dict[str, str]]) -> Optional["SpanContext"]:
        if not wire:
            return None
        return SpanContext(wire["trace_id"], wire["span_id"])


class SpanReceiver:
    """SPI (ref: HTrace SpanReceiver picked by HTraceParameters)."""

    def receive(self, span: Span) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class InMemorySpanReceiver(SpanReceiver):
    def __init__(self) -> None:
        self.spans: List[Span] = []
        self._lock = threading.Lock()

    def receive(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    def by_description(self, desc: str) -> List[Span]:
        with self._lock:
            return [s for s in self.spans if s.description == desc]


class LocalFileSpanReceiver(SpanReceiver):
    """JSON-lines span log (ref: the HTrace local-file receiver option).

    Lifecycle hardening: ``close`` is registered with :mod:`atexit`, so a
    short-lived follower/worker process that never reaches an orderly
    ``Tracing.close()`` still flushes its tail spans instead of silently
    dropping them; and the file ROTATES at ``max_bytes`` (keeping one
    ``<path>.1`` predecessor) so a long-lived jobserver's span log stays
    bounded instead of growing without limit. ``max_bytes=0`` disables
    rotation; the default comes from ``HARMONY_TRACE_MAX_BYTES``
    (64 MiB)."""

    def __init__(self, path: str, max_bytes: Optional[int] = None) -> None:
        import atexit

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        if max_bytes is None:
            try:
                max_bytes = int(os.environ.get(
                    "HARMONY_TRACE_MAX_BYTES", str(64 << 20)))
            except ValueError:
                max_bytes = 64 << 20
        self.max_bytes = max_bytes
        self._f = open(path, "a", buffering=1)
        self._written = self._f.tell()  # appending: count existing bytes
        self._lock = threading.Lock()
        self._closed = False
        atexit.register(self.close)

    def _rotate_locked(self) -> None:
        self._f.close()
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            pass  # rotation is best-effort; keep appending regardless
        self._f = open(self.path, "a", buffering=1)
        self._written = self._f.tell()

    def receive(self, span: Span) -> None:
        line = json.dumps(span.to_dict()) + "\n"
        with self._lock:
            if self._closed:
                return  # an atexit-closed receiver drops, never crashes
            if self.max_bytes and self._written + len(line) > self.max_bytes:
                self._rotate_locked()
            self._f.write(line)
            self._written += len(line)

    def close(self) -> None:
        import atexit

        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._f.flush()
            self._f.close()
        # this receiver is done; keep the process-exit hook list short
        try:
            atexit.unregister(self.close)
        except Exception:  # pragma: no cover - interpreter teardown
            pass


class Tracing:
    """Process-wide tracing state: receivers + sampling.

    ``sample_rate``: 1.0 traces everything, 0.0 nothing (HTrace samplers);
    child spans of a sampled trace are always kept so traces stay whole.
    """

    def __init__(self, process_id: str = "", sample_rate: float = 1.0) -> None:
        self.process_id = process_id or f"proc-{os.getpid()}"
        self.sample_rate = sample_rate
        #: replaced whole under the lock, read without it: a span that
        #: closes with no receiver takes no lock
        self._receivers: Tuple[SpanReceiver, ...] = ()
        self._lock = threading.Lock()

    def add_receiver(self, receiver: SpanReceiver) -> SpanReceiver:
        with self._lock:
            self._receivers = (*self._receivers, receiver)
        return receiver

    def remove_receiver(self, receiver: SpanReceiver) -> None:
        with self._lock:
            self._receivers = tuple(r for r in self._receivers
                                    if r is not receiver)

    def emit(self, span: Span) -> None:
        for r in self._receivers:
            r.receive(span)

    def close(self) -> None:
        with self._lock:
            receivers, self._receivers = self._receivers, ()
        for r in receivers:
            r.close()


_tracing = Tracing()
_current: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "harmony_current_span", default=None
)
_rng = threading.local()


def get_tracing() -> Tracing:
    return _tracing


def set_tracing(tracing: Tracing) -> Tracing:
    global _tracing
    _tracing = tracing
    return tracing


def current_span() -> Optional[Span]:
    return _current.get()


def _new_id() -> str:
    return "%016x" % random.getrandbits(64)


def _sampled() -> bool:
    rate = _tracing.sample_rate
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    if not hasattr(_rng, "r"):
        _rng.r = random.Random()
    return _rng.r.random() < rate


def _scalars(annotations: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in annotations.items()
            if isinstance(v, (str, int, float, bool, type(None)))}


# -- the profiler sink -----------------------------------------------------

_annotation_cls: Any = None


def _annotation(description: str, annotations: Dict[str, Any]):
    """An entered ``jax.profiler.TraceAnnotation`` named
    ``harmony/<description>`` carrying the small scalar annotations, or
    None while nothing in the process has imported jax (a jax-free client
    has no profiler to write to, and this module must not be what imports
    it)."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation as cls
        except Exception:  # pragma: no cover - profiler always importable
            cls = False
        _annotation_cls = cls
    if cls is False:
        return None
    try:
        ann = cls(ANNOTATION_PREFIX + description, **_scalars(annotations))
        ann.__enter__()
        return ann
    except Exception:
        return None


# -- what is open, and the longest of each kind ----------------------------
#
# Every open span of either form sits on its thread's stack; the stacks are
# registered once per thread so another thread (STATUS, a flight dump) can
# read them. Pushing and popping is a list operation on the owner's side.

_open_lock = threading.Lock()
#: thread ident -> (thread name, [[description, start_ns, annotations]])
_open_stacks: Dict[int, Tuple[str, List[list]]] = {}
_tls = threading.local()
#: description -> (duration_ns, start_ns, annotations) of its longest close
_longest: Dict[str, Tuple[int, int, Dict[str, Any]]] = {}


def _stack() -> List[list]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
        t = threading.current_thread()
        with _open_lock:
            _open_stacks[t.ident] = (t.name, stack)
    return stack


def open_spans() -> List[Dict[str, Any]]:
    """Every span open in this process right now, oldest first:
    ``{description, thread, open_sec, start_sec, annotations}``."""
    now = time.monotonic_ns()
    alive = {t.ident for t in threading.enumerate()}
    with _open_lock:
        for ident in [i for i, (_, st) in _open_stacks.items()
                      if i not in alive and not st]:
            del _open_stacks[ident]
        stacks = [(name, list(st)) for name, st in _open_stacks.values()]
    out = [{"description": desc, "thread": name,
            "open_sec": (now - start) * 1e-9, "start_sec": wall_sec(start),
            "annotations": _scalars(dict(ann))}
           for name, st in stacks for desc, start, ann in st]
    out.sort(key=lambda r: -r["open_sec"])
    return out


def longest_spans() -> List[Dict[str, Any]]:
    """The longest closed span of each description, longest first."""
    rows = [{"description": d, "duration_sec": dur * 1e-9,
             "start_sec": wall_sec(start),
             "annotations": _scalars(dict(ann))}
            for d, (dur, start, ann) in list(_longest.items())]
    rows.sort(key=lambda r: -r["duration_sec"])
    return rows


def reset_span_watch() -> None:
    """Forget the longest spans (tests)."""
    _longest.clear()


class trace_span:
    """Open a span (a context manager); nests under the current span unless
    ``parent`` (a wire context from a remote caller) overrides it. Yields
    the :class:`Span`, or None when the trace is sampled out or the light
    form was asked for — callers never branch on it.

    ``record=False`` is the light form (module docstring): for regions
    that run once a step. ``acc`` is called with the region's seconds when
    it closes, whatever the form — how a span's time reaches a phase
    accumulator or a counter without a second clock read."""

    __slots__ = ("_description", "_parent", "_record", "_acc",
                 "_annotations", "_span", "_token", "_entry", "_ann")

    def __init__(self, description: str,
                 parent: Optional[SpanContext] = None, *,
                 record: bool = True,
                 acc: Optional[Callable[[float], None]] = None,
                 **annotations: Any) -> None:
        self._description = description
        self._parent = parent
        self._record = record
        self._acc = acc
        self._annotations = annotations
        self._span: Optional[Span] = None
        self._token = None

    def __enter__(self) -> Optional[Span]:
        cur = _current.get()
        if (self._record and self._parent is None and cur is None
                and not _sampled()):
            self._record = False
        self._ann = _annotation(self._description, self._annotations)
        start = time.monotonic_ns()
        self._entry = [self._description, start, self._annotations]
        _stack().append(self._entry)
        if not self._record:
            return None
        parent = self._parent
        self._span = span = Span(
            trace_id=None if parent is None else parent.trace_id,
            parent_id=None if parent is None else parent.span_id,
            parent_span=cur if parent is None else None,
            description=self._description,
            annotations=self._annotations,
            process_id=_tracing.process_id,
            start_ns=start,
        )
        if parent is not None and span.job is None and cur is not None:
            span.job = cur.job
        self._token = _current.set(span)
        return span

    def __exit__(self, *exc: Any) -> None:
        stop = time.monotonic_ns()
        desc, start, _ = self._entry
        stack = _stack()
        if stack and stack[-1] is self._entry:
            stack.pop()
        else:  # closed out of order (interleaved tasks on one thread)
            try:
                stack.remove(self._entry)
            except ValueError:
                pass
        if self._ann is not None:
            self._ann.__exit__(*exc)
        span = self._span
        if span is not None:
            _current.reset(self._token)
            span._close(stop)
            if span._discarded:
                return
            _tracing.emit(span)
        dur = stop - start
        best = _longest.get(desc)
        if best is None or dur > best[0]:
            _longest[desc] = (dur, start, self._annotations)
        if self._acc is not None:
            self._acc(dur * 1e-9)


def record_span(description: str, start_ns: int,
                stop_ns: Optional[int] = None, *,
                acc: Optional[Callable[[float], None]] = None,
                **annotations: Any) -> Span:
    """Emit a span whose region was not a ``with`` block — it began on one
    thread and ended on another (queued -> granted). Both ends are
    ``time.monotonic_ns()`` readings; it nests under the current span and
    reaches the receivers only (an annotation cannot be back-dated)."""
    span = Span(parent_span=_current.get(), description=description,
                annotations=annotations, process_id=_tracing.process_id,
                start_ns=start_ns)
    span._close(time.monotonic_ns() if stop_ns is None else stop_ns)
    _tracing.emit(span)
    if acc is not None:
        acc(span.duration_sec)
    return span


def job_stage(job_id: str, stage: str, **annotations: Any) -> trace_span:
    """The span ``job.<stage>`` of a job's start, whose seconds also go to
    ``harmony_job_stage_seconds_total{job,stage}``."""
    return trace_span(  # lint: allow(span-hygiene) a factory: span-hygiene holds every job_stage(...) caller to `with` / enter_context
        "job." + stage, job_id=job_id,
        acc=job_stage_adder(job_id, stage), **annotations)


def _stage_family():
    from harmony_tpu.metrics.registry import get_registry

    return get_registry().counter(
        "harmony_job_stage_seconds_total",
        "Seconds of a job's start by stage (grant_wait / table_create / "
        "init / data_load / build_step / first_window)",
        ("job", "stage"),
    )


def job_stage_adder(job_id: str, stage: str
                    ) -> Optional[Callable[[float], None]]:
    """``add(seconds)`` of one (job, stage) cell of the stage counter, or
    None when the registry cannot be had — a span never fails over it."""
    try:
        return _stage_family().labels(job=str(job_id), stage=stage).inc
    except Exception:
        return None


def job_stage_seconds(newest: int = 64) -> Dict[str, Dict[str, float]]:
    """``{job: {stage: seconds}}`` of the ``newest`` jobs that recorded a
    stage — STATUS ``job_stages``."""
    out: Dict[str, Dict[str, float]] = {}
    try:
        for (job, stage), child in _stage_family().children():
            out.setdefault(job, {})[stage] = round(child.value, 6)
    except Exception:
        return {}
    return dict(list(out.items())[-newest:])


def current_job() -> Optional[str]:
    """The job of the span open on this thread's context, if any."""
    span = _current.get()
    return None if span is None else span.job


def wire_context() -> Optional[Dict[str, str]]:
    """Current span as a message-embeddable dict (None outside any span)."""
    span = _current.get()
    if span is None:
        return None
    return SpanContext(span.trace_id, span.span_id).to_wire()
