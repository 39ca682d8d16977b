"""Crash-correlated flight recorder.

When a pod process dies, the evidence of WHAT it was doing — which
trace, which elastic attempt, which fault site — historically lived only
in interleaved operator logs. The flight recorder keeps a bounded
per-process ring of the most recent spans and structured events, and
dumps it to a JSON file at the moments that matter:

  * a fault site trips (once per site per process — the injection
    harness fires sites repeatedly and one dump per site is the signal;
    a ``crash`` rule dumps BEFORE ``os._exit``, so even a SIGKILL-style
    death leaves its black box on disk);
  * the pod leader observes a follower death;
  * ``SIGTERM`` lands on a long-running entry point
    (:func:`install_signal_dump` — wired by the CLI, never on import).

Beside the ring the recorder reports every span STILL OPEN in the
process and the LONGEST closed span of each description (tracing/span.py
keeps both for either span form): STATUS ``flight_spans`` and every dump
carry them, so a stall names itself (``open: taskunit.wait 4.2 s,
job_id=...``) while it lasts and afterwards, with nothing attached.

Each dump is correlated: it carries every ``trace_id`` seen in the ring
and the elastic ``attempt_key`` (``job@aN``) when the trigger's context
names one, so ``harmony-tpu obs flight`` / the STATUS endpoint can join
flight records against the distributed trace they belong to.

Knobs (docs/OBSERVABILITY.md): ``HARMONY_FLIGHT_DIR`` (dump directory;
default ``<tmp>/harmony-flight``), ``HARMONY_FLIGHT_CAP`` (ring size,
default 256).
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from harmony_tpu.tracing.span import (
    Span,
    SpanReceiver,
    get_tracing,
    longest_spans,
    open_spans,
)

ENV_DIR = "HARMONY_FLIGHT_DIR"
ENV_CAP = "HARMONY_FLIGHT_CAP"
_MAX_DUMP_SUMMARIES = 64


def _default_dir() -> str:
    return os.environ.get(ENV_DIR) or os.path.join(
        tempfile.gettempdir(), "harmony-flight")


def _default_cap() -> int:
    try:
        return max(16, int(os.environ.get(ENV_CAP, "256")))
    except ValueError:
        return 256


def _tenant_snapshot() -> Dict[str, Any]:
    """Tenant ledger snapshot for a dump, or {} — a dying process must
    never die HARDER because accounting could not be read (and the
    tracing package must not hard-depend on metrics)."""
    try:
        from harmony_tpu.metrics.accounting import peek_ledger

        store = peek_ledger()
        return store.snapshot() if store is not None else {}
    except Exception:
        return {}


def _phase_snapshot() -> Dict[str, Any]:
    """Step-phase budget snapshot for a dump, or {} — same contract as
    the tenant snapshot: peek, never create, never die harder."""
    try:
        from harmony_tpu.metrics.phases import peek_budget

        store = peek_budget()
        return store.snapshot() if store is not None else {}
    except Exception:
        return {}


def profile_capture_path() -> Optional[str]:
    """Newest sampled device-profile capture THIS process wrote, or
    None — guarded once here for every surface (flight dumps and the
    jobserver's STATUS): a dump that can point at the xplane trace of
    the dying process's last epochs answers the post-mortem's second
    question, and a STATUS reply must never fail because the profile
    dir is odd."""
    try:
        from harmony_tpu.tracing.profiler import newest_capture

        return newest_capture()
    except Exception:
        return None


def _diagnoses_snapshot() -> List[Dict[str, Any]]:
    """Recent doctor diagnoses for a dump, or [] — same contract as the
    tenant snapshot: a dying process must never die HARDER because its
    diagnosis history could not be read, and tracing must not
    hard-depend on metrics."""
    try:
        from harmony_tpu.metrics.doctor import peek_doctor

        doc = peek_doctor()
        return doc.recent() if doc is not None else []
    except Exception:
        return []


def _incidents_snapshot() -> List[Dict[str, Any]]:
    """Open incidents for a dump, or [] — same contract as the doctor
    snapshot: peek, never create. A crash dump that carries the
    incident narrative that was in flight answers "what episode was
    this process in the middle of" without the leader's STATUS."""
    try:
        from harmony_tpu.metrics.incidents import peek_incidents

        eng = peek_incidents()
        return eng.open_incidents() if eng is not None else []
    except Exception:
        return []


def _attempt_key(ctx: Dict[str, Any]) -> Optional[str]:
    """The ``job@aN`` attempt key a trigger context names, if any (same
    scheme as jobserver/elastic.attempt_key, inlined so the tracing
    package never imports the jobserver)."""
    job = ctx.get("job") or ctx.get("job_id")
    if job is None:
        return None
    try:
        attempt = int(ctx.get("attempt", 0) or 0)
    except (TypeError, ValueError):
        attempt = 0
    return str(job) if attempt <= 0 else f"{job}@a{attempt}"


class FlightRecorder(SpanReceiver):
    """Bounded ring of recent spans + events, dumpable to JSON."""

    def __init__(self, capacity: Optional[int] = None,
                 out_dir: Optional[str] = None) -> None:
        self._lock = threading.Lock()
        self._ring: "deque[Dict[str, Any]]" = deque(
            maxlen=capacity or _default_cap())
        self.out_dir = out_dir or _default_dir()
        #: summaries of dumps written by this process, newest last
        self.dumps: List[Dict[str, Any]] = []
        self.dump_count = 0
        self._dumped_sites: set = set()

    # -- capture ---------------------------------------------------------

    def receive(self, span: Span) -> None:
        rec = {"kind": "span", **span.to_dict()}
        with self._lock:
            self._ring.append(rec)

    def event(self, kind: str, **fields: Any) -> None:
        rec = {"kind": "event", "event": kind, "ts": time.time(), **fields}
        with self._lock:
            self._ring.append(rec)

    def span_watch(self, limit: int = 32) -> Dict[str, Any]:
        """``{"open": [...], "longest": [...]}``: the spans open right now
        (longest-open first) and the longest closed span per description —
        the ring keeps the last 256 records, these keep the outliers."""
        return {"open": open_spans()[:limit],
                "longest": longest_spans()[:limit]}

    def ring_size(self) -> int:
        with self._lock:
            return len(self._ring)

    def ring_events(self) -> List[Dict[str, Any]]:
        """Structured (non-span) ring records, oldest first — the fault
        evidence (``fault_trip``, ``follower_death``, ...) the incident
        engine correlates against the joblog stream."""
        with self._lock:
            return [dict(r) for r in self._ring
                    if r.get("kind") == "event"]

    # -- dump ------------------------------------------------------------

    def dump(self, reason: str, **meta: Any) -> Optional[str]:
        """Write the current ring (plus ``meta``) to one JSON file;
        returns its path, or None when the write failed (a dying process
        must never die HARDER because its black box could not flush)."""
        with self._lock:
            records = list(self._ring)
        trace_ids = sorted({
            r["trace_id"] for r in records
            if r.get("kind") == "span" and r.get("trace_id")
        })
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in reason)[:80]
        body = {
            "reason": reason,
            "ts": time.time(),
            "pid": os.getpid(),
            "process_id": get_tracing().process_id,
            "meta": meta,
            "trace_ids": trace_ids,
            # who was costing what when this process died: the tenant
            # cost vectors (metrics/accounting.py) snapshotted INTO the
            # black box, so a post-mortem can tell a starved tenant from
            # a runaway one without a live scrape
            "tenants": _tenant_snapshot(),
            # where inside the step each tenant's time was going when
            # this process died (metrics/phases.py) — the budget beside
            # the cost vectors, so a post-mortem can tell comm-starved
            # from compute-saturated without a live scrape
            "phase_budget": _phase_snapshot(),
            # the newest sampled device-profile capture on disk, when
            # the sampler ran (tracing/profiler.py)
            "profile_capture": profile_capture_path(),
            # what the doctor had already concluded when this process
            # died (metrics/doctor.py) — a dump with "input_bound on
            # tenant X" inside answers the post-mortem's first question
            "diagnoses": _diagnoses_snapshot(),
            # the incident narrative in flight when this process died
            # (metrics/incidents.py): open episodes with their causal
            # chains, beside the diagnoses that fed them
            "incidents": _incidents_snapshot(),
            # what was open when this was written, and the longest closed
            # span of each kind: the stall, by name
            "spans": self.span_watch(),
            "records": records,
        }
        path = os.path.join(
            self.out_dir,
            f"flight-{os.getpid()}-{int(time.time() * 1000)}-{safe}.json",
        )
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            tmp = path + ".writing"
            with open(tmp, "w") as f:
                json.dump(body, f, default=repr)
            os.replace(tmp, path)
        except OSError:
            return None
        summary = {"path": path, "reason": reason, "ts": body["ts"],
                   "meta": {k: repr(v) if not isinstance(
                       v, (str, int, float, bool, type(None))) else v
                       for k, v in meta.items()},
                   "trace_ids": trace_ids, "records": len(records)}
        with self._lock:
            self.dumps.append(summary)
            del self.dumps[:-_MAX_DUMP_SUMMARIES]
            self.dump_count += 1
        return path

    def records(self) -> List[Dict[str, Any]]:
        """Dump summaries (path/reason/trace_ids), newest last — what the
        STATUS endpoint and ``harmony-tpu obs flight`` surface."""
        with self._lock:
            return [dict(d) for d in self.dumps]

    # -- triggers --------------------------------------------------------

    def on_fault_trip(self, site: str, action: str,
                      ctx: Dict[str, Any]) -> None:
        """Fault-site trip: always an event in the ring; ONE dump per
        site per process (repeat fires of the same site would bury the
        first — and most diagnostic — ring snapshot under copies)."""
        # ctx keys that collide with the ring-record envelope (fault
        # rules match on a ``kind`` field, which would shadow the event
        # kind) get a ctx_ prefix instead of being dropped
        fields = {}
        for k, v in ctx.items():
            if not isinstance(v, (str, int, float, bool, type(None))):
                continue
            fields[f"ctx_{k}" if k in ("kind", "event", "ts", "site",
                                       "action") else k] = v
        self.event("fault_trip", site=site, action=action, **fields)
        with self._lock:
            if site in self._dumped_sites:
                return
            self._dumped_sites.add(site)
        meta: Dict[str, Any] = {"site": site, "action": action, **fields}
        ak = _attempt_key(ctx)
        if ak is not None:
            meta["attempt_key"] = ak
        self.dump(f"fault:{site}", **meta)


# -- process-wide recorder -------------------------------------------------

_rec_lock = threading.Lock()
_recorder: Optional[FlightRecorder] = None


def get_recorder() -> FlightRecorder:
    """The process recorder, created on first use and subscribed to the
    process-wide tracing so recent spans land in the ring."""
    global _recorder
    with _rec_lock:
        if _recorder is None:
            _recorder = FlightRecorder()
            get_tracing().add_receiver(_recorder)
        return _recorder


def peek_recorder() -> Optional[FlightRecorder]:
    """The recorder if one exists — never creates (metric callbacks must
    not instantiate observability state as a side effect of a scrape)."""
    with _rec_lock:
        return _recorder


def reset_recorder() -> None:
    """Drop the process recorder (tests)."""
    global _recorder
    with _rec_lock:
        rec, _recorder = _recorder, None
    if rec is not None:
        get_tracing().remove_receiver(rec)


def install_signal_dump(signals: Optional[List[int]] = None) -> None:
    """Dump the ring when a termination signal lands, then chain to the
    previous handler (or exit, matching the default action). Called by
    long-running CLI entry points only — never on import, and only from
    the main thread (signal.signal's requirement)."""
    import signal as _signal

    sigs = signals or [_signal.SIGTERM]
    rec = get_recorder()
    for signum in sigs:
        previous = _signal.getsignal(signum)

        def handler(num, frame, _prev=previous):
            rec.dump(f"signal:{num}")
            if callable(_prev):
                _prev(num, frame)
            elif _prev == _signal.SIG_DFL:
                _signal.signal(num, _signal.SIG_DFL)
                _signal.raise_signal(num)

        try:
            _signal.signal(signum, handler)
        except (ValueError, OSError):
            pass  # not the main thread / unsupported signal: no hook
