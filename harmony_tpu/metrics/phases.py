"""Per-tenant step-phase time budget — where inside the step the time goes.

The ledger (metrics/accounting.py) can say *that* a tenant is slow and
the doctor (metrics/doctor.py) can say *who* lags, but until now nothing
said *where inside the step* the wall time went: the fused step charged
its whole wall to COMP and the comm probe's split was stashed on a
private table attr. The TPU-pod papers get their wins precisely from this
breakdown — overlapping cross-host transfers with compute
(arXiv:2011.03641) and per-phase tuning at pod scale (MLPerf-0.6 on
v3 pods) — and the device autoscaler (ROADMAP item 1) cannot choose
between *scale out*, *pack tighter* and *leave alone* without it.

Every worker continuously attributes its wall time per epoch to a
CLOSED phase set:

* ``input_wait``    — prefetch consumer-stall seconds (PR 1, measured);
* ``host_dispatch`` — host seconds between batch-ready and device
  dispatch (placement/staging on the training thread, measured);
* ``pull_comm`` / ``compute`` / ``push_comm`` — the device-work split,
  a MODEL: the comm-probe's absolute pull/push seconds applied to the
  measured step wall, refined by ``cost_analysis`` FLOP seconds when
  the backend exposes a cost model (the probe can overestimate comm on
  tiny tables; compute never drops below its FLOP floor);
* ``grant_wait``    — admission: every ``taskunit.wait`` (COMP / NET /
  CPU units) and dispatch-turn entry of the worker's training thread,
  measured by the spans' own clock reads (tracing/span.py ``acc``);
* ``probe``         — the blocking comm probe, admission excluded;
* ``bookkeeping``   — the post-drain replay of ``_account_ops`` +
  ``_finish_epoch`` (span ``window.bookkeeping``); the three are carved
  out of what used to be ``residual`` and nothing else;
* ``barrier_wait``  — the chief-observed gap between a worker's last
  step and the epoch drain (computed from sibling workers' epoch walls
  at the same epoch index — the straggler report says *who*, this says
  what the fast workers paid waiting);
* ``residual``      — everything unattributed (metric drains' host
  share, trainer hooks between epochs, whatever no span covers), kept as
  an EXPLICIT series, never silently absorbed into a real phase.

**Budget invariant**: per window, ``sum(phases) + residual == wall``
within tolerance — feeds are sanitized (no negative phase, and a feed
whose measured phases exceed its wall — an elastic shrink truncating
the epoch mid-window — is scaled down, never allowed to imply >100%).

Surfaces: ``harmony_phase_budget_seconds{job,attempt,worker,phase}``
callback gauges, first-class ``tenant.phase.*`` history series (the
scraper folds the ledger join each cycle), STATUS ``phase_budget``,
flight-recorder dumps, ``harmony-tpu obs critpath`` and the dashboard's
``/critpath`` panel. :mod:`harmony_tpu.metrics.critpath` classifies and
names the epoch critical path from this store.

Knob: ``HARMONY_PHASE_WINDOW`` (seconds of budget window, default =
``HARMONY_LEDGER_WINDOW`` — the two vectors describe the same tenant
and should cover the same span; docs/OBSERVABILITY.md §9).

**The window ledger.** The budget above is fed an epoch at a time with a
window's wall / k, and every reader sums it: a window that came late is
smeared over the run. So the store also keeps one RECORD a drained window
(``observe_window``; the worker closes it where it takes the window's wall,
dolphin/worker.py ``_take_budget_feed``)::

    {window, epoch, epochs, steps, start_ns, end_ns, wall_s,
     spans: {name: seconds}, unnamed_s, compile_s, first, late}

``start_ns`` / ``end_ns`` are ``time.monotonic_ns()`` readings, the spans'
own clock; ``spans`` is the training thread's SELF time under each light
span of the window (a span's seconds less those of the spans that closed
inside it), so ``spans`` and ``unnamed_s = wall_s - sum(spans)`` partition
the wall. A window is LATE when its wall an epoch exceeds ``STALL_FACTOR``
times the median of the ring's earlier regular windows (``_judge``); its
verdict ``{window, epoch, epochs, lost_s, cause, excess, start_sec}`` names
as CAUSE the span whose seconds stand furthest over that span's own median,
``unnamed`` for what ran under no span and ``compile`` when JAX compiled for
most of the loss. The causes are the spans' names; what each means is a
line of docs/OBSERVABILITY.md §9.
"""
from __future__ import annotations

import os
import statistics
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

ENV_PHASE_WINDOW = "HARMONY_PHASE_WINDOW"

#: the closed phase taxonomy, in waterfall order (docs/OBSERVABILITY.md
#: §9 documents each); ``residual`` rides beside them as the explicit
#: unattributed series
PHASES = ("input_wait", "host_dispatch", "grant_wait", "pull_comm",
          "compute", "push_comm", "probe", "bookkeeping", "barrier_wait")
RESIDUAL = "residual"

#: feed samples kept per tenant — one per worker-epoch; covers days of
#: a long job while bounding a pathological feeder (accounting's shape)
_MAX_SAMPLES = 4096

#: window records (and verdicts) kept per (job, worker): the newest few
#: minutes of a job that drains about once a second
_MAX_WINDOWS = 256
#: a window whose wall an epoch is this many times the median of the
#: earlier regular ones is LATE — perf/rates.py's ``STALL_FACTOR``, so the
#: benchmark's polled ``stall_s`` and this side can be laid side by side
STALL_FACTOR = 1.5
#: the median is over the newest this many regular windows, and there is
#: no verdict before this many
_REGULAR_NEWEST = 32
_REGULAR_MIN = 3
#: how many records / verdicts a snapshot row (STATUS, a flight dump) shows
_SHOWN_WINDOWS = 32
#: the cause of what ran under no span, and of a window that compiled
UNNAMED = "unnamed"
COMPILE = "compile"
#: wall an epoch (seconds) of a drained window: a keyed tenant's 15 ms
#: epochs through hour-long production ones
WINDOW_SECONDS_BUCKETS: Tuple[float, ...] = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0, 300.0,
    900.0, 3600.0,
)


def phase_window_seconds() -> float:
    """The budget window (seconds): ``HARMONY_PHASE_WINDOW``, defaulting
    to the ledger window so the cost vector and the phase vector of one
    tenant describe the same span."""
    raw = os.environ.get(ENV_PHASE_WINDOW, "")
    if raw:
        try:
            return max(1.0, float(raw))
        except ValueError:
            pass
    from harmony_tpu.metrics.accounting import window_seconds

    return window_seconds()


def split_device_phases(work_sec: float, steps: int, *,
                        dispatch_sec: float = 0.0,
                        probe_split: Optional[Tuple[float, float]] = None,
                        flops_per_step: Optional[float] = None,
                        peak_flops: Optional[float] = None,
                        devices: int = 1) -> Dict[str, float]:
    """Split one epoch's measured device-work seconds (``work_sec`` =
    smeared per-batch time × steps, which INCLUDES host placement) into
    ``pull_comm`` / ``compute`` / ``push_comm``.

    ``probe_split`` is the comm probe's absolute per-step (pull, push)
    device seconds, applied to the measured wall; ``compute`` is the
    remainder (PR 6's documented convention — with the probe off the
    whole work charges to compute, the conservative default). When
    ``flops_per_step`` AND ``peak_flops`` are known, the remainder is
    refined: compute never drops below the FLOP floor
    ``flops × steps / (peak × devices)`` — on tiny tables the probe's
    sub-millisecond measurements can rival the step wall and would
    otherwise starve compute to zero.

    Returns non-negative seconds with
    ``pull + comp + push <= max(work - dispatch, 0)``.
    """
    avail = max(float(work_sec) - max(float(dispatch_sec), 0.0), 0.0)
    steps = max(int(steps), 0)
    if avail <= 0.0 or steps == 0:
        return {"pull_comm": 0.0, "compute": 0.0, "push_comm": 0.0}
    pull0 = push0 = 0.0
    if probe_split is not None:
        pull0 = max(float(probe_split[0]), 0.0) * steps
        push0 = max(float(probe_split[1]), 0.0) * steps
    comp_floor = 0.0
    if flops_per_step is not None and peak_flops:
        comp_floor = min(
            float(flops_per_step) * steps / (float(peak_flops)
                                             * max(int(devices), 1)),
            avail)
    comm0 = pull0 + push0
    comm = min(comm0, avail - comp_floor) if comm0 > 0 else 0.0
    comm = max(comm, 0.0)
    scale = comm / comm0 if comm0 > 0 else 0.0
    return {"pull_comm": pull0 * scale,
            # nothing separates in-work overhead from compute (one XLA
            # program) — the remainder IS compute by the documented
            # convention
            "compute": avail - comm,
            "push_comm": push0 * scale}


class _TenantPhases:
    """Mutable per-job phase state; all mutation under the store lock."""

    __slots__ = ("job", "attempt", "samples", "device_split", "windows",
                 "stalls")

    def __init__(self, job: str) -> None:
        self.job = job
        self.attempt = job
        #: how pull_comm / compute / push_comm were told apart in the
        #: newest feed: "modelled" (the probe's seconds applied to the
        #: step wall) is all a worker feeds today; "measured" is for a
        #: feed that reads them off a trace (ROADMAP D10)
        self.device_split = "modelled"
        #: (ts, attempt, worker, epoch_idx, wall_sec, {phase: sec}) —
        #: the attempt rides each sample so the barrier join never
        #: mixes epoch walls across an elastic restart (attempt 2
        #: re-runs the same epoch indices; see snapshot())
        self.samples: deque = deque(maxlen=_MAX_SAMPLES)
        #: worker -> ring of its newest window records; the verdicts on
        #: the late ones beside them
        self.windows: Dict[str, deque] = {}
        self.stalls: deque = deque(maxlen=_MAX_WINDOWS)


class PhaseBudgetStore:
    """Process-wide per-tenant phase-budget store; see module docstring.

    Fed once per worker-epoch (never per batch); ``snapshot()`` joins
    sibling workers' walls at the same epoch index into ``barrier_wait``
    and emits per-tenant and per-worker budgets whose phases + residual
    sum to the wall exactly."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tenants: Dict[str, _TenantPhases] = {}
        #: bumped on every mutation — the memoized-snapshot validity key
        self._version = 0
        #: window -> (version, expires, rows): see snapshot_memoized
        self._memo: Dict[float, Tuple[int, float, Dict[str, Any]]] = {}

    # -- feeds (worker side) ---------------------------------------------

    def observe_epoch(self, job: str, attempt: str, worker: str,
                      epoch_idx: int, wall_sec: float,
                      phases: Dict[str, float],
                      device_split: str = "modelled") -> None:
        """One worker-epoch's budget feed. Sanitized at the door: every
        phase is clamped non-negative, and a feed whose measured phases
        exceed its wall (elastic shrink truncating the epoch mid-window,
        timer overlap) is scaled to fit — the invariant "phases sum to
        <= 100% of wall" holds at ingest, not just at render."""
        wall = max(float(wall_sec), 0.0)
        clean = {str(k): max(float(v), 0.0)
                 for k, v in (phases or {}).items()}
        total = sum(clean.values())
        if total > wall and total > 0:
            scale = wall / total
            clean = {k: v * scale for k, v in clean.items()}
        now = time.monotonic()
        with self._lock:
            t = self._tenants.get(job)
            if t is None:
                t = self._tenants[job] = _TenantPhases(job)
            if attempt:
                t.attempt = attempt
            t.device_split = str(device_split)
            t.samples.append((now, str(attempt or job), str(worker),
                              int(epoch_idx), wall, clean))
            self._version += 1

    def observe_window(self, job: str, attempt: str, worker: str,
                       record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """Keep one drained window's record (module docstring) and judge
        it against the ring's earlier regular windows. Returns the verdict
        when the window is LATE, else None. A window of another attempt
        than the ring's newest starts the ring anew: an elastic restart
        runs on another mesh, and its walls are not this one's."""
        spans = {str(k): round(float(v), 6)
                 for k, v in record["spans"].items()}
        wall = round(max(float(record["wall_s"]), 0.0), 6)
        rec = dict(
            record, epochs=max(int(record["epochs"]), 1), wall_s=wall,
            spans=spans, unnamed_s=round(wall - sum(spans.values()), 6),
            compile_s=round(max(float(record["compile_s"]), 0.0), 6),
            first=bool(record["first"]), attempt=str(attempt or job),
            worker=str(worker))
        with self._lock:
            t = self._tenants.get(job)
            if t is None:
                t = self._tenants[job] = _TenantPhases(job)
            ring = t.windows.get(rec["worker"])
            if ring is None:
                ring = t.windows[rec["worker"]] = deque(maxlen=_MAX_WINDOWS)
            if ring and ring[-1]["attempt"] != rec["attempt"]:
                ring.clear()
            stall = None if rec["first"] else _judge(rec, ring)
            rec["late"] = stall is not None
            ring.append(rec)
            if stall is not None:
                t.stalls.append(stall)
            self._version += 1
        return stall

    # -- queries ---------------------------------------------------------

    def window_ledger(self, job: str, newest: int = _MAX_WINDOWS
                      ) -> Dict[str, List[Dict[str, Any]]]:
        """``{windows, stalls}`` of ``job``: its newest window records over
        all workers, oldest first, and the verdicts on its late windows.
        READ-ONLY rows, as a snapshot's are."""
        with self._lock:
            t = self._tenants.get(job)
            if t is None:
                return {"windows": [], "stalls": []}
            rings = [list(r) for r in t.windows.values()]
            stalls = list(t.stalls)
        windows = (rings[0] if len(rings) == 1 else
                   sorted((r for ring in rings for r in ring),
                          key=lambda r: r["end_ns"]))
        return {"windows": windows[-newest:], "stalls": stalls[-newest:]}

    def snapshot(self, window_sec: Optional[float] = None
                 ) -> Dict[str, Dict[str, Any]]:
        """Per-tenant phase budgets over the window. Each row:

        ``{job, attempt, window_sec, wall_sec, epochs, device_split,
        phases, fractions, per_worker, epoch_walls}`` — ``phases`` maps every
        taxonomy phase plus ``residual`` to windowed seconds;
        ``fractions`` the same over the tenant's wall (sums to 1.0 when
        wall > 0); ``per_worker`` one budget per worker;
        ``epoch_walls`` maps epoch index -> {worker: wall_sec} (the
        critical-path analyzer's raw material); ``windows`` / ``stalls``
        are the window ledger's newest records and verdicts (module
        docstring). ``barrier_wait`` for a
        worker-epoch is ``max(sibling walls) - own wall`` — the
        chief-observed gap between that worker's last step and the
        epoch drain; single-worker epochs pay none. The join is
        partitioned by the LIVE attempt: an elastic restart re-runs the
        same epoch indices, and mixing attempt 1's epoch-0 wall into
        attempt 2's epoch-0 gate would charge phantom barrier seconds
        nobody paid (the ledger keys by ``job@attempt`` for the same
        reason) — stale-attempt samples are simply dropped."""
        w = (window_sec if window_sec is not None
             else phase_window_seconds())
        cutoff = time.monotonic() - w
        with self._lock:
            tenants = [(t.job, t.attempt, list(t.samples), t.device_split)
                       for t in self._tenants.values()]
        rows: Dict[str, Dict[str, Any]] = {}
        for job, attempt, samples, device_split in tenants:
            live = [(ts, wk, ep, wall, ph)
                    for (ts, att, wk, ep, wall, ph) in samples
                    if ts >= cutoff and att == attempt]
            if not live:
                continue
            # sibling walls per epoch index: the barrier join's input
            epoch_walls: Dict[int, Dict[str, float]] = {}
            for _ts, wk, ep, wall, _ph in live:
                epoch_walls.setdefault(ep, {})[wk] = max(
                    epoch_walls.get(ep, {}).get(wk, 0.0), wall)
            per_worker: Dict[str, Dict[str, Any]] = {}
            for _ts, wk, ep, wall, ph in live:
                gate = max(epoch_walls[ep].values())
                barrier = max(gate - wall, 0.0)
                wrow = per_worker.setdefault(
                    wk, {"wall_sec": 0.0, "epochs": 0,
                         "phases": {p: 0.0 for p in PHASES}})
                wrow["epochs"] += 1
                # the worker's share of the JOB epoch spans its own wall
                # plus the gap to the drain — residual closes the sum
                wrow["wall_sec"] += wall + barrier
                for p in PHASES:
                    if p == "barrier_wait":
                        continue
                    wrow["phases"][p] += ph.get(p, 0.0)
                wrow["phases"]["barrier_wait"] += barrier
            for wrow in per_worker.values():
                attributed = sum(wrow["phases"].values())
                wrow["phases"][RESIDUAL] = max(
                    wrow["wall_sec"] - attributed, 0.0)
                wrow["fractions"] = _fractions(wrow["phases"],
                                               wrow["wall_sec"])
            wall_sum = sum(r["wall_sec"] for r in per_worker.values())
            phases = {p: sum(r["phases"][p] for r in per_worker.values())
                      for p in (*PHASES, RESIDUAL)}
            rows[job] = {
                "job": job,
                "attempt": attempt,
                "window_sec": w,
                "wall_sec": round(wall_sum, 6),
                "epochs": len(epoch_walls),
                # pull_comm / compute / push_comm are a model in fused
                # mode: no reader may mistake one for the other
                "device_split": device_split,
                "phases": {p: round(v, 6) for p, v in phases.items()},
                "fractions": _fractions(phases, wall_sum),
                "per_worker": {
                    wk: {"wall_sec": round(r["wall_sec"], 6),
                         "epochs": r["epochs"],
                         "phases": {p: round(v, 6)
                                    for p, v in r["phases"].items()},
                         "fractions": r["fractions"]}
                    for wk, r in sorted(per_worker.items())},
                "epoch_walls": {
                    str(ep): {wk: round(v, 6) for wk, v in ws.items()}
                    for ep, ws in sorted(epoch_walls.items())},
                # the window ledger's newest records and verdicts
                **self.window_ledger(job, _SHOWN_WINDOWS),
            }
        return rows

    #: memo TTL: bounds staleness when nothing feeds but the clock
    #: moves the window edge (a scrape cadence is >> this)
    _MEMO_TTL = 0.2

    def snapshot_memoized(self, window_sec: Optional[float] = None
                          ) -> Dict[str, Dict[str, Any]]:
        """:meth:`snapshot`, memoized per window while no feed landed
        (version check) and for at most ``_MEMO_TTL`` seconds. One
        STATUS walks the store for both its ``tenants`` join and its
        ``phase_budget``, and every /metrics scrape samples the budget
        gauge — without the memo each request paid N independent
        full-deque walks (PR 8's scrape-callback memo precedent).
        Callers must treat the returned rows as READ-ONLY (the critpath
        analyzer copies before enriching)."""
        w = (window_sec if window_sec is not None
             else phase_window_seconds())
        now = time.monotonic()
        with self._lock:
            hit = self._memo.get(w)
            version = self._version
        if hit is not None and hit[0] == version and now < hit[1]:
            return hit[2]
        rows = self.snapshot(w)
        with self._lock:
            if len(self._memo) > 8:  # windows are a handful of values
                self._memo.clear()
            self._memo[w] = (version, now + self._MEMO_TTL, rows)
        return rows

    def clear(self) -> None:
        with self._lock:
            self._tenants.clear()
            self._memo.clear()
            self._version += 1


def _judge(rec: Dict[str, Any], ring) -> Optional[Dict[str, Any]]:
    """The verdict on ``rec`` against the earlier records of its ring, or
    None: no verdict before ``_REGULAR_MIN`` regular windows (never the
    first, never a late one), LATE above ``STALL_FACTOR`` medians an epoch.
    The loss is the wall over the median's; the excess of a span (and of
    ``unnamed``) is its seconds over that span's own median an epoch in the
    same regular windows."""
    regular = [r for r in ring
               if not r["first"] and not r["late"]][-_REGULAR_NEWEST:]
    if len(regular) < _REGULAR_MIN:
        return None
    k = rec["epochs"]
    med = statistics.median(r["wall_s"] / r["epochs"] for r in regular)
    if med <= 0.0 or rec["wall_s"] / k <= STALL_FACTOR * med:
        return None
    lost = rec["wall_s"] - med * k
    usually = [(_seconds(r), r["epochs"]) for r in regular]
    excess: Dict[str, float] = {}
    for name, sec in _seconds(rec).items():
        over = sec - k * statistics.median(
            by.get(name, 0.0) / epochs for by, epochs in usually)
        if over > 0.0:
            excess[name] = round(over, 6)
    cause = max(excess, key=excess.get) if excess else UNNAMED
    if rec["compile_s"] >= 0.5 * lost:
        cause = COMPILE
    from harmony_tpu.tracing.span import wall_sec

    return {"window": rec["window"], "epoch": rec["epoch"],
            "epochs": k, "lost_s": round(lost, 6), "cause": cause,
            "excess": dict(sorted(excess.items(), key=lambda kv: -kv[1])),
            "start_sec": wall_sec(rec["start_ns"])}


def _seconds(rec: Dict[str, Any]) -> Dict[str, float]:
    """A record's wall by span, what ran under none among them."""
    return {**rec["spans"], UNNAMED: rec["unnamed_s"]}


def _fractions(phases: Dict[str, float],
               wall: float) -> Dict[str, float]:
    if wall <= 0:
        return {p: 0.0 for p in phases}
    return {p: round(min(max(v / wall, 0.0), 1.0), 6)
            for p, v in phases.items()}


# -- process-wide store ----------------------------------------------------

_store_lock = threading.Lock()
_store: Optional[PhaseBudgetStore] = None


def budget() -> PhaseBudgetStore:
    """The process phase-budget store, created (and its /metrics
    callback gauge registered) on first use — the ledger's shape."""
    global _store
    with _store_lock:
        if _store is None:
            _store = PhaseBudgetStore()
            _install_callbacks()
        return _store


def peek_budget() -> Optional[PhaseBudgetStore]:
    """The store if one exists — never creates (crash-path consumers
    like the flight recorder must not instantiate budget state as a
    side effect of dying)."""
    with _store_lock:
        return _store


def reset_budget() -> None:
    """Drop the process store (tests). The registry callback re-binds
    to whatever store exists at sample time."""
    global _store
    with _store_lock:
        _store = None


def count_window(job: str, record: Dict[str, Any],
                 stall: Optional[Dict[str, Any]]) -> None:
    """The window ledger's exposition, fed by a job's chief once a drained
    window: the wall an epoch into ``harmony_window_seconds{job}`` and a
    late window's loss into ``harmony_window_stall_seconds_total{job,
    cause}`` / ``harmony_window_stalls_total{job,cause}``."""
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    if not record["first"]:
        reg.histogram(
            "harmony_window_seconds",
            "Wall seconds an epoch of a drained window (its first, which "
            "holds the compile, left out)",
            ("job",), buckets=WINDOW_SECONDS_BUCKETS,
        ).labels(job=job).observe(
            float(record["wall_s"]) / max(int(record["epochs"]), 1))
    if stall is None:
        return
    labels = {"job": job, "cause": stall["cause"]}
    reg.counter(
        "harmony_window_stall_seconds_total",
        "Seconds late windows lost over the regular windows' median wall, "
        "by the span that grew (unnamed: under no span; compile)",
        ("job", "cause")).labels(**labels).inc(stall["lost_s"])
    reg.counter(
        "harmony_window_stalls_total",
        "Drained windows whose wall an epoch exceeded 1.5 x the median of "
        "the regular windows before them, by cause",
        ("job", "cause")).labels(**labels).inc()


def _install_callbacks() -> None:
    """One labeled callback gauge sampled at scrape time: windowed
    per-phase seconds per (job, attempt, worker, phase) — the
    exposition face of the budget (pod followers' budgets reach the
    leader's history through this family). Registration failure must
    never fail store creation."""
    try:
        from harmony_tpu.metrics.registry import get_registry

        def sample():
            s = _store
            if s is None:
                return []
            out = []
            for row in s.snapshot_memoized().values():
                for wk, wrow in row["per_worker"].items():
                    for phase, sec in wrow["phases"].items():
                        out.append((
                            {"job": row["job"],
                             "attempt": row["attempt"],
                             "worker": wk, "phase": phase},
                            float(sec)))
            return out

        get_registry().register_callback(
            "harmony_phase_budget_seconds",
            "Windowed per-phase wall seconds per worker (input_wait / "
            "host_dispatch / grant_wait / pull_comm / compute / push_comm "
            "/ probe / bookkeeping / barrier_wait / residual; phases + "
            "residual sum to the window wall)",
            "gauge", sample)
    except Exception:
        pass  # already registered by an earlier store in this process
