"""Telemetry-driven device policy engine — the loop that SPENDS the sensors.

Every input this engine needs has existed since PRs 8-14 — the per-tenant
ledger (device-seconds, MFU, input-wait, SLO attainment), the doctor's
structured diagnoses, the step-phase critical-path classification, and
the elastic shrink/re-grow fences — but nothing acted on *device*
resources: grow and shrink only triggered on failures, and an SLO breach
merely logged. This module closes the loop (ROADMAP item 1; the
reference's pluggable-policy JobScheduler + ET plan engine, SURVEY.md
L3/L4; elastic replanning per "Elastic Model Aggregation with Parameter
Service" and utilization packing per "Exploring the limits of Concurrency
in ML Training on Google TPUs", PAPERS.md).

Each evaluation window the :class:`PolicyEngine` reads the tenant ledger
(`MetricManager.tenant_ledger` — attainment, MFU, input-wait, and the
critpath ``phase_class``), the doctor's recent diagnoses, and the
scheduler's idle/queued state, and replans placement through the
EXISTING mechanisms — every action is a lockstep elastic fence on a
running ``user.elastic_shrink`` submission, never an in-flight mutation:

* **grow** — an under-SLO tenant whose bound classification says more
  devices genuinely help (compute-bound / balanced / unclassified)
  expands onto idle executors via a re-grow fence;
* **shrink** — under contention (queued arrivals, or an under-SLO
  claimant with nothing idle) a strictly lower-priority tenant holding
  more than one executor degrades to a smaller exclusive carve;
* **pack** — an input- or dispatch-bound victim (the device sits idle
  under it either way) consolidates onto a packable sibling's executors
  as a SHARED grant (ShareAll-style overlap, arbitrated by the TaskUnit
  fair queue), freeing its exclusive carve for the claimant. Comm-bound
  tenants are never packed — model traffic owns their step and an
  overlapping neighbor makes it strictly worse;
* **preempt** — when the victim can neither shrink (one executor) nor
  pack (not idle-classed), a strictly higher-priority claimant still
  wins: the victim surrenders its carve and is re-granted shared on the
  lowest-priority surviving sibling. Priorities come from
  ``TrainerParams.priority``; equal priority never preempts.

Rate limiting is the :class:`ActionGate`: an action fires only after its
signal persisted ``HARMONY_POLICY_CONFIRM`` consecutive evaluations
(hysteresis — a noisy window cannot thrash) and outside the per-subject
AND per-signal ``HARMONY_POLICY_COOLDOWN`` (the input-worker autoscaler
shares the same gate under the ``input_wait`` signal, so device packing
and input-worker scaling can never fight over one stall signal). A
``rebalance_ineffective`` diagnosis (metrics/doctor.py) backs the
subject off multiplicatively.

Every decision is durable and observable: actions record structured
``kind="policy"`` joblog events (which the HA sink tees into the
replicated log, so a takeover inherits the in-flight plan), ride STATUS
(``policy``), render via ``harmony-tpu obs plan``, and tee to the
dashboard as ``kind="policy"`` rows. A deposed HA leader's actions are
rejected at the gate — fenced exactly like its TCP mutations.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

ENV_MODE = "HARMONY_POLICY"
ENV_PERIOD = "HARMONY_POLICY_PERIOD"
ENV_COOLDOWN = "HARMONY_POLICY_COOLDOWN"
ENV_CONFIRM = "HARMONY_POLICY_CONFIRM"
ENV_SLO_GROW = "HARMONY_POLICY_SLO_GROW"
ENV_MAX_ACTIONS = "HARMONY_POLICY_MAX_ACTIONS"

#: the engine's action vocabulary — gate sweeps are scoped to it so a
#: SHARED gate's other tenants (the input autoscaler's "up"/"down"
#: keys) keep their streaks
_ACTION_KINDS = frozenset(
    ("grow", "shrink", "pack", "preempt", "protect"))

#: a serving tenant whose windowed p99 is at/over this fraction of its
#: registered SLO is latency-critical: the `protect` action pins its
#: executors out of pack/preempt victim selection
_PROTECT_RATIO = 0.8

#: bound classifications under which a tenant is a PACK victim — the
#: device sits idle beneath it, so overlapping a sibling costs little
_PACKABLE_CLASSES = ("input-bound", "dispatch-bound")
#: ... and under which growing it is pointless (more chips would idle
#: just as hard) or actively harmful (comm scales with devices)
_NO_GROW_CLASSES = ("input-bound", "dispatch-bound", "comm-bound")


def policy_mode() -> str:
    """``HARMONY_POLICY``: ``off`` (no evaluation), ``advise`` (default
    — plans are computed, gated and surfaced, but never executed) or
    ``act`` (plans execute through the elastic fences)."""
    raw = os.environ.get(ENV_MODE, "").strip().lower()
    if raw in ("off", "0", "false"):
        return "off"
    if raw in ("act", "on", "1", "true"):
        return "act"
    return "advise"


def policy_period() -> float:
    """``HARMONY_POLICY_PERIOD`` (default 10 s): seconds between policy
    evaluations (rides the history-scraper cycle, so the effective
    cadence is the next scrape at or after the period)."""
    try:
        return max(0.1, float(os.environ.get(ENV_PERIOD, "") or 10.0))
    except ValueError:
        return 10.0


def policy_cooldown() -> float:
    """``HARMONY_POLICY_COOLDOWN`` (default 30 s): minimum seconds
    between actions on one subject (tenant) and on one SIGNAL — the
    anti-thrash half of the gate."""
    try:
        return max(0.0, float(os.environ.get(ENV_COOLDOWN, "") or 30.0))
    except ValueError:
        return 30.0


def policy_confirm() -> int:
    """``HARMONY_POLICY_CONFIRM`` (default 2): consecutive evaluations a
    signal must persist before its action may fire — the hysteresis
    half of the gate."""
    try:
        return max(1, int(os.environ.get(ENV_CONFIRM, "") or 2))
    except ValueError:
        return 2


def slo_grow_threshold() -> float:
    """``HARMONY_POLICY_SLO_GROW`` (default 0.9): SLO attainment below
    which a tenant is a grow candidate."""
    try:
        return float(os.environ.get(ENV_SLO_GROW, "") or 0.9)
    except ValueError:
        return 0.9


def max_actions_per_window() -> int:
    """``HARMONY_POLICY_MAX_ACTIONS`` (default 1): executed actions per
    evaluation — placement ramps, it does not slosh."""
    try:
        return max(1, int(os.environ.get(ENV_MAX_ACTIONS, "") or 1))
    except ValueError:
        return 1


class ActionGate:
    """Cooldown + hysteresis rate limiter shared by the device policy
    engine and the input-worker autoscaler.

    Keys are ``(subject, action)``; cooldowns apply per SUBJECT and per
    SIGNAL (a fired action on signal ``input_wait`` cools every other
    key on that signal — the device engine and the input autoscaler
    cannot fight over one stall measurement). ``observe`` maintains the
    consecutive-wanting streak; ``fired`` stamps the cooldowns;
    ``back_off`` (driven by ``rebalance_ineffective`` diagnoses)
    multiplies the subject's next cooldown.
    """

    def __init__(self, cooldown_sec: Optional[float] = None,
                 confirm: Optional[int] = None,
                 stale_after: Optional[float] = None,
                 backoff_factor: float = 4.0) -> None:
        self.cooldown_sec = (policy_cooldown() if cooldown_sec is None
                             else float(cooldown_sec))
        self.confirm = policy_confirm() if confirm is None else max(1, int(confirm))
        #: a streak older than this is stale (the engine stopped seeing
        #: the signal) and restarts at 1; default spans ~3 periods so a
        #: single missed evaluation does not reset hysteresis
        self.stale_after = (3.0 * policy_period() if stale_after is None
                            else float(stale_after))
        self.backoff_factor = float(backoff_factor)
        self._lock = threading.Lock()
        self._streak: Dict[Tuple[str, str], Tuple[int, float]] = {}
        self._cool_until: Dict[str, float] = {}  # subject or signal
        self._backoffs: Dict[str, int] = {}      # subject -> count
        self.fired_total = 0

    def observe(self, subject: str, action: str, wanted: bool,
                signal: str = "device",
                now: Optional[float] = None) -> bool:
        """Record one evaluation's view of (subject, action); True when
        the action may fire NOW (streak >= confirm, subject and signal
        both outside cooldown)."""
        now = time.monotonic() if now is None else float(now)
        key = (subject, action)
        with self._lock:
            if not wanted:
                self._streak.pop(key, None)
                return False
            n, last = self._streak.get(key, (0, now))
            n = 1 if (n and now - last > self.stale_after) else n + 1
            self._streak[key] = (n, now)
            if n < self.confirm:
                return False
            for scope in (subject, signal):
                if now < self._cool_until.get(scope, 0.0):
                    return False
            return True

    def fired(self, subject: str, action: str,
              signal: Optional[str] = "device",
              now: Optional[float] = None) -> None:
        """An action executed: reset its streak and start the subject +
        signal cooldowns (scaled by any pending backoff).
        ``signal=None`` cools ONLY the subject — an ADVISORY firing must
        pace its own re-planning without throttling live actuators
        (the input autoscaler) sharing the signal scope."""
        now = time.monotonic() if now is None else float(now)
        with self._lock:
            self._streak.pop((subject, action), None)
            cool = self.cooldown_sec
            if self._backoffs.get(subject):
                cool *= self.backoff_factor * self._backoffs[subject]
            self._cool_until[subject] = now + cool
            if signal is not None:
                self._cool_until[signal] = max(
                    self._cool_until.get(signal, 0.0),
                    now + self.cooldown_sec)
            self.fired_total += 1

    def sweep(self, observed: "set[Tuple[str, str]]",
              among: Optional["frozenset[str]"] = None,
              subjects: Optional["set[str]"] = None) -> None:
        """Drop streaks for keys NOT observed this round: hysteresis
        means CONSECUTIVE windows, so a candidate the planner stopped
        surfacing restarts from zero — and a long-lived server never
        accumulates streak entries for tenants long gone. ``among``
        restricts the sweep to keys whose ACTION is in the set — on a
        SHARED gate each loop sweeps only its own action vocabulary
        (the policy engine must never reset the input autoscaler's
        streaks). ``subjects`` restricts it to keys whose SUBJECT is in
        the set — an incremental (overload-degraded) evaluation swept
        only the tenants it actually looked at; the rest keep their
        streaks for their next rotation turn."""
        with self._lock:
            for key in [k for k in self._streak
                        if k not in observed
                        and (among is None or k[1] in among)
                        and (subjects is None or k[0] in subjects)]:
                del self._streak[key]

    def back_off(self, subject: str, now: Optional[float] = None) -> None:
        """A past action on ``subject`` proved ineffective: extend its
        cooldown multiplicatively so the engine stops churning it."""
        now = time.monotonic() if now is None else float(now)
        with self._lock:
            self._backoffs[subject] = self._backoffs.get(subject, 0) + 1
            self._cool_until[subject] = max(
                self._cool_until.get(subject, 0.0),
                now + self.cooldown_sec * self.backoff_factor
                * self._backoffs[subject])

    def cooling(self, scope: str, now: Optional[float] = None) -> bool:
        now = time.monotonic() if now is None else float(now)
        with self._lock:
            return now < self._cool_until.get(scope, 0.0)

    def stats(self) -> Dict[str, Any]:
        now = time.monotonic()
        with self._lock:
            return {
                "cooldown_sec": self.cooldown_sec,
                "confirm": self.confirm,
                "fired_total": self.fired_total,
                "streaks": {f"{s}:{a}": n
                            for (s, a), (n, _) in self._streak.items()},
                "cooling": sorted(k for k, t in self._cool_until.items()
                                  if now < t),
                "backoffs": dict(self._backoffs),
            }


class PolicyAction:
    """One planned placement change. ``executors`` is the target set the
    scheduler will grant the tenant's NEXT elastic attempt; ``shared``
    marks an overlapping (pack/preempt) grant."""

    __slots__ = ("kind", "job", "executors", "shared", "signal", "reason",
                 "evidence", "ts", "executed", "outcome", "epoch",
                 "baseline")

    def __init__(self, kind: str, job: str, executors: List[str],
                 reason: str, evidence: Dict[str, Any],
                 shared: bool = False, signal: str = "device") -> None:
        self.kind = kind
        self.job = job
        self.executors = list(executors)
        self.shared = bool(shared)
        self.signal = signal
        self.reason = reason
        self.evidence = dict(evidence)
        self.ts = 0.0
        self.executed = False
        self.outcome = "planned"
        self.epoch: Optional[int] = None
        self.baseline: Dict[str, Any] = {}

    @property
    def fence_kind(self) -> str:
        """The elastic fence flavor carrying this action: capacity gains
        ride the re-grow fence, every reduction/consolidation the
        shrink fence. `protect` never reaches a fence at all (its
        actuator is planner-side victim exemption); it classes with the
        non-reductions."""
        return "regrow" if self.kind in ("grow", "protect") else "shrink"

    def to_dict(self) -> Dict[str, Any]:
        return {s: getattr(self, s) for s in self.__slots__}


class PolicyEngine:
    """See the module docstring. Constructor wires the sensor and
    actuator surfaces so the engine itself stays pure and testable:

    * ``scheduler`` — the live :class:`JobScheduler` (idle/queued state,
      ``plan_grant`` targets);
    * ``ledger_fn`` — ``MetricManager.tenant_ledger`` (rows carry
      ``slo``, ``phase_class``, ``mfu``, ``input_wait_frac``);
    * ``tenants_fn`` — actuatable running tenants: ``{job: {"executors",
      "attempt", "priority"}}`` (the pod server's elastic-active view;
      a plain server has none and the engine stays advisory);
    * ``fence_fn(job, kind)`` — schedule a lockstep elastic fence on a
      running attempt, returning the fence epoch or None;
    * ``diagnoses_fn`` — the doctor's recent diagnoses
      (``rebalance_ineffective`` drives backoff);
    * ``leader_ok_fn`` — the HA fence: False on a deposed leader, whose
      actions are rejected, never executed;
    * ``sinks`` — observe every recorded action dict (the jobserver
      tees them to the dashboard).
    """

    def __init__(
        self,
        scheduler: Any,
        ledger_fn: Callable[[], Dict[str, Dict[str, Any]]],
        tenants_fn: Callable[[], Dict[str, Dict[str, Any]]],
        fence_fn: Optional[Callable[[str, str], Optional[int]]] = None,
        diagnoses_fn: Optional[Callable[[], List[Dict[str, Any]]]] = None,
        leader_ok_fn: Optional[Callable[[], bool]] = None,
        gate: Optional[ActionGate] = None,
        sinks: Tuple[Callable[[Dict[str, Any]], None], ...] = (),
    ) -> None:
        self._scheduler = scheduler
        self._ledger_fn = ledger_fn
        self._tenants_fn = tenants_fn
        self._fence_fn = fence_fn
        self._diagnoses_fn = diagnoses_fn
        self._leader_ok_fn = leader_ok_fn
        self.gate = gate or ActionGate()
        self._sinks = tuple(sinks)
        self._lock = threading.Lock()
        self._last_eval = 0.0
        self._last_eval_ms = 0.0
        self._evaluations = 0
        self._actions_total = 0
        self._rejected_total = 0
        self._last_plan: Dict[str, Any] = {}
        self._recent: List[Dict[str, Any]] = []
        #: newest rebalance_ineffective ts already backed off per job —
        #: one diagnosis must back a subject off exactly once
        self._backoff_seen: Dict[str, float] = {}
        #: job -> attempt index at the moment an action fenced it: the
        #: fence lands EPOCHS later, and until the tenant's attempt
        #: advances the plan is in flight — re-fencing it would stack
        #: redundant fences on the same attempt
        self._inflight: Dict[str, int] = {}
        #: job -> monotonic ts of its last fired `protect` action: while
        #: fresh, the tenant's executors are exempt from pack/preempt
        #: victim selection. TTL-scoped (protected_jobs) so a tenant
        #: whose latency recovered — or whose serving traffic stopped —
        #: rejoins the victim pool without an explicit release action
        self._protected: Dict[str, float] = {}

    # -- cadence ---------------------------------------------------------

    def maybe_evaluate(self, jobs: Optional["set[str]"] = None
                       ) -> Optional[Dict[str, Any]]:
        """Evaluate if the period elapsed (the scrape-cycle hook); the
        direct :meth:`evaluate` stays available for tests and benches
        that drive time themselves. ``jobs`` restricts the pass to a
        tenant subset (overload degraded mode)."""
        if policy_mode() == "off":
            return None
        now = time.monotonic()
        with self._lock:
            if now - self._last_eval < policy_period():
                return None
            self._last_eval = now
        return self.evaluate(jobs=jobs)

    # -- one evaluation --------------------------------------------------

    def evaluate(self, now: Optional[float] = None,
                 jobs: Optional["set[str]"] = None) -> Dict[str, Any]:
        """One full plan-and-maybe-act pass; returns the plan (also kept
        as ``last_plan`` for STATUS / ``obs plan``). ``jobs`` restricts
        planning to a tenant subset — the overload ladder's incremental
        degraded mode (jobserver/overload.py): only tenants with fresh
        samples this cycle are considered, and the gate's sweep is
        scoped to them so absent tenants keep their streaks."""
        mode = policy_mode()
        t0 = time.perf_counter()
        now = time.monotonic() if now is None else float(now)
        plan: Dict[str, Any] = {"ts": time.time(), "mode": mode,
                                "considered": [], "actions": []}
        if mode == "off":
            return self._finish(plan, t0)
        rows = self._safe(self._ledger_fn, {})
        tenants = self._safe(self._tenants_fn, {})
        if jobs is not None:
            scope = {str(j) for j in jobs}
            plan["tenant_subset"] = sorted(scope)
            rows = {k: v for k, v in rows.items() if str(k) in scope}
            tenants = {k: v for k, v in tenants.items()
                       if str(k) in scope}
        self._apply_backoffs()
        idle = self._safe(getattr(self._scheduler, "idle_executors",
                                  lambda: []), [])
        # grow takes GRANT units, not loose executors: on a process-
        # carved pod a unit is a whole host process (splitting one
        # between exclusive tenants would break carve disjointness)
        units = self._safe(getattr(self._scheduler, "idle_units",
                                   lambda: [[e] for e in idle]),
                           [[e] for e in idle])
        queued = self._safe(getattr(self._scheduler, "queued_jobs",
                                    lambda: []), [])
        plan["idle_executors"] = list(idle)
        plan["queued"] = [getattr(q, "job_id", str(q)) for q in queued]
        actions = self._decide(rows, tenants, idle, queued,
                               plan["considered"], units)
        budget = max_actions_per_window()
        for a in actions:
            a.ts = time.time()
            with self._lock:
                pending = a.job in self._inflight
            if pending:
                # an earlier action THIS window already fenced the job
                # (cooldown 0 + a multi-action budget could otherwise
                # stack contradictory fences on one attempt)
                a.outcome = "in_flight"
                plan["actions"].append(a.to_dict())
                continue
            ready = self.gate.observe(a.job, a.kind, wanted=True,
                                      signal=a.signal, now=now)
            if not ready:
                # name the actual blocker: an operator chasing a quiet
                # engine must land on the right knob
                a.outcome = ("cooldown"
                             if (self.gate.cooling(a.job, now=now)
                                 or self.gate.cooling(a.signal, now=now))
                             else "hysteresis")
            elif budget <= 0:
                a.outcome = "window_budget"
            else:
                budget -= 1
                self._execute(a, mode, now)
            plan["actions"].append(a.to_dict())
        # hysteresis means CONSECUTIVE windows: candidates the planner
        # stopped surfacing restart their streaks (and never leak).
        # Swept ONLY among this engine's action vocabulary — the input
        # autoscaler's streaks on the shared gate are not ours to reset
        self.gate.sweep({(a.job, a.kind) for a in actions},
                        among=_ACTION_KINDS,
                        subjects=({str(j) for j in jobs}
                                  if jobs is not None else None))
        return self._finish(plan, t0)

    # -- decision --------------------------------------------------------

    def protected_jobs(self, now: Optional[float] = None) -> "set[str]":
        """Tenants currently pinned by a fired `protect` action. Pins
        age out after a few periods — protection must be re-earned from
        live latency, exactly like every other signal-driven streak."""
        now = time.monotonic() if now is None else float(now)
        ttl = max(3.0 * policy_period(), policy_cooldown())
        with self._lock:
            for job in [j for j, ts in self._protected.items()
                        if now - ts > ttl]:
                del self._protected[job]
            return set(self._protected)

    def _decide(self, rows: Dict[str, Any], tenants: Dict[str, Any],
                idle: List[str], queued: List[Any],
                considered: List[Dict[str, Any]],
                units: Optional[List[List[str]]] = None
                ) -> List[PolicyAction]:
        """Pure planning over one window's sensor view (no side
        effects): at most one grow plus at most one contention action
        per window reach the gate."""
        from harmony_tpu.jobserver import elastic as _elastic

        grow_below = slo_grow_threshold()
        cap = _elastic.max_shrinks()

        # prune landed plans (the attempt advanced — or the job left);
        # a still-pending fence keeps its tenant out of this window
        with self._lock:
            for job in list(self._inflight):
                t = tenants.get(job)
                if t is None or int(t.get("attempt", 0)) > self._inflight[job]:
                    del self._inflight[job]
            inflight = set(self._inflight)
        tenants = {j: t for j, t in tenants.items() if j not in inflight}

        def row(job: str) -> Dict[str, Any]:
            return rows.get(job) or {}

        def prio(job: str) -> int:
            return int((tenants.get(job) or {}).get("priority", 0))

        grow_wants: List[Tuple[float, str]] = []
        for job, t in sorted(tenants.items()):
            r = row(job)
            att = (r.get("slo") or {}).get("attainment")
            cls = r.get("phase_class")
            note = {"job": job, "check": "grow", "attainment": att,
                    "class": cls, "priority": prio(job)}
            if att is None or att >= grow_below:
                note["blocked"] = "slo met or unknown"
            elif cls in _NO_GROW_CLASSES:
                note["blocked"] = f"{cls}: more devices would not help"
            elif int(t.get("attempt", 0)) >= cap:
                note["blocked"] = "elastic recovery budget exhausted"
            else:
                grow_wants.append((att, job))
            considered.append(note)
        grow_wants.sort(key=lambda x: (-prio(x[1]), x[0]))

        if units is None:
            units = [[e] for e in idle]
        actions: List[PolicyAction] = []
        # latency-sensitive serving tenants near/over their p99 SLO earn
        # a `protect` pin (gated and judged like every other action):
        # while pinned, their executors are exempt from pack/preempt
        # victim selection below
        protected = self.protected_jobs()
        for job in sorted(tenants):
            srv = row(job).get("serving") or {}
            p99 = srv.get("p99_ms")
            slo = srv.get("slo_p99_ms")
            if not srv.get("enabled") or p99 is None or not slo:
                continue
            note = {"job": job, "check": "protect", "p99_ms": p99,
                    "slo_p99_ms": slo}
            if p99 < float(slo) * _PROTECT_RATIO:
                note["blocked"] = "serving latency within SLO headroom"
            else:
                actions.append(PolicyAction(
                    "protect", job,
                    list((tenants.get(job) or {}).get("executors") or ()),
                    signal="serving_latency",
                    reason=(f"serving p99 {p99:.1f}ms at/over "
                            f"{_PROTECT_RATIO:.0%} of its {float(slo):.1f}ms "
                            "SLO: exempting executors from pack/preempt "
                            "victim selection"),
                    evidence={"serving": dict(srv)}))
                # the pin covers THIS cycle's victim sweep too — deciding
                # protect and preempt for the same tenant in one plan
                # would be self-contradictory
                protected.add(job)
            considered.append(note)
        if grow_wants and units:
            att, job = grow_wants[0]
            cur = list((tenants.get(job) or {}).get("executors") or ())
            # one GRANT UNIT per action (ramp, don't slosh): a single
            # executor normally, a whole host process on a carved pod
            add = [e for e in units[0] if e not in cur]
            if add:
                actions.append(PolicyAction(
                    "grow", job, cur + add,
                    reason=(f"SLO attainment {att:.2f} < {grow_below} "
                            "with idle capacity"),
                    evidence={"attainment": att,
                              "class": row(job).get("phase_class"),
                              "idle": list(idle), "unit": list(add)}))

        # contention: someone wants capacity nothing idle can satisfy
        claimants: List[Tuple[int, str]] = [
            (int(getattr(getattr(q, "params", None), "priority", 0)),
             getattr(q, "job_id", str(q))) for q in queued]
        if not units:
            claimants += [(prio(j), j) for _, j in grow_wants]
        if not claimants:
            return actions
        claim_prio, claim_job = max(claimants)
        # strictly lower priority only — equal priority never preempts
        # (or shrinks, or packs): contention between peers is the fair
        # queue's job, not the policy's. Tenants under an active
        # `protect` pin are exempt outright: a latency-critical serving
        # tenant's executors are not contention inventory
        victims = sorted(
            (j for j in tenants if prio(j) < claim_prio and j != claim_job
             and j not in protected),
            key=lambda j: (prio(j), j))
        note = {"check": "contention", "claimant": claim_job,
                "claim_priority": claim_prio,
                "victims": list(victims),
                "protected": sorted(protected)}
        considered.append(note)
        for victim in victims:
            t = tenants.get(victim) or {}
            if int(t.get("attempt", 0)) >= cap:
                continue
            execs = list(t.get("executors") or ())
            r = row(victim)
            cls = r.get("phase_class")
            wait = r.get("input_wait_frac")
            packable = (cls in _PACKABLE_CLASSES
                        or (wait is not None and wait >= 0.5))
            if len(execs) > 1:
                keep = execs[:max(1, len(execs) // 2)]
                actions.append(PolicyAction(
                    "shrink", victim, keep,
                    reason=(f"contention: {claim_job} (priority "
                            f"{claim_prio}) waits; shrinking priority "
                            f"{prio(victim)} tenant to {len(keep)} "
                            "executor(s)"),
                    evidence={"claimant": claim_job, "class": cls,
                              "released": execs[len(keep):]}))
                break
            host = self._pack_host(victim, tenants, rows,
                                   exclude=(claim_job,))
            if host is None:
                continue
            kind = "pack" if packable else "preempt"
            signal = ("input_wait" if (packable and cls == "input-bound")
                      else "device")
            actions.append(PolicyAction(
                kind, victim,
                list((tenants.get(host) or {}).get("executors") or ()),
                shared=True, signal=signal,
                reason=(f"contention: {claim_job} (priority {claim_prio}) "
                        f"waits; {kind}ing "
                        + (f"{cls or 'low-utilization'} tenant "
                           if packable else
                           f"priority {prio(victim)} tenant ")
                        + f"onto {host}'s executors (shared)"),
                evidence={"claimant": claim_job, "host": host,
                          "class": cls, "input_wait_frac": wait,
                          "released": execs}))
            break
        return actions

    def _pack_host(self, victim: str, tenants: Dict[str, Any],
                   rows: Dict[str, Any],
                   exclude: Tuple[str, ...] = ()) -> Optional[str]:
        """The sibling a packed/preempted victim overlaps: the
        lowest-priority OTHER tenant that still holds executors,
        preferring one whose own class is packable (two idle-device
        tenants sharing one carve is the cheapest shape). ``exclude``
        bars the CLAIMANT — overlapping the victim onto the tenant the
        action is meant to help would steal back the cycles it frees."""
        best: Optional[Tuple[int, int, str]] = None
        for job, t in sorted(tenants.items()):
            if job == victim or job in exclude or not t.get("executors"):
                continue
            cls = (rows.get(job) or {}).get("phase_class")
            rank = (0 if cls in _PACKABLE_CLASSES else 1,
                    int(t.get("priority", 0)), job)
            if best is None or rank < best:
                best = rank
        return best[2] if best else None

    # -- execution -------------------------------------------------------

    def _execute(self, a: PolicyAction, mode: str, now: float) -> None:
        r = self._safe(self._ledger_fn, {}).get(a.job) or {}
        a.baseline = {"mfu": r.get("mfu"),
                      "attainment": (r.get("slo") or {}).get("attainment"),
                      "samples_per_sec": r.get("samples_per_sec")}
        if self._leader_ok_fn is not None and not self._leader_ok_fn():
            # the HA fence, policy half: a deposed leader must not
            # reshape the pod it no longer owns — same contract as its
            # refused TCP mutations and dropped durable appends
            a.outcome = "rejected_not_leader"
            with self._lock:
                self._rejected_total += 1
            self._record(a)
            return
        if a.kind == "protect":
            # the protect actuator is planner-side state, not a fence:
            # the pin exempts the tenant from victim selection in every
            # later window until it ages out. It executes in advise
            # mode too — exempting a victim moves no executor, so the
            # "advisory plans never reshape the pod" contract holds
            a.executed = True
            a.outcome = "pinned"
            self.gate.fired(a.job, a.kind, signal=a.signal, now=now)
            with self._lock:
                self._actions_total += 1
                self._protected[a.job] = now
            self._record(a)
            return
        if mode != "act" or self._fence_fn is None:
            a.outcome = "advisory"
            # subject-only cooldown (signal=None): the dry run paces its
            # own re-planning but must never throttle the LIVE input
            # autoscaler sharing the input_wait signal scope
            self.gate.fired(a.job, a.kind, signal=None, now=now)
            self._record(a)
            return
        try:
            self._scheduler.plan_grant(a.job, a.executors, shared=a.shared)
            epoch = self._fence_fn(a.job, a.fence_kind)
        except Exception as e:  # noqa: BLE001 - surfaced in the plan
            self._scheduler.plan_grant(a.job, None)
            a.outcome = f"error: {type(e).__name__}: {e}"[:200]
            self._record(a)
            return
        if epoch is None:
            self._scheduler.plan_grant(a.job, None)
            a.outcome = "skipped_no_fence"
            self._record(a)
            return
        a.executed = True
        a.outcome = "fenced"
        a.epoch = int(epoch)
        self.gate.fired(a.job, a.kind, signal=a.signal, now=now)
        att = int((self._safe(self._tenants_fn, {}).get(a.job)
                   or {}).get("attempt", 0))
        with self._lock:
            self._actions_total += 1
            self._inflight[a.job] = att
        self._record(a)

    def _record(self, a: PolicyAction) -> None:
        """Structured ``kind="policy"`` joblog event (HA-replicated via
        the joblog sink tee) + the bounded recent ring + sinks."""
        d = a.to_dict()
        with self._lock:
            self._recent.append(d)
            del self._recent[:-64]
        try:
            from harmony_tpu.jobserver.joblog import record_event

            record_event(a.job, "policy", action=a.kind,
                         executors=list(a.executors), shared=a.shared,
                         reason=a.reason, outcome=a.outcome,
                         executed=a.executed, fence_epoch=a.epoch,
                         baseline=dict(a.baseline), signal=a.signal)
        except Exception:
            pass  # a joblog hiccup must not fail the control loop
        for sink in self._sinks:
            try:
                sink(d)
            except Exception:
                pass  # sinks are best-effort by contract

    def _apply_backoffs(self) -> None:
        """``rebalance_ineffective`` diagnoses back their tenant off —
        each diagnosis exactly once."""
        if self._diagnoses_fn is None:
            return
        for d in self._safe(self._diagnoses_fn, []):
            if d.get("rule") != "rebalance_ineffective":
                continue
            job = d.get("job")
            # key the dedup on the judged ACTION's timestamp, not the
            # diagnosis's: a re-diagnosis of the same action in a later
            # doctor window must not back the tenant off twice
            ev = (d.get("evidence") or {}).get("policy_event") or {}
            ts = float(ev.get("ts") or d.get("ts") or 0.0)
            if not job or self._backoff_seen.get(job, -1.0) >= ts:
                continue
            self._backoff_seen[job] = ts
            self.gate.back_off(job)

    # -- surfaces --------------------------------------------------------

    def _finish(self, plan: Dict[str, Any], t0: float) -> Dict[str, Any]:
        ms = (time.perf_counter() - t0) * 1000.0
        with self._lock:
            self._evaluations += 1
            self._last_eval_ms = ms
            self._last_plan = plan
        return plan

    def status(self) -> Dict[str, Any]:
        """The STATUS ``policy`` section / ``obs plan`` payload."""
        with self._lock:
            return {
                "mode": policy_mode(),
                "period_sec": policy_period(),
                "evaluations": self._evaluations,
                "eval_ms": round(self._last_eval_ms, 3),
                "actions_total": self._actions_total,
                "rejected_total": self._rejected_total,
                "last_plan": dict(self._last_plan),
                "recent_actions": list(self._recent)[-16:],
                "gate": self.gate.stats(),
                "protected": sorted(self._protected),
            }

    @staticmethod
    def _safe(fn: Callable[[], Any], default: Any) -> Any:
        try:
            out = fn()
        except Exception:
            return default
        return default if out is None else out
