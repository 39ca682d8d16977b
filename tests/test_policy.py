"""Telemetry-driven device policy engine (jobserver/policy.py).

Fast tier: ActionGate cooldown/hysteresis/backoff semantics, every
action type (grow, shrink, pack, preempt, no-op under hysteresis) over
synthetic ledger/diagnosis scenarios, deposed-leader rejection (the HA
fence, policy half), the scheduler SPI (plan_grant targets, shared
overlap accounting, idle/queued surfaces), the shared gate contract
with the input autoscaler, and the ``rebalance_ineffective`` doctor
rule. Slow tier: a two-tenant acceptance where an under-SLO tenant is
grown onto an idle executor through a REAL elastic fence with loss
parity against an uninterrupted run.
"""
import time

import pytest

from harmony_tpu.config.params import JobConfig, TrainerParams
from harmony_tpu.jobserver import joblog
from harmony_tpu.jobserver.policy import ActionGate, PolicyEngine
from harmony_tpu.jobserver.scheduler import CarveScheduler, JobScheduler


@pytest.fixture(autouse=True)
def _clean_events():
    joblog.clear_events()
    yield
    joblog.clear_events()


# -- gate semantics -------------------------------------------------------


class TestActionGate:
    def test_hysteresis_needs_consecutive_windows(self):
        g = ActionGate(cooldown_sec=0.0, confirm=2, stale_after=999.0)
        assert not g.observe("t1", "grow", True, now=0.0)
        assert g.observe("t1", "grow", True, now=1.0)
        # an unwanted window resets the streak
        assert not g.observe("t1", "grow", False, now=2.0)
        assert not g.observe("t1", "grow", True, now=3.0)
        assert g.observe("t1", "grow", True, now=4.0)

    def test_stale_streak_restarts(self):
        g = ActionGate(cooldown_sec=0.0, confirm=2, stale_after=5.0)
        assert not g.observe("t1", "grow", True, now=0.0)
        # the signal vanished for longer than stale_after: restart at 1
        assert not g.observe("t1", "grow", True, now=100.0)
        assert g.observe("t1", "grow", True, now=101.0)

    def test_cooldown_blocks_subject_and_signal(self):
        g = ActionGate(cooldown_sec=10.0, confirm=1, stale_after=999.0)
        assert g.observe("t1", "grow", True, now=0.0)
        g.fired("t1", "grow", signal="device", now=0.0)
        # same subject, different action: cooled
        assert not g.observe("t1", "shrink", True, now=5.0)
        # different subject, SAME signal: cooled too
        assert not g.observe("t2", "grow", True, signal="device", now=5.0)
        # different signal escapes the signal cooldown
        assert g.observe("t2", "pack", True, signal="input_wait", now=5.0)
        # cooldowns expire
        assert g.observe("t1", "grow", True, now=11.0)

    def test_backoff_multiplies_cooldown(self):
        g = ActionGate(cooldown_sec=10.0, confirm=1, stale_after=999.0,
                       backoff_factor=4.0)
        g.back_off("t1", now=0.0)
        assert not g.observe("t1", "grow", True, now=30.0)  # 4x10 = 40
        assert g.observe("t1", "grow", True, now=41.0)
        assert g.stats()["backoffs"] == {"t1": 1}


# -- the engine over synthetic scenarios ----------------------------------


class FakeScheduler:
    def __init__(self, idle=(), queued=()):
        self.idle = list(idle)
        self.queued = list(queued)
        self.grants = {}

    def idle_executors(self):
        return list(self.idle)

    def queued_jobs(self):
        return list(self.queued)

    def plan_grant(self, job_id, executors, shared=False):
        if executors is None:
            self.grants.pop(job_id, None)
        else:
            self.grants[job_id] = (list(executors), bool(shared))


def _row(att=None, cls=None, wait=None, mfu=None, sps=None):
    return {"slo": {"attainment": att}, "phase_class": cls,
            "input_wait_frac": wait, "mfu": mfu, "samples_per_sec": sps}


def _queued(job_id, priority):
    return JobConfig(job_id=job_id, app_type="dolphin",
                     params=TrainerParams(priority=priority))


def _engine(rows, tenants, sched, fences=None, gate=None,
            diagnoses=None, leader_ok=None):
    fences = fences if fences is not None else []

    def fence(job, kind):
        fences.append((job, kind))
        return 7

    return PolicyEngine(
        scheduler=sched,
        ledger_fn=lambda: rows,
        tenants_fn=lambda: tenants,
        fence_fn=fence,
        diagnoses_fn=(lambda: diagnoses or []),
        leader_ok_fn=leader_ok,
        gate=gate or ActionGate(cooldown_sec=0.0, confirm=1,
                                stale_after=999.0),
    )


@pytest.fixture()
def act_mode(monkeypatch):
    monkeypatch.setenv("HARMONY_POLICY", "act")


class TestDecisions:
    def test_grow_under_slo_onto_idle(self, act_mode):
        sched = FakeScheduler(idle=["e1"])
        fences = []
        eng = _engine({"a": _row(att=0.3, cls="compute-bound")},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}},
                      sched, fences)
        plan = eng.evaluate()
        (a,) = plan["actions"]
        assert a["kind"] == "grow" and a["outcome"] == "fenced"
        assert a["executed"] and a["epoch"] == 7
        assert fences == [("a", "regrow")]
        assert sched.grants["a"] == (["e0", "e1"], False)
        # the action landed as a structured joblog event (the HA log
        # tee rides the joblog sink, so this IS the replicated record)
        evs = [e for e in joblog.job_events("a") if e["kind"] == "policy"]
        assert evs and evs[-1]["action"] == "grow" and evs[-1]["executed"]

    def test_noop_under_hysteresis(self, act_mode):
        sched = FakeScheduler(idle=["e1"])
        fences = []
        gate = ActionGate(cooldown_sec=0.0, confirm=2, stale_after=999.0)
        eng = _engine({"a": _row(att=0.3, cls="compute-bound")},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}},
                      sched, fences, gate=gate)
        plan = eng.evaluate()
        assert [a["outcome"] for a in plan["actions"]] == ["hysteresis"]
        assert not fences and not sched.grants
        plan = eng.evaluate()
        assert [a["outcome"] for a in plan["actions"]] == ["fenced"]
        assert fences == [("a", "regrow")]

    @pytest.mark.parametrize("cls,ledger_extra", [
        ("input-bound", {}), ("dispatch-bound", {}), ("comm-bound", {}),
        # the row a worker of before PR 27 fed: the `async` lever went
        # with the async step mode, so a comm-bound tenant under its SLO
        # is blocked like the others — no action, no fence, no relaunch
        ("comm-bound", {"async": {"available": True, "enabled": False}}),
    ], ids=["input-bound", "dispatch-bound", "comm-bound",
            "comm-bound-old-async-row"])
    def test_grow_blocked_for_non_compute_bound(self, act_mode, cls,
                                                ledger_extra):
        sched = FakeScheduler(idle=["e1"])
        fences = []
        eng = _engine({"a": {**_row(att=0.3, cls=cls), **ledger_extra}},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}},
                      sched, fences)
        plan = eng.evaluate()
        assert plan["actions"] == [] and not fences
        (note,) = [c for c in plan["considered"] if c.get("job") == "a"]
        assert note["blocked"] == f"{cls}: more devices would not help"
        assert set(note) == {"job", "check", "attainment", "class",
                             "priority", "blocked"}

    def test_shrink_low_priority_under_contention(self, act_mode):
        sched = FakeScheduler(idle=[], queued=[_queued("hi", 2)])
        fences = []
        eng = _engine({"lo": _row(att=1.0, cls="compute-bound")},
                      {"lo": {"executors": ["e0", "e1"], "attempt": 0,
                              "priority": 0}},
                      sched, fences)
        plan = eng.evaluate()
        (a,) = plan["actions"]
        assert a["kind"] == "shrink" and a["outcome"] == "fenced"
        assert fences == [("lo", "shrink")]
        assert sched.grants["lo"] == (["e0"], False)

    def test_pack_idle_device_victim_onto_sibling(self, act_mode):
        sched = FakeScheduler(idle=[], queued=[_queued("hi", 1)])
        fences = []
        eng = _engine(
            {"a-victim": _row(cls="dispatch-bound"),
             "b-host": _row(cls="input-bound")},
            {"a-victim": {"executors": ["e1"], "attempt": 0,
                          "priority": 0},
             "b-host": {"executors": ["e0"], "attempt": 0,
                        "priority": 0}},
            sched, fences)
        plan = eng.evaluate()
        (a,) = plan["actions"]
        assert a["kind"] == "pack" and a["shared"]
        assert a["executors"] == ["e0"]
        assert fences == [("a-victim", "shrink")]
        assert sched.grants["a-victim"] == (["e0"], True)

    def test_input_bound_pack_shares_the_input_wait_signal(self, act_mode):
        """A pack justified by input-boundness fires on the SAME signal
        the input autoscaler scales on — one cooldown scope, no
        fighting."""
        sched = FakeScheduler(idle=[], queued=[_queued("hi", 1)])
        gate = ActionGate(cooldown_sec=60.0, confirm=1, stale_after=999.0)
        eng = _engine(
            {"lo": _row(cls="input-bound", wait=0.8),
             "host": _row(cls="input-bound", wait=0.7)},
            {"lo": {"executors": ["e1"], "attempt": 0, "priority": 0},
             "host": {"executors": ["e0"], "attempt": 0, "priority": 0}},
            sched, gate=gate)
        plan = eng.evaluate()
        (a,) = plan["actions"]
        assert a["kind"] == "pack" and a["signal"] == "input_wait"
        assert a["outcome"] == "fenced"
        # the shared signal is now cooling: the input autoscaler's next
        # step on input_wait is gated off
        assert gate.cooling("input_wait")

    def test_preempt_unpackable_victim_on_priority(self, act_mode):
        sched = FakeScheduler(idle=[], queued=[_queued("hi", 1)])
        fences = []
        eng = _engine(
            {"a-victim": _row(cls="compute-bound"),
             "b-host": _row(cls="compute-bound")},
            {"a-victim": {"executors": ["e1"], "attempt": 0,
                          "priority": 0},
             "b-host": {"executors": ["e0"], "attempt": 0,
                        "priority": 0}},
            sched, fences)
        plan = eng.evaluate()
        (a,) = plan["actions"]
        assert a["kind"] == "preempt" and a["shared"]
        assert a["executors"] == ["e0"]
        assert fences == [("a-victim", "shrink")]

    def test_equal_priority_never_preempts(self, act_mode):
        sched = FakeScheduler(idle=[], queued=[_queued("peer", 0)])
        fences = []
        eng = _engine(
            {"lo": _row(cls="compute-bound")},
            {"lo": {"executors": ["e1"], "attempt": 0, "priority": 0}},
            sched, fences)
        plan = eng.evaluate()
        assert plan["actions"] == [] and not fences

    def test_recovery_budget_exhausted_tenant_untouched(self, act_mode,
                                                        monkeypatch):
        monkeypatch.setenv("HARMONY_ELASTIC_MAX_SHRINKS", "2")
        sched = FakeScheduler(idle=["e1"])
        fences = []
        eng = _engine({"a": _row(att=0.3, cls="compute-bound")},
                      {"a": {"executors": ["e0"], "attempt": 2,
                             "priority": 0}},
                      sched, fences)
        plan = eng.evaluate()
        assert plan["actions"] == [] and not fences
        (note,) = [c for c in plan["considered"] if c.get("job") == "a"]
        assert "budget" in note["blocked"]

    def test_deposed_leader_actions_rejected(self, act_mode):
        """The HA fence, policy half: a deposed leader must not reshape
        the pod it no longer owns — the action is rejected before any
        grant or fence, mirroring its refused TCP mutations."""
        sched = FakeScheduler(idle=["e1"])
        fences = []
        eng = _engine({"a": _row(att=0.3, cls="compute-bound")},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}},
                      sched, fences, leader_ok=lambda: False)
        plan = eng.evaluate()
        (a,) = plan["actions"]
        assert a["outcome"] == "rejected_not_leader" and not a["executed"]
        assert not fences and not sched.grants
        assert eng.status()["rejected_total"] == 1
        evs = [e for e in joblog.job_events("a") if e["kind"] == "policy"]
        assert evs and evs[-1]["outcome"] == "rejected_not_leader"

    def test_advisory_mode_plans_but_never_fences(self, monkeypatch):
        monkeypatch.setenv("HARMONY_POLICY", "advise")
        sched = FakeScheduler(idle=["e1"])
        fences = []
        gate = ActionGate(cooldown_sec=60.0, confirm=1, stale_after=999.0)
        eng = _engine({"a": _row(att=0.3, cls="compute-bound")},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}},
                      sched, fences, gate=gate)
        plan = eng.evaluate()
        (a,) = plan["actions"]
        assert a["outcome"] == "advisory" and not a["executed"]
        assert not fences and not sched.grants
        # the dry run cools its SUBJECT (paced re-planning) but never
        # the shared signal — advise mode must not throttle the live
        # input autoscaler off the same stall scope
        assert gate.cooling("a")
        assert not gate.cooling("device")

    def test_hysteresis_is_strictly_consecutive(self, act_mode):
        """A window where the candidate vanishes resets its streak —
        non-consecutive wanting windows can never sum to CONFIRM."""
        rows = {"a": _row(att=0.3, cls="compute-bound")}
        tenants = {"a": {"executors": ["e0"], "attempt": 0,
                         "priority": 0}}
        sched = FakeScheduler(idle=["e1"])
        fences = []
        gate = ActionGate(cooldown_sec=0.0, confirm=2, stale_after=999.0)
        eng = _engine(rows, tenants, sched, fences, gate=gate)
        assert [a["outcome"] for a in eng.evaluate()["actions"]] == \
            ["hysteresis"]
        # the tenant recovers for one window: candidate not surfaced
        rows["a"] = _row(att=1.0, cls="compute-bound")
        assert eng.evaluate()["actions"] == []
        # dips again: streak restarted at 1 — still gated
        rows["a"] = _row(att=0.3, cls="compute-bound")
        assert [a["outcome"] for a in eng.evaluate()["actions"]] == \
            ["hysteresis"]
        assert [a["outcome"] for a in eng.evaluate()["actions"]] == \
            ["fenced"]

    def test_one_fence_per_attempt_even_with_zero_cooldown(
            self, act_mode, monkeypatch):
        """cooldown=0 + a multi-action budget must still never stack a
        second fence on the same attempt: the in-flight check covers
        every action in the window, not just _decide entry."""
        monkeypatch.setenv("HARMONY_POLICY_MAX_ACTIONS", "4")
        # "a" is BOTH the grow candidate (idle exists) and the
        # contention victim (higher-priority queued claimant)
        sched = FakeScheduler(idle=["e1"], queued=[_queued("hi", 2)])
        fences = []
        eng = _engine(
            {"a": _row(att=0.3, cls="compute-bound")},
            {"a": {"executors": ["e0", "e2"], "attempt": 0,
                   "priority": 0}},
            sched, fences)
        plan = eng.evaluate()
        outcomes = [x["outcome"] for x in plan["actions"]]
        assert outcomes == ["fenced", "in_flight"]
        assert len(fences) == 1

    def test_off_mode_is_inert(self, monkeypatch):
        monkeypatch.setenv("HARMONY_POLICY", "off")
        sched = FakeScheduler(idle=["e1"])
        eng = _engine({"a": _row(att=0.1)},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}}, sched)
        plan = eng.evaluate()
        assert plan["mode"] == "off" and plan["actions"] == []

    def test_rebalance_ineffective_diagnosis_backs_off(self, act_mode):
        sched = FakeScheduler(idle=["e1"])
        fences = []
        gate = ActionGate(cooldown_sec=10.0, confirm=1, stale_after=999.0)
        eng = _engine({"a": _row(att=0.3, cls="compute-bound")},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}},
                      sched, fences, gate=gate,
                      diagnoses=[{"rule": "rebalance_ineffective",
                                  "job": "a", "ts": 123.0}])
        plan = eng.evaluate()
        # the diagnosis backed the subject off BEFORE the decision ran:
        # the grow stays planned but gated — and the outcome names the
        # ACTUAL blocker (a cooling subject), not hysteresis
        assert [x["outcome"] for x in plan["actions"]] == ["cooldown"]
        assert not fences
        assert gate.stats()["backoffs"] == {"a": 1}
        # the same diagnosis never backs off twice
        eng.evaluate()
        assert gate.stats()["backoffs"] == {"a": 1}

    def test_rediagnosed_action_backs_off_once(self, act_mode):
        """A later doctor window re-diagnosing the SAME policy action
        (same event ts) must not double the backoff — the dedup keys on
        the judged action, not the diagnosis."""
        gate = ActionGate(cooldown_sec=10.0, confirm=1, stale_after=999.0)
        diags = [{"rule": "rebalance_ineffective", "job": "a",
                  "ts": 200.0,
                  "evidence": {"policy_event": {"ts": 100.0}}}]
        eng = _engine({}, {}, FakeScheduler(), gate=gate, diagnoses=diags)
        eng.evaluate()
        diags.append({"rule": "rebalance_ineffective", "job": "a",
                      "ts": 500.0,
                      "evidence": {"policy_event": {"ts": 100.0}}})
        eng.evaluate()
        assert gate.stats()["backoffs"] == {"a": 1}

    def test_window_budget_caps_actions(self, act_mode, monkeypatch):
        monkeypatch.setenv("HARMONY_POLICY_MAX_ACTIONS", "1")
        # a grow candidate AND a queued claimant with a shrinkable
        # victim: two plannable actions, one budget slot
        sched = FakeScheduler(idle=["e3"], queued=[_queued("hi", 2)])
        fences = []
        eng = _engine(
            {"a": _row(att=0.3, cls="compute-bound"),
             "lo": _row(cls="compute-bound")},
            {"a": {"executors": ["e0"], "attempt": 0, "priority": 1},
             "lo": {"executors": ["e1", "e2"], "attempt": 0,
                    "priority": 0}},
            sched, fences)
        plan = eng.evaluate()
        outcomes = sorted(a["outcome"] for a in plan["actions"])
        assert outcomes == ["fenced", "window_budget"]
        assert len(fences) == 1

    def test_obs_plan_renderer(self, act_mode):
        from harmony_tpu.cli import _render_policy

        sched = FakeScheduler(idle=["e1"])
        eng = _engine({"a": _row(att=0.3, cls="compute-bound")},
                      {"a": {"executors": ["e0"], "attempt": 0,
                             "priority": 0}}, sched)
        eng.evaluate()
        text = "\n".join(_render_policy(eng.status()))
        assert "mode=act" in text and "grow" in text and "a" in text
        assert "gate:" in text


    def test_sweep_spares_other_loops_on_a_shared_gate(self, act_mode):
        """The engine's per-window sweep resets only ITS OWN action
        vocabulary — the input autoscaler's streaks on the shared gate
        survive every policy evaluation."""
        gate = ActionGate(cooldown_sec=0.0, confirm=2, stale_after=999.0)
        eng = _engine({}, {}, FakeScheduler(), gate=gate)
        # the autoscaler has one wanting tick banked
        assert not gate.observe("input_workers", "up", True,
                                signal="input_wait")
        eng.evaluate()  # plans nothing; sweeps its own kinds only
        # the banked streak survived: the SECOND tick confirms
        assert gate.observe("input_workers", "up", True,
                            signal="input_wait")

    def test_pack_host_never_the_claimant(self, act_mode):
        """An under-SLO grower claiming capacity must not become the
        pack host — overlapping the victim onto the claimant would
        steal back the cycles the action frees."""
        sched = FakeScheduler(idle=[])  # nothing idle: grower claims
        fences = []
        eng = _engine(
            {"a-victim": _row(cls="input-bound", wait=0.8),
             "z-claim": _row(att=0.3, cls="compute-bound")},
            {"a-victim": {"executors": ["e1"], "attempt": 0,
                          "priority": 0},
             "z-claim": {"executors": ["e0"], "attempt": 0,
                         "priority": 1}},
            sched, fences)
        plan = eng.evaluate()
        # the only possible host is the claimant itself -> no action
        assert plan["actions"] == [] and not fences


# -- scheduler SPI --------------------------------------------------------


class TestSchedulerSPI:
    def test_base_reacquire_honors_planned_grant(self):
        s = JobScheduler()
        s.bind(["e0", "e1", "e2"], lambda c, e: None)
        s.plan_grant("j", ["e0", "e1"])
        assert s.reacquire("j", ["e2"]) == ["e0", "e1"]
        # one-shot: consumed
        assert s.reacquire("j", ["e2"]) == ["e2"]

    def test_carve_exclusive_target_takes_only_free(self):
        s = CarveScheduler(min_slice=1, max_share=1)
        launched = []
        s.bind(["e0", "e1"], lambda c, e: launched.append((c.job_id, e)))
        s.on_job_arrival(_queued("a", 0))
        assert s.slice_of("a") == ["e0"]
        assert s.idle_executors() == ["e1"]
        # grow target: a's slice came back to free at attempt end
        s.plan_grant("a", ["e0", "e1"])
        s.on_job_finish("a")
        assert s.reacquire("a", ["e0"]) == ["e0", "e1"]
        assert s.idle_executors() == []

    def test_carve_shared_target_overlaps_and_frees_last(self):
        s = CarveScheduler(min_slice=1, max_share=1)
        launched = []
        s.bind(["e0", "e1"], lambda c, e: launched.append((c.job_id, e)))
        s.on_job_arrival(_queued("a", 0))
        s.on_job_arrival(_queued("b", 0))
        assert s.slice_of("a") == ["e0"] and s.slice_of("b") == ["e1"]
        s.on_job_arrival(_queued("c", 1))
        assert s.queued_jobs() and s.queued_jobs()[0].job_id == "c"
        # pack b onto a's executor: b's next grant overlaps a
        s.plan_grant("b", ["e0"], shared=True)
        s.on_job_finish("b")          # attempt ends; e1 frees -> c launches
        assert ("c", ["e1"]) in launched
        assert s.reacquire("b", ["e1"]) == ["e0"]  # the shared grant
        # a finishing must NOT free e0 while b still holds it
        s.on_job_finish("a")
        assert "e0" not in s.idle_executors()
        s.on_job_finish("b")
        s.on_job_finish("c")
        assert sorted(s.idle_executors()) == ["e0", "e1"]

    def test_carve_unsatisfiable_target_falls_back(self):
        s = CarveScheduler(min_slice=1)
        s.bind(["e0", "e1"], lambda c, e: None)
        s.on_job_arrival(_queued("a", 0))  # takes both (no max_share)
        s.plan_grant("b", ["e9"])          # unknown executor
        # target dead -> normal carve path (nothing free -> [])
        assert s.reacquire("b", []) == []

    def test_plan_grant_clear(self):
        s = JobScheduler()
        s.bind(["e0"], lambda c, e: None)
        s.plan_grant("j", ["e0"])
        s.plan_grant("j", None)
        assert s.planned_grant("j") is None

    def test_process_carve_units_and_whole_process_backstop(self):
        from harmony_tpu.jobserver.scheduler import ProcessCarveScheduler

        s = ProcessCarveScheduler(min_procs=1)
        s.bind(["p0e0", "p0e1", "p1e0", "p1e1"], lambda c, e: None)
        s.set_process_map({"p0e0": 0, "p0e1": 0, "p1e0": 1, "p1e1": 1})
        # idle capacity reports in WHOLE-process units
        assert s.idle_units() == [["p0e0", "p0e1"], ["p1e0", "p1e1"]]
        # an exclusive target splitting a process is rejected outright
        s.plan_grant("j", ["p0e0"])
        granted = s.reacquire("j", [])
        assert set(granted) != {"p0e0"}  # the split grant never lands
        s.on_job_finish("j")
        # a whole-process target lands as planned
        s.plan_grant("k", ["p1e0", "p1e1"])
        assert sorted(s.reacquire("k", [])) == ["p1e0", "p1e1"]


# -- dashboard surface ----------------------------------------------------


class TestDashboardPolicyApi:
    def test_posted_policy_rows_served_per_job_and_clusterwide(self):
        import json as _json
        import urllib.request

        from harmony_tpu.dashboard.server import DashboardServer

        server = DashboardServer().start()
        try:
            for i, (jid, kind) in enumerate(
                    [("t-a", "pack"), ("t-a", "grow"), ("t-b", "shrink")]):
                req = urllib.request.Request(
                    server.url + "/api/metrics",
                    data=_json.dumps({
                        "job_id": jid, "kind": "policy",
                        "payload": {"kind": kind, "job": jid,
                                    "outcome": "fenced",
                                    "reason": f"r{i}"}}).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req).read()
            one = _json.loads(urllib.request.urlopen(
                server.url + "/api/policy?job_id=t-a").read())
            assert [a["kind"] for a in one["actions"]] == ["pack", "grow"]
            allr = _json.loads(urllib.request.urlopen(
                server.url + "/api/policy").read())
            assert len(allr["actions"]) == 3  # oldest first, both jobs
            assert allr["actions"][-1]["job_id"] == "t-b"
        finally:
            server.stop()


# -- the rebalance_ineffective doctor rule --------------------------------


class TestRebalanceIneffectiveRule:
    def _diagnose(self, after_vals, monkeypatch):
        from harmony_tpu.metrics.doctor import Doctor
        from harmony_tpu.metrics.history import HistoryStore

        monkeypatch.setenv("HARMONY_POLICY_PERIOD", "1")  # judge age 2s
        store = HistoryStore(window_sec=60.0, resolution_sec=1.0)
        now = time.time()
        act_ts = now - 10.0
        labels = {"job": "t1", "attempt": "t1"}
        for i, v in enumerate([0.5, 0.5, 0.5]):
            store.ingest("tenant.slo_attainment", labels, v,
                         ts=act_ts - 6 + i)
        for i, v in enumerate(after_vals):
            store.ingest("tenant.slo_attainment", labels, v,
                         ts=act_ts + 2 + i * 2)
        events = {"t1": [{"kind": "policy", "executed": True,
                          "ts": act_ts, "action": "grow",
                          "outcome": "fenced"}]}
        doc = Doctor(store, events_fn=lambda: events)
        return [d for d in doc.diagnose(now=now)
                if d.rule == "rebalance_ineffective"]

    def test_fires_when_action_changed_nothing(self, monkeypatch):
        out = self._diagnose([0.5, 0.5, 0.5], monkeypatch)
        assert len(out) == 1
        d = out[0]
        assert d.job == "t1"
        assert d.evidence["policy_event"]["action"] == "grow"
        assert "tenant.slo_attainment" in d.evidence["series"]

    def test_silent_when_tenant_improved(self, monkeypatch):
        assert self._diagnose([0.8, 0.9, 0.9], monkeypatch) == []

    def test_silent_without_post_action_data(self, monkeypatch):
        assert self._diagnose([], monkeypatch) == []


# -- slow acceptance: a REAL grow through a REAL fence --------------------


EPOCHS = 32


def _elastic_cfg(job_id, epochs=EPOCHS, slo=None, elastic=True, seed=3):
    user = {"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
            "data_args": {"n": 64, "num_features": 16, "num_classes": 4,
                          "seed": seed}}
    if elastic:
        user["elastic_shrink"] = True
    return JobConfig(
        job_id=job_id, app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=epochs, num_mini_batches=2, model_chkp_period=1,
            target_samples_per_sec=(slo or 0.0),
            app_params={"num_classes": 4, "num_features": 16,
                        "features_per_partition": 4, "step_size": 0.1},
        ),
        num_workers=1,
        user=user,
    )


@pytest.mark.slow
class TestGrowAcceptance:
    def test_under_slo_tenant_grows_onto_idle_executor_with_parity(
            self, tmp_path, monkeypatch):
        """The closed loop end to end, in one process: tenant churn
        frees an executor, the ledger says the surviving tenant misses
        its SLO, the policy engine grows it onto the idle executor
        through a REAL re-grow fence, and the regrown submission lands
        numerically exactly where an uninterrupted run lands."""
        monkeypatch.setenv("HARMONY_POLICY", "act")
        monkeypatch.setenv("HARMONY_POLICY_PERIOD", "0.2")
        monkeypatch.setenv("HARMONY_POLICY_COOLDOWN", "5")
        monkeypatch.setenv("HARMONY_POLICY_CONFIRM", "2")
        from harmony_tpu.jobserver.pod import PodJobServer

        srv = PodJobServer(
            num_executors=2, num_followers=0,
            scheduler=CarveScheduler(min_slice=1, max_share=1),
            chkp_root=str(tmp_path / "chkp"))
        srv.start()
        srv.serve_pod(0)
        try:
            # churn: a short-lived co-tenant occupies (then frees) e1 —
            # the idle capacity the policy will spend
            srv.submit(_elastic_cfg("pol-churn", epochs=1, elastic=False,
                                    seed=9)).result(timeout=180)
            fut = srv.submit(_elastic_cfg("pol-grow", slo=1e9))
            # wait for the sensor layer: the tenant active AND its
            # ledger attainment known (first epoch-window drain)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                rows = srv.metrics.tenant_ledger()
                att = ((rows.get("pol-grow") or {}).get("slo")
                       or {}).get("attainment")
                with srv._pod_cond:
                    active = "pol-grow" in srv._elastic_active
                if att is not None and active:
                    break
                time.sleep(0.05)
            assert att is not None, "ledger never learned the SLO gap"
            # drive the loop deterministically: evaluate until the grow
            # fences (hysteresis needs two consecutive windows)
            fenced = False
            for _ in range(400):
                plan = srv.policy.evaluate()
                if any(a["outcome"] == "fenced" and a["kind"] == "grow"
                       for a in plan["actions"]):
                    fenced = True
                    break
                if fut.future.done() if hasattr(fut, "future") else False:
                    break
                time.sleep(0.05)
            assert fenced, f"policy never grew: {plan}"
            res = fut.result(timeout=300)
            meta = res["elastic"]
            assert meta["attempts"] == 2 and meta["recoveries"] == 1
            (grow_ev,) = [e for e in meta["events"]
                          if e["kind"] == "elastic_regrow"]
            # the regrown attempt holds BOTH executors — the idle one
            # was actually spent
            assert len(grow_ev["executors"]) == 2
            # the action is on the record: structured policy event +
            # STATUS policy section + the fence event marked policy
            pol = [e for e in joblog.job_events("pol-grow", limit=64)
                   if e["kind"] == "policy" and e.get("executed")]
            assert pol and pol[-1]["action"] == "grow"
            status = srv._status()
            assert status["policy"]["actions_total"] >= 1
            kinds = [(e["kind"], e.get("origin")) for e in
                     status["elastic"]["events"]
                     if e.get("job_id") == "pol-grow"]
            assert ("elastic_regrow_fence", "policy") in kinds
            # loss parity: an uninterrupted non-elastic run of the same
            # model lands on the same final loss
            from harmony_tpu.jobserver.server import JobServer

            ref = JobServer(num_executors=2)
            ref.start()
            try:
                r2 = ref.submit(_elastic_cfg("pol-ref", elastic=False)
                                ).result(timeout=300)
            finally:
                ref.shutdown(timeout=60)
            (w,) = res["workers"].values()
            (w2,) = r2["workers"].values()
            assert round(w["losses"][-1], 6) == round(w2["losses"][-1], 6)
        finally:
            srv.shutdown(timeout=120)
