#!/usr/bin/env python
"""Telemetry-driven device autoscaling: policy-on vs policy-off A/B.

The FAIRNESS/POD_TENANTS successor for the closed loop (PR 15,
docs/SCHEDULING.md): a churning three-tenant mix on a 2-executor carved
pool, measured with the policy engine OFF (the pre-PR behavior: a
queued high-priority tenant waits for a carve to free) and ON in
``act`` mode (the engine detects the queued claimant, preempts a
device-idle low-priority tenant onto its sibling's executor — a shared
grant through a REAL elastic fence — and the freed carve unblocks the
claimant).

The mix:

* ``t-low-a`` / ``t-low-b`` — priority-0 elastic tenants, one executor
  each, DEVICE-IDLE by construction: a deterministic ``worker.epoch``
  delay rule (the blockmove.send delay-rule precedent) stalls each
  epoch boundary a fixed time, so the tenants hold their carves while
  barely using the device. The injected pacing is what makes the
  measurement honest on a saturated CPU host: a host-bound mix would
  hide any scheduling win inside CPU contention, while real pods idle
  devices exactly this way (the boundary stall deliberately sits
  OUTSIDE the TaskUnit admission scope — on this CPU backend COMP
  units meter serially across tenants, and a stall held inside a unit
  would serialize the claimant behind sleeping tenants, a backend
  artifact no real pod pays);
* ``t-high`` — a priority-1 compute tenant with a samples/sec SLO,
  submitted once the low tenants are mid-run. Under carve max_share=1
  both executors are taken, so it QUEUES — the contention the policy
  resolves.

Reported per arm: aggregate samples/sec (total examples / makespan),
the high tenant's end-to-end SLO attainment (examples / (completion -
submit) over its target — queue time counts, exactly as an operator
sees it), time-to-rebalance (t-high submit -> its dispatch start), and
cross-arm loss parity per tenant (fences must not change the math).
Interleaved rounds, best-of per arm. CPU-mesh numbers — comparable
across rounds, not to a chip.

Writes benchmarks/AUTOSCALE_<suffix>.json (argv[1], default r15);
prints ONE JSON line. Run: python benchmarks/autoscale.py
"""
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

METRIC = ("autoscale A/B: aggregate samples/sec + SLO attainment, "
          "policy off vs act (churning 3-tenant mix, 2-executor carve)")
OUT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    f"AUTOSCALE_{sys.argv[1] if len(sys.argv) > 1 else 'r15'}.json")

#: low tenants: paced (delay per epoch boundary) so the device idles
#: under them while they hold their carves
LOW_EPOCHS = 40
LOW_N = 64
DELAY_SEC = 0.35
#: high tenant: real compute, sized so in the OFF arm it finishes LAST
#: (its queue wait extends the makespan the policy then reclaims)
HI_EPOCHS = 24
HI_N = 262144
BATCHES = 2
#: the high tenant's samples/sec SLO — end-to-end (queue time counts)
HI_SLO_SPS = 450000.0
#: t-high enters once the low tenants are this far in (seconds)
CHURN_DELAY = 1.0


def _low_cfg(job_id, seed):
    from harmony_tpu.config.params import JobConfig, TrainerParams

    return JobConfig(
        job_id=job_id, app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=LOW_EPOCHS, num_mini_batches=BATCHES,
            model_chkp_period=1, priority=0,
            app_params={"num_classes": 4, "num_features": 16,
                        "features_per_partition": 4, "step_size": 0.1},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
              "data_args": {"n": LOW_N, "num_features": 16,
                            "num_classes": 4, "seed": seed},
              "elastic_shrink": True},
    )


def _hi_cfg():
    from harmony_tpu.config.params import JobConfig, TrainerParams

    return JobConfig(
        job_id="t-high", app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=HI_EPOCHS, num_mini_batches=BATCHES,
            priority=1, target_samples_per_sec=HI_SLO_SPS,
            app_params={"num_classes": 16, "num_features": 256,
                        "features_per_partition": 32, "step_size": 0.05},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
              "data_args": {"n": HI_N, "num_features": 256,
                            "num_classes": 16, "seed": 5}},
    )


def _pace_low_tenants():
    """Deterministic per-epoch host stall on the low tenants only —
    carve-holding, device-idle tenants; t-high is untouched."""
    from harmony_tpu import faults

    faults.arm(faults.FaultPlan([
        faults.FaultRule("worker.epoch", match={"job": jid},
                         count=-1, action="delay", delay_sec=DELAY_SEC)
        for jid in ("t-low-a", "t-low-b")
    ]))


def _final_loss(result):
    (w,) = result["workers"].values()
    return round(w["losses"][-1], 6)


def _run_arm(policy_on, low_epochs=LOW_EPOCHS, hi_epochs=HI_EPOCHS):
    """One full mix under a fresh in-process pod server; returns the
    measured section dict."""
    from harmony_tpu import faults
    from harmony_tpu.jobserver import joblog
    from harmony_tpu.jobserver.pod import PodJobServer
    from harmony_tpu.jobserver.scheduler import CarveScheduler
    from harmony_tpu.metrics import accounting

    env = {
        "HARMONY_POLICY": "act" if policy_on else "off",
        "HARMONY_POLICY_PERIOD": "0.4",
        "HARMONY_POLICY_COOLDOWN": "2",
        "HARMONY_POLICY_CONFIRM": "2",
        "HARMONY_OBS_SCRAPE_PERIOD": "0.4",
    }
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    accounting.reset_ledger()
    joblog.clear_events()
    root = tempfile.mkdtemp(prefix="harmony-autoscale-")
    srv = PodJobServer(num_executors=2, num_followers=0,
                       scheduler=CarveScheduler(min_slice=1, max_share=1),
                       chkp_root=os.path.join(root, "chkp"))
    srv.start()
    srv.serve_pod(0)
    try:
        _pace_low_tenants()
        t0 = time.monotonic()
        futs = {"t-low-a": srv.submit(_low_cfg("t-low-a", seed=1)),
                "t-low-b": srv.submit(_low_cfg("t-low-b", seed=2))}
        time.sleep(CHURN_DELAY)
        hi_submit = time.monotonic()
        futs["t-high"] = srv.submit(_hi_cfg())
        done, results = {}, {}
        for jid, f in futs.items():
            results[jid] = f.result(timeout=900)
            done[jid] = time.monotonic()
        makespan = max(done.values()) - t0
        hi_elapsed = done["t-high"] - hi_submit
        hi_start = srv.job_walls.get("t-high", (None, None))[0]
        ttr = (hi_start - hi_submit) if hi_start is not None else None
        examples = {"t-low-a": low_epochs * LOW_N,
                    "t-low-b": low_epochs * LOW_N,
                    "t-high": hi_epochs * HI_N}
        hi_sps = examples["t-high"] / hi_elapsed
        actions = [dict(e, job=jid)
                   for jid, evs in joblog.job_events(limit=64).items()
                   for e in evs
                   if e.get("kind") == "policy" and e.get("executed")]
        return {
            "policy": "act" if policy_on else "off",
            "makespan_sec": round(makespan, 2),
            "agg_sps": round(sum(examples.values()) / makespan, 1),
            "hi_end_to_end_sps": round(hi_sps, 1),
            "slo_attainment": round(min(1.0, hi_sps / HI_SLO_SPS), 4),
            "time_to_rebalance_sec": (round(ttr, 2)
                                      if ttr is not None else None),
            "policy_actions": [
                {"job": a.get("job", "?"), "action": a["action"],
                 "outcome": a["outcome"]} for a in actions],
            "losses": {j: _final_loss(results[j]) for j in results},
            "elastic": {j: results[j].get("elastic", {}).get("attempts", 1)
                        for j in results},
        }
    finally:
        faults.disarm()
        try:
            srv.shutdown(timeout=120)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            shutil.rmtree(root, ignore_errors=True)


def run_autoscale(rounds: int = 2) -> dict:
    """Interleaved OFF/ON rounds, best-of (highest agg_sps) per arm;
    importable."""
    arms = {"off": [], "act": []}
    # warmup: compile every program shape once so neither timed arm
    # pays a compile the other inherits (interleaving absorbs drift,
    # not one-time costs)
    _run_arm(policy_on=False, low_epochs=LOW_EPOCHS, hi_epochs=HI_EPOCHS)
    for _ in range(rounds):
        arms["off"].append(_run_arm(policy_on=False))
        arms["act"].append(_run_arm(policy_on=True))
    best = {arm: max(rs, key=lambda r: r["agg_sps"])
            for arm, rs in arms.items()}
    off, act = best["off"], best["act"]
    parity = all(off["losses"][j] == act["losses"][j]
                 for j in ("t-low-b", "t-high"))
    # t-low-a is packed mid-run in the act arm (mesh moves executors);
    # its parity is asserted separately so a drift is named, not hidden
    parity_packed = off["losses"]["t-low-a"] == act["losses"]["t-low-a"]
    return {
        "metric": METRIC,
        "unit": "samples/sec aggregate (policy act arm)",
        "value": act["agg_sps"],
        "agg_sps": act["agg_sps"],
        "slo_attainment": act["slo_attainment"],
        "agg_speedup": round(act["agg_sps"] / off["agg_sps"], 3),
        "attainment_gain": round(
            act["slo_attainment"] - off["slo_attainment"], 4),
        "time_to_rebalance_sec": act["time_to_rebalance_sec"],
        "loss_parity": bool(parity and parity_packed),
        "off": off,
        "act": act,
        "rounds": rounds,
        "mix": {"low_epochs": LOW_EPOCHS, "low_n": LOW_N,
                "pace_delay_sec": DELAY_SEC, "hi_epochs": HI_EPOCHS,
                "hi_n": HI_N, "hi_slo_sps": HI_SLO_SPS,
                "batches": BATCHES},
        "host_cores": os.cpu_count(),
        "note": (
            "2-executor CPU carve (max_share=1), paced low tenants "
            "(deterministic worker.epoch boundary delay -> device "
            "idle) + a queued priority-1 SLO tenant. OFF: the claimant "
            "waits for a carve to free; ACT: the policy preempts the "
            "lowest-priority tenant onto its sibling's executor (a "
            "shared grant through a real elastic fence) and the freed "
            "executor unblocks the claimant. agg_sps = total examples "
            "/ makespan; slo_attainment is END-TO-END (queue time "
            "counts); time_to_rebalance = claimant submit -> dispatch "
            "start."),
    }


def main() -> None:
    try:
        out = run_autoscale(rounds=2)
    except Exception as e:  # noqa: BLE001 - still print one line
        print(json.dumps({"metric": METRIC, "value": None,
                          "error": f"{type(e).__name__}: {e}"}))
        return
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    # a CPU-mesh benchmark: run standalone it opens no accelerator and
    # carves its tenants out of 8 virtual CPU devices (importers keep
    # their own platform — nothing is set at import)
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
    main()
