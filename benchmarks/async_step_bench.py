#!/usr/bin/env python
"""Bounded-staleness async step — overlap push/pull with compute.

One MLR WorkerTasklet workload measured in four arms under an injected
``worker.pull`` comm delay (FaultRule action="delay": a slow link), with
the sparse_step_bench methodology (interleaved rounds, best-of per arm,
an in-bench parity assertion before any number is reported):

  * ``sync``     — the host-driven unfused baseline (pull -> comp ->
    push serialized on the training thread; the delay is exposed on the
    critical path every batch);
  * ``async b=0`` — AsyncStepDriver with staleness bound 0: same
    programs, same apply order, fully serialized by the staleness gate —
    the BIT-IDENTICAL control arm (asserted in-bench against ``sync``);
  * ``async b=1`` / ``async b=2`` — the overlap arms: step k+1's compute
    runs while the comm thread drains step k's push + k+1's pull, so the
    injected delay moves off the critical path (bounded by the window).

Quality is reported honestly: per-epoch losses for every arm (staleness
reorders nothing at bound 0; at bound >= 1 updates apply against a view
up to ``bound`` deltas stale, so the curves may differ — they are
committed as measured, not asserted equal).

CPU-backend honesty note: compute and comm here share ~2 host cores, so
the overlap win is bounded by the injected sleep (a sleep yields the
GIL/cores; real D2H/H2D transfer time would too, but a real TPU also
overlaps the device-side collective with the next step's MXU work,
which this bench cannot see).

Writes benchmarks/ASYNC_STEP_r16.json and prints ONE JSON line.
Run: python benchmarks/async_step_bench.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

ROUNDS = 3

# MLR shape: enough compute per batch that overlap has something to hide
# the injected comm delay behind (comp ~ comm is the interesting regime;
# when either side dominates, overlap can only save the smaller one).
N, FEATURES, CLASSES, FPP = 4096, 2048, 64, 256
EPOCHS, BATCHES = 3, 8
PULL_DELAY_SEC = 0.004  # injected per-batch "slow link" on worker.pull

ARMS = (
    ("sync", False, 0),
    ("async_b0", True, 0),
    ("async_b1", True, 1),
    ("async_b2", True, 2),
)


def run_arm(async_on: bool, bound: int, *, n=None, features=None,
            classes=None, fpp=None, epochs=None, batches=None,
            delay=None):
    """One full training run; returns (steps_per_sec, losses, stats).

    Shape/delay kwargs default to the module constants; bench.py's
    ``measure_async_step`` hook passes a smaller probe shape."""
    n = N if n is None else n
    features = FEATURES if features is None else features
    classes = CLASSES if classes is None else classes
    fpp = FPP if fpp is None else fpp
    epochs = EPOCHS if epochs is None else epochs
    batches = BATCHES if batches is None else batches
    delay = PULL_DELAY_SEC if delay is None else delay
    from harmony_tpu import faults
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
    from harmony_tpu.config.params import TrainerParams
    from harmony_tpu.dolphin import (
        TrainerContext,
        TrainingDataProvider,
        WorkerTasklet,
    )
    from harmony_tpu.faults.plan import FaultPlan, FaultRule
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.table import DenseTable, TableSpec

    mesh = build_mesh(jax.devices("cpu")[:1])
    trainer = MLRTrainer(num_classes=classes, num_features=features,
                         features_per_partition=fpp)
    table = DenseTable(TableSpec(trainer.model_table_config()), mesh)
    params = TrainerParams(num_epochs=epochs, num_mini_batches=batches,
                           fused_step=False, async_step=async_on,
                           staleness_bound=bound)
    ctx = TrainerContext(params=params, model_table=table)
    data = TrainingDataProvider(
        make_synthetic(n, features, classes, seed=16), batches)
    w = WorkerTasklet(f"async-bench-{async_on}-{bound}", ctx, trainer,
                      data, mesh)
    # the slow link fires on whichever thread performs the pull: the
    # training thread (sync — exposed) or the comm thread (async —
    # overlapped up to the staleness window)
    faults.arm(FaultPlan([FaultRule("worker.pull", action="delay",
                                    delay_sec=delay, count=-1)]))
    try:
        t0 = time.perf_counter()
        result = w.run()
        dt = time.perf_counter() - t0
    finally:
        faults.disarm()
    stats = {}
    stats_fn = getattr(w._step, "staleness_stats", None)
    if stats_fn is not None:
        s = stats_fn()
        stats = {"max_lag": s["max_lag"],
                 "exposed_wait_s": round(s["exposed_wait_sec"], 4),
                 "overlapped_comm_s": round(s["overlapped_comm_sec"], 4)}
    split = getattr(w._step, "mean_phase_seconds", None)
    if split is not None:
        p, c, q = split()
        stats["mean_phase_s"] = {"pull": round(p, 5), "comp": round(c, 5),
                                 "push": round(q, 5)}
    return epochs * batches / dt, result["losses"], stats


def main() -> None:
    best = {name: 0.0 for name, _, _ in ARMS}
    stats = {name: {} for name, _, _ in ARMS}
    losses = {}
    for _ in range(ROUNDS):
        # interleave arms inside every round (host throughput drifts
        # round to round), best-of per arm
        for name, async_on, bound in ARMS:
            sps, arm_losses, st = run_arm(async_on, bound)
            if bound == 0 and name in losses:
                # only the serialized arms are run-to-run deterministic;
                # at bound >= 1 the view lag anywhere in [0, bound] is
                # timing-dependent, so those curves legitimately vary
                assert arm_losses == losses[name], (
                    f"{name}: nondeterministic losses within one arm")
            if bound == 0 or sps > best[name]:
                losses[name] = arm_losses
            if sps > best[name]:
                best[name] = sps
                stats[name] = st
    # the parity gate: bound 0 only counts if it learns EXACTLY what the
    # synchronous path learns (same programs, same apply order)
    assert losses["async_b0"] == losses["sync"], (
        "staleness-0 parity broke: "
        f"{losses['async_b0'][:3]} vs {losses['sync'][:3]}")
    arms = {}
    for name, _, bound in ARMS:
        arms[name] = {
            "steps_per_sec": round(best[name], 2),
            "speedup_vs_sync": round(best[name] / best["sync"], 2),
            "staleness_bound": bound,
            **stats[name],
        }
    out = {
        "metric": "async_step",
        "unit": "steps/sec",
        "rounds": ROUNDS,
        "mode": "interleaved arms, best-of per arm, in-bench staleness-0 "
                "bit-identical loss parity asserted vs sync",
        "pull_delay_sec": PULL_DELAY_SEC,
        "workload": {"app": "mlr", "samples": N, "features": FEATURES,
                     "classes": CLASSES, "epochs": EPOCHS,
                     "batches": BATCHES},
        "arms": arms,
        "quality": {
            "losses_by_arm": {name: [round(v, 6) for v in losses[name]]
                              for name, _, _ in ARMS},
            "note": "per-epoch loss curves, committed as measured: bound "
                    "0 is bit-identical to sync (asserted); bounds 1-2 "
                    "apply updates against a view up to `bound` deltas "
                    "stale — the lag is timing-dependent within [0, "
                    "bound], so those rows are the best-throughput "
                    "round's curve, not a deterministic replay",
        },
        "note": "CPU backend: the overlap win is the injected sleep "
                "moving off the critical path; a real TPU additionally "
                "overlaps device collectives with next-step MXU work",
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ASYNC_STEP_r16.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    # a CPU-backend benchmark: run standalone it opens no accelerator
    # (importers keep their own platform — nothing is set at import)
    jax.config.update("jax_platforms", "cpu")
    main()
