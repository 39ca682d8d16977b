#!/usr/bin/env python
"""Input-pipeline benchmarks: prefetch micro-bench + input-service A/B.

Two modes, both host-bound on purpose (wide features, modest classes —
the benchmark measures the INPUT path, not the MXU; CPU backend, run
with JAX_PLATFORMS=cpu for a stable result):

  * default — the PR-1 micro-bench: ONE shuffling MLR job (shuffling
    forces real host work every epoch: the permutation gather +
    ``device_put`` the pipeline moves off the training thread) run twice
    at identical settings, ``input_prefetch`` off then on;
  * ``--service-ab`` — the multi-tenant input-service A/B: N tenant
    PROCESSES (the pod-follower / one-jobserver-per-job host shape —
    separate processes share no arrays, no devcache, no page locality)
    training on the SAME shuffling dataset, assembly in-process (every
    tenant process redoes the per-epoch permutation gather on the
    trainers' cores) vs through a STANDALONE input-service process (one
    shared assembly per epoch via the cross-tenant batch cache, batches
    over framed TCP, input work on the service's own cores — the
    disaggregation contract). Interleaved rounds with the arm order
    alternating, best-of per arm, and an in-bench bit-identical
    loss-parity gate per tenant per round.
    ``benchmarks/INPUT_SVC_r10.json`` is the committed capture.

Usage: python benchmarks/bench_input_pipeline.py [--n 8192] [--features
2048] [--epochs 6] [--batches 8] [--service-ab] [--tenants 3]
[--rounds 3] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_bench(
    n: int = 8192,
    features: int = 2048,
    classes: int = 16,
    epochs: int = 6,
    batches: int = 8,
    seed: int = 3,
) -> dict:
    """Run the A/B pair; returns the result dict (also usable from tests:
    tiny sizes keep it sub-second)."""
    import jax
    import numpy as np

    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
    from harmony_tpu.config.params import TrainerParams
    from harmony_tpu.dolphin import (
        TrainerContext,
        TrainingDataProvider,
        WorkerTasklet,
    )
    from harmony_tpu.metrics import MetricCollector, MetricManager
    from harmony_tpu.parallel.mesh import build_mesh
    from harmony_tpu.table import DenseTable, TableSpec

    mesh = build_mesh(jax.devices()[:1])
    x, y = make_synthetic(n, num_features=features, num_classes=classes,
                          seed=1)

    def one(prefetch: bool) -> "tuple[float, list, MetricManager]":
        trainer = MLRTrainer(
            num_classes=classes, num_features=features,
            features_per_partition=max(features // 8, 1), step_size=0.1,
        )
        params = TrainerParams(
            num_epochs=epochs, num_mini_batches=batches,
            comm_probe_period=0, input_prefetch=prefetch,
        )
        manager = MetricManager()
        manager.start_collection()
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh)
        ctx = TrainerContext(params=params, model_table=table)
        # shuffling: real host assembly every epoch (the prefetch target),
        # same seed both runs so the batch streams are identical
        data = TrainingDataProvider([x, y], batches,
                                    shuffle_each_epoch=True, seed=seed)
        worker = WorkerTasklet(
            "bench-input", ctx, trainer, data, mesh,
            collector=MetricCollector(sink=manager.on_metric,
                                      job_id="bench-input", worker_id="w0"),
        )
        t0 = time.perf_counter()
        result = worker.run()
        wall = time.perf_counter() - t0
        return wall, result["losses"], manager

    # warmup pass compiles the step for both runs (shared progcache)
    one(False)
    wall_sync, losses_sync, _ = one(False)
    wall_pre, losses_pre, manager = one(True)

    total = epochs * (n // batches) * batches
    pipe = manager.input_pipeline_metrics(job_id="bench-input")
    out = {
        "metric": "input pipeline: sync vs prefetched dispatch (1 MLR job, "
                  "shuffling, cpu-sized)",
        "unit": "samples/sec",
        "sync": round(total / wall_sync, 1),
        "prefetched": round(total / wall_pre, 1),
        "speedup": round(wall_sync / wall_pre, 3),
        "losses_bit_identical": losses_sync == losses_pre,
        "pipeline": {
            "epochs_reported": len(pipe),
            "staged_batches": sum(m.staged_batches for m in pipe),
            "prefetch_hits": sum(m.prefetch_hits for m in pipe),
            "consumer_stall_sec": round(
                sum(m.consumer_stall_sec for m in pipe), 4),
            "producer_idle_sec": round(
                sum(m.producer_idle_sec for m in pipe), 4),
        },
        "config": {"n": n, "features": features, "classes": classes,
                   "epochs": epochs, "batches": batches},
    }
    return out


def _spawn_standalone_service(cache_mb: int = 768, pin_cores=None):
    """A standalone input-service process on an ephemeral port; returns
    (proc, (host, port)). The separate process is the honest
    disaggregation unit: its assembly work leaves the trainers' GIL and
    core share entirely. ``cache_mb`` sizes the cross-tenant cache so a
    few in-flight epochs fit (prefetch overlap keeps ~2 epochs live per
    tenant; an undersized cache degrades to per-tenant assembly);
    ``pin_cores`` pins the service to its own host cores
    (HARMONY_INPUT_PIN_CORES — input capacity scaled separately from
    the trainers', which is the point of disaggregating)."""
    env = dict(os.environ)
    # a host-side process: it must never reach for an accelerator the
    # caller's process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("HARMONY_INPUT_CACHE_MB", str(cache_mb))
    if pin_cores:
        env["HARMONY_INPUT_PIN_CORES"] = ",".join(str(c) for c in pin_cores)
    proc = subprocess.Popen(
        [sys.executable, "-m", "harmony_tpu.inputsvc", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env,
    )
    line = proc.stdout.readline()
    info = json.loads(line)
    return proc, (info["host"], int(info["port"]))


def tenant_worker_main(cfg_json: str) -> int:
    """``--tenant-worker`` entry: ONE tenant process of the service A/B.

    Builds and compile-warms everything shape-dependent on a zeros
    dataset (program-cache keys are structural, so the measured run
    reuses the compiled programs), signals READY, then on GO runs the
    REAL job — dataset materialization, per-epoch assembly (or service
    fetch) and training are all inside the measured window, exactly the
    work a fresh tenant process pays."""
    import numpy as np

    cfg = json.loads(cfg_json)
    import jax

    from harmony_tpu import inputsvc
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
    from harmony_tpu.config.params import TrainerParams
    from harmony_tpu.dolphin import (
        DeferredTrainingDataProvider,
        TrainerContext,
        TrainingDataProvider,
        WorkerTasklet,
    )
    from harmony_tpu.parallel.mesh import build_mesh
    from harmony_tpu.table import DenseTable, TableSpec

    n, feats, classes = cfg["n"], cfg["features"], cfg["classes"]
    batches, epochs, seed = cfg["batches"], cfg["epochs"], cfg["seed"]
    mesh = build_mesh(jax.devices()[:1])

    def build_worker(data, feed, num_epochs):
        trainer = MLRTrainer(
            num_classes=classes, num_features=feats,
            features_per_partition=max(feats // 8, 1), step_size=0.1,
        )
        params = TrainerParams(num_epochs=num_epochs,
                               num_mini_batches=batches,
                               comm_probe_period=0)
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh)
        ctx = TrainerContext(params=params, model_table=table)
        return WorkerTasklet(cfg["tenant"], ctx, trainer, data, mesh,
                             input_feed=feed)

    warm = TrainingDataProvider(
        [np.zeros((n, feats), np.float32), np.zeros(n, np.int32)],
        batches, shuffle_each_epoch=False,
    )
    build_worker(warm, None, 1).run()
    print("READY", flush=True)
    sys.stdin.readline()  # GO

    t0 = time.perf_counter()
    data_args = cfg["data_args"]
    feed = None
    if cfg.get("endpoint"):
        # service tenant: the local dataset exists only as the fallback
        # source — defer its materialization (the data_fn call is the
        # single most expensive host step) until a fallback needs it
        data = DeferredTrainingDataProvider(
            lambda: make_synthetic(**data_args), n, batches,
            shuffle_each_epoch=True, seed=seed,
            array_specs=[((feats,), "float32"), ((), "int32")],
        )
        spec = inputsvc.DatasetSpec.build(
            "harmony_tpu.apps.mlr:make_synthetic", data_args,
            lo=0, hi=n, num_mini_batches=batches, shuffle=True, seed=seed,
        )
        feed = inputsvc.TrainerInputFeed(
            spec, data, tenant=cfg["tenant"],
            endpoint=(cfg["endpoint"][0], int(cfg["endpoint"][1])),
        )
    else:
        x, y = make_synthetic(**data_args)
        data = TrainingDataProvider([x, y], batches,
                                    shuffle_each_epoch=True, seed=seed)
    result = build_worker(data, feed, epochs).run()
    out = {"wall": time.perf_counter() - t0, "losses": result["losses"]}
    if feed is not None:
        out["feed"] = feed.stats()
    print(json.dumps(out), flush=True)
    return 0


def run_service_bench(
    tenants: int = 8,
    n: int = 2097152,
    features: int = 4,
    classes: int = 2,
    epochs: int = 2,
    batches: int = 8,
    seed: int = 3,
    rounds: int = 3,
    standalone: bool = True,
    cores: int = 2,
    service_cores: int = 2,
) -> dict:
    """Multi-tenant service-vs-in-process A/B (see module docstring).
    Returns the result dict; tiny sizes keep it test-runnable.

    Tenants are PROCESSES: separate trainer processes share no arrays,
    no page-cache locality and no in-process devcache — each one pays
    its own dataset materialization and its own per-epoch permutation
    gather, which is the duplicated host work the service exists to
    deduplicate (same-process tenants already share host arrays through
    the jobserver's host-data cache, and their concurrent same-pattern
    gathers even share CPU cache — measuring THAT shape undersells
    nothing because the framework already solved it).

    Shapes are tall and NARROW (2M x 4): per byte, a permutation gather
    of 16-byte rows costs ~5 memcpys (random access), the same
    assembly-per-byte asymmetry real input pipelines have. The default
    tenant mix — MANY short same-dataset jobs — is the hyperparameter-
    sweep shape, where per-tenant dataset materialization plus the
    early epochs' assembly dominate and disaggregation pays most;
    longer-epoch mixes taper toward parity as the per-epoch wire cost
    approaches the per-epoch gather cost on a byte-bound host (run
    ``--epochs 4`` to see the taper — the committed JSON records it).

    Core budgets: ``cores`` pins the parent — and so every spawned
    tenant process — to the trainers' budget; ``service_cores`` gives
    the standalone service its OWN cores (HARMONY_INPUT_PIN_CORES),
    which is the disaggregation contract: input capacity scales
    independently of the trainers'. The in-process arm cannot use those
    extra cores BY CONSTRUCTION — in-process assembly runs inside the
    trainer processes; that asymmetry is the deployment reality being
    measured, and the result records both budgets."""
    all_cores = (sorted(os.sched_getaffinity(0))
                 if hasattr(os, "sched_getaffinity") else [])
    old_affinity = None
    svc_pin = None
    if cores and all_cores:
        old_affinity = set(all_cores)
        trainer_set = set(all_cores[:max(1, cores)])
        svc_pin = all_cores[max(1, cores):max(1, cores) + service_cores]
        os.sched_setaffinity(0, trainer_set)  # children inherit
    samples_per_tenant = epochs * (n // batches) * batches
    data_args = {"n": n, "num_features": features, "num_classes": classes,
                 "seed": 1}
    me = os.path.abspath(__file__)

    def run_arm(endpoint, round_seed: int):
        """One arm: ``tenants`` concurrent tenant PROCESSES. endpoint=
        None -> in-process assembly; else the service feed. The wall
        clock covers GO -> last result (materialization + assembly/
        fetch + training), not process spawn or compile warmup.
        Returns (wall_sec, losses per tenant)."""
        procs = []
        for i in range(tenants):
            cfg = {
                "n": n, "features": features, "classes": classes,
                "batches": batches, "epochs": epochs, "seed": round_seed,
                "tenant": f"t{i}", "data_args": data_args,
                "endpoint": list(endpoint) if endpoint else None,
            }
            wenv = dict(os.environ)
            # host-side probe: tenant trainers run on the CPU backend,
            # whatever accelerator the caller's process holds
            wenv["JAX_PLATFORMS"] = "cpu"
            # hold ~3 epochs of fetched batches (live epoch + the
            # prespawned next + slack): an undersized client cache
            # evicts live entries and turns shared reads into misses
            wenv.setdefault(
                "HARMONY_INPUT_CLIENT_CACHE_MB",
                str(max(256, 4 * (n * (features + 1) * 4 >> 20))))
            procs.append(subprocess.Popen(
                [sys.executable, me, "--tenant-worker", json.dumps(cfg)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                env=wenv,
            ))
        try:
            for p in procs:
                line = p.stdout.readline()
                if line.strip() != "READY":
                    raise RuntimeError(f"tenant worker died: {line!r}")
            t0 = time.perf_counter()
            for p in procs:
                p.stdin.write("GO\n")
                p.stdin.flush()
            outs = [json.loads(p.stdout.readline()) for p in procs]
            wall = time.perf_counter() - t0
        finally:
            # terminate ALL first, then reap with kill escalation: a
            # wedged worker must not leave its siblings orphaned (still
            # pinned to the trainer cores) or mask the original error
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)
        return wall, [o["losses"] for o in outs]

    from harmony_tpu import inputsvc  # jax-free import (client side only)

    svc_proc = None
    svc = None
    # the service accumulates every round's epochs (fresh keys per
    # round): size its cache so LIVE epochs never churn against dead
    # rounds' entries
    svc_cache_mb = max(768, (3 * rounds + 6) * epochs
                       * (n * (features + 1) * 4 >> 20) // 2)
    if standalone:
        svc_proc, endpoint = _spawn_standalone_service(
            cache_mb=svc_cache_mb, pin_cores=svc_pin)
    else:
        svc = inputsvc.InputService()
        endpoint = ("127.0.0.1", svc.start())
    try:
        # service warmup: one-time costs (its data_fn import + dataset
        # materialization) land outside the timed rounds; the tenant
        # processes warm their own compiles before READY
        run_arm(endpoint, seed - 1)
        best = {"inproc": 0.0, "service": 0.0}
        parity = True
        for r in range(rounds):
            round_seed = seed + 1000 * r  # fresh epoch keys every round
            arms = (("inproc", None), ("service", endpoint))
            if r % 2:  # alternate order: neither arm owns the warm cache
                arms = arms[::-1]
            losses: dict = {}
            for name, ep in arms:
                wall, tenant_losses = run_arm(ep, round_seed)
                losses[name] = tenant_losses
                sps = tenants * samples_per_tenant / wall
                best[name] = max(best[name], sps)
                print(f"  round {r} {name}: wall {wall:.2f}s "
                      f"({sps:,.0f} samples/s)", file=sys.stderr)
            parity = parity and losses["inproc"] == losses["service"]
        stats = inputsvc.fetch_stats(endpoint)
    finally:
        if svc_proc is not None:
            svc_proc.terminate()
            svc_proc.wait(timeout=10)
        if svc is not None:
            svc.stop()
        if old_affinity is not None:
            os.sched_setaffinity(0, old_affinity)
    return {
        "metric": f"input service: {tenants} same-dataset shuffling MLR "
                  "tenant processes, service vs in-process assembly "
                  "(cpu bench)",
        "unit": "aggregate samples/sec",
        "inproc_sps": round(best["inproc"], 1),
        "service_sps": round(best["service"], 1),
        "speedup": round(best["service"] / best["inproc"], 3)
        if best["inproc"] else None,
        "losses_bit_identical": parity,
        "service": {
            "mode": "standalone process" if standalone else "embedded",
            "batches_from_cache": stats["batches_from_cache"],
            "batches_assembled": stats["batches_assembled"],
            "cache": {k: stats["cache"][k]
                      for k in ("hits", "misses", "evictions")},
            "workers": stats["workers"],
        },
        "note": "honest core budgets: tenant processes pinned to "
                "config.cores trainer cores in BOTH arms; the service "
                "arm additionally spends config.service_cores on its "
                "own input-worker process (HARMONY_INPUT_PIN_CORES) — "
                "scaling input on separate cores IS the disaggregation "
                "being measured, and the in-process arm cannot use "
                "those cores by construction (its assembly runs inside "
                "the trainer processes). The win: tenant processes "
                "share one epoch assembly through the cross-tenant "
                "cache instead of each redoing the permutation gather "
                "of a dataset only it can see",
        "config": {"tenants": tenants, "n": n, "features": features,
                   "classes": classes, "epochs": epochs,
                   "batches": batches, "rounds": rounds,
                   "cores": cores, "service_cores": service_cores},
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    # size defaults differ per mode: the micro-bench wants wide rows
    # (device_put-heavy), the service A/B wants tall-narrow (assembly-
    # compute-heavy — see run_service_bench)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--features", type=int, default=None)
    ap.add_argument("--classes", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--service-ab", action="store_true",
                    help="multi-tenant service-vs-in-process A/B instead "
                         "of the single-job prefetch micro-bench")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--cores", type=int, default=2,
                    help="service-ab: trainer-core budget every tenant "
                         "process is pinned to, both arms (0 = none)")
    ap.add_argument("--service-cores", type=int, default=2,
                    help="service-ab: input-worker cores the standalone "
                         "service pins itself to, OUTSIDE the trainer "
                         "budget (the disaggregation contract)")
    ap.add_argument("--tenant-worker", default=None, metavar="CFG_JSON",
                    help=argparse.SUPPRESS)  # internal: one A/B tenant
    ap.add_argument("--embedded", action="store_true",
                    help="service-ab: run the service in-process instead "
                         "of as a standalone worker process")
    ap.add_argument("--json", action="store_true",
                    help="print only the JSON line")
    args = ap.parse_args(argv)
    if args.tenant_worker:
        sys.exit(tenant_worker_main(args.tenant_worker))
    if args.service_ab:
        res = run_service_bench(
            tenants=args.tenants,
            n=args.n if args.n is not None else 2097152,
            features=args.features if args.features is not None else 4,
            classes=args.classes if args.classes is not None else 2,
            epochs=args.epochs if args.epochs is not None else 2,
            batches=args.batches,
            rounds=args.rounds, standalone=not args.embedded,
            cores=args.cores, service_cores=args.service_cores,
        )
        if not args.json:
            print(f"  inproc {res['inproc_sps']:,} vs service "
                  f"{res['service_sps']:,} aggregate samples/sec -> "
                  f"{res['speedup']}x (parity="
                  f"{res['losses_bit_identical']})", file=sys.stderr)
    else:
        res = run_bench(n=args.n if args.n is not None else 8192,
                        features=(args.features if args.features is not None
                                  else 2048),
                        classes=args.classes if args.classes is not None
                        else 16,
                        epochs=args.epochs if args.epochs is not None else 6,
                        batches=args.batches)
        if not args.json:
            print(f"  sync {res['sync']:,} vs prefetched "
                  f"{res['prefetched']:,} samples/sec -> "
                  f"{res['speedup']}x", file=sys.stderr)
    print(json.dumps(res))
    return res


if __name__ == "__main__":
    main()
