#!/usr/bin/env python
"""Multi-tenant interference: per-job slowdown under concurrency.

The headline (bench.py) records the AGGREGATE rate of concurrent
MLR+NMF+LDA; this companion records what sharing costs each tenant — the
quantity the reference's global TaskUnit schedule exists to keep fair
(SURVEY.md §2.10: CPU/NET phase interleaving of concurrent jobs on shared
executors). Each job runs once ALONE on the mesh (isolation baseline),
then all three run CONCURRENTLY; per-job slowdown = concurrent wall /
isolated wall (>1 = the tenant got slower), and Jain's index over
per-job slowdowns summarizes fairness (1.0 = perfectly even; 1/n = one
job absorbed all the interference).

With ideal time-slicing of a single device, each of n jobs slows ~n x; a
job slowing far more than its peers means the scheduler is starving it.

Prints ONE JSON line. Runs on whatever backend JAX is pointed at (the
real chip, or the virtual mesh via
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from bench import job_configs  # noqa: E402
from harmony_tpu.jobserver.server import JobServer  # noqa: E402
from harmony_tpu.parallel.mesh import DevicePool  # noqa: E402
from harmony_tpu.utils.compcache import enable_compile_cache  # noqa: E402

EPOCHS = 6  # shorter than the headline: 4 passes of the 3-job set


def _run(devices, configs, timeout_s: float = 1800.0, scheduler=None):
    """Submit ``configs`` together; returns {job_id: wall_seconds} from
    the common start (bench.submit_and_time: done-callback stamping, so a
    fast tenant isn't charged a slow one's completion)."""
    from bench import submit_and_time

    server = JobServer(num_executors=len(devices),
                       device_pool=DevicePool(devices),
                       scheduler=scheduler)
    server.start()
    try:
        return submit_and_time(server, configs, timeout_s)
    finally:
        server.shutdown(timeout=120)


def main() -> None:
    enable_compile_cache()
    devices = jax.devices()
    scale = 1.0 if devices[0].platform != "cpu" else 0.125
    configs, _ = job_configs(scale, epochs=EPOCHS)

    # warmup: compile every job's programs once so neither phase pays them
    print("warmup (compile) pass:", file=sys.stderr)
    _run(devices, [c for c in configs])

    print("isolation baselines:", file=sys.stderr)
    iso = {}
    for c in configs:
        iso.update(_run(devices, [c]))
        print(f"  {c.job_id}: {iso[c.job_id]:.1f}s alone", file=sys.stderr)

    out = {
        "metric": "multi-tenant fairness (slowdown under concurrency)",
        "unit": "jain index over per-job slowdowns",
        "jobs": len(configs),
        "isolated_wall_s": {j: round(w, 1) for j, w in iso.items()},
        "epochs": EPOCHS,
    }
    # share_all = every job on all executors (the reference's default);
    # carve = disjoint mesh slices per tenant (the BASELINE north-star
    # sharing mode). max_share caps each slice at pool//jobs — WITHOUT it
    # the first arrival's fair share is the whole idle pool and "carve"
    # silently degenerates to FIFO. Needs one executor per job to carve.
    from harmony_tpu.jobserver.scheduler import CarveScheduler

    modes = {"share_all": lambda: "share_all"}
    if len(devices) >= len(configs):
        modes["carve"] = lambda: CarveScheduler(
            max_share=max(1, len(devices) // len(configs)))
    for mode, make_sched in modes.items():
        if mode == "carve":
            # slice-shaped programs differ from the full-mesh shapes the
            # isolation runs compiled — warm them outside the timed run
            print("carve warmup (slice-shape compile) pass:", file=sys.stderr)
            _run(devices, configs, scheduler=make_sched())
        print(f"concurrent run ({mode}):", file=sys.stderr)
        conc = _run(devices, configs, scheduler=make_sched())
        slowdown = {j: conc[j] / iso[j] for j in conc}
        for j, s in slowdown.items():
            print(f"  {j}: {conc[j]:.1f}s concurrent -> slowdown {s:.2f}x",
                  file=sys.stderr)
        vals = list(slowdown.values())
        jain = (sum(vals) ** 2) / (len(vals) * sum(v * v for v in vals))
        out[mode] = {
            "jain": round(jain, 3),
            "slowdown": {j: round(s, 2) for j, s in slowdown.items()},
            "max_slowdown": round(max(vals), 2),
            "concurrent_wall_s": {j: round(w, 1) for j, w in conc.items()},
        }
    out["value"] = out["share_all"]["jain"]
    if "carve" not in out and len(devices) < len(configs):
        out["note"] = (f"carve skipped: {len(devices)} device(s) cannot "
                       f"slice among {len(configs)} jobs")
    elif devices[0].platform == "cpu":
        out["note"] = (
            "cpu-mesh carve numbers are a FLOOR: the in-process-collective "
            "backend serializes multi-device program execution across "
            "slices (parallel/dispatch.py); real TPU slices run "
            "concurrently"
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
