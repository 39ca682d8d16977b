#!/usr/bin/env python
"""Per-app throughput — BASELINE.md configs 1, 2, 3, 5 as single jobs.

The repo-root ``bench.py`` measures config 4 (the headline: concurrent
MLR+NMF+LDA under the multi-tenant JobServer). This file measures the
remaining BASELINE configs individually so regressions localize to an
app instead of hiding in the aggregate:

  1. MLR — single job
  2. NMF — single job
  3. LDA — single job (sparse topic-word table)
  5. Wide&Deep / FM (sparse embedding tables, keyed pulls)

One JSON line per app: {"metric", "value" (samples/sec), "unit", ...}.
Run: python benchmarks/apps.py [mlr|nmf|lda|fm|widedeep|fm-hash|all]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import bench  # noqa: E402
from harmony_tpu.config.params import JobConfig, TrainerParams  # noqa: E402
from harmony_tpu.jobserver.server import JobServer  # noqa: E402
from harmony_tpu.parallel.mesh import DevicePool  # noqa: E402

EPOCHS = bench.EPOCHS
BATCHES = bench.BATCHES


def _sparse_jobs():
    fm = JobConfig(
        job_id="bench-fm", app_type="dolphin",
        trainer="harmony_tpu.apps.widedeep:FMTrainer",
        params=TrainerParams(
            num_epochs=EPOCHS, num_mini_batches=BATCHES, comm_probe_period=6,
            app_params={"vocab_size": 100_000, "num_slots": 16,
                        "emb_dim": 16, "step_size": 0.1},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.widedeep:make_synthetic",
              "data_args": {"n": 32768, "vocab_size": 100_000,
                            "num_slots": 16}},
    )
    wd = JobConfig(
        job_id="bench-widedeep", app_type="dolphin",
        trainer="harmony_tpu.apps.widedeep:WideDeepTrainer",
        params=TrainerParams(
            num_epochs=EPOCHS, num_mini_batches=BATCHES, comm_probe_period=6,
            app_params={"vocab_size": 100_000, "num_slots": 16,
                        "emb_dim": 16, "hidden": 128, "step_size": 0.1},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.widedeep:make_synthetic",
              "data_args": {"n": 32768, "vocab_size": 100_000,
                            "num_slots": 16}},
    )
    # BASELINE config 5's true "sparse embedding tables" shape: the model
    # lives in the DeviceHashTable, ids drawn from the whole int32 domain
    # (no dense preallocation possible), lazy per-key init.
    fmh = JobConfig(
        job_id="bench-fm-hash", app_type="dolphin",
        trainer="harmony_tpu.apps.widedeep:FMTrainer",
        params=TrainerParams(
            num_epochs=EPOCHS, num_mini_batches=BATCHES, comm_probe_period=6,
            app_params={"vocab_size": 100_000, "num_slots": 16,
                        "emb_dim": 16, "step_size": 0.1, "sparse": True},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.widedeep:make_synthetic_sparse",
              "data_args": {"n": 32768, "vocab_size": 100_000,
                            "num_slots": 16}},
    )
    # total = epochs x dataset size, derived from the config itself so a
    # tuned data_args['n'] cannot silently skew the reported rate
    return {
        name: (cfg, cfg.params.num_epochs * cfg.user["data_args"]["n"])
        for name, cfg in (("fm", fm), ("widedeep", wd), ("fm-hash", fmh))
    }


def run_single(config: JobConfig, total_examples: int) -> dict:
    devices = jax.devices()  # bounded probe already ran in main()
    server = JobServer(num_executors=len(devices),
                       device_pool=DevicePool(devices))
    server.start()
    try:
        t0 = time.perf_counter()
        server.submit(config).result(timeout=3600)
        wall = time.perf_counter() - t0
    finally:
        server.shutdown(timeout=120)
    return {
        "metric": f"{config.job_id} throughput",
        "value": round(total_examples / wall, 1),
        "unit": "samples/sec",
        "examples": total_examples,
        "wall_sec": round(wall, 2),
    }


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    jobs, totals = bench.job_configs(1.0)
    table = {c.job_id.removeprefix("bench-"): (c, totals[c.job_id])
             for c in jobs}
    table.update(_sparse_jobs())
    if which != "all" and which not in table:
        sys.exit(f"unknown app {which!r}; available: {sorted(table)} or 'all'")
    names = list(table) if which == "all" else [which]
    for name in names:
        cfg, total = table[name]
        # per-job containment: one failing app must not abort the
        # remaining apps or leave gaps in the metric series
        try:
            print(json.dumps(run_single(cfg, total)))
        except Exception as e:  # noqa: BLE001 - recorded as a metric line
            print(json.dumps({
                "metric": f"{cfg.job_id} throughput",
                "value": None, "unit": "samples/sec",
                "error": f"{type(e).__name__}: {e}",
            }))


if __name__ == "__main__":
    main()
