#!/usr/bin/env python
"""Where does the headline bench's wall-clock actually go?

At the headline bench's sizes the per-sample compute is small, so its
wall-clock can go to host<->device overheads the virtual-mesh runs never
see. This harness separates them:

  primitives   dispatch round-trip, D2H scalar read, H2D bandwidth, and
               compile-cache behavior (fresh-closure re-jit) — the per-op
               budget everything else is made of.
  phases       one MLR job (the bench's config) run under the JobServer
               with the in-memory span receiver installed; prints total
               time per span type (epoch / comm_probe / metric_drain /
               dataset_upload) so the overhead shows up named.

Run on the real chip (plain) or CPU (JAX_PLATFORMS=cpu). Prints one JSON
line per section, like the other bench files.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np


def _t(fn, repeats=10, warmup=1):
    for _ in range(warmup):
        fn()
    best, total = float("inf"), 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = min(best, dt)
        total += dt
    return best, total / repeats


def bench_primitives() -> dict:
    dev = jax.devices()[0]
    one = jax.device_put(jnp.float32(1.0), dev)
    add = jax.jit(lambda x: x + 1.0)
    float(add(one))
    # dispatch_only: enqueue + block — the per-op host overhead.
    # rtt: dispatch + VALUE read — adds the D2H copy of the result.
    disp_best, _ = _t(lambda: jax.block_until_ready(add(one)))
    rtt_best, rtt_mean = _t(lambda: float(add(one)))

    arr = jax.device_put(jnp.zeros((256, 256), jnp.float32), dev)
    d2h_best, d2h_mean = _t(lambda: np.asarray(arr))

    big = np.zeros((64, 1024, 1024), np.float32)  # 256 MB
    h2d_best, _ = _t(
        lambda: jax.block_until_ready(jax.device_put(big, dev)),
        repeats=3, warmup=1,
    )
    h2d_gbps = big.nbytes / h2d_best / 1e9

    # compile-cache behavior: same jaxpr, fresh closure each time — the jit
    # in-memory cache cannot hit, so this measures trace + (persistent-cache
    # hit or full compile). The headline bench rebuilds its jitted steps per
    # JobServer run, so THIS is the cost its measured pass pays per program.
    x = jax.device_put(jnp.ones((1024, 1024), jnp.bfloat16), dev)

    def fresh():
        f = jax.jit(lambda a: (a @ a).sum())
        jax.block_until_ready(f(x))

    t0 = time.perf_counter()
    fresh()
    first_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fresh()
    refresh_compile_s = time.perf_counter() - t0

    return {
        "metric": "headline primitives",
        "device": str(dev),
        "dispatch_only_ms": round(disp_best * 1e3, 2),
        "dispatch_rtt_ms": round(rtt_best * 1e3, 2),
        "dispatch_rtt_mean_ms": round(rtt_mean * 1e3, 2),
        "d2h_small_ms": round(d2h_best * 1e3, 2),
        "d2h_small_mean_ms": round(d2h_mean * 1e3, 2),
        "h2d_gbps": round(h2d_gbps, 2),
        "fresh_jit_first_s": round(first_compile_s, 2),
        "fresh_jit_again_s": round(refresh_compile_s, 2),
        "value": round(rtt_best * 1e3, 2),
        "unit": "ms dispatch RTT",
    }


def bench_phases(epochs: int = 3) -> dict:
    from bench import job_configs  # repo root on sys.path via parent insert
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel.mesh import DevicePool
    from harmony_tpu.tracing import InMemorySpanReceiver, get_tracing

    recv = get_tracing().add_receiver(InMemorySpanReceiver())
    configs, totals = job_configs(scale=1.0, epochs=epochs)
    mlr = configs[0]
    devices = jax.devices()[:1]
    server = JobServer(num_executors=1, device_pool=DevicePool(devices))
    server.start()
    try:
        t0 = time.perf_counter()
        server.submit(mlr).result(timeout=1800)
        wall = time.perf_counter() - t0
    finally:
        server.shutdown(timeout=60)
        get_tracing().remove_receiver(recv)
    agg: dict = {}
    for s in recv.spans:
        a = agg.setdefault(s.description, [0, 0.0])
        a[0] += 1
        a[1] += s.duration_sec
    return {
        "metric": "headline phase profile (1 MLR job)",
        "epochs": epochs,
        "wall_s": round(wall, 2),
        "value": round(wall, 2),
        "unit": "s",
        "spans": {
            k: {"n": n, "total_s": round(t, 2)} for k, (n, t) in sorted(agg.items())
        },
    }


SECTIONS = {"primitives": bench_primitives, "phases": bench_phases}


METRIC_UNITS = {"primitives": ("headline primitives", "ms dispatch RTT"),
                "phases": ("headline phase profile (1 MLR job)", "s")}


def main():
    names = sys.argv[1:] or ["primitives", "phases"]
    if names == ["all"]:
        names = ["primitives", "phases"]
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        sys.exit(f"unknown section(s) {unknown}; have {sorted(SECTIONS)} or 'all'")
    for n in names:
        print(json.dumps(SECTIONS[n]()), flush=True)


if __name__ == "__main__":
    main()
