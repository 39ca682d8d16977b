"""Shared helpers for the benchmark scripts (micro.py, lm.py)."""
from __future__ import annotations

import time

import jax


def timed_chain(step, state, repeats: int = 10):
    """Mean wall time per iteration of ``state = step(state)``.

    The data dependency between iterations chains them on the device, so
    blocking on the final state waits for the whole loop. Returns
    (seconds_per_iter, final_state)."""
    state = step(state)  # warmup: compile + first execution
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(repeats):
        state = step(state)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / repeats, state


def timed_inner(body, state, inner: int = 32, outer: int = 3):
    """Per-iteration time of ``state = body(state)`` with ``inner``
    iterations folded into ONE compiled program (lax.fori_loop).

    A sub-ms program timed across dispatches measures the host's dispatch
    cost, not the device; folding the loop into the program amortizes it
    while the data dependency keeps the timing honest. Returns
    (seconds_per_inner_iter, final_state)."""
    prog = jax.jit(
        lambda s: jax.lax.fori_loop(0, inner, lambda i, t: body(t), s)
    )
    dt, state = timed_chain(prog, state, repeats=outer)
    return dt / inner, state


def on_tpu() -> bool:
    """Do these scripts' unplaced jits run on a TPU? (They time whatever
    the process's default device is.)"""
    return jax.devices()[0].platform == "tpu"


def mfu(achieved_flops: float):
    """achieved/peak for ONE chip, or None off-TPU."""
    from harmony_tpu.utils.platform import peak_bf16_flops

    peak = peak_bf16_flops(jax.devices()[0])
    return round(achieved_flops / peak, 3) if peak else None


# ---------------------------------------------------------------------------
# Pod-launch harness shared by benchmarks/pod.py and tests/test_multihost.py
# ---------------------------------------------------------------------------

def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def sanitized_cpu_env(devices_per_proc: int) -> dict:
    """Child env for spawned pod/distributed workers: an n-virtual-device
    CPU backend, whatever accelerator the parent holds."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}"
    )
    return env


def wait_for_ready(proc, deadline_s: float, marker: str = "READY") -> bool:
    """Read ``proc.stdout`` lines until ``marker`` (skipping benign startup
    prints), EOF, or the deadline. Each readline runs on a helper thread so
    a silently-wedged process hits the deadline instead of blocking
    forever."""
    import threading
    import time

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        box = {}
        t = threading.Thread(
            target=lambda: box.update(line=proc.stdout.readline()),
            daemon=True,
        )
        t.start()
        t.join(max(0.1, deadline - time.monotonic()))
        line = box.get("line", "")
        if line.strip() == marker:
            return True
        if not line:  # EOF: process exited without the marker
            return False
    return False
